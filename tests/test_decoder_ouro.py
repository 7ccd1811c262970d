"""models/decoder_lm.py as Ouro (a looped decoder) at a tiny size on the CPU
against the plain reference (benchmark/references/ouro_pretrain.py: a Python
loop over passes and layers, no loop op, no recomputation) on seeded weights
and a seeded non-zero exit gate: the loss, each pass's cross-entropy of
every position, the exit probabilities, and every parameter's gradient --
the shared weights' sums over their four uses, the gate, the table and the
head --, with recomputation by layer inside the loop and without; what the
Program holds whatever ``total_ut_steps`` is; ``total_ut_steps: 1`` equal to
today's builder; the step's temporaries lower with recomputation; the parts
the configuration states in float32 shown to matter at the written
tolerance; the flash kernels inside the loop saying that their backward reads
kept statistics; and what the builder still refuses, by name.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark import probe
from benchmark.programs import ouro_pretrain as program
from benchmark.references import ouro_pretrain as reference
from paddle_tpu.models import decoder_lm

MODEL = {
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96,
    "vocab_size": 512, "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "layer_types": ["full_attention", "full_attention"],
    "rope_theta": 1000000, "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "max_window_layers": 48,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "qk_norm": "none",
    "norm_placement": "sandwich", "exit_entropy_coef": 0.05,
    "learning_rate": 1e-5, "adam_beta1": 0.9, "adam_beta2": 0.95,
    "adam_epsilon": 1e-8, "weight_decay": 0.1}
PARAMS = {"batch": 2, "seq": 24}
STEPS = MODEL["total_ut_steps"]
TOKENS = PARAMS["batch"] * PARAMS["seq"]


def built(dtype="float32", recompute="layer", seed=5, params=PARAMS,
          **changed):
    """The cell's own program (``benchmark/programs/ouro_pretrain.py``) at
    the tiny size, started from ``seed``, with a gate that reads
    something: from its zero start every exit probability is a constant."""
    model = dict(MODEL, dtype=dtype, recompute=recompute, **changed)
    b = program.build(model, params)
    b["startup"].random_seed = seed
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(b["startup"], scope=scope)
    rng = np.random.RandomState(seed)
    for name, std in (("exit_gate_w", 0.3), ("exit_gate_b", 0.3)):
        shape = np.asarray(scope.find_var(name)).shape
        scope.set_var(name, jnp.asarray(
            rng.randn(*shape).astype("float32") * std))
    # at std 0.02 every score is near zero and the softmax even: sharper q
    # and k, so that the rotary positions and the causal mask matter
    for layer in range(model["num_hidden_layers"]):
        for name in ("q", "k"):
            name = f"layer{layer}_attn_{name}_w"
            scope.set_var(name, scope.find_var(name) * 5)
    return dict(b, model=model, exe=exe, scope=scope,
                batch=program.batch(model, params, rng))


def weights_of(b):
    return [jnp.asarray(b["scope"].find_var(n), jnp.float32)
            for n in b["params"]]


def run_both(b):
    weights = weights_of(b)
    with jax.default_matmul_precision("highest"):
        want = reference.forward(weights, b["batch"], b["model"])
        want_grads = dict(zip(b["params"], jax.grad(
            lambda ws: reference.forward(ws, b["batch"],
                                         b["model"])["loss"])(weights)))
    check = b["check"]["loss"] + b["check"]["each"]
    got = b["exe"].run(b["main"], feed=b["batch"], scope=b["scope"],
                       fetch_list=check + [n + "@GRAD" for n in b["params"]])
    return {"loss": float(np.asarray(got[0]).reshape(-1)[0]),
            "passes": np.asarray(got[1], np.float32).reshape(STEPS, -1),
            "exit_p": np.asarray(got[2], np.float32).reshape(STEPS, -1),
            "grads": dict(zip(b["params"], got[3:])),
            "want": want, "want_grads": want_grads}


@pytest.fixture(scope="module", params=["layer", "none"])
def f32(request):
    b = built("float32", request.param)
    yield b, run_both(b)
    b["exe"].close()


def test_float32_loss_passes_and_exit_probabilities_match_the_reference(f32):
    _, r = f32
    want = r["want"]
    assert abs(r["loss"] - float(want["loss"])) <= 2e-6 * abs(r["loss"])
    np.testing.assert_allclose(r["passes"], np.asarray(want["passes"]),
                               atol=1e-4)
    np.testing.assert_allclose(r["exit_p"], np.asarray(want["exit_p"]),
                               atol=2e-6)
    # a distribution over the passes at every position, and no constant
    np.testing.assert_allclose(r["exit_p"].sum(0), 1.0, atol=1e-6)
    assert r["exit_p"].std(axis=1).min() > 1e-3
    # the passes differ: the loop does carry the state
    assert np.abs(r["passes"][0] - r["passes"][-1]).max() > 1e-3


LEAVES = ["tok_emb"] + [
    f"layer{i}_{n}" for i in range(MODEL["num_hidden_layers"]) for n in (
        "attn_norm_w", "attn_q_w", "attn_k_w", "attn_v_w", "attn_o_w",
        "attn_post_norm_w", "ffn_norm_w", "ffn_gate_w", "ffn_up_w",
        "ffn_down_w", "ffn_post_norm_w")] + [
    "final_norm_w", "lm_head_w", "exit_gate_w", "exit_gate_b"]


def test_every_leaf_is_named_and_exists_once(f32):
    b, r = f32
    assert b["params"] == LEAVES == list(r["want_grads"])


@pytest.mark.parametrize("name", LEAVES)
def test_float32_gradient_of_every_leaf(f32, name):
    """Each layer weight is used four times a step: its gradient is the
    sum over the uses (the reference's ``jax.grad`` through its Python
    loop), kept or recomputed."""
    _, r = f32
    got = np.asarray(r["grads"][name], np.float32)
    want = np.asarray(r["want_grads"][name], np.float32)
    assert got.shape == want.shape and np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-4 * np.abs(want).max())


def _kinds(p):
    return [[op.type for op in blk.ops] for blk in p.blocks]


def test_the_program_does_not_grow_with_the_number_of_passes():
    two, four = (program.build(dict(MODEL, dtype="float32",
                                    recompute="layer", total_ut_steps=n),
                               PARAMS) for n in (2, 4))
    for key in ("main", "startup", "test"):
        assert _kinds(two[key]) == _kinds(four[key]), key
    assert two["params"] == four["params"] == LEAVES
    main = four["main"].global_block()
    kinds = [op.type for op in main.ops]
    assert kinds.count("scan") == kinds.count("scan_grad") == 1
    assert kinds.count("mul") == 1                  # the one head
    assert kinds.count("softmax_with_cross_entropy") == 1
    assert kinds.count("exit_gate_loss") == 1
    assert "remat_segment" not in kinds             # the cuts are inside
    loop = main.ops[kinds.index("scan")]
    assert loop.attr("steps") == 4
    # every layer weight and the final norm enter as declared inputs
    assert loop.input("Static") == LEAVES[1:-3]
    sub = four["main"].blocks[loop.attr("sub_block")]
    assert [op.type for op in sub.ops] == \
        ["remat_segment"] * MODEL["num_hidden_layers"]
    # without recomputation the sub-block holds the layers' ops themselves
    plain = program.build(dict(MODEL, dtype="float32", recompute="none"),
                          PARAMS)["main"]
    body = [op.type for op in plain.blocks[1].ops]
    assert body.count("fused_attention") == MODEL["num_hidden_layers"]
    assert body.count("rms_norm") == 4 * MODEL["num_hidden_layers"] + 1


def test_one_pass_without_gate_and_sandwich_is_todays_builder():
    """``total_ut_steps: 1`` and ``norm_placement: "pre"`` build the Program
    the builder builds without the keys, to the op, and give its loss."""
    base = {k: v for k, v in MODEL.items() if k not in (
        "total_ut_steps", "norm_placement", "exit_entropy_coef",
        "early_exit_threshold")}

    def build(model):
        main, startup = fluid.Program(), fluid.Program()
        startup.random_seed = 3
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            A = dict(append_batch_size=False)
            ids = fluid.data("ids", [2, 24], "int64", **A)
            labels = fluid.data("labels", [48, 1], "int64", **A)
            out = decoder_lm.build(dict(model, dtype="float32"), ids, labels)
        return main, startup, out

    told = dict(base, total_ut_steps=1, norm_placement="pre",
                early_exit_threshold=1)
    feed = program.batch(MODEL, PARAMS, np.random.RandomState(0))
    losses = []
    for model in (base, told):
        main, startup, out = build(model)
        losses.append((_kinds(main), _kinds(startup),
                       [p.name for p in main.all_parameters()]))
        exe, scope = fluid.Executor(), fluid.Scope()
        exe.run(startup, scope=scope)
        losses[-1] += (float(exe.run(main, feed=feed, scope=scope,
                                     fetch_list=[out["loss"]])[0][0]),)
        exe.close()
    assert losses[0] == losses[1]
    assert "scan" not in losses[0][0][0] and "exit_gate_w" not in losses[0][2]


def test_recomputation_lowers_the_compiled_steps_temporaries():
    """The compiled train step keeps the layers' inputs and one layer's
    intermediates where it recomputes, every layer application's where it
    does not: XLA's own count of its temporaries."""
    params = {"batch": 2, "seq": 128}
    temp, kept = {}, {}
    for how in ("layer", "none"):
        b = built("float32", how, params=params, num_hidden_layers=4,
                  layer_types=["full_attention"] * 4)
        b["exe"].run(b["main"], feed=b["batch"], scope=b["scope"],
                     fetch_list=[b["loss"]])
        temp[how] = probe.step_memory(b["exe"])["temp"]
        from paddle_tpu.observability.metrics import REGISTRY
        label = f"{id(b['main'])}:v{b['main']._version}"
        kept[how] = next(
            child.value for labels, child in
            REGISTRY.get("loop_kept_bytes").items()
            if dict(labels)["program"] == label)
        b["exe"].close()
    assert temp["layer"] < 0.6 * temp["none"], temp
    # steps x layers arrays of [tokens, hidden] float32: a layer
    # application's input (the final norm is recomputed with the last layer)
    tokens = params["batch"] * params["seq"]
    inputs = STEPS * 4 * tokens * 64 * 4
    assert inputs <= kept["layer"] < 1.01 * inputs
    assert kept["none"] > 5 * kept["layer"]


def test_bfloat16_agrees_at_the_written_tolerance():
    b = built("bfloat16")
    try:
        r = run_both(b)
    finally:
        b["exe"].close()
    tol = reference.tolerance(b["model"])
    want = r["want"]
    assert abs(r["loss"] - float(want["loss"])) <= tol["loss"] * 10 \
        * abs(float(want["loss"]))
    got = np.concatenate([r["passes"].reshape(-1), r["exit_p"].reshape(-1)])
    err = np.abs(got - np.asarray(want["each"])).max() \
        / np.abs(np.asarray(want["each"])).max()
    assert err <= tol["each"], err
    # gradients: bfloat16 activations, the four uses summed in bfloat16
    for name in LEAVES:
        g = np.asarray(r["grads"][name], np.float32)
        w = np.asarray(r["want_grads"][name], np.float32)
        assert np.linalg.norm(g - w) <= 0.06 * np.linalg.norm(w), name


def test_float32_parts_in_bfloat16_show_against_a_float32_program(f32):
    """What the configuration states in float32 -- the norms, the softmax
    inputs, the gate and the exit distribution -- rounded through bfloat16
    in the reference moves the result hundreds of times further than the
    float32 program lies from the float32 reference: at float32 the tests
    see those parts, which the chip's check behind bfloat16 activations
    cannot (``reference.tolerance``)."""
    b, r = f32
    with jax.default_matmul_precision("highest"):
        low = reference.forward(weights_of(b), b["batch"], b["model"],
                                cast=jnp.bfloat16)
    want = np.asarray(r["want"]["each"])
    got = np.concatenate([r["passes"].reshape(-1), r["exit_p"].reshape(-1)])
    as_it_is = np.abs(got - want).max() / np.abs(want).max()
    moved = np.abs(np.asarray(low["each"]) - want).max() / np.abs(want).max()
    assert as_it_is < 2e-5 and moved > 100 * as_it_is, (as_it_is, moved)
    # the gate and the exit distribution alone: the probabilities move
    p_moved = np.abs(np.asarray(low["exit_p"])
                     - np.asarray(r["want"]["exit_p"])).max()
    assert p_moved > 1e-3


@pytest.mark.parametrize("recompute", ["layer", "none"])
def test_the_flash_kernels_inside_the_loop_keep_their_statistics(recompute):
    """From 256 tokens a sequence the layers' attention lowers to the flash
    kernels (interpreted here), inside the loop's sub-block: no
    ``fused_attention_grad`` is built there, JAX differentiates the kernels
    through their own ``custom_vjp``, whose forward rule keeps the rows'
    softmax statistic for the backward kernel, and each attention op says
    so once (``attention_backward_total{stats=saved}``), whatever the
    number of passes and however often JAX traces the body. Loss and
    gradients against the reference."""
    from paddle_tpu.observability.metrics import REGISTRY
    params = {"batch": 1, "seq": 256}
    b = built("float32", recompute, params=params)
    r = run_both(b)
    label = f"{id(b['main'])}:v{b['main']._version}"
    b["exe"].close()

    def count(family, **want):
        return sum(child.value for labels, child in
                   (REGISTRY.get(family) or {}).items()
                   if dict(labels)["program"] == label
                   and all(dict(labels)[k] == v for k, v in want.items()))

    layers_ = MODEL["num_hidden_layers"]
    assert count("attention_lowering_total", impl="pallas") == layers_
    assert count("attention_backward_total", stats="saved") == layers_
    assert count("attention_backward_total") == layers_
    assert abs(r["loss"] - float(r["want"]["loss"])) <= 1e-5 * abs(r["loss"])
    for name in ("layer0_attn_q_w", "layer1_attn_v_w", "tok_emb"):
        want = np.asarray(r["want_grads"][name], np.float32)
        np.testing.assert_allclose(
            np.asarray(r["grads"][name], np.float32), want, rtol=0,
            atol=2e-4 * np.abs(want).max())


@pytest.mark.parametrize("changed,labels,error", [
    ({"norm_placement": "post"}, True, "norm_placement='post'"),
    ({"total_ut_steps": 0}, True, "total_ut_steps=0"),
    ({"num_experts": 8, "num_experts_per_tok": 2}, True,
     "expert layers under total_ut_steps"),
    ({"num_nextn_predict_layers": 1}, True,
     "num_nextn_predict_layers under total_ut_steps"),
    ({"vocab_axis": "mp"}, True, "under total_ut_steps above 1"),
    ({"attention_impl": "pallas"}, True, "impl='pallas'"),
])
def test_what_the_builder_does_not_build_raises_by_name(changed, labels,
                                                        error):
    model = dict(MODEL, dtype="float32", **changed)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [2, 24], "int64", **A)
        targets = fluid.data("labels", [48, 1], "int64", **A) if labels \
            else None
        if "attention_impl" in changed:
            # built, and refused where it is lowered: no kernel off a TPU
            out = decoder_lm.build(model, ids, targets)
            exe, scope = fluid.Executor(), fluid.Scope()
            exe.run(startup, scope=scope)
            with pytest.raises(Exception, match="pallas"):
                exe.run(main, feed=program.batch(
                    MODEL, PARAMS, np.random.RandomState(0)), scope=scope,
                    fetch_list=[out["loss"]])
            return
        with pytest.raises((NotImplementedError, ValueError), match=error):
            decoder_lm.build(model, ids, targets)


def test_a_training_program_ignores_the_exit_threshold():
    a, b = (program.build(dict(MODEL, dtype="float32", recompute="layer",
                               early_exit_threshold=t), PARAMS)
            for t in (1, 0.5))
    assert _kinds(a["main"]) == _kinds(b["main"])
