"""models/decoder_lm.py as granite-4.0-h-micro at a tiny size on the CPU
against the plain reference (benchmark/references/granite_pretrain.py, whose
scan is the recurrence position by position) on seeded weights: the loss,
every position's loss and every parameter's gradient -- tight in float32, at
the written tolerance in bfloat16 --; each of the four multipliers, the tied
head and the missing rotary shown to matter (a reference told otherwise
disagrees); and what the builder still refuses, by name."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark.programs import granite_pretrain as program
from benchmark.references import granite_pretrain as reference
from paddle_tpu.models import decoder_lm

MODEL = {
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 96,
    "shared_intermediate_size": 96, "vocab_size": 512, "hidden_act": "silu",
    "layer_types": ["mamba", "attention", "mamba"], "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "rope_scaling": None, "attention_bias": False,
    "position_embedding_type": "nope", "qk_norm": "none",
    "tie_word_embeddings": True, "attention_multiplier": 0.0625,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "normalization_function": "rmsnorm",
    "num_local_experts": 0, "num_experts_per_tok": 0}
PARAMS = {"batch": 2, "seq": 24}    # three chunks of 8 a sequence


def built(dtype, seed=5, **changed):
    """The tiny Program with backward, started from ``seed``."""
    model = dict(MODEL, dtype=dtype, **changed)
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [PARAMS["batch"], PARAMS["seq"]], "int64", **A)
        labels = fluid.data("labels", [PARAMS["batch"] * PARAMS["seq"], 1],
                            "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        pairs = fluid.append_backward(out["loss"])
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    # at std 0.02 every score is near zero, the softmax even whatever scales
    # it and the layer's output small: sharper q and k and larger v and o,
    # so that the attention's scale and positions can matter to the loss
    for name, times in (("q", 40), ("k", 40), ("v", 10), ("o", 10)):
        name = f"layer1_attn_{name}_w"
        scope.set_var(name, scope.find_var(name) * times)
    batch = program.batch(model, PARAMS, np.random.RandomState(seed))
    return {"model": model, "main": main, "out": out, "exe": exe,
            "scope": scope, "batch": batch,
            "params": [p.name for p, _ in pairs],
            "grads": [g.name for _, g in pairs]}


def run_both(b):
    out = b["out"]
    got = b["exe"].run(b["main"], feed=b["batch"], scope=b["scope"],
                       fetch_list=[out["loss"].name, out["each"].name]
                       + b["grads"])
    ordered = [p.name for p in b["main"].global_block().all_parameters()]
    weights = [jnp.asarray(b["scope"].find_var(n), jnp.float32)
               for n in ordered]
    with jax.default_matmul_precision("highest"):
        want = reference.forward(weights, b["batch"], b["model"])
        want_grads = dict(zip(ordered, jax.grad(lambda ws: reference.forward(
            ws, b["batch"], b["model"])["loss"])(weights)))
    return {"loss": float(np.asarray(got[0]).reshape(-1)[0]),
            "each": np.asarray(got[1], np.float32).reshape(-1),
            "grads": dict(zip(b["params"], got[2:])), "ordered": ordered,
            "weights": weights, "want": want, "want_grads": want_grads}


@pytest.fixture(scope="module")
def f32():
    b = built("float32")
    yield b, run_both(b)
    b["exe"].close()


def test_float32_loss_and_every_position_match_the_reference(f32):
    _, r = f32
    assert abs(r["loss"] - float(r["want"]["loss"])) <= 2e-6 * r["loss"]
    np.testing.assert_allclose(r["each"], np.asarray(r["want"]["each"]),
                               atol=5e-6)
    # a tied head: no output matrix among the parameters, and the state-space
    # layer's scalars a head are float32 whatever dtype says
    assert "lm_head_w" not in r["ordered"] and r["ordered"][0] == "tok_emb"


LEAVES = ["tok_emb", "layer0_mamba_norm_w", "layer0_mamba_in_w",
          "layer0_mamba_conv_w", "layer0_mamba_conv_b",
          "layer0_mamba_dt_bias", "layer0_mamba_A_log", "layer0_mamba_D",
          "layer0_mamba_gated_norm_w", "layer0_mamba_out_w",
          "layer0_ffn_norm_w", "layer0_ffn_gate_w", "layer0_ffn_up_w",
          "layer0_ffn_down_w", "layer1_attn_norm_w", "layer1_attn_q_w",
          "layer1_attn_k_w", "layer1_attn_v_w", "layer1_attn_o_w",
          "layer1_ffn_norm_w", "layer1_ffn_gate_w", "layer1_ffn_up_w",
          "layer1_ffn_down_w", "layer2_mamba_norm_w", "layer2_mamba_in_w",
          "layer2_mamba_conv_w", "layer2_mamba_conv_b",
          "layer2_mamba_dt_bias", "layer2_mamba_A_log", "layer2_mamba_D",
          "layer2_mamba_gated_norm_w", "layer2_mamba_out_w",
          "layer2_ffn_norm_w", "layer2_ffn_gate_w", "layer2_ffn_up_w",
          "layer2_ffn_down_w", "final_norm_w"]


def test_every_leaf_is_named(f32):
    _, r = f32
    assert r["ordered"] == LEAVES == list(r["want_grads"])


@pytest.mark.parametrize("name", LEAVES)
def test_float32_gradient_of_every_leaf(f32, name):
    _, r = f32
    got = np.asarray(r["grads"][name], np.float32)
    want = np.asarray(r["want_grads"][name], np.float32)
    assert got.shape == want.shape and np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=5e-5 * np.abs(want).max())


@pytest.mark.parametrize("told", [
    {"embedding_multiplier": 1}, {"residual_multiplier": 1.0},
    {"logits_scaling": 1}, {"attention_multiplier": 0.25}, "untied",
    "rotary"], ids=str)
def test_each_multiplier_the_tied_head_and_no_rotary_matter(f32, told):
    """The same weights through a model told one thing otherwise: the
    comparison that passed is off by fifty times what it was off by (a tiny
    random model's loss hardly feels its one attention layer, so the bar is
    relative, not the cell's tolerance). ``untied``
    gives the Program an output matrix of its own (seeded apart from the
    table); ``rotary`` turns the rotary embedding on."""
    _, r = f32
    if told in ("untied", "rotary"):
        change = ({"tie_word_embeddings": False} if told == "untied"
                  else {"position_embedding_type": "rope"})
        b = built("float32", **change)
        try:
            names = [p.name for p in b["main"].global_block()
                     .all_parameters()]
            assert ("lm_head_w" in names) == (told == "untied")
            for n, w in zip(r["ordered"], r["weights"]):
                b["scope"].set_var(n, w)
            got = b["exe"].run(b["main"], feed=b["batch"], scope=b["scope"],
                               fetch_list=[b["out"]["each"].name])[0]
        finally:
            b["exe"].close()
        other = np.asarray(got, np.float32).reshape(-1)
    else:
        with jax.default_matmul_precision("highest"):
            other = np.asarray(reference.forward(
                r["weights"], f32[0]["batch"], dict(MODEL, **told))["each"])
    want = np.asarray(r["want"]["each"])
    right = np.abs(r["each"] - want).max()
    assert right <= 1e-5 * want.max()
    assert np.abs(other - want).max() > 50 * max(right, 1e-6 * want.max())


def test_bfloat16_agrees_at_the_written_tolerance():
    """bfloat16 weights and activations against the float32 reference on
    the same (bfloat16-representable) weights: inside ``tolerance(model)``,
    gradients to a few bfloat16 roundings of the largest entry."""
    b = built("bfloat16")
    try:
        r = run_both(b)
    finally:
        b["exe"].close()
    tol = reference.tolerance(b["model"])
    want_loss = float(r["want"]["loss"])
    want_each = np.asarray(r["want"]["each"])
    assert abs(r["loss"] - want_loss) <= tol["loss"] * want_loss
    assert np.abs(r["each"] - want_each).max() <= tol["each"] * want_each.max()
    block = b["main"].global_block()
    assert block.var("tok_emb").dtype == "float32"
    for name in ("layer0_mamba_A_log", "layer0_mamba_D",
                 "layer0_mamba_dt_bias"):
        assert block.var(name).dtype == "float32"
    assert block.var("layer0_mamba_in_w").dtype == "bfloat16"
    for name in LEAVES:
        got = np.asarray(r["grads"][name], np.float32)
        want = np.asarray(r["want_grads"][name], np.float32)
        # a head's scalar sums thousands of bfloat16-rounded terms
        room = 0.15 if want.ndim == 1 and "norm" not in name else 0.05
        assert np.abs(got - want).max() <= room * np.abs(want).max(), name


def test_mamba_parameters_start_as_mamba2_starts_them():
    b = built("bfloat16", seed=11)
    try:
        get = lambda n: np.asarray(b["scope"].find_var(n), np.float32)  # noqa
        a = np.exp(get("layer0_mamba_A_log"))
        assert ((a >= 1) & (a <= 16)).all() and a.std() > 1
        dt = np.log1p(np.exp(get("layer0_mamba_dt_bias")))
        assert ((dt >= 0.99e-3) & (dt <= 1.01e-1)).all()
        assert (get("layer2_mamba_D") == 1).all()
        w = get("layer0_mamba_conv_w")
        assert np.abs(w).max() <= 0.5 and w.std() > 0.2
        assert not np.array_equal(get("layer0_mamba_A_log"),
                                  get("layer2_mamba_A_log"))
    finally:
        b["exe"].close()


@pytest.mark.parametrize("key,value,error", [
    ("layer_types", ["mamba", "gated_delta", "mamba"], NotImplementedError),
    ("mamba_n_groups", 8, NotImplementedError),
    ("mamba_proj_bias", True, NotImplementedError),
    ("position_embedding_type", "alibi", NotImplementedError),
    ("num_local_experts", 8, NotImplementedError),
    ("normalization_function", "layernorm", NotImplementedError),
    ("qk_norm", "layer", NotImplementedError),
    ("mamba_expand", 3, ValueError)])
def test_what_the_builder_does_not_build_raises_by_name(key, value, error):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = fluid.data("ids", [2, 8], "int64", append_batch_size=False)
        labels = fluid.data("labels", [16, 1], "int64",
                            append_batch_size=False)
        with pytest.raises(error, match=(
                "gated_delta" if key == "layer_types" else
                "shared experts" if key == "num_local_experts" else
                "mamba_n_heads" if key == "mamba_expand" else key)):
            decoder_lm.build(dict(MODEL, **{key: value}), ids, labels)
