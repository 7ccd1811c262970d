"""models/decoder_lm.py at a tiny size on the CPU against the plain reference
(benchmark/references/olmoe_pretrain.py) on seeded weights: the total loss,
every position's loss, the chosen experts, and the gradient of every kind of
parameter -- tight in float32, at the written tolerance in bfloat16 -- and
what the compiled step holds of sorts and grouped matmuls."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark.programs import olmoe_pretrain as program
from benchmark.references import olmoe_pretrain as reference
from paddle_tpu.models import decoder_lm

MODEL = {
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "intermediate_size": 32, "vocab_size": 512, "hidden_act": "silu",
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "norm_topk_prob": False,
    "tie_word_embeddings": False, "router_aux_loss_coef": 0.01,
    "router_z_loss_coef": 0.001, "learning_rate": 4e-4, "adam_beta1": 0.9,
    "adam_beta2": 0.95, "adam_epsilon": 1e-8, "weight_decay": 0.1}
PARAMS = {"batch": 2, "seq": 24}     # 48 tokens: no size equals hidden


def built(dtype, seed=5):
    """The tiny Program with backward, started from ``seed``; returns what
    the builder returns plus executor, scope, a seeded batch and the
    parameter gradients' names."""
    model = dict(MODEL, dtype=dtype)
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
    batch = program.batch(model, PARAMS, np.random.RandomState(seed))
    return {"model": model, "main": main, "out": out, "exe": exe,
            "scope": scope, "batch": batch,
            "params": [p.name for p, _ in pairs],
            "grads": [g.name for _, g in pairs]}


def run_both(b):
    out = b["out"]
    names = ([out["loss"].name, out["each"].name]
             + [v.name for v in out["expert_index"]]
             + [v.name for v in out["expert_load"]] + b["grads"])
    got = b["exe"].run(b["main"], feed=b["batch"], fetch_list=names,
                       scope=b["scope"])
    L = b["model"]["num_hidden_layers"]
    ordered = [p.name for p in b["main"].global_block().all_parameters()]
    weights = [b["scope"].find_var(n) for n in ordered]
    want = reference.loss(weights, b["batch"], b["model"], PARAMS)

    def total(ws):      # the reference's loss as a function of the weights
        return reference.loss(ws, b["batch"], b["model"], PARAMS)["loss"]
    want_grads = dict(zip(ordered, jax.grad(total)(
        [jnp.asarray(w, jnp.float32) for w in weights])))
    return {"loss": float(np.asarray(got[0]).reshape(-1)[0]),
            "each": np.asarray(got[1], np.float32).reshape(-1),
            "index": np.sort(np.stack(got[2:2 + L]), axis=-1),
            "load": np.stack(got[2 + L:2 + 2 * L]),
            "grads": dict(zip(b["params"], got[2 + 2 * L:])),
            "want": want, "want_grads": want_grads}


@pytest.fixture(scope="module")
def f32():
    b = built("float32")
    yield b, run_both(b)
    b["exe"].close()


def test_float32_loss_and_every_position_match_the_reference(f32):
    _, r = f32
    assert abs(r["loss"] - float(r["want"]["loss"])) <= 2e-6 * r["loss"]
    np.testing.assert_allclose(r["each"], np.asarray(r["want"]["positions"]),
                               atol=5e-6)


def test_float32_chosen_experts_match_and_every_assignment_is_served(f32):
    b, r = f32
    np.testing.assert_array_equal(r["index"], np.asarray(r["want"]["experts"]))
    tokens = PARAMS["batch"] * PARAMS["seq"]
    assert (r["load"].sum(axis=1)
            == tokens * b["model"]["num_experts_per_tok"]).all()


KINDS = ["tok_emb", "layer0_attn_norm_w", "layer0_attn_q_w",
         "layer0_attn_q_norm_w", "layer0_attn_k_norm_w", "layer0_attn_v_w",
         "layer0_attn_o_w", "layer0_ffn_norm_w", "layer0_moe_router_w",
         "layer0_moe_gate_w", "layer0_moe_up_w", "layer0_moe_down_w",
         "layer1_moe_router_w", "layer1_moe_down_w", "layer1_attn_k_w",
         "final_norm_w", "lm_head_w"]


@pytest.mark.parametrize("name", KINDS)
def test_float32_gradient_of_every_parameter_kind(f32, name):
    _, r = f32
    got = np.asarray(r["grads"][name], np.float32)
    want = np.asarray(r["want_grads"][name], np.float32)
    assert got.shape == want.shape and np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * np.abs(want).max())


def test_bfloat16_agrees_at_the_written_tolerance():
    """bfloat16 weights and activations against the float32 reference on
    the same (bfloat16-representable) weights: inside ``tolerance(model)``,
    the chosen experts inside ``flipped_share(model)``, gradients to a few
    bfloat16 roundings of the largest entry."""
    b = built("bfloat16")
    try:
        r = run_both(b)
    finally:
        b["exe"].close()
    tol = reference.tolerance(b["model"])
    want_loss = float(r["want"]["loss"])
    want_each = np.asarray(r["want"]["positions"])
    assert abs(r["loss"] - want_loss) <= tol["loss"] * want_loss
    assert np.abs(r["each"] - want_each).max() <= tol["each"] * want_each.max()
    differ = (r["index"] != np.asarray(r["want"]["experts"])).mean()
    assert differ <= reference.flipped_share(b["model"])
    block = b["main"].global_block()
    assert block.var("tok_emb").dtype == "float32"
    assert block.var("layer0_moe_router_w").dtype == "float32"
    assert block.var("layer0_moe_gate_w").dtype == "bfloat16"
    for name in KINDS:
        got = np.asarray(r["grads"][name], np.float32)
        want = np.asarray(r["want_grads"][name], np.float32)
        assert np.abs(got - want).max() <= 0.05 * np.abs(want).max(), name


def test_config_a_builder_does_not_build_yet_raises_by_name():
    for key, value in (("moe_router_logit_softcapping", 30.0),
                       ("hidden_act", "gelu"), ("attention_bias", True)):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            ids = fluid.data("ids", [2, 8], "int64", append_batch_size=False)
            labels = fluid.data("labels", [16, 1], "int64",
                                append_batch_size=False)
            with pytest.raises(NotImplementedError, match=key):
                decoder_lm.build(dict(MODEL, **{key: value}), ids, labels)


def test_compiled_step_holds_one_sort_a_layer_and_no_dense_fallback(f32):
    """D11: every grad op re-lowers its forward. What survives in the
    compiled step (CPU here; the grouped-matmul kernels are counted for a
    described v5e in test_pallas_attention_mosaic.py): one sort a layer --
    the copy ``moe_dispatch_grad`` traces is merged with the forward's --
    and no product whose shape says "every expert on every token"."""
    b, _ = f32
    step = next(reversed(b["exe"]._cache.values()))
    text = step.executable.as_text()
    assert len(re.findall(r"\ssort\(", text)) == \
        b["model"]["num_hidden_layers"]
    scopes = collections.Counter(
        re.findall(r'op_name="[^"]*?/(moe_\w+?|swiglu\w*?)#\d+/', text))
    assert {"moe_router", "moe_dispatch", "moe_expert_matmul",
            "moe_expert_matmul_grad", "moe_combine"} <= set(scopes)
    tokens, E = PARAMS["batch"] * PARAMS["seq"], b["model"]["num_experts"]
    width = b["model"]["intermediate_size"]
    assert f"f32[{E},{tokens},{width}]" not in text
    assert f"f32[{tokens},{E},{width}]" not in text
    # the traced program: 3 grouped matmul ops a layer and their 3 grad ops
    types = collections.Counter(op.type for op in b["main"].global_block().ops)
    L = b["model"]["num_hidden_layers"]
    assert types["moe_expert_matmul"] == 3 * L
    assert types["moe_expert_matmul_grad"] == 3 * L
    assert types["moe_dispatch"] == types["moe_combine"] == L
