"""What a latent-attention decoder with a multi-token-prediction module adds
to the decoder ops (``layers.*`` -> ``Program`` -> ``Executor`` on the CPU):
``latent_qkv`` against its one-line form and its registered grad lowering
against ``jax.vjp`` of the forward; the latent mixer, an expert block, the
module and a tiny GLM-4.7-Flash Program against
``benchmark/references/glm_4_7_flash_pretrain.py`` (which forms the two
score parts apart and never assembles a k) in loss, both cross-entropies,
routing and every parameter's gradient -- the table's and the head's, each
the sum of two, among them -- with each mechanism shown to matter; the eight
shares of a layer against the uncut reference; the catalog's DeepSeek-style
keys; what the builder still refuses."""
import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import registry
from paddle_tpu.models import decoder_lm
from benchmark.references import glm_4_7_flash_pretrain as reference
from tests.test_decoder_ops import close, rng, run_with_grads

B, S, HEADS, D_N, D_R = 2, 8, 3, 12, 4
D = D_N + D_R


def assembled(q, kv, k_r, theta=1e6):
    """The op's one-line form: cut, rotate, broadcast, concatenate."""
    q_n = q[:, :HEADS * D_N].reshape(B, S, HEADS, D_N)
    q_r = reference.rope(q[:, HEADS * D_N:].reshape(B, S, HEADS, D_R), theta)
    k_n = kv[:, :HEADS * D_N].reshape(B, S, HEADS, D_N)
    v = kv[:, HEADS * D_N:].reshape(B, S, HEADS, D)
    k_r = jnp.broadcast_to(reference.rope(k_r.reshape(B, S, D_R), theta)[
        :, :, None], (B, S, HEADS, D_R))
    t = lambda x: x.transpose(0, 2, 1, 3)                    # noqa: E731
    return (t(jnp.concatenate([q_n, q_r], -1)),
            t(jnp.concatenate([k_n, k_r], -1)), t(v))


def latent_feeds():
    return {"q": rng(0).randn(B * S, HEADS * D).astype("float32"),
            "kv": rng(1).randn(B * S, HEADS * (D_N + D)).astype("float32"),
            "k_r": rng(2).randn(B * S, D_R).astype("float32")}


@pytest.mark.parametrize("which", [0, 1, 2])
def test_latent_qkv_matches_its_one_line_form_and_gradient(which):
    feeds = latent_feeds()

    def build(q, kv, k_r):
        return layers.latent_qkv(q, kv, k_r, B, S, HEADS, D_N, D_R,
                                 theta=1e6)[which]
    out, grads, _, g, _ = run_with_grads(build, feeds, ["q", "kv", "k_r"])
    args = [jnp.asarray(feeds[n]) for n in ("q", "kv", "k_r")]
    close(out, assembled(*args)[which])
    want = jax.grad(lambda *a: jnp.sum(assembled(*a)[which] * g),
                    (0, 1, 2))(*args)
    for got, ref in zip(grads, want):
        close(got, ref)
    assert out.shape == (B, HEADS, S, D)


def _op_inputs(dtype=jnp.float32):
    feeds = latent_feeds()
    return {"Q": [jnp.asarray(feeds["q"], dtype)],
            "KV": [jnp.asarray(feeds["kv"], dtype)],
            "KRope": [jnp.asarray(feeds["k_r"], dtype)]}


ATTRS = {"batch": B, "seq": S, "heads": HEADS, "nope_dim": D_N,
         "rope_dim": D_R, "theta": 1e6}


def test_latent_qkv_grad_lowering_equals_jax_vjp_of_its_forward():
    """The registered grad lowering (the parts put back, the rotations
    turned back, the key head's gradient summed over the heads) against
    ``jax.vjp`` of the forward lowering, all three cotangents at once."""
    ctx, ins = registry.LowerCtx(dict(ATTRS)), _op_inputs()
    forward = registry.get("latent_qkv").lower

    def f(q, kv, k_r):
        out = forward(ctx, {"Q": [q], "KV": [kv], "KRope": [k_r]})
        return out["OutQ"][0], out["OutK"][0], out["OutV"][0]
    outs, vjp = jax.vjp(f, ins["Q"][0], ins["KV"][0], ins["KRope"][0])
    cots = [jnp.asarray(rng(3 + i).randn(*o.shape), jnp.float32)
            for i, o in enumerate(outs)]
    want = vjp(tuple(cots))
    got = registry.get("latent_qkv_grad").lower(ctx, dict(
        ins, **{"OutQ@GRAD": [cots[0]], "OutK@GRAD": [cots[1]],
                "OutV@GRAD": [cots[2]]}))
    for slot, ref in zip(("Q@GRAD", "KV@GRAD", "KRope@GRAD"), want):
        close(got[slot][0], ref, 1e-6)
    # dk_r is a sum over the heads: larger than any one head's part
    assert got["KRope@GRAD"][0].shape == (B * S, D_R)


def test_latent_qkv_grad_reads_its_cotangents_alone():
    """Traced with the forward's inputs and the cotangents as arguments, the
    grad lowering's jaxpr uses none of the inputs: no forward is lowered
    again, nothing of the forward is kept for it."""
    spec = {k: jax.ShapeDtypeStruct(v[0].shape, jnp.bfloat16)
            for k, v in _op_inputs().items()}
    out = jax.ShapeDtypeStruct((B, HEADS, S, D), jnp.bfloat16)
    ctx = registry.LowerCtx(dict(ATTRS))

    def grad(Q, KV, KRope, gq, gk, gv):
        got = registry.get("latent_qkv_grad").lower(ctx, {
            "Q": [Q], "KV": [KV], "KRope": [KRope], "OutQ@GRAD": [gq],
            "OutK@GRAD": [gk], "OutV@GRAD": [gv]})
        return got["Q@GRAD"][0], got["KV@GRAD"][0], got["KRope@GRAD"][0]
    jaxpr = jax.make_jaxpr(grad)(spec["Q"], spec["KV"], spec["KRope"], out,
                                 out, out)
    used = {v for eqn in jaxpr.jaxpr.eqns for v in eqn.invars
            if isinstance(v, jax.extend.core.Var)}
    assert not set(jaxpr.jaxpr.invars[:3]) & used
    assert set(jaxpr.jaxpr.invars[3:]) <= used
    assert [v.aval.dtype for v in jaxpr.jaxpr.outvars] == [jnp.bfloat16] * 3


def test_latent_qkv_keeps_float32_to_the_rotation():
    """bfloat16 in, bfloat16 out: the parts that are not rotated are the
    inputs' own bits, and the rotated ones are one rounding of the float32
    rotation of the inputs."""
    ctx, ins = registry.LowerCtx(dict(ATTRS)), _op_inputs(jnp.bfloat16)
    got = registry.get("latent_qkv").lower(ctx, ins)
    want = assembled(*(ins[k][0].astype(jnp.float32)
                       for k in ("Q", "KV", "KRope")))
    for slot, ref in zip(("OutQ", "OutK", "OutV"), want):
        assert got[slot][0].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(got[slot][0], np.float32),
            np.asarray(ref.astype(jnp.bfloat16), np.float32))


def test_latent_qkv_refuses_a_v_as_wide_as_nothing_it_can_attend_with():
    bad = dict(_op_inputs(), KV=[jnp.zeros((B * S, HEADS * (D_N + D + 2)))])
    with pytest.raises(ValueError, match=r"are not 3 heads of \[12 \| 4\]"):
        registry.get("latent_qkv").lower(registry.LowerCtx(dict(ATTRS)), bad)


# -- Kimi Linear's form: no rotation, v narrower than q / k, zero columns ----

D_V, D_PAD = 8, 24


def unrotated(q, kv, k_r):
    """``assembled`` without the rotation, v ``D_V`` wide, q and k written
    ``D_PAD`` wide with zeros behind their two parts."""
    q_n = q[:, :HEADS * D_N].reshape(B, S, HEADS, D_N)
    q_r = q[:, HEADS * D_N:].reshape(B, S, HEADS, D_R)
    k_n = kv[:, :HEADS * D_N].reshape(B, S, HEADS, D_N)
    v = kv[:, HEADS * D_N:].reshape(B, S, HEADS, D_V)
    k_r = jnp.broadcast_to(k_r.reshape(B, S, 1, D_R), (B, S, HEADS, D_R))
    zeros = jnp.zeros((B, S, HEADS, D_PAD - D))
    t = lambda x: x.transpose(0, 2, 1, 3)                    # noqa: E731
    return (t(jnp.concatenate([q_n, q_r, zeros], -1)),
            t(jnp.concatenate([k_n, k_r, zeros], -1)), t(v))


@pytest.mark.parametrize("which", [0, 1, 2])
def test_latent_qkv_unrotated_with_a_narrow_v_and_zero_columns(which):
    feeds = dict(latent_feeds(), kv=rng(1).randn(
        B * S, HEADS * (D_N + D_V)).astype("float32"))

    def build(q, kv, k_r):
        return layers.latent_qkv(q, kv, k_r, B, S, HEADS, D_N, D_R,
                                 rotate=False, value_dim=D_V,
                                 head_dim=D_PAD)[which]
    out, grads, _, g, _ = run_with_grads(build, feeds, ["q", "kv", "k_r"])
    args = [jnp.asarray(feeds[n]) for n in ("q", "kv", "k_r")]
    close(out, unrotated(*args)[which])
    want = jax.grad(lambda *a: jnp.sum(unrotated(*a)[which] * g),
                    (0, 1, 2))(*args)
    for got, ref in zip(grads, want):
        close(got, ref)
    assert out.shape == (B, HEADS, S, D_V if which == 2 else D_PAD)


def test_latent_qkv_says_what_it_assembled_and_keeps_old_attrs_as_they_were():
    import lowering_reports
    import paddle_tpu as fluid
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        A = dict(append_batch_size=False)
        q = fluid.data("q", [B * S, HEADS * D], "float32", **A)
        k_r = fluid.data("k_r", [B * S, D_R], "float32", **A)
        layers.latent_qkv(q, fluid.data("kv", [B * S, HEADS * (D_N + D)],
                                        "float32", **A), k_r, B, S, HEADS,
                          D_N, D_R, theta=1e6, value_dim=D, head_dim=D)
        layers.latent_qkv(q, fluid.data("kv2", [B * S, HEADS * (D_N + D_V)],
                                        "float32", **A), k_r, B, S, HEADS,
                          D_N, D_R, rotate=False, value_dim=D_V,
                          head_dim=D_PAD)
    old, new = [op for op in main.global_block().ops
                if op.type == "latent_qkv"]
    assert sorted(old.attrs) == sorted(ATTRS)       # the defaults add none
    assert (new.attr("rotate"), new.attr("value_dim"),
            new.attr("head_dim")) == (False, D_V, D_PAD)
    for salt, (attrs, ins) in enumerate((
            (ATTRS, _op_inputs()),
            (dict(ATTRS, rotate=False, value_dim=D_V, head_dim=D_PAD),
             dict(_op_inputs(), KV=[jnp.zeros((B * S, HEADS * (D_N + D_V)))])))):
        registry.get("latent_qkv").lower(registry.LowerCtx(
            dict(attrs), salt=salt + 1, program=main), ins)
    assert lowering_reports.read(
        lowering_reports.publish(main), "latent_qkv_lowering_total",
        "rotated", "head_dim", "value_dim") == {
            ("1", str(D), str(D)): 1, ("0", str(D_PAD), str(D_V)): 1}


# -- the model ---------------------------------------------------------------

MODEL = {
    "model_type": "glm4_moe_lite", "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_attention_heads": 3,
    "num_key_value_heads": 3, "q_lora_rank": 24, "kv_lora_rank": 20,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 4, "v_head_dim": 16,
    "n_routed_experts": 4, "num_experts_routed": 8, "first_expert_held": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1.8, "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "attention_bias": False,
    "hidden_act": "silu", "max_position_embeddings": 202752,
    "moe_row_budget": 48, "vocab_size": 64, "dtype": "float32"}
PARAMS = {"batch": 2, "seq": 16}
T = PARAMS["batch"] * PARAMS["seq"]


def built(model, seed=5, optimizer=None):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [PARAMS["batch"], PARAMS["seq"]], "int64", **A)
        labels = fluid.data("labels", [T, 1], "int64", **A)
        after = fluid.data("labels_next", [T, 1], "int64", **A)
        out = decoder_lm.build(model, ids, labels, after)
        params = [p.name for p in main.global_block().all_parameters()]
        if optimizer is None:
            fluid.append_backward(out["loss"])
        else:
            optimizer.minimize(out["loss"])
            decoder_lm.balance_experts(out, 1e-3)
    return {"main": main, "startup": startup, "out": out, "params": params}


def batch():
    tokens = rng(7).randint(0, MODEL["vocab_size"], (
        PARAMS["batch"], PARAMS["seq"] + 2)).astype(np.int32)
    return {"ids": np.ascontiguousarray(tokens[:, :-2]),
            "labels": np.ascontiguousarray(tokens[:, 1:-1]).reshape(-1, 1),
            "labels_next": np.ascontiguousarray(tokens[:, 2:]).reshape(-1, 1)}


def sharpened(scope, names):
    """Weights at which every mechanism shows: the up-projections and the
    rotary key's eight times their start (at std 0.02 and a hidden size of
    32 every score is near zero and the softmax uniform: positions, the
    scale and the latent norms would hardly show), the router's sixteen
    times (scores away from a half), and every norm's scale away from 1."""
    for n in names:
        v = scope.find_var(n)
        if n.endswith(("_q_b_w", "_kv_b_w", "_kv_a_w")):
            scope.set_var(n, v * 8.0)
        elif n.endswith("_router_w"):
            scope.set_var(n, v * 16.0)
        elif n.endswith("norm_w"):
            seed = sum(n.encode()) % 1000
            scope.set_var(n, jnp.asarray(
                1.0 + rng(seed).randn(*v.shape).astype("float32") * 0.3))


@pytest.fixture(scope="module")
def f32():
    b = built(MODEL)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(b["startup"], scope=scope)
    sharpened(scope, b["params"])
    weights = [np.array(scope.find_var(n)) for n in b["params"]]
    out = b["out"]
    e = len(out["expert_index"])
    fetch = [out[k].name for k in ("loss", "ce", "mtp_ce", "each",
                                   "mtp_each")] \
        + [n + "@GRAD" for n in b["params"]] \
        + [v.name for v in out["expert_index"] + out["expert_load"]
           + out["expert_dropped"] + out["expert_routed"]]
    got = exe.run(b["main"], feed=batch(), fetch_list=fetch, scope=scope)
    exe.close()
    n = len(b["params"])
    with jax.default_matmul_precision("highest"):
        w = [jnp.asarray(x) for x in weights]
        want = reference.forward(w, batch(), MODEL)
        grads = jax.grad(lambda w: reference.forward(w, batch(), MODEL)[
            "loss"])(w)
    rest = got[5 + n:]
    return {"b": b, "weights": weights,
            "loss": float(got[0].reshape(-1)[0]),
            "ce": float(got[1].reshape(-1)[0]),
            "mtp_ce": float(got[2].reshape(-1)[0]),
            "each": got[3].reshape(-1), "mtp_each": got[4].reshape(-1),
            "grads": dict(zip(b["params"], got[5:5 + n])),
            "index": np.stack(rest[:e]), "load": np.stack(rest[e:2 * e]),
            "dropped": np.stack(rest[2 * e:3 * e]), "routed": rest[3 * e:],
            "want": want, "want_grads": dict(zip(b["params"], grads))}


def test_program_equals_the_reference_in_both_losses_and_routing(f32):
    want = f32["want"]
    assert f32["loss"] == pytest.approx(float(want["loss"]), rel=2e-6)
    assert f32["ce"] == pytest.approx(float(want["ce"]), rel=2e-6)
    assert f32["mtp_ce"] == pytest.approx(float(want["mtp_ce"]), rel=2e-6)
    assert f32["loss"] == pytest.approx(f32["ce"] + 0.3 * f32["mtp_ce"],
                                        rel=1e-6)
    close(f32["each"], want["positions"], 5e-6)
    close(f32["mtp_each"], want["mtp_positions"], 5e-6)
    np.testing.assert_array_equal(np.sort(f32["index"], -1), want["experts"])
    np.testing.assert_array_equal(f32["load"], want["load"])
    assert f32["dropped"].sum() == 0
    norms = [np.mean(np.linalg.norm(r, axis=-1)) for r in f32["routed"]]
    np.testing.assert_allclose(norms, want["routed"], rtol=1e-5)
    # trunk positions, the module's mean and positions, 2 + 1 sparse layers
    assert len(want["each"]) == T + 1 + T + 3
    # the same norms, over the sum of sqrt(held experts a token chose)
    first, held = MODEL["first_expert_held"], MODEL["n_routed_experts"]
    here = ((f32["index"] >= first) & (f32["index"] < first + held)).sum(-1)
    np.testing.assert_allclose(want["held_norm"] * np.sqrt(here).sum(-1),
                               np.asarray(want["routed"]) * T, rtol=1e-5)
    ops = f32["b"]["main"].global_block().ops
    kinds = [op.type for op in ops]
    assert kinds.count("latent_qkv") == kinds.count("latent_qkv_grad") == 4
    assert kinds.count("fused_attention") == 4
    assert kinds.count("rotary_embedding") == 0     # inside latent_qkv
    assert kinds.count("moe_dispatch") == 3         # layers 1, 2, the module
    assert kinds.count("softmax_with_cross_entropy") == 2
    assert kinds.count("lookup_table_v2") == 2      # the table, twice
    # the router is LFM2's: sigmoid scores, chosen by score + bias
    routers = [op for op in ops if op.type == "moe_router"]
    assert all(op.attr("scoring") == "sigmoid" and "Bias" in op.inputs
               and op.attr("scale") == 1.8 and op.attr("norm_topk")
               for op in routers)
    out = f32["b"]["out"]
    assert len(out["expert_bias"]) == len(out["expert_load"]) == 3
    assert out["expert_bias"][-1].name == "mtp_moe_router_bias"


LEAVES = ["tok_emb", "layer0_attn_norm_w", "layer0_attn_q_a_w",
          "layer0_attn_q_a_norm_w", "layer0_attn_q_b_w", "layer0_attn_kv_a_w",
          "layer0_attn_kv_a_norm_w", "layer0_attn_kv_b_w", "layer0_attn_o_w",
          "layer0_ffn_norm_w", "layer0_ffn_gate_w", "layer0_ffn_up_w",
          "layer0_ffn_down_w", "layer1_attn_q_b_w", "layer1_attn_kv_a_w",
          "layer1_ffn_norm_w", "layer1_moe_router_w", "layer1_moe_gate_w",
          "layer1_moe_up_w", "layer1_moe_down_w", "layer1_moe_shared_gate_w",
          "layer1_moe_shared_up_w", "layer1_moe_shared_down_w",
          "layer2_attn_kv_b_w", "layer2_moe_router_w", "final_norm_w",
          "lm_head_w", "mtp_h_norm_w", "mtp_e_norm_w", "mtp_eh_w",
          "mtp_attn_norm_w", "mtp_attn_q_a_w", "mtp_attn_q_a_norm_w",
          "mtp_attn_q_b_w", "mtp_attn_kv_a_w", "mtp_attn_kv_a_norm_w",
          "mtp_attn_kv_b_w", "mtp_attn_o_w", "mtp_ffn_norm_w",
          "mtp_moe_router_w", "mtp_moe_gate_w", "mtp_moe_up_w",
          "mtp_moe_down_w", "mtp_moe_shared_gate_w", "mtp_moe_shared_up_w",
          "mtp_moe_shared_down_w", "mtp_final_norm_w"]


def test_the_leaves_tested_are_the_parameter_kinds_in_creation_order(f32):
    params = f32["b"]["params"]
    assert [p for p in params if p in LEAVES] == LEAVES
    assert len(params) == 67 and params[0] == "tok_emb"
    shapes = {n: tuple(w.shape) for n, w in zip(params, f32["weights"])}
    assert shapes["layer0_attn_q_a_w"] == (32, 24)
    assert shapes["layer0_attn_q_b_w"] == (24, 3 * (12 + 4))
    assert shapes["layer0_attn_kv_a_w"] == (32, 20 + 4)   # c_kv | k_r
    assert shapes["layer0_attn_kv_a_norm_w"] == (20,)
    assert shapes["layer0_attn_kv_b_w"] == (20, 3 * (12 + 16))
    assert shapes["layer0_attn_o_w"] == (3 * 16, 32)
    assert shapes["layer0_ffn_gate_w"] == (32, 48)        # the dense layer
    assert shapes["layer1_moe_gate_w"] == (4, 32, 16)     # the held experts
    assert shapes["layer1_moe_router_w"] == (32, 8)       # all routed
    assert shapes["layer1_moe_shared_gate_w"] == (32, 16)  # 16 x 1 shared
    assert shapes["mtp_eh_w"] == (64, 32)


@pytest.mark.parametrize("name", LEAVES)
def test_float32_gradient_of_every_parameter_kind(f32, name):
    got = np.asarray(f32["grads"][name], np.float32)
    want = np.asarray(f32["want_grads"][name], np.float32)
    assert got.shape == want.shape and np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=3e-5 * np.abs(want).max())


def test_the_table_and_the_head_collect_two_gradients_each(f32):
    """``tok_emb`` is read by the trunk's lookup and the module's, ``lm_
    head_w`` by both heads: ``append_backward`` renames the two
    contributions of each and sums them, and the sum is the reference's
    gradient of the total loss (the test above); the trunk's loss alone
    gives another."""
    block = f32["b"]["main"].global_block()
    for name in ("tok_emb", "lm_head_w"):
        sums = [op for op in block.ops if op.type == "sum"
                and op.outputs["Out"] == [name + "@GRAD"]]
        assert len(sums) == 1 and len(sums[0].inputs["X"]) == 2
    with jax.default_matmul_precision("highest"):
        trunk = jax.grad(lambda w: reference.forward(w, batch(), MODEL)[
            "ce"])([jnp.asarray(x) for x in f32["weights"]])
    by_name = dict(zip(f32["b"]["params"], trunk))
    for name in ("tok_emb", "lm_head_w"):
        total = np.asarray(f32["want_grads"][name])
        apart = np.abs(total - np.asarray(by_name[name])).max()
        assert apart > 1e-2 * np.abs(total).max()


@pytest.mark.parametrize("control,entry", [
    ("k_r_unrotated", "each"), ("no_latent_norms", "each"),
    ("scale_nope_only", "each"), ("no_routed_scale", "routed"),
    ("no_e_norm", "mtp"), ("labels_next_is_labels", "mtp")])
def test_each_mechanism_shows_at_sharpened_weights(f32, control, entry):
    """Every control of ``tools/glm_probe.py controls`` moves what the
    cell's check compares, by far more than float32 does: the CPU test sees
    each at sharpened weights, whatever seeded ones hide on the chip."""
    w = [jnp.asarray(x) for x in f32["weights"]]
    feed = batch()
    if control == "labels_next_is_labels":
        feed["labels_next"], control = feed["labels"], None
    with jax.default_matmul_precision("highest"):
        other = reference.forward(w, feed, MODEL, control=control)
    want = f32["want"]
    key = {"each": "positions", "mtp": "mtp_positions", "routed": "routed"}[
        entry]
    moved = np.abs(np.asarray(other[key]) - np.asarray(want[key])).max() \
        / np.abs(np.asarray(want[key])).max()
    assert moved > 3e-3, (control, moved)


def test_latent_norms_in_bfloat16_move_the_reference_by_bfloat16(f32):
    with jax.default_matmul_precision("highest"):
        other = reference.forward([jnp.asarray(x) for x in f32["weights"]],
                                  batch(), MODEL, control="bf16_latent_norms")
    moved = np.abs(np.asarray(other["positions"])
                   - np.asarray(f32["want"]["positions"])).max()
    assert 1e-5 < moved < 1e-1


def test_one_adamw_step_updates_every_bias_the_modules_too(f32):
    """The first AdamW step from the sharpened weights is the reference's
    gradient through AdamW (the moments start at zero), and ``balance_
    experts`` moves the module's selection bias with the trunk's: down by
    the rate where an expert got more than the mean load, up where less."""
    lr, wd, eps = 1e-3, 0.1, 1e-8
    b = built(MODEL, optimizer=fluid.optimizer.AdamW(
        lr, weight_decay=wd, beta1=0.9, beta2=0.95, epsilon=eps))
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(b["startup"], scope=scope)
    for n, w in zip(f32["b"]["params"], f32["weights"]):
        scope.set_var(n, jnp.asarray(w))
    loss, = exe.run(b["main"], feed=batch(), scope=scope,
                    fetch_list=[b["out"]["loss"].name])
    assert float(loss.reshape(-1)[0]) == pytest.approx(f32["loss"], rel=1e-6)
    for n, w in zip(f32["b"]["params"], f32["weights"]):
        g = np.asarray(f32["want_grads"][n], np.float64)
        # sign(g) where g is sure, and far above the update's epsilon
        big = np.abs(g) > max(1e-2 * np.abs(g).max(), 1e-5)
        want = w * (1 - lr * wd) - lr * g / (np.abs(g) + eps)
        got = np.asarray(scope.find_var(n), np.float64)
        np.testing.assert_allclose(got[big], want[big], rtol=0, atol=5e-5,
                                   err_msg=n)
    for bias, load in zip(b["out"]["expert_bias"], f32["load"]):
        want = 1e-3 * np.sign(load.mean() - load)
        np.testing.assert_allclose(np.asarray(scope.find_var(bias.name)),
                                   want, atol=1e-7)
    exe.close()


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: two shares of four experts each (the tests'
    eighths) give, with the shared expert counted once, what the uncut
    reference gives for the whole layer -- the program's layer on each
    share against the reference's layer over all eight experts."""
    H, W, E, k, tokens = 32, 16, 8, 2, 24
    x = rng(0).randn(tokens, H).astype("float32")
    router = rng(1).randn(H, E).astype("float32")
    gate, up = (rng(s).randn(E, H, W).astype("float32") * 0.3 for s in (2, 3))
    down = rng(4).randn(E, W, H).astype("float32") * 0.3
    shared = [rng(5).randn(H, W).astype("float32") * 0.3,
              rng(6).randn(H, W).astype("float32") * 0.3,
              rng(7).randn(W, H).astype("float32") * 0.3]
    model = dict(MODEL, n_routed_experts=E, num_experts_routed=E,
                 first_expert_held=0)
    with jax.default_matmul_precision("highest"):
        whole, _, load = reference.expert_layer(
            jnp.asarray(x), router, gate, up, down, jnp.zeros((E,)), model)
        whole = whole + reference.swiglu(jnp.asarray(x), *shared)
    assert int(load.sum()) == tokens * k

    def share(first):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            xv = fluid.data("x", [tokens, H], "float32",
                            append_batch_size=False)
            cfg = dict(MODEL, first_expert_held=first, moe_row_budget=None)
            out, aux = decoder_lm.experts(xv, cfg, "moe")
        exe, scope = fluid.Executor(), fluid.Scope()
        exe.run(startup, scope=scope)
        held = slice(first, first + 4)
        for name, value in (("moe_router_w", router),
                            ("moe_gate_w", gate[held]),
                            ("moe_up_w", up[held]),
                            ("moe_down_w", down[held]),
                            ("moe_shared_gate_w", shared[0]),
                            ("moe_shared_up_w", shared[1]),
                            ("moe_shared_down_w", shared[2])):
            scope.set_var(name, jnp.asarray(value))
        got = exe.run(main, feed={"x": x}, scope=scope,
                      fetch_list=[out.name, aux["routed"].name])
        exe.close()
        return got
    first, second = share(0), share(4)
    # each share's output holds the shared expert once: take it off one
    shared_part = first[0] - first[1]
    close(first[1] + second[1] + shared_part, whole, 1e-5)
    close(second[0] - second[1], shared_part, 1e-6)
    assert np.abs(first[1]).max() > 0 and np.abs(second[1]).max() > 0


def test_deepseek_style_keys_read_as_the_repos_own():
    """``first_k_dense_replace``, ``n_routed_experts``, ``n_shared_experts``
    with the derived width and ``topk_method: "noaux_tc"`` build op for op
    what ``num_dense_layers``, ``num_experts``,
    ``shared_expert_intermediate_size``, ``router_scoring: "sigmoid"`` and
    ``use_expert_bias`` build."""
    own = {k: v for k, v in MODEL.items()
           if k not in ("first_k_dense_replace", "n_routed_experts",
                        "n_shared_experts", "topk_method", "n_group",
                        "topk_group")}
    own.update(num_dense_layers=1, num_experts=4, router_scoring="sigmoid",
               shared_expert_intermediate_size=16, use_expert_bias=True)

    def ops(model):
        return [(op.type, sorted(op.inputs), sorted(op.outputs),
                 sorted(op.attrs.items()))
                for op in built(model)["main"].global_block().ops]
    assert ops(MODEL) == ops(own)
    assert decoder_lm._is_dense(MODEL, 0) and not decoder_lm._is_dense(
        MODEL, 1)
    assert decoder_lm._shared_width(MODEL) == 16
    assert decoder_lm._scoring(MODEL) == "sigmoid"
    assert decoder_lm._scoring({"num_experts": 4}) == "softmax"


@pytest.mark.parametrize("change,error,match", [
    ({"rope_scaling": {"type": "linear", "factor": 4}},
     NotImplementedError, "rope_scaling inside latent attention"),
    ({"partial_rotary_factor": 0.5}, NotImplementedError,
     "partial_rotary_factor other than 1 inside latent"),
    ({"layer_types": ["full_attention", "sliding_attention",
                      "full_attention"], "sliding_window": 8},
     NotImplementedError, "full_attention layers only"),
    ({"n_group": 8, "topk_group": 4}, NotImplementedError,
     "group-limited routing"),
    ({"topk_method": "group_limited_greedy"}, NotImplementedError,
     "topk_method"),
    ({"n_shared_experts": 2}, NotImplementedError, "shared experts"),
    ({"num_nextn_predict_layers": 2}, NotImplementedError,
     "more than one multi-token-prediction module"),
    ({"moe_intermediate_size": 0}, ValueError, "a shared expert needs")])
def test_what_the_builder_does_not_build_raises_by_name(change, error, match):
    with pytest.raises(error, match=match):
        decoder_lm._check(dict(MODEL, **change))


@pytest.mark.parametrize("change", [{"q_lora_rank": None},
                                    {"v_head_dim": 12}])
def test_what_latent_attention_refused_until_pr_51_builds(change):
    """``q_lora_rank: null`` (q from one projection) and a ``v_head_dim``
    other than the q / k head's width went from ``_check_latent`` with Kimi
    Linear's latent attention (tests/test_decoder_kimi_linear.py)."""
    decoder_lm._check(dict(MODEL, **change))
    params = built(dict(MODEL, **change))["params"]
    assert ("layer0_attn_q_w" in params) == (change.get(
        "q_lora_rank", 1) is None)


def test_the_module_needs_its_second_label():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        with pytest.raises(ValueError, match="labels_next"):
            decoder_lm.build(MODEL, fluid.data("ids", [2, 16], "int64", **A),
                             fluid.data("labels", [T, 1], "int64", **A))


def test_every_key_of_the_published_config_is_read_or_named():
    """The catalog row's keys: each is read by ``decoder_lm`` (its name in
    the source) or is one of the four that say nothing a builder acts on
    here (``model_type``, the position limit, ``num_key_value_heads``,
    which equals the head count under latent attention, and
    ``attention_bias`` / ``hidden_act``, which ``_REQUIRED`` holds)."""
    import inspect
    import json
    import os
    source = inspect.getsource(decoder_lm)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    published = json.load(open(os.path.join(
        root, "benchmark", "configs", "glm_4_7_flash.json")))["published"]
    unread = [k for k in published if f'"{k}"' not in source]
    assert sorted(unread) == ["max_position_embeddings", "model_type"]
