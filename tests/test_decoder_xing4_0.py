"""What a decoder whose residual path is four streams mixed by
manifold-constrained hyper-connections adds (``layers.*`` -> ``Program`` ->
``Executor`` on the CPU): the two ops ``hyper_connection_pre`` / ``_post``
against their one-line forms, their registered grads against ``jax.vjp``
and what the grad ops keep; ``H_res`` doubly stochastic; YaRN inside
``latent_qkv`` against HF's formulas; a tiny Xing4.0 Program against
``benchmark/references/xing4_0_pretrain.py`` in loss, streams, routing and
every parameter kind's gradient, with ``alpha`` of order 1 and random ``b``
so that the dynamic path, the clamp, all 20 iterations, the factor 2, the
YaRN blend and the softmax scale's ``mscale^2`` each show; ``hc_mult`` 1 and
absent build the one-stream Program; the eight shares of a layer against the
uncut reference; what the builder still refuses."""
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import registry
from paddle_tpu.models import decoder_lm
from paddle_tpu.observability import lowerings
from paddle_tpu.ops import decoder_ops
from benchmark.references import xing4_0_pretrain as reference
from tests import lowering_reports
from tests.test_decoder_ops import close, rng

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, C, TOKENS = 4, 8, 12
K = 2 * N + N * N
HC = {"streams": N, "iters": 20, "eps": 1e-6, "clamp_min": -30.0,
      "clamp_max": 30.0}
HC_MODEL = {"hc_mult": N, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
            "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30}


def hc_feeds(std=1.5):
    return {"X": rng(0).randn(TOKENS, N * C).astype("float32"),
            "Phi": rng(1).randn(N * C, K).astype("float32") * 0.3,
            "B": rng(2).randn(K).astype("float32") * std,
            "Alpha": np.asarray([0.9, 1.1, 1.3], "float32")}


def lower(kind, ins, attrs=HC, salt=1, program=None):
    return registry.get(kind).lower(
        registry.LowerCtx(dict(attrs), salt=salt, program=program),
        {k: [None if v is None else jnp.asarray(v)] for k, v in ins.items()})


def one_line(f, model=HC_MODEL):
    """The reference's forms on the ops' operands: X as ``[T, n, C]``."""
    X = jnp.asarray(f["X"]).reshape(TOKENS, N, C)
    with jax.default_matmul_precision("highest"):
        pre, post, res = reference.coefficients(
            X, [jnp.asarray(f[k]) for k in ("Phi", "B", "Alpha")], model)
    return X, pre, post, res


# -- the two ops -------------------------------------------------------------

def test_pre_gives_the_reference_coefficients_and_the_read():
    f = hc_feeds()
    out = lower("hyper_connection_pre", f)
    X, pre, post, res = one_line(f)
    coef = out["Coef"][0]
    assert coef.shape == (TOKENS, K) and coef.dtype == jnp.float32
    close(coef[:, :N], pre, 2e-6)
    close(coef[:, N:2 * N], post, 2e-6)
    close(coef[:, 2 * N:].reshape(TOKENS, N, N), res, 2e-6)
    close(out["U"][0], jnp.einsum("tn,tnc->tc", pre, X), 2e-6)
    assert float(post.max()) > 1.0          # the factor 2


@pytest.mark.parametrize("std", [0.0, 0.5, 1.0])
def test_h_res_is_doubly_stochastic_to_1e_4(std):
    """At logits within a few units (Phi a third of the other tests'): 20
    iterations leave the rows' sums within 1e-4 of 1 (the columns' are 1 by
    the last division); the wider the logits, the slower (std 1.5: 2e-3)."""
    f = hc_feeds(std)
    coef = lower("hyper_connection_pre", dict(f, Phi=f["Phi"] / 3))["Coef"][0]
    res = np.asarray(coef[:, 2 * N:]).reshape(TOKENS, N, N)
    assert (res > 0).all()
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-4)


@pytest.mark.parametrize("attrs,moved", [
    (dict(HC, iters=5), True), (dict(HC, clamp_min=-0.5, clamp_max=0.5), True),
    (dict(HC, eps=1e-2), True), (dict(HC), False)])
def test_each_attr_of_the_read_side_is_read(attrs, moved):
    f = hc_feeds(3.0)
    base = lower("hyper_connection_pre", f)["Coef"][0]
    other = lower("hyper_connection_pre", f, attrs)["Coef"][0]
    assert (float(jnp.abs(base - other).max()) > 1e-5) == moved


def test_post_is_h_res_x_plus_h_post_y():
    f = hc_feeds()
    coef = lower("hyper_connection_pre", f)["Coef"][0]
    y = rng(3).randn(TOKENS, C).astype("float32")
    out = lower("hyper_connection_post",
                {"X": f["X"], "Y": y, "Coef": coef})["Out"][0]
    X, _, post, res = one_line(f)
    want = jnp.einsum("tij,tjc->tic", res, X) + post[:, :, None] * y[:, None]
    close(out, want.reshape(TOKENS, N * C), 2e-6)


def test_registered_grads_equal_jax_vjp_of_the_forwards():
    f = hc_feeds()
    y = rng(3).randn(TOKENS, C).astype("float32")
    du = rng(4).randn(TOKENS, C).astype("float32")
    dcoef = rng(5).randn(TOKENS, K).astype("float32")
    dout = rng(6).randn(TOKENS, N * C).astype("float32")

    def pre(x, phi, b, alpha):
        out = lower("hyper_connection_pre",
                    {"X": x, "Phi": phi, "B": b, "Alpha": alpha})
        return out["U"][0], out["Coef"][0]
    args = [jnp.asarray(f[k]) for k in ("X", "Phi", "B", "Alpha")]
    (_, coef), pullback = jax.vjp(pre, *args)
    want = pullback((jnp.asarray(du), jnp.asarray(dcoef)))
    got = lower("hyper_connection_pre_grad",
                {**f, "U@GRAD": du, "Coef@GRAD": dcoef})
    for slot, w in zip(("X", "Phi", "B", "Alpha"), want):
        close(got[slot + "@GRAD"][0], w, 1e-5)
        assert float(jnp.abs(w).max()) > 0
    # a Coef no write side read: its cotangent is zero, not an error
    alone = lower("hyper_connection_pre_grad",
                  {**f, "U@GRAD": du, "Coef@GRAD": None})
    close(alone["X@GRAD"][0], pullback((jnp.asarray(du),
                                        jnp.zeros_like(coef)))[0], 1e-5)

    def post(x, y, coef):
        return lower("hyper_connection_post",
                     {"X": x, "Y": y, "Coef": coef})["Out"][0]
    _, pullback = jax.vjp(post, args[0], jnp.asarray(y), coef)
    want = pullback(jnp.asarray(dout))
    got = lower("hyper_connection_post_grad",
                {"X": f["X"], "Y": y, "Coef": coef, "Out@GRAD": dout})
    for slot, w in zip(("X", "Y", "Coef"), want):
        close(got[slot + "@GRAD"][0], w, 1e-5)
    assert float(jnp.abs(got["Coef@GRAD"][0][:, :N]).max()) == 0   # H_pre


def test_ops_keep_float32_inside_and_return_the_state_s_dtype():
    f = hc_feeds()
    f["X"] = jnp.asarray(f["X"], jnp.bfloat16)
    out = lower("hyper_connection_pre", f)
    assert out["U"][0].dtype == jnp.bfloat16
    assert out["Coef"][0].dtype == jnp.float32
    wide = dict(f, X=jnp.asarray(f["X"], jnp.float32))
    close(out["Coef"][0], lower("hyper_connection_pre", wide)["Coef"][0],
          1e-6)                 # the same numbers: the bfloat16 state is exact
    y = jnp.asarray(rng(3).randn(TOKENS, C), jnp.bfloat16)
    state = lower("hyper_connection_post",
                  {"X": f["X"], "Y": y, "Coef": out["Coef"][0]})["Out"][0]
    assert state.dtype == jnp.bfloat16 and state.shape == (TOKENS, N * C)


def test_the_ops_count_their_lowerings_by_part_and_direction():
    main = fluid.Program()
    f = hc_feeds()
    coef = lower("hyper_connection_pre", f, salt=1, program=main)["Coef"][0]
    y = np.zeros((TOKENS, C), "float32")
    lower("hyper_connection_post", {"X": f["X"], "Y": y, "Coef": coef},
          salt=2, program=main)
    lower("hyper_connection_pre_grad",
          {**f, "U@GRAD": y, "Coef@GRAD": None}, salt=3, program=main)
    lower("hyper_connection_post_grad",
          {"X": f["X"], "Y": y, "Coef": coef, "Out@GRAD": f["X"]},
          salt=4, program=main)
    # a bfloat16 state: the read side's products by pieces, both ways
    half = dict(f, X=jnp.asarray(f["X"], jnp.bfloat16))
    lower("hyper_connection_pre", half, salt=5, program=main)
    lower("hyper_connection_pre_grad",
          {**half, "U@GRAD": y, "Coef@GRAD": None}, salt=6, program=main)
    lower("hyper_connection_post", {"X": half["X"], "Y": y, "Coef": coef},
          salt=7, program=main)
    assert lowering_reports.read(
        lowering_reports.publish(main), "hyper_connection_lowering_total",
        "part", "direction", "streams", "iters", "product") == {
            ("pre", "forward", "4", "20", "highest"): 1,
            ("pre", "backward", "4", "20", "highest"): 1,
            ("pre", "forward", "4", "20", "pieces"): 1,
            ("pre", "backward", "4", "20", "pieces"): 1,
            ("post", "forward", "4", "20", "none"): 2,
            ("post", "backward", "4", "20", "none"): 1}


# -- the product with Phi by bfloat16 pieces ---------------------------------

def whole(pieces):
    """The pieces added, the smallest first, in float32."""
    hi, mid, lo = (p.astype(jnp.float32) for p in pieces)
    return (lo + mid) + hi


@pytest.mark.parametrize("scale", [0.02, 1.0, 3e-7, 5e5])
def test_three_bfloat16_pieces_add_up_to_a_float32_phi_bit_for_bit(scale):
    phi = jnp.asarray(rng(1).randn(512, K) * scale, jnp.float32)
    pieces = decoder_ops.bf16_pieces(phi)
    assert [p.dtype for p in pieces] == [jnp.bfloat16] * 3
    assert np.array_equal(np.asarray(whole(pieces)), np.asarray(phi))
    # and each piece is needed: two leave some of the 24 bits out
    assert not np.array_equal(np.asarray(whole(pieces[:2] + [0 * pieces[2]])),
                              np.asarray(phi))


@pytest.fixture(scope="module")
def products():
    """At the cell's contraction (k = 14,336, c = 24; 512 tokens): each of
    the read side's three products with Phi -- forward ``z``, ``dPhi``,
    ``dX`` -- as (float64's, precision ``highest``'s, the pieces', and what a
    product that keeps ONE bfloat16 piece of its float32 operands gives,
    summed in float64: its error is the dropped pieces')."""
    T, k = 512, 14336
    x = jnp.asarray(rng(0).randn(T, k), jnp.bfloat16)
    phi = jnp.asarray(rng(1).randn(k, K) * 0.02, jnp.float32)
    g = jnp.asarray(rng(2).randn(K, T), jnp.float32)
    xf = x.astype(jnp.float32)

    def f64(a):
        return np.asarray(a.astype(jnp.float32), np.float64)

    def all_three(exact):
        z, pullback = jax.vjp(
            lambda a, b: decoder_ops._phi_product(a, b, exact), xf, phi)
        return (z,) + tuple(pullback(g))[::-1]
    one = (lambda a: a.astype(jnp.bfloat16))
    want = (f64(phi).T @ f64(x).T, f64(x).T @ f64(g).T, f64(g).T @ f64(phi).T)
    dropped = (f64(one(phi)).T @ f64(x).T, f64(x).T @ f64(one(g)).T,
               f64(one(g)).T @ f64(one(phi)).T)
    return dict(zip(("z", "dphi", "dx"), zip(
        want, all_three(False), all_three(True), dropped)))


@pytest.mark.parametrize("which", ["z", "dphi", "dx"])
def test_products_by_pieces_keep_what_highest_keeps(products, which):
    """The error is the norm of the difference over the float64 product's
    (the largest entry's error is the tail of 12 k to 7 M roundings and
    moves by a factor of 2 with the seed and the backend's summation
    order; the norm does not)."""
    want, highest, pieces, one_piece = products[which]

    def error(got):
        got = np.asarray(got, np.float64)
        assert got.shape == want.shape
        return np.linalg.norm(got - want) / np.linalg.norm(want)
    assert 0 < error(highest) < 1e-6
    assert error(pieces) <= 2 * error(highest)
    assert error(one_piece) > 50 * error(highest)     # a lost piece shows


def dots(kind, state_dtype):
    """(precision, operand element types) of every ``dot_general`` in the
    lowered text of one read-side lowering under a state of that dtype."""
    f = {k: jnp.asarray(v) for k, v in hc_feeds().items()}
    f["X"] = f["X"].astype(state_dtype)
    more = {} if kind == "hyper_connection_pre" else {
        "U@GRAD": jnp.ones((TOKENS, C), state_dtype),
        "Coef@GRAD": jnp.ones((TOKENS, K), jnp.float32)}
    text = jax.jit(lambda f: lower(kind, {**f, **more})).lower(f).as_text()
    found = re.findall(r"stablehlo\.dot_general .*precision = \[(\w+), (\w+)\]"
                       r" : \(tensor<[\dx]*x(\w+)>, tensor<[\dx]*x(\w+)>\)",
                       text)
    assert len(found) == text.count("dot_general") > 0
    return found


@pytest.mark.parametrize("kind,count", [("hyper_connection_pre", 1),
                                        ("hyper_connection_pre_grad", 3)])
def test_the_state_s_dtype_alone_chooses_pieces_or_highest(kind, count):
    assert dots(kind, jnp.bfloat16) == [
        ("DEFAULT", "DEFAULT", "bf16", "bf16")] * count
    assert dots(kind, jnp.float32) == [
        ("HIGHEST", "HIGHEST", "f32", "f32")] * count
    assert dots(kind, jnp.float16) == [
        ("HIGHEST", "HIGHEST", "f32", "f32")] * count


def test_a_bfloat16_state_s_grads_are_the_float32_state_s():
    """The same numbers in: the pieces' read side and ``highest``'s give the
    same coefficients and gradients, to float32 rounding."""
    f = hc_feeds()
    du = jnp.asarray(rng(4).randn(TOKENS, C), jnp.bfloat16)
    dcoef = rng(5).randn(TOKENS, K).astype("float32")
    half = dict(f, X=jnp.asarray(f["X"], jnp.bfloat16))
    wide = dict(half, X=half["X"].astype(jnp.float32))
    got = lower("hyper_connection_pre_grad",
                {**half, "U@GRAD": du, "Coef@GRAD": dcoef})
    want = lower("hyper_connection_pre_grad",
                 {**wide, "U@GRAD": du.astype(jnp.float32),
                  "Coef@GRAD": dcoef})
    for slot in ("Phi", "B", "Alpha"):
        close(got[slot + "@GRAD"][0], want[slot + "@GRAD"][0], 2e-6)
    assert got["X@GRAD"][0].dtype == jnp.bfloat16
    close(got["X@GRAD"][0].astype(jnp.float32), want["X@GRAD"][0], 2e-2)


def test_the_probe_s_hyper_mode_reads_the_products_of_the_four_lowerings(
        capsys):
    """``tools/xing4_0_probe.py hyper`` at the rehearsal's sizes on the CPU
    (a debug run: its times mean nothing): the read side's lowerings hold
    one and three products, the write side's none."""
    from tools import xing4_0_probe
    assert xing4_0_probe.main(["hyper", "--rehearsal", "--calls", "2"]) == 0
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    rows = got["lowerings"]
    assert list(rows) == ["pre", "post", "post_grad", "pre_grad"]
    assert [len(rows[k]["products"]) for k in rows] == [1, 0, 0, 3]
    for row in rows.values():
        assert row["ms"] > 0 and row["device_ms"] >= row["product_ms"]
    assert xing4_0_probe._products(
        "ENTRY %main (a: f32[2]) -> f32[2] {\n"
        "  %d = f32[2] dot(%a, %a)\n  %f = f32[2] fusion(%a), calls=%c\n"
        "  %g = f32[2] fusion(%a), calls=%e\n}\n"
        "%c (p: f32[2]) -> f32[2] {\n  %x = f32[2] convolution(%p, %p)\n}\n"
        "%e (p: f32[2]) -> f32[2] {\n  %y = f32[2] add(%p, %p)\n}\n"
    ) == {"d", "f", "x"}


# -- YaRN inside latent_qkv --------------------------------------------------

YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


def hf_yarn(theta, dim, scaling):
    """HF ``_compute_yarn_parameters`` written out once more, apart from the
    op's ``yarn_inv_freq`` and the reference's."""
    factor, original = scaling["factor"], scaling[
        "original_max_position_embeddings"]

    def find_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))) / (
            2 * math.log(theta))
    low = max(math.floor(find_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(find_dim(scaling["beta_slow"])), dim - 1)
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (1 / (factor * pos)) * ramp + (1 / pos) * (1 - ramp)


def test_latent_qkv_rotates_at_hf_s_yarn_frequencies():
    B, S, h, d_n, d_r = 1, 64, 2, 8, 64
    q = rng(0).randn(B * S, h * (d_n + d_r)).astype("float32")
    kv = rng(1).randn(B * S, h * (d_n + d_n + d_r)).astype("float32")
    k_r = rng(2).randn(B * S, d_r).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        vs = [fluid.data(n, list(a.shape), "float32", **A)
              for n, a in (("q", q), ("kv", kv), ("k_r", k_r))]
        outs = layers.latent_qkv(*vs, B, S, h, d_n, d_r, theta=10000.0,
                                 scaling=YARN)
        plain = layers.latent_qkv(*vs, B, S, h, d_n, d_r, theta=10000.0)
    op, plain_op = [o for o in main.global_block().ops
                    if o.type == "latent_qkv"]
    assert op.attr("scaling") == "yarn" and op.attr("factor") == 64.0
    assert op.attr("attention_factor") == 1.0      # m(1) / m(1)
    assert "scaling" not in plain_op.attrs
    exe = fluid.Executor()
    got_q, got_k, _, plain_q = exe.run(
        main, feed={"q": q, "kv": kv, "k_r": k_r},
        fetch_list=[outs[0], outs[1], outs[2], plain[0]])
    exe.close()
    inv_freq = hf_yarn(10000.0, d_r, YARN)
    # the blend is no plain rescale: fast dimensions as they were, slow
    # ones over 64, a ramp between
    base = 10000.0 ** (-np.arange(0, d_r, 2) / d_r)
    assert inv_freq[0] == base[0] and inv_freq[-1] == pytest.approx(
        base[-1] / 64)
    ang = np.arange(S)[:, None] * inv_freq[None, :]
    cos, sin = np.cos(ang), np.sin(ang)

    def rotated(x):             # [S, ..., d_r], HF's rotate_half
        x1, x2 = x[..., :d_r // 2], x[..., d_r // 2:]
        shape = (S,) + (1,) * (x.ndim - 2) + (d_r // 2,)
        c, s = cos.reshape(shape), sin.reshape(shape)
        return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)
    want_q = rotated(q[:, h * d_n:].reshape(S, h, d_r)).transpose(1, 0, 2)
    close(got_q[0, :, :, d_n:], want_q, 2e-5)
    close(got_k[0, 0, :, d_n:], rotated(k_r), 2e-5)
    assert np.abs(got_q - plain_q).max() > 0.1
    assert lowerings.LATER_LABELS["latent_qkv_lowering_total"] == {
        "frequencies": "default"}


def test_rope_scaling_attrs_follow_hf_s_attention_factor():
    attrs = layers.nn._rope_scaling_attrs
    m = 0.1 * math.log(64) + 1.0
    assert attrs(None) == {} and attrs({"rope_type": "default"}) == {}
    assert attrs(YARN)["attention_factor"] == 1.0
    assert attrs({k: v for k, v in YARN.items() if "mscale" not in k})[
        "attention_factor"] == pytest.approx(m)
    assert attrs(dict(YARN, mscale=0.707, mscale_all_dim=1))[
        "attention_factor"] == pytest.approx(
            (0.0707 * math.log(64) + 1.0) / m)
    assert attrs(dict(YARN, attention_factor=1.25))["attention_factor"] == 1.25
    with pytest.raises(NotImplementedError, match="rope_type"):
        attrs({"rope_type": "llama3", "factor": 8})


# -- the model ---------------------------------------------------------------

MODEL = {
    "model_type": "xing4_0", "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_attention_heads": 3,
    "num_key_value_heads": 3, "q_lora_rank": 24, "kv_lora_rank": 20,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "n_routed_experts": 4, "num_experts_routed": 8, "first_expert_held": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "num_nextn_predict_layers": 0, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": dict(YARN, original_max_position_embeddings=8),
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -3, "mhc_h_res_clamp_max": 3,
    "hc_alpha_init": 1.0, "hc_bias_std": 2.0,
    "tie_word_embeddings": False, "attention_bias": False,
    "hidden_act": "silu", "max_position_embeddings": 262144, "ep_size": 1,
    "moe_layer_freq": 1, "moe_row_budget": 48, "vocab_size": 64,
    "dtype": "float32"}
PARAMS = {"batch": 2, "seq": 16}
T = PARAMS["batch"] * PARAMS["seq"]


def built(model, seed=5, backward=True):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [PARAMS["batch"], PARAMS["seq"]], "int64", **A)
        labels = fluid.data("labels", [T, 1], "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        params = [p.name for p in main.global_block().all_parameters()]
        if backward:
            fluid.append_backward(out["loss"])
    return {"main": main, "startup": startup, "out": out, "params": params}


def batch():
    tokens = rng(7).randint(0, MODEL["vocab_size"], (
        PARAMS["batch"], PARAMS["seq"] + 1)).astype(np.int32)
    return {"ids": np.ascontiguousarray(tokens[:, :-1]),
            "labels": np.ascontiguousarray(tokens[:, 1:]).reshape(-1, 1)}


def sharpened(scope, names):
    """Weights at which every mechanism shows: the up-projections and the
    rotary key's eight times their start (scores away from zero: positions
    and the softmax scale show), the router's sixteen times, every norm's
    scale away from 1, and Phi ten times its start (the dynamic part of the
    coefficients of the order of the static one)."""
    for n in names:
        v = scope.find_var(n)
        if n.endswith(("_q_b_w", "_kv_b_w", "_kv_a_w")):
            scope.set_var(n, v * 8.0)
        elif n.endswith("_router_w"):
            scope.set_var(n, v * 16.0)
        elif n.endswith("_hc_phi"):
            scope.set_var(n, v * 10.0)
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
    e, n = len(out["expert_index"]), len(b["params"])
    coefs = [op.output("Coef")[0] for op in b["main"].global_block().ops
             if op.type == "hyper_connection_pre"]
    fetch = [out["loss"].name, out["each"].name] \
        + [p + "@GRAD" for p in b["params"]] \
        + [v.name for v in out["expert_index"] + out["expert_load"]
           + out["expert_dropped"] + out["stream_states"]] + coefs
    got = exe.run(b["main"], feed=batch(), fetch_list=fetch, scope=scope)
    exe.close()
    with jax.default_matmul_precision("highest"):
        w = [jnp.asarray(x) for x in weights]
        want = reference.forward(w, batch(), MODEL)
        grads = jax.grad(lambda w: reference.forward(w, batch(), MODEL)[
            "loss"])(w)
    rest = got[2 + n:]
    layers_ = MODEL["num_hidden_layers"]
    return {"b": b, "weights": weights, "loss": float(got[0].reshape(-1)[0]),
            "each": got[1].reshape(-1),
            "grads": dict(zip(b["params"], got[2:2 + n])),
            "index": np.stack(rest[:e]), "load": np.stack(rest[e:2 * e]),
            "dropped": np.stack(rest[2 * e:3 * e]),
            "states": rest[3 * e:3 * e + layers_],
            "coefs": rest[3 * e + layers_:],
            "want": want, "want_grads": dict(zip(b["params"], grads))}


def test_program_equals_the_reference_in_loss_streams_and_routing(f32):
    want = f32["want"]
    assert f32["loss"] == pytest.approx(float(want["loss"]), rel=2e-6)
    close(f32["each"], want["positions"], 5e-6)
    np.testing.assert_array_equal(np.sort(f32["index"], -1), want["experts"])
    np.testing.assert_array_equal(f32["load"], want["load"])
    assert f32["dropped"].sum() == 0
    rms = np.stack([np.sqrt(np.mean(np.square(
        s.reshape(T, N, MODEL["hidden_size"])), axis=(0, 2)))
        for s in f32["states"]])
    np.testing.assert_allclose(rms, want["stream_rms"], rtol=1e-5)
    # the streams differ: no hyper-connection is blind to H_res here
    assert rms.std(axis=1).min() > 1e-2 * rms.mean()
    # positions, 2 sparse layers' held norms, 3 blocks x 4 streams
    assert len(want["each"]) == T + 2 + 3 * N
    # at these wide logits (b of std 2 held to +-3, Phi sharpened) twenty
    # iterations leave the rows' sums a hundredth off: what the model does
    assert float(want["res_sums"]) < 5e-2
    kinds = [op.type for op in f32["b"]["main"].global_block().ops]
    for kind in ("hyper_connection_pre", "hyper_connection_post"):
        assert kinds.count(kind) == kinds.count(kind + "_grad") == 6
    assert kinds.count("latent_qkv") == 3


def test_program_h_res_ends_on_a_column_normalisation_in_every_sub_layer(f32):
    """The columns' sums are 1 by the last division, the rows' as near as
    twenty iterations bring them at the test's wide logits (the op's own
    test holds them to 1e-4 at gentle ones)."""
    assert len(f32["coefs"]) == 6
    for coef in f32["coefs"]:
        res = coef[:, 2 * N:].reshape(T, N, N)
        np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)
        np.testing.assert_allclose(res.sum(-1), 1.0, atol=5e-2)
        assert np.abs(res.sum(-1) - 1.0).max() > 1e-6
        assert 0 < coef[:, :N].min() and coef[:, :N].max() < 1
        assert coef[:, N:2 * N].max() > 1           # 2 sigmoid


def test_the_grad_ops_keep_the_state_the_branch_s_output_and_coefficients(
        f32):
    """Asserted on the Program: a grad op's inputs are the forward's inputs
    and the cotangents, no forward output (``U``, ``Coef``, ``Out``): X
    (and the three parameters) for the read side, X, y and the coefficients
    for the write side."""
    block = f32["b"]["main"].global_block()
    pre = [op for op in block.ops if op.type == "hyper_connection_pre_grad"]
    post = [op for op in block.ops if op.type == "hyper_connection_post_grad"]
    assert len(pre) == len(post) == 6
    for op in pre:
        assert sorted(op.inputs) == ["Alpha", "B", "Coef@GRAD", "Phi",
                                     "U@GRAD", "X"]
    for op in post:
        assert sorted(op.inputs) == ["Coef", "Out@GRAD", "X", "Y"]
        assert block.var(op.input("Coef")[0]).dtype == "float32"
    kept = {n for op in pre + post for s in ("X", "Y", "Coef")
            for n in op.inputs.get(s, [])}
    wide = [n for n in kept if tuple(block.var(n).shape) == (
        T, N * MODEL["hidden_size"])]
    assert len(wide) == 6       # a state a sub-layer, no float32 copy of it


LEAVES = ["tok_emb", "layer0_attn_hc_phi", "layer0_attn_hc_b",
          "layer0_attn_hc_alpha", "layer0_attn_norm_w", "layer0_attn_q_a_w",
          "layer0_attn_q_a_norm_w", "layer0_attn_q_b_w", "layer0_attn_kv_a_w",
          "layer0_attn_kv_a_norm_w", "layer0_attn_kv_b_w", "layer0_attn_o_w",
          "layer0_ffn_hc_phi", "layer0_ffn_hc_b", "layer0_ffn_hc_alpha",
          "layer0_ffn_norm_w", "layer0_ffn_gate_w", "layer0_ffn_up_w",
          "layer0_ffn_down_w", "layer1_attn_hc_phi", "layer1_attn_q_b_w",
          "layer1_ffn_hc_phi", "layer1_ffn_hc_b", "layer1_ffn_hc_alpha",
          "layer1_ffn_norm_w", "layer1_moe_router_w", "layer1_moe_gate_w",
          "layer1_moe_up_w", "layer1_moe_down_w", "layer1_moe_shared_gate_w",
          "layer1_moe_shared_up_w", "layer1_moe_shared_down_w",
          "layer2_attn_hc_alpha", "layer2_ffn_hc_phi", "layer2_attn_kv_b_w",
          "final_norm_w", "lm_head_w"]


def test_the_leaves_tested_are_the_parameter_kinds(f32):
    params = f32["b"]["params"]
    assert set(LEAVES) <= set(params) and params[0] == "tok_emb"
    # table; 15 a block + 3 dense or 7 sparse; final norm and head
    assert len(params) == 1 + (15 + 3) + 2 * (15 + 7) + 2
    shapes = {n: tuple(w.shape) for n, w in zip(params, f32["weights"])}
    assert shapes["layer0_attn_hc_phi"] == (N * 32, K)
    assert shapes["layer0_attn_hc_b"] == (K,)
    assert shapes["layer0_attn_hc_alpha"] == (3,)
    assert all(f32["weights"][params.index(n)].dtype == np.float32
               for n in params if "_hc_" in n)


@pytest.mark.parametrize("name", LEAVES)
def test_float32_gradient_of_every_parameter_kind(f32, name):
    got = np.asarray(f32["grads"][name], np.float32)
    want = np.asarray(f32["want_grads"][name], np.float32)
    assert got.shape == want.shape and np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=5e-5 * np.abs(want).max())


@pytest.mark.parametrize("control", [
    "static_only", "sinkhorn_5", "post_without_2", "no_clamp",
    "scale_without_mscale", "no_yarn_blend", "hc_bfloat16"])
def test_each_mechanism_shows_at_sharpened_weights(f32, control):
    """A reference with one departure is off the program by far more than
    the two agree: the dynamic part of the coefficients, all 20 iterations,
    the factor 2, the clamp, mscale^2 on the softmax scale, YaRN's blend,
    float32 inside the coefficients."""
    with jax.default_matmul_precision("highest"):
        other = reference.forward([jnp.asarray(w) for w in f32["weights"]],
                                  batch(), MODEL, control=control)
    agree = np.abs(np.asarray(f32["want"]["each"]) - np.concatenate([
        f32["each"], np.asarray(f32["want"]["each"])[T:]])).max()
    apart = np.abs(np.asarray(other["each"])
                   - np.asarray(f32["want"]["each"])).max()
    assert agree < 1e-4 and apart > 30 * max(agree, 1e-5), (agree, apart)


def test_one_stream_programs_are_what_they_were():
    """``hc_mult`` absent and ``hc_mult: 1`` build, for GLM-4.7-Flash's
    rehearsal, one Program op for op, with the one-stream residual adds and
    no hyper-connection op."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm_4_7_flash.json")) as f:
        config = json.load(f)
    model = {k: v for k, v in config.items() if k != "rehearsal"}
    model.update(config["rehearsal"])

    def ops(model):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            A = dict(append_batch_size=False)
            out = decoder_lm.build(
                model, fluid.data("ids", [2, 32], "int64", **A),
                fluid.data("labels", [64, 1], "int64", **A),
                fluid.data("labels_next", [64, 1], "int64", **A))
            fluid.append_backward(out["loss"])
        return [(op.type, sorted(op.inputs.items()),
                 sorted(op.outputs.items()), sorted(op.attrs.items()))
                for op in main.global_block().ops], out
    absent, out = ops(model)
    one, _ = ops(dict(model, hc_mult=1))
    assert absent == one and "stream_states" not in out
    kinds = [o[0] for o in absent]
    assert not [k for k in kinds if k.startswith("hyper_connection")]
    # two residual adds a block (2 layers and the module), and the shared
    # expert's onto the routed ones' in the two sparse blocks
    assert kinds.count("elementwise_add") == 2 * 3 + 2


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test at this model's router (sigmoid, top-2 of 8,
    scale 2): two shares of four experts each (the tests' eighths) give,
    with the shared expert counted once, what the uncut reference gives for
    the whole layer."""
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
    shared_part = first[0] - first[1]       # the shared expert, once
    close(first[1] + second[1] + shared_part, whole, 1e-5)
    close(second[0] - second[1], shared_part, 1e-6)
    assert np.abs(first[1]).max() > 0 and np.abs(second[1]).max() > 0


@pytest.mark.parametrize("change,error,match", [
    ({"rope_scaling": {"type": "linear", "factor": 4}}, NotImplementedError,
     "rope_scaling inside latent attention"),
    ({"rope_scaling": YARN, "kv_lora_rank": None, "head_dim": 8},
     NotImplementedError, "rope_scaling="),
    ({"num_nextn_predict_layers": 1}, NotImplementedError,
     "prediction module over a multi-stream trunk"),
    ({"total_ut_steps": 2, "n_routed_experts": None, "num_experts_routed": 0,
      "moe_row_budget": None}, NotImplementedError, "inside the scan op"),
    ({"expert_axis": "ep", "num_experts_routed": 4, "moe_row_budget": None},
     NotImplementedError, "under a mesh"),
    ({"norm_placement": "sandwich"}, NotImplementedError, "sandwich"),
    ({"hc_mult": 0.5}, ValueError, "whole number of residual streams"),
    ({"hc_sinkhorn_iters": None, "hc_eps": None}, None, None)])
def test_what_the_builder_does_not_build_raises_by_name(change, error, match):
    model = dict(MODEL, **change)
    if error is None:       # the four keys are needed beside hc_mult
        for key in [k for k, v in change.items() if v is None]:
            del model[key]
        with pytest.raises(ValueError, match="hc_mult=4 needs"):
            decoder_lm._check(model)
        return
    with pytest.raises(error, match=match):
        decoder_lm._check(model)


def test_every_key_of_the_published_config_is_read_or_named():
    """The catalog row's keys: each is read by ``decoder_lm`` (its name in
    the source) or is one the configuration file names as unread
    (``assumed.unused_keys``) or ``_REQUIRED`` holds."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4_0_29b_a4b.json")) as f:
        config = json.load(f)
    with open(decoder_lm.__file__) as f:
        source = f.read()
    unread = {"model_type", "ep_size", "num_key_value_heads",
              "max_position_embeddings"}
    for key in config["published"]:
        assert f'"{key}"' in source or key in unread, key
    for key in unread - {"model_type"}:
        assert key in config["assumed"]["unused_keys"]
