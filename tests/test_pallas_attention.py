"""Flash-attention Pallas kernel: parity vs the composed lowering.

Mirrors the reference OpTest pattern (numpy/composed oracle vs the fused kernel;
reference: multihead_matmul fusion is tested by comparing fused vs unfused graphs).
Runs in interpreter mode on CPU -- the same kernel code compiles on TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.ops import pallas_attention as pa


def _qkv(B=2, H=2, S=128, D=32, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, H, S, D), dtype)
    v = jax.random.normal(ks[2], (B, H, S, D), dtype)
    bias = jnp.where(jax.random.bernoulli(ks[3], 0.9, (B, 1, 1, S)),
                     0.0, -1e4).astype(jnp.float32)
    return q, k, v, bias


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_bias", [False, True])
def test_flash_forward_parity(causal, use_bias):
    q, k, v, bias = _qkv()
    b = bias if use_bias else None
    ref = pa.composed_attention(q, k, v, b, 0.125, 0.0, causal,
                                jax.random.PRNGKey(0))
    out = pa._flash(q, k, v, b, jnp.int32(7), 0.125, 0.0, causal, True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-5)


def _f32(x):
    return x.astype(jnp.float32)


def _grads(attend, q, k, v, g):
    """(dq, dk, dv) of <attend(q, k, v), g>, as float32 numpy arrays."""
    out, vjp = jax.vjp(attend, q, k, v)
    return [np.asarray(_f32(x)) for x in vjp(g.astype(out.dtype))]


@pytest.mark.parametrize("use_bias", [False, True])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_grad_parity(dtype, causal, use_bias):
    """dQ, dK, dV against the composed lowering evaluated in float32 on the
    same (exactly representable) inputs.

    float32 inputs take float32 products: atol 5e-5 / rtol 1e-4, as ever.

    bfloat16 keeps 8 significant bits, so one rounding is off by at most
    half a step: 2^-9 relative. Every gradient element is a sum over a row of
    S products (dQ over the keys, dK and dV over the queries) one factor of
    which (ds, or the probabilities) the kernel rounds to bfloat16 first,
    and the sum is rounded once more on the way out. A sum of S terms of
    independent sign is about sqrt(S) terms large, so a term is about
    rms(gradient) / sqrt(S); if all S rounding errors line up (the worst
    case) they come to 2^-9 x S x that = 2^-9 x sqrt(S) x rms(gradient).
    The last rounding adds at most 2^-9 x max|gradient|.
    """
    q, k, v, bias = _qkv(dtype=dtype)
    S = q.shape[2]
    g = jax.random.normal(jax.random.PRNGKey(1), q.shape, dtype)
    b = bias if use_bias else None
    ref = _grads(lambda q, k, v: pa.composed_attention(
        q, k, v, b, 0.125, 0.0, causal, None), _f32(q), _f32(k), _f32(v), g)
    got = _grads(lambda q, k, v: pa._flash(
        q, k, v, b, jnp.int32(7), 0.125, 0.0, causal, True), q, k, v, g)
    for r, x in zip(ref, got):
        if dtype == jnp.float32:
            np.testing.assert_allclose(x, r, atol=5e-5, rtol=1e-4)
        else:
            atol = 2.0 ** -9 * (np.sqrt(S) * np.sqrt((r * r).mean())
                                + np.abs(r).max())
            np.testing.assert_allclose(x, r, atol=atol, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_dk_dv_rounded_once(dtype):
    """dK and dV come back in the input's dtype, and accumulating them over
    the Q blocks costs no rounding: four blocks summed in the float32
    scratch and one block (one MXU product, float32 accumulation) give the
    same float32 sum up to its order, so after the one rounding to bfloat16
    they differ nowhere by more than the last bit (or, where a sum cancels
    to near nothing, by the float32 order noise: 2^-24 x S x the largest
    element), and almost nowhere at all. Rounding at every Q block would be
    off by up to two bits."""
    q, k, v, bias = _qkv(S=512, dtype=dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), q.shape, dtype)

    def grads(block_q):
        out, vjp = jax.vjp(lambda q, k, v: pa._flash(
            q, k, v, bias, jnp.int32(7), 0.125, 0.0, False, True, block_q),
            q, k, v)
        return vjp(g)

    one, four = grads(512), grads(128)
    for a, b, x in zip(one, four, (q, k, v)):
        assert a.dtype == b.dtype == x.dtype
    for a, b in zip(one[1:], four[1:]):
        a, b = np.asarray(_f32(a)), np.asarray(_f32(b))
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
        else:
            last_bit = 2.0 ** -7 * np.maximum(np.abs(a), np.abs(b))
            order = 2.0 ** -24 * q.shape[2] * np.abs(a).max()
            assert (np.abs(a - b) <= last_bit + order).all()
            assert (a == b).mean() > 0.99


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_scale_not_a_power_of_two(dtype):
    """0.125 moves into q exactly; 0.17 cannot, and stays on the scores."""
    assert pa._scale_is_exact(0.125) and not pa._scale_is_exact(0.17)
    q, k, v, bias = _qkv(dtype=dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), q.shape, dtype)
    ref_f = lambda q, k, v: pa.composed_attention(
        q, k, v, bias, 0.17, 0.0, False, None)
    fl_f = lambda q, k, v: pa._flash(
        q, k, v, bias, jnp.int32(7), 0.17, 0.0, False, True)
    tol = 5e-5 if dtype == jnp.float32 else 2e-2   # test_flash_bf16_close's
    np.testing.assert_allclose(
        np.asarray(_f32(fl_f(q, k, v))),
        np.asarray(ref_f(_f32(q), _f32(k), _f32(v))), atol=tol)
    for r, x in zip(_grads(ref_f, _f32(q), _f32(k), _f32(v), g),
                    _grads(fl_f, q, k, v, g)):
        np.testing.assert_allclose(x, r, atol=tol * np.abs(r).max(), rtol=0)


def test_flash_default_block_q_divides_s_and_no_other_is_taken():
    """S=384 is a multiple of 128 and not of BLK_Q=256: the default block
    divides it (nothing falls back silently inside the kernel's wrapper),
    and a block_q that does not is refused."""
    assert 384 % pa.BLK_Q and pa.supports_pallas(2, 2, 384, 32, None, 0.0,
                                                  is_tpu=False)
    for S in range(128, 4097, 128):
        assert S % pa.default_block_q(S) == 0
        assert pa.default_block_q(S) % 128 == 0
    q, k, v, bias = _qkv(S=384)
    ref = pa.composed_attention(q, k, v, bias, 0.125, 0.0, False, None)
    out = pa._flash(q, k, v, bias, jnp.int32(7), 0.125, 0.0, False, True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-5)
    for block_q in (256, 192):
        with pytest.raises(ValueError, match="block_q"):
            pa._flash(q, k, v, bias, jnp.int32(7), 0.125, 0.0, False, True,
                      block_q)


def test_flash_bf16_close():
    q, k, v, _ = _qkv(dtype=jnp.bfloat16)
    ref = pa.composed_attention(q, k, v, None, 0.125, 0.0, False,
                                jax.random.PRNGKey(0))
    out = pa._flash(q, k, v, None, jnp.int32(7), 0.125, 0.0, False, True)
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(out, np.float32), atol=2e-2)


def test_supports_gate():
    # ragged S and CPU-dropout fall back to the composed lowering
    assert not pa.supports_pallas(2, 2, 100, 32, None, 0.0, is_tpu=False)
    assert not pa.supports_pallas(2, 2, 128, 32, None, 0.1, is_tpu=False)
    assert pa.supports_pallas(2, 2, 128, 32, None, 0.1, is_tpu=True)
    assert pa.supports_pallas(2, 2, 128, 32, (2, 1, 1, 128), 0.0, is_tpu=False)
    assert not pa.supports_pallas(2, 2, 128, 32, (2, 1, 128, 128), 0.0,
                                  is_tpu=False)


def _bert_program(impl, B=2, S=128, M=8):
    from paddle_tpu.models import bert
    cfg = bert.BertConfig(vocab_size=64, hidden=64, n_layers=1, n_heads=2,
                          max_seq_len=S, dropout=0.0, attn_impl=impl)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0
    startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        src = fluid.data("src_ids", [B, S], "int64", **A)
        pos = fluid.data("pos_ids", [B, S], "int64", **A)
        sent = fluid.data("sent_ids", [B, S], "int64", **A)
        mask = fluid.data("input_mask", [B, S], "float32", **A)
        mpos = fluid.data("mask_pos", [M, 1], "int64", **A)
        mlabel = fluid.data("mask_label", [M, 1], "int64", **A)
        nsp = fluid.data("nsp_label", [B, 1], "int64", **A)
        total, _, _ = bert.pretrain(src, pos, sent, mask, mpos, mlabel, nsp,
                                    cfg)
        fluid.optimizer.Adam(1e-3).minimize(total)
    return main, startup, total


def test_bert_program_parity_fused_vs_composed():
    """Full train steps (fwd+bwd+Adam) agree between attention lowerings."""
    B, S, M = 2, 128, 8
    rng = np.random.RandomState(0)
    feed = {"src_ids": rng.randint(0, 64, (B, S)).astype(np.int32),
            "pos_ids": np.tile(np.arange(S, dtype=np.int32), (B, 1)),
            "sent_ids": rng.randint(0, 2, (B, S)).astype(np.int32),
            "input_mask": np.ones((B, S), np.float32),
            "mask_pos": rng.randint(0, B * S, (M, 1)).astype(np.int32),
            "mask_label": rng.randint(0, 64, (M, 1)).astype(np.int32),
            "nsp_label": rng.randint(0, 2, (B, 1)).astype(np.int32)}
    losses = {}
    for impl in ("composed", "pallas"):
        main, startup, total = _bert_program(impl)
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            losses[impl] = [float(np.asarray(
                exe.run(main, feed=feed, fetch_list=[total])[0]).item())
                for _ in range(2)]
    assert losses["composed"] == pytest.approx(losses["pallas"], abs=2e-4)
    assert losses["pallas"][1] < losses["pallas"][0]  # it actually trains


@pytest.mark.parametrize("S,dp,want", [(128, 1, ("xla", "0")),
                                       (256, 1, ("pallas", "256")),
                                       (256, 2, ("xla", "0"))])
def test_executor_counts_the_lowering_each_attention_op_took(S, dp, want):
    """impl='auto' with no tuning decision: XLA's lowering at S=128, the
    kernels at one Q block a head from S=256, and XLA's again where the step
    is jitted over a mesh of two devices (GSPMD cannot partition a Mosaic
    call); the executor adds one count a fused_attention op at the compile
    (the forward its grad op lowers again is the same op), labelled by
    program."""
    from paddle_tpu.observability.metrics import REGISTRY
    B, M = 2, 8
    rng = np.random.RandomState(0)
    ids = lambda hi, shape: rng.randint(0, hi, shape).astype(np.int32)  # noqa: E731
    feed = {"src_ids": ids(64, (B, S)),
            "pos_ids": np.tile(np.arange(S, dtype=np.int32), (B, 1)),
            "sent_ids": ids(2, (B, S)),
            "input_mask": np.ones((B, S), np.float32),
            "mask_pos": ids(B * S, (M, 1)), "mask_label": ids(64, (M, 1)),
            "nsp_label": ids(2, (B, 1))}
    main, startup, total = _bert_program("auto", S=S)
    run = main if dp == 1 else fluid.CompiledProgram(main).with_strategy(
        fluid.DistributedStrategy(
            mesh_shape={"dp": dp},
            data_rules=[("mask_pos|mask_label", ()), (".", ("dp",))]))

    def counts():
        fam = REGISTRY.get("attention_lowering_total")
        return {} if fam is None else {
            (dict(k)["impl"], dict(k)["block_q"], dict(k)["s"]): c.value
            for k, c in fam.items()}
    before = counts()
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(run, feed=feed, fetch_list=[total])
        exe.run(run, feed=feed, fetch_list=[total])     # no second compile
    after = counts()
    grown = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    assert grown == {want + (str(S),): 1}


def test_clone_for_test_disables_attention_dropout():
    """clone(for_test=True) must flip is_test on fused_attention (round-3
    review finding: inference was stochastic otherwise)."""
    main, startup, total = _bert_program("auto")
    test_prog = main.clone(for_test=True)
    ops = [op for b in test_prog.blocks for op in b.ops
           if op.type == "fused_attention"]
    assert ops, "expected fused_attention ops in the cloned program"
    assert all(op.attrs.get("is_test") for op in ops)


def test_a_test_mode_op_on_the_kernels_draws_no_random_number():
    """The kernels read their seed for a dropout mask alone: an is_test op
    (an inference clone, a saved model) holds no random op, as the dropout
    op does not; a training op still draws the seed the parent drew."""
    import paddle_tpu.core.registry as registry
    d = registry.get("fused_attention")
    q = jnp.zeros((1, 2, 256, 32), jnp.float32)

    def jaxpr(is_test):
        ctx = registry.LowerCtx(
            {"impl": "auto", "is_test": is_test, "dropout_prob": 0.1},
            base_key=jax.random.PRNGKey(0))
        return str(jax.make_jaxpr(lambda q: d.lower(
            ctx, {"Q": [q], "K": [q], "V": [q]})["Out"][0])(q))
    assert "pallas_call" in jaxpr(True) and "random_" not in jaxpr(True)
    # in training the CPU has no in-kernel PRNG: the composed lowering's mask
    assert "random_" in jaxpr(False)


def test_forced_pallas_rejects_bad_shapes():
    import paddle_tpu.core.registry as registry
    d = registry.get("fused_attention")
    q = jnp.zeros((2, 2, 100, 32), jnp.float32)  # S % 128 != 0
    ctx = registry.LowerCtx({"impl": "pallas"})
    with pytest.raises(RuntimeError, match="pallas"):
        try:
            d.lower(ctx, {"Q": [q], "K": [q], "V": [q]})
        except ValueError as e:
            raise RuntimeError(str(e))
