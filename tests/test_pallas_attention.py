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


def _op_grads(q, k, v, bias, g, scale, causal, blocks, monkeypatch,
              with_lse=True):
    """(out, lse, dq, dk, dv) as a Program computes them: the registry's
    ``fused_attention`` lowering, then ``fused_attention_grad``'s on what
    the desc maker hands a grad op (the forward's inputs, its outputs
    ``Out`` and ``Lse``, the cotangent), at the kernels' ``blocks``.
    ``with_lse=False`` is a desc from before the op declared ``Lse``."""
    import paddle_tpu.core.registry as registry
    from paddle_tpu import tuning
    monkeypatch.setattr(tuning, "decide", lambda choice, params: (
        "pallas" if choice == "fused_attention.backend" else blocks))
    attrs = {"impl": "auto", "scale": scale, "causal": causal,
             "dropout_prob": 0.0, "is_test": False}
    ins = {"Q": [q], "K": [k], "V": [v]}
    if bias is not None:
        ins["Bias"] = [bias]
    key = jax.random.PRNGKey(0)
    outs = registry.get("fused_attention").lower(
        registry.LowerCtx(attrs, key, 5), dict(ins))
    grad_ins = dict(ins, Out=outs["Out"], **{"Out@GRAD": [g]})
    slots = ["Out"]
    if with_lse:
        grad_ins["Lse"] = outs["Lse"]
        slots.append("Lse")
    grads = registry.get("fused_attention_grad").lower(
        registry.LowerCtx(dict(attrs, __fwd_out_slots__=slots), key, 5),
        grad_ins)
    assert set(grads) == {"Q@GRAD", "K@GRAD", "V@GRAD"}
    return (outs["Out"][0], outs["Lse"][0],
            *(grads[s + "@GRAD"][0] for s in "QKV"))


@pytest.mark.parametrize("grouped", [False, True])
@pytest.mark.parametrize("tiles", ["one_tile", "k_tiles"])
@pytest.mark.parametrize("use_bias", [False, True])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_grad_parity(dtype, causal, use_bias, tiles, grouped,
                           monkeypatch):
    """dQ, dK, dV against the composed lowering evaluated in float32 on the
    same (exactly representable) inputs: through ``jax.vjp`` of the kernels
    (the custom VJP) and through the op's own grad lowering, which hands the
    forward op's ``Lse`` to the backward kernel (what a Program runs); with
    one tile a row and with K tiles, with as many key/value as query heads
    and with half as many.

    float32 inputs take float32 products: atol 5e-5 / rtol 1e-4, as ever.

    bfloat16 keeps 8 significant bits, so one rounding is off by at most
    half a step: 2^-9 relative. Every gradient element is a sum over a row of
    S products (dQ over the keys, dK and dV over the queries) one factor of
    which (ds, or the probabilities) the kernel rounds to bfloat16 first,
    and the sum is rounded once more on the way out. A sum of S terms of
    independent sign is about sqrt(S) terms large, so a term is about
    rms(gradient) / sqrt(S); if all S rounding errors line up (the worst
    case) they come to 2^-9 x S x that = 2^-9 x sqrt(S) x rms(gradient).
    The last rounding adds at most 2^-9 x max|gradient|. A key/value head's
    gradient sums over its group's rows too (S x group terms).
    """
    S, blocks = (128, (128, 128)) if tiles == "one_tile" else (256, (128, 128))
    q, k, v, bias = _qkv(S=S, dtype=dtype)
    group = 2 if grouped else 1
    k, v = k[:, ::group], v[:, ::group]
    g = jax.random.normal(jax.random.PRNGKey(1), q.shape, dtype)
    b = bias if use_bias else None
    ref = _grads(lambda q, k, v: pa.composed_attention(
        q, k, v, b, 0.125, 0.0, causal, None), _f32(q), _f32(k), _f32(v), g)
    got = _grads(lambda q, k, v: pa._flash(
        q, k, v, b, jnp.int32(7), 0.125, 0.0, causal, True, *blocks),
        q, k, v, g)
    op = [np.asarray(_f32(x)) for x in _op_grads(
        q, k, v, b, g, 0.125, causal, blocks, monkeypatch)[2:]]
    for r, x, y in zip(ref, got, op):
        np.testing.assert_array_equal(x, y)     # the same two kernels
        if dtype == jnp.float32:
            np.testing.assert_allclose(x, r, atol=5e-5, rtol=1e-4)
        else:
            terms = S * (group if r.shape[1] != q.shape[1] else 1)
            atol = 2.0 ** -9 * (np.sqrt(terms) * np.sqrt((r * r).mean())
                                + np.abs(r).max())
            np.testing.assert_allclose(x, r, atol=atol, rtol=0)


@pytest.mark.parametrize("S,blocks", [(128, (128, 128)), (512, (256, 128)),
                                      (512, (128, 512))])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_lse_is_the_logsumexp_of_the_composed_scores(causal, S, blocks):
    """The statistic the forward kernel hands the backward, one float32 a
    row: log(sum(exp(scores))) over the row's scores as the composed
    lowering forms them -- scale, bias and the causal mask's -1e30 included.
    A key biased by -1e30 adds nothing to its rows; a sequence whose every
    key is (its rows have no key at all) reads -1e30, where log S is under
    float32's spacing, as the plain logsumexp reads it."""
    q, k, v, bias = _qkv(S=S)
    bias = bias.at[0, :, :, -S // 4:].set(-1e30).at[1].set(-1e30)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.125 + bias
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, jnp.float32(-1e30))
    want = jax.scipy.special.logsumexp(s, axis=-1)
    out, lse = pa._flash_stats(q, k, v, bias, jnp.int32(7), 0.125, 0.0,
                               causal, True, *blocks)
    B, H = q.shape[:2]
    assert lse.shape == (B, H, 1, S) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse[:, :, 0]), np.asarray(want),
                               rtol=1e-6, atol=1e-5)
    assert (np.asarray(lse[1]) == np.float32(-1e30)).all()
    # and the output beside it is what it was (where a row has a key)
    ref = pa.composed_attention(q, k, v, bias, 0.125, 0.0, causal, None)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grad_op_without_lse_takes_the_generic_path_and_the_same_gradients(
        causal, monkeypatch):
    """A desc from before the op declared ``Lse`` has no such input on its
    grad op: the lowering falls back to the generic grad (``jax.vjp`` over
    the forward's lowering, the custom VJP's own statistics) and gives the
    gradients the explicit path gives, bit for bit -- the same backward
    kernel on the same numbers."""
    q, k, v, bias = _qkv(S=256)
    g = jax.random.normal(jax.random.PRNGKey(1), q.shape, q.dtype)
    args = (q, k, v, bias, g, 0.125, causal, (128, 128), monkeypatch)
    for a, b in zip(_op_grads(*args)[2:], _op_grads(*args, with_lse=False)[2:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


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


def _decoder_qkv(S, D, group, dtype, use_bias, B=2, kv=2):
    ks = jax.random.split(jax.random.PRNGKey(S + D + group), 5)
    q, g = (jax.random.normal(kk, (B, kv * group, S, D), dtype)
            for kk in ks[:2])
    k, v = (jax.random.normal(kk, (B, kv, S, D), dtype) for kk in ks[2:4])
    bias = jnp.where(jax.random.bernoulli(ks[4], 0.9, (B, 1, 1, S)),
                     0.0, -1e4).astype(jnp.float32) if use_bias else None
    return q, k, v, g, bias


# (S, block_q, block_k): K tiles narrower than, as wide as and wider than the
# Q block, S a multiple of neither, one tile (block_k = S) among them
TILES = [(256, 128, 128), (512, 256, 128), (512, 128, 256), (768, 256, 128),
         (768, 384, 256), (1024, 256, 512), (1024, 512, 256),
         (1024, 512, 1024)]


@pytest.mark.parametrize("D,group,dtype,use_bias", [
    (64, 1, jnp.float32, False), (128, 4, jnp.bfloat16, True),
    (64, 4, jnp.float32, True), (128, 1, jnp.bfloat16, False)])
@pytest.mark.parametrize("S,block_q,block_k", TILES)
def test_causal_k_tiles_match_composed(S, block_q, block_k, D, group, dtype,
                                       use_bias):
    """Causal forward and gradients with the K axis in tiles (the Q blocks
    loop over the tiles at or under their diagonal) against the composed
    lowering in float32, with fewer key/value than query heads too.
    Tolerances as test_flash_grad_parity derives them, a key/value head's
    gradient summing over its group's rows too, and doubled in bfloat16: a
    causal row's first few probabilities are large, not 1/S, so single
    elements at d=128 lie up to 1.35 times the bound away with one tile a
    row as with many."""
    q, k, v, g, bias = _decoder_qkv(S, D, group, dtype, use_bias)
    scale = D ** -0.5

    def both(attend, *xs):
        out, vjp = jax.vjp(attend, *xs)
        return [np.asarray(_f32(x)) for x in (out, *vjp(g.astype(out.dtype)))]

    ref = both(lambda q, k, v: pa.composed_attention(
        q, k, v, bias, scale, 0.0, True, None), _f32(q), _f32(k), _f32(v))
    got = both(lambda q, k, v: pa._flash(
        q, k, v, bias, jnp.int32(7), scale, 0.0, True, True, block_q,
        block_k), q, k, v)
    for r, x in zip(ref, got):
        if dtype == jnp.float32:
            np.testing.assert_allclose(x, r, atol=5e-5, rtol=1e-4)
        else:
            atol = 2.0 ** -8 * (np.sqrt(S * group) * np.sqrt((r * r).mean())
                                + np.abs(r).max())
            np.testing.assert_allclose(x, r, atol=atol, rtol=0)


@pytest.mark.parametrize("S,block_q,block_k", [
    (512, 128, 128), (512, 256, 128), (512, 128, 256), (1024, 256, 512)])
def test_causal_k_tiles_above_the_diagonal_are_not_visited(S, block_q,
                                                           block_k):
    """No counter needed: with the K and V rows of every tile wholly above
    the first Q block's diagonal set to NaN, that block's output and dQ stay
    finite. A tile computed and masked afterwards gives 0 x NaN there (one
    tile a row does: the last assertion)."""
    q, k, v, g, _ = _decoder_qkv(S, 64, 2, jnp.float32, False)
    first_unvisited = -(-block_q // block_k) * block_k
    assert pa.k_tiles(S, block_q, block_k, True)[1] > 0
    rows = jnp.arange(S)[None, None, :, None] >= first_unvisited
    k, v = (jnp.where(rows, jnp.nan, x) for x in (k, v))

    def first_block(block_k):
        out, vjp = jax.vjp(lambda q: pa._flash(
            q, k, v, None, jnp.int32(7), 0.125, 0.0, True, True, block_q,
            block_k), q)
        return (np.asarray(out[:, :, :block_q]),
                np.asarray(vjp(g)[0][:, :, :block_q]))

    out, dq = first_block(block_k)
    assert np.isfinite(out).all() and np.isfinite(dq).all()
    assert np.abs(out).max() > 0 and np.abs(dq).max() > 0
    assert not np.isfinite(first_block(S)[0]).any()


def _single_pass(q, k, v, bias, g, scale, block_q):
    """The kernels written plainly for one tile a row (PR 25's bodies, no
    dropout, no _KTiles): one pass over a Q block's [block_q, S] scores,
    the forward handing the backward each row's ``lse`` (here as a 128-lane
    copy a row, which only a test can afford). Kept here as the oracle for
    what one tile a row must compute, bit for bit: (out, dq, dk, dv)."""
    import functools
    from jax.experimental import pallas as pl
    B, H, S, D = q.shape
    n_q = S // block_q
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)
    nt, nn, tn = ((((1,), (1,)), ((), ())), (((1,), (0,)), ((), ())),
                  (((0,), (0,)), ((), ())))

    def scores(q_ref, k_ref, bias_ref):
        q_s = q_ref[0] * jnp.asarray(scale, q_ref.dtype)
        return dot(q_s, k_ref[0], nt) + bias_ref[0].astype(jnp.float32), q_s

    def fwd(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref):
        s, _ = scores(q_ref, k_ref, bias_ref)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        l = jnp.sum(e, axis=-1, keepdims=True)
        o = dot(e.astype(v_ref.dtype), v_ref[0], nn)
        o_ref[0] = (o * (1.0 / (l * 1.0))).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape[1:])

    def bwd(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, dq_ref, dk_ref,
            dv_ref, dkt_acc, dvt_acc):
        step = pl.program_id(1)
        dtype = q_ref.dtype
        s, q_s = scores(q_ref, k_ref, bias_ref)
        p = jnp.exp(s - lse_ref[0][:, :1])
        do = do_ref[0]
        dp = dot(do, v_ref[0], nt)
        row = jnp.sum(dp * p, axis=-1, keepdims=True)
        ds = (p * (dp - row)).astype(dtype)
        dq_ref[0] = (dot(ds, k_ref[0], nn) * (1.0 * scale)).astype(
            dq_ref.dtype)

        @pl.when(step == 0)
        def _():
            dkt_acc[...] = jnp.zeros_like(dkt_acc)
            dvt_acc[...] = jnp.zeros_like(dvt_acc)
        dkt_acc[...] += dot(q_s, ds, tn)
        dvt_acc[...] += dot(do, p.astype(dtype), tn)

        @pl.when(step == pl.num_programs(1) - 1)
        def _():
            dk_ref[0] = (dkt_acc[...] * 1.0).T.astype(dk_ref.dtype)
            dv_ref[0] = (dvt_acc[...] * 1.0).T.astype(dv_ref.dtype)

    from jax.experimental.pallas import tpu as pltpu
    qspec = pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0))
    kvspec = pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0))
    bspec = pl.BlockSpec((1, 1, S), lambda b, i: (b // H, 0, 0))
    flat = [x.reshape(B * H, S, D) for x in (q, k, v)] + [
        bias.reshape(B, 1, S)]
    shape = jax.ShapeDtypeStruct((B * H, S, D), q.dtype)
    lspec = pl.BlockSpec((1, block_q, 128), lambda b, i: (b, i, 0))
    out, lse = pl.pallas_call(
        fwd, grid=(B * H, n_q), in_specs=[qspec, kvspec, kvspec, bspec],
        out_specs=[qspec, lspec],
        out_shape=[shape, jax.ShapeDtypeStruct((B * H, S, 128), jnp.float32)],
        interpret=True)(*flat)
    grads = pl.pallas_call(
        bwd, grid=(B * H, n_q),
        in_specs=[qspec, kvspec, kvspec, bspec, qspec, lspec],
        out_specs=[qspec, kvspec, kvspec], out_shape=[shape] * 3,
        scratch_shapes=[pltpu.VMEM((D, S), jnp.float32)] * 2,
        interpret=True)(*flat, g.reshape(B * H, S, D), lse)
    return [x.reshape(B, H, S, D) for x in (out, *grads)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,block_q", [(256, 256), (512, 128), (1024, 256)])
def test_one_tile_a_row_is_the_single_pass_it_was(S, block_q, dtype):
    """Without `causal` the K tile is the row (default_block_k), and the
    kernels then compute what the plain single-pass bodies compute (_KTiles
    adds nothing, and the row statistic's way through HBM as one float a
    row changes no bit): output and gradients equal theirs bit for bit, on
    a padding bias and at a power-of-two scale as BERT's cells run them."""
    assert pa.default_block_k(S) == S and pa.default_block_k(S, True) in (
        S, pa.CAUSAL_BLOCKS[1])
    q, k, v, bias = _qkv(S=S, D=64, dtype=dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), q.shape, dtype)
    want = _single_pass(q, k, v, bias, g, 0.125, block_q)
    out, vjp = jax.vjp(lambda q, k, v: pa._flash(
        q, k, v, bias, jnp.int32(7), 0.125, 0.0, False, True, block_q), q, k,
        v)
    for w, x in zip(want, (out, *vjp(g))):
        assert x.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(_f32(x)), np.asarray(_f32(w)))


@pytest.mark.parametrize("S,block_q,block_k,causal,want", [
    (4096, 256, 256, True, (136, 120)), (4096, 512, 1024, True, (20, 12)),
    (4096, 512, 512, True, (36, 28)), (4096, 256, 4096, True, (16, 0)),
    (512, 512, 512, False, (1, 0)), (2048, 256, 2048, False, (8, 0)),
    (1024, 256, 256, False, (16, 0))])
def test_k_tiles_counts_what_the_q_blocks_visit(S, block_q, block_k, causal,
                                                want):
    """The figures attention_k_tiles_total adds for one op: a causal op's Q
    blocks leave out the tiles wholly above their diagonal, one tile a row
    leaves out none."""
    assert pa.k_tiles(S, block_q, block_k, causal) == want
    assert sum(want) == (S // block_q) * (S // block_k)


@pytest.mark.parametrize("S", [1024, 1536, 2048, 2560, 4096])
def test_default_blocks_are_chosen_from_causal_and_s(S):
    """One tile a row without `causal` at every S, and with it below
    CAUSAL_TILES_MIN_S or where the pair does not divide S; CAUSAL_BLOCKS
    from there. Both always divide S."""
    assert pa.default_block_k(S) == S
    assert pa.default_block_q(S) == pa.BLK_Q
    tiled = S >= pa.CAUSAL_TILES_MIN_S and S % 1024 == 0
    assert (pa.default_block_q(S, True), pa.default_block_k(S, True)) == (
        pa.CAUSAL_BLOCKS if tiled else (pa.BLK_Q, S))
    with pytest.raises(ValueError, match="block_k"):
        q, k, v, _ = _qkv(S=256)
        pa._flash(q, k, v, None, jnp.int32(7), 0.125, 0.0, True, True, 128,
                  192)


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


def _feed(B, S, M):
    rng = np.random.RandomState(0)
    ids = lambda hi, shape: rng.randint(0, hi, shape).astype(np.int32)  # noqa: E731
    return {"src_ids": ids(64, (B, S)),
            "pos_ids": np.tile(np.arange(S, dtype=np.int32), (B, 1)),
            "sent_ids": ids(2, (B, S)),
            "input_mask": np.ones((B, S), np.float32),
            "mask_pos": ids(B * S, (M, 1)), "mask_label": ids(64, (M, 1)),
            "nsp_label": ids(2, (B, 1))}


def test_bert_program_parity_fused_vs_composed():
    """Full train steps (fwd+bwd+Adam) agree between attention lowerings."""
    feed = _feed(2, 128, 8)
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


def _backward_counts():
    from paddle_tpu.observability.metrics import REGISTRY
    out = {}
    for k, c in (REGISTRY.get("attention_backward_total") or {}).items():
        out[dict(k)["stats"]] = out.get(dict(k)["stats"], 0) + c.value
    return out


@pytest.mark.parametrize("S,dp,want,stats", [
    (128, 1, ("xla", "0", "0"), "generic"),
    (256, 1, ("pallas", "256", "256"), "saved"),
    (256, 2, ("xla", "0", "0"), "generic")])
def test_executor_counts_the_lowering_each_attention_op_took(S, dp, want,
                                                             stats):
    """impl='auto' with no tuning decision: XLA's lowering at S=128, the
    kernels at one Q block a head from S=256, and XLA's again where the step
    is jitted over a mesh of two devices (GSPMD cannot partition a Mosaic
    call); the executor adds one count a fused_attention op at the compile
    (the forward a generic grad op lowers again is the same op), labelled by
    program. Its grad op is counted by where its softmax statistics came
    from: the forward op's ``Lse`` on the kernels (``saved``), the generic
    vjp elsewhere."""
    from paddle_tpu.observability.metrics import REGISTRY
    feed = _feed(2, S, 8)
    main, startup, total = _bert_program("auto", S=S)
    run = main if dp == 1 else fluid.CompiledProgram(main).with_strategy(
        fluid.DistributedStrategy(
            mesh_shape={"dp": dp},
            data_rules=[("mask_pos|mask_label", ()), (".", ("dp",))]))

    def counts():
        fam = REGISTRY.get("attention_lowering_total")
        return {} if fam is None else {
            (dict(k)["impl"], dict(k)["block_q"], dict(k)["block_k"],
             dict(k)["s"]): c.value
            for k, c in fam.items()}

    def tiles():
        out = {"visited": 0, "skipped": 0}
        for k, c in (REGISTRY.get("attention_k_tiles_total") or {}).items():
            out[dict(k)["state"]] += c.value
        return out
    before, tiles_before, back_before = counts(), tiles(), _backward_counts()
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(run, feed=feed, fetch_list=[total])
        exe.run(run, feed=feed, fetch_list=[total])     # no second compile
    after = counts()
    grown = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    assert grown == {want + (str(S),): 1}
    # the one op's forward kernel: one Q block, one K tile, none left out
    assert {k: v - tiles_before[k] for k, v in tiles().items()} == {
        "visited": int(want[0] == "pallas"), "skipped": 0}
    back = _backward_counts()
    assert {k: v - back_before.get(k, 0) for k, v in back.items()
            if v != back_before.get(k, 0)} == {stats: 1}


def test_a_program_without_lse_trains_as_one_with_it():
    """A Program built before the op declared ``Lse`` (here: the output
    taken off the op and off its grad op's inputs, as such a desc reads)
    still trains on the kernels: its grad op takes the generic path, counted
    ``recomputed`` (the vjp lowers the forward kernel again for the
    statistics),
    and two steps' losses equal those of the Program that saves them."""
    S = 256
    losses = {}
    for strip in (False, True):
        main, startup, total = _bert_program("auto", S=S)
        if strip:
            for op in main.global_block().ops:
                if op.type == "fused_attention":
                    del op.outputs["Lse"]
                elif op.type == "fused_attention_grad":
                    del op.inputs["Lse"]
                    op.attrs["__fwd_out_slots__"] = ["Out"]
        before = _backward_counts()
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            losses[strip] = [float(np.asarray(exe.run(
                main, feed=_feed(2, S, 8), fetch_list=[total])[0]).item())
                for _ in range(2)]
        after = _backward_counts()
        assert {k: v - before.get(k, 0) for k, v in after.items()
                if v != before.get(k, 0)} == {
                    "recomputed" if strip else "saved": 1}
    assert losses[True] == losses[False]
    assert losses[True][1] < losses[True][0]


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


# -- a value head of its own width (latent attention, v narrower than q / k) --

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,block_q,block_k,D,Dv", [
    (256, 128, 128, 192, 128), (512, 256, 128, 256, 128),
    (512, 128, 512, 192, 128), (256, 256, 256, 64, 128)])
def test_causal_flash_with_a_value_head_of_its_own_width(S, block_q, block_k,
                                                         D, Dv, dtype):
    """q / k heads of ``D`` beside v heads of ``Dv`` (Kimi Linear's latent
    attention: 192 -- or 256 with 64 zero columns -- and 128): the kernels'
    output and dq, dk (``D`` wide), dv (``Dv`` wide) against plain softmax
    attention in float32, K in tiles and one tile a row."""
    r = np.random.RandomState(0)
    q, k = (jnp.asarray(r.randn(2, 2, S, D), dtype) for _ in range(2))
    v, g = (jnp.asarray(r.randn(2, 2, S, Dv), dtype) for _ in range(2))
    scale = 192 ** -0.5

    def plain(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    def both(attend, *xs):
        out, vjp = jax.vjp(attend, *xs)
        return [np.asarray(_f32(x)) for x in (out, *vjp(g.astype(out.dtype)))]
    with jax.default_matmul_precision("highest"):
        ref = both(plain, _f32(q), _f32(k), _f32(v))
    got = both(lambda q, k, v: pa._flash(
        q, k, v, None, jnp.int32(7), scale, 0.0, True, True, block_q,
        block_k), q, k, v)
    assert [x.shape[-1] for x in got] == [Dv, D, D, Dv]
    for r_, x in zip(ref, got):
        if dtype == jnp.float32:
            np.testing.assert_allclose(x, r_, atol=5e-5, rtol=1e-4)
        else:
            atol = 2.0 ** -7 * (np.sqrt(S) * np.sqrt((r_ * r_).mean())
                                + np.abs(r_).max())
            np.testing.assert_allclose(x, r_, atol=atol, rtol=0)
    # the composed lowering takes the same shapes
    close = pa.composed_attention(_f32(q), _f32(k), _f32(v), None, scale, 0.0,
                                  True, None)
    np.testing.assert_allclose(np.asarray(close), ref[0], atol=1e-4, rtol=1e-4)


def test_the_attention_op_counts_a_value_width_of_its_own():
    """Through the executor: ``fused_attention`` given v narrower than q / k
    returns ``[B, heads, S, Dv]``, trains, and its lowering carries
    ``value_dim`` (0 where v is as wide as q); a k that is not q's width is
    refused."""
    from paddle_tpu.observability.metrics import REGISTRY
    from test_decoder_ops import run_with_grads

    def count(**want):
        family = REGISTRY.get("attention_lowering_total")
        return sum(c.value for labels, c in family.items()
                   if set(want.items()) <= set(labels)) if family else 0
    r = np.random.RandomState(1)
    feeds = {"q": r.randn(1, 2, 128, 24).astype("float32"),
             "k": r.randn(1, 2, 128, 24).astype("float32"),
             "v": r.randn(1, 2, 128, 8).astype("float32")}
    before = count(value_dim="8"), count(value_dim="0")
    out, grads, _, _, _ = run_with_grads(
        lambda q, k, v: fluid.layers.fused_attention(q, k, v, causal=True,
                                               scale=0.25), feeds,
        ["q", "k", "v"])
    assert out.shape == (1, 2, 128, 8)
    assert [g.shape for g in grads] == [feeds[n].shape for n in "qkv"]
    run_with_grads(lambda q, k, v: fluid.layers.fused_attention(
        q, k, q, causal=True), feeds, [])
    assert (count(value_dim="8") - before[0],
            count(value_dim="0") - before[1]) == (1, 1)
    with pytest.raises(Exception, match="against k heads"):
        run_with_grads(lambda q, k, v: fluid.layers.fused_attention(
            q, v, v, causal=True), feeds, [])
