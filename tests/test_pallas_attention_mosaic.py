"""The Pallas kernels through Mosaic, at the benchmark's shapes, for a TPU v5e
that is described and not attached: what the interpreter cannot see (VMEM
budgets, tiling, the in-kernel PRNG). The flash kernels at BERT's and the
decoders' shapes (Laguna's window and full layers among them), the short
convolution in both its forms, the chunked
state-space scan at Granite's, the grouped expert matmuls at OLMoE's, and a
small expert layer's whole train step (what a Program's grad ops leave in
it). Nothing
runs, so this says nothing about results or times. All such compiles live in
this one file: the worker that gets it loads libtpu, and keeps it until it
exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest

import lowering_reports
from paddle_tpu.ops import pallas_attention as pa


@pytest.fixture(scope="module")
def v5e():
    """The described 2x2 of TPU v5e chips."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry written for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e.devices[0])


def _kernels(compiled):
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _lse(q, one_chip):
    """The forward op's ``Lse`` for queries ``q``, as a Program hands it to
    the grad op."""
    B, H, S, _ = q.shape
    return jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32, sharding=one_chip)


def _grad_op(q, k, v, bias, g, lse, scale, dropout, causal, block_q=None,
             block_k=None):
    """What a Program's ``fused_attention_grad`` lowers on the kernels
    (ops/pallas_attention.py): the backward kernel on the forward op's
    statistics, and no forward."""
    return pa._bwd_call(q, k, v, bias, jnp.int32(3), g, lse, scale, dropout,
                        causal, False,
                        *pa._blocks(q.shape[2], causal, block_q, block_k))


# (batch, heads, S, head dim, dtype, dropout, causal, bias), each at the
# blocks the op takes by default: bert_base.pretrain_s512's attention (one Q
# block a head), the same tokens at the shortest S that 'auto' gives the
# kernels, and at S=384; bert_base.pretrain_s2048's attention; the longest S
# one K tile a row takes at BLK_Q; f32 inputs, causal, in K tiles;
# olmoe_1b_7b.pretrain_s4096's (its second shape: causal, no bias, no
# dropout, d=128; CAUSAL_BLOCKS' tiles) at 2 and at 4 sequences
CASES = [(32, 12, 512, 64, jnp.bfloat16, 0.1, False, True),
         (64, 12, 256, 64, jnp.bfloat16, 0.1, False, True),
         (42, 12, 384, 64, jnp.bfloat16, 0.1, False, True),
         (8, 12, 2048, 64, jnp.bfloat16, 0.1, False, True),
         (4, 12, 4096, 64, jnp.bfloat16, 0.1, False, True),
         (8, 12, 2048, 64, jnp.float32, 0.0, True, False),
         (2, 16, 4096, 128, jnp.bfloat16, 0.0, True, False),
         (4, 16, 4096, 128, jnp.bfloat16, 0.0, True, False)]


@pytest.mark.parametrize("B,H,S,D,dtype,dropout,causal,use_bias", CASES)
def test_flash_compiles_for_v5e(one_chip, B, H, S, D, dtype, dropout, causal,
                                use_bias):
    x = jax.ShapeDtypeStruct((B, H, S, D), dtype, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((B, 1, 1, S), jnp.float32, sharding=one_chip)

    def attend(q, k, v, bias):
        return pa._flash(q, k, v, bias if use_bias else None, jnp.int32(3),
                         D ** -0.5, dropout, causal, False)

    def grads(q, k, v, bias, g):
        return jax.vjp(lambda q, k, v: attend(q, k, v, bias), q, k, v)[1](g)

    def grad_op(q, k, v, bias, g, lse):
        return _grad_op(q, k, v, bias if use_bias else None, g, lse,
                        D ** -0.5, dropout, causal)

    assert _kernels(jax.jit(attend).lower(x, x, x, bias).compile()) == 1
    # What a Program's grad op lowers: the backward kernel alone, on the
    # forward op's Lse. A direct jax.vjp (the custom VJP: tests, the tuner)
    # keeps its own forward for the statistics: two kernels.
    assert _kernels(jax.jit(grad_op).lower(
        x, x, x, bias, x, _lse(x, one_chip)).compile()) == 1
    assert _kernels(jax.jit(grads).lower(x, x, x, bias, x).compile()) == 2


@pytest.mark.parametrize("B,dropout,use_bias", [(4, 0.0, False),
                                                (2, 0.1, True)])
def test_grouped_query_flash_compiles_for_v5e(one_chip, B, dropout, use_bias):
    """lfm2_8b_a1b.pretrain_s4096's attention: 32 query heads over 8
    key/value heads of 64 at S=4096, causal, at the default tiles (and with
    a bias and a dropout mask by tile). The forward reads a key/value
    head's rows in place (its block index is the query head's // 4) and the
    backward's grid runs over a key/value head's four query heads (their
    rows of the forward op's Lse with them), so dK and dV leave at the
    key/value heads' shape: one kernel each way, and no array of the query
    heads' shape among the backward's outputs but dQ."""
    H, kv, S, D = 32, 8, 4096, 64
    q = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((B, kv, S, D), jnp.bfloat16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((B, 1, 1, S), jnp.float32, sharding=one_chip)

    def attend(q, k, v, bias):
        return pa._flash(q, k, v, bias if use_bias else None, jnp.int32(3),
                         D ** -0.5, dropout, True, False)

    def grad_op(q, k, v, bias, g, lse):
        return _grad_op(q, k, v, bias if use_bias else None, g, lse,
                        D ** -0.5, dropout, True)

    assert _kernels(jax.jit(attend).lower(q, k, k, bias).compile()) == 1
    back = jax.jit(grad_op).lower(q, k, k, bias, q, _lse(q, one_chip))
    assert [tuple(o.shape) for o in back.out_info] == [
        (B, H, S, D), (B, kv, S, D), (B, kv, S, D)]
    assert _kernels(back.compile()) == 1


@pytest.mark.parametrize("B,H,kv", [(1, 20, 20), (2, 16, 2)])
def test_wide_head_flash_compiles_alone_for_v5e(one_chip, B, H, kv):
    """Heads of 256 at S=4096, causal, at the default 512 x 1024 tiles:
    glm_4_7_flash's latent attention as the kernels see it (20 query = 20
    key/value heads, group 1) and qwen3_next's one attention layer (16 over
    2). Under Mosaic's default scoped VMEM the forward call compiled inside
    one train step and not alone (16.39 M asked of 16.00 M: PERF.md section
    7 (u)); with a limit of its own for heads wider than 128 it compiles
    alone, as the backward always did. Narrower heads keep the default."""
    S, D = 4096, 256
    assert pa.fwd_vmem_limit_bytes(D) == pa.FWD_VMEM_LIMIT_BYTES_WIDE
    assert pa.fwd_vmem_limit_bytes(128) is None
    assert pa._blocks(S, True) == pa.CAUSAL_BLOCKS
    q = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((B, kv, S, D), jnp.bfloat16, sharding=one_chip)

    def attend(q, k, v):
        return pa._flash(q, k, v, None, jnp.int32(3), D ** -0.5, 0.0, True,
                         False)

    def grad_op(q, k, v, g, lse):
        return _grad_op(q, k, v, None, g, lse, D ** -0.5, 0.0, True)

    fwd = jax.jit(attend).lower(q, k, k).compile()
    assert _kernels(fwd) == 1
    back = jax.jit(grad_op).lower(q, k, k, q, _lse(q, one_chip))
    assert [tuple(o.shape) for o in back.out_info] == [
        (B, H, S, D), (B, kv, S, D), (B, kv, S, D)]
    assert _kernels(back.compile()) == 1


@pytest.mark.parametrize("block_q,block_k", [
    pa.CAUSAL_BLOCKS, (512, 512), (256, 256), (256, 4096)])
@pytest.mark.parametrize("H,kv,D", [(16, 16, 128), (32, 8, 64)])
def test_causal_flash_compiles_for_v5e_in_k_tiles(one_chip, H, kv, D, block_q,
                                                  block_k):
    """The two decoder cells' attention (4 x 4096 tokens, causal, bf16:
    olmoe_1b_7b's 16 heads of 128, lfm2_8b_a1b's 32 query over 8 key/value
    heads of 64) at the tiles the op takes by default, at two narrower pairs
    and at one tile a row (what a persisted decision may still choose): the
    loops of dynamic trip count, the staged probability and dP tiles and
    dK^T / dV^T by tile fit Mosaic's VMEM, and a grad op holds the backward
    kernel alone (it reads the forward op's Lse, by the group's heads where
    the key/value heads are fewer)."""
    B, S = 4, 4096
    assert (pa.default_block_q(S, True),
            pa.default_block_k(S, True)) == pa.CAUSAL_BLOCKS
    q = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((B, kv, S, D), jnp.bfloat16, sharding=one_chip)

    def attend(q, k, v):
        return pa._flash(q, k, v, None, jnp.int32(3), D ** -0.5, 0.0, True,
                         False, block_q, block_k)

    def grad_op(q, k, v, g, lse):
        return _grad_op(q, k, v, None, g, lse, D ** -0.5, 0.0, True, block_q,
                        block_k)

    assert _kernels(jax.jit(attend).lower(q, k, k).compile()) == 1
    back = jax.jit(grad_op).lower(q, k, k, q, _lse(q, one_chip))
    assert [tuple(o.shape) for o in back.out_info] == [
        (B, H, S, D), (B, kv, S, D), (B, kv, S, D)]
    assert _kernels(back.compile()) == 1


@pytest.mark.parametrize("H,window,block_q,block_k", [
    (72, 512, *pa.WINDOW_BLOCKS), (72, 512, 512, 1024), (72, 512, 256, 256),
    (48, None, *pa.CAUSAL_BLOCKS)])
def test_laguna_s_attention_compiles_for_v5e(one_chip, H, window, block_q,
                                             block_k):
    """The Laguna cell's attention (1 x 4096 tokens, causal, bf16, 8
    key/value heads of 128): the three window layers' 72 query heads (group
    9, window 512) at the tiles a window op takes by default and at two
    other pairs, and the two full layers' 48 (group 6) at the causal tiles:
    the three loops of dynamic trip count, a stage that holds the two tiles
    a Q block's window reaches and dK^T / dV^T by tile fit Mosaic's VMEM,
    and a grad op holds the backward kernel alone."""
    B, S, kv, D = 1, 4096, 8, 128
    assert (pa.default_block_q(S, True, 512),
            pa.default_block_k(S, True, 512)) == pa.WINDOW_BLOCKS
    q = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((B, kv, S, D), jnp.bfloat16, sharding=one_chip)

    def attend(q, k, v):
        return pa._flash(q, k, v, None, jnp.int32(3), D ** -0.5, 0.0, True,
                         False, block_q, block_k, window)

    def grad_op(q, k, v, g, lse):
        return pa._bwd_call(q, k, v, None, jnp.int32(3), g, lse, D ** -0.5,
                            0.0, True, False, block_q, block_k, window)

    assert _kernels(jax.jit(attend).lower(q, k, k).compile()) == 1
    back = jax.jit(grad_op).lower(q, k, k, q, _lse(q, one_chip))
    assert [tuple(o.shape) for o in back.out_info] == [
        (B, H, S, D), (B, kv, S, D), (B, kv, S, D)]
    assert _kernels(back.compile()) == 1


@pytest.mark.parametrize("batch,seq,taps", [(4, 4096, 3), (1, 8192, 4)])
def test_short_conv_kernels_compile_for_v5e(one_chip, batch, seq, taps):
    """The gated short convolution at the LFM2 cell's shape ([16384, 3 x
    2048] bf16, sequences of 4096) and at the longest sequence the kernels
    take: one kernel forward; the backward a Program's grad op lowers holds
    the backward kernel alone."""
    from paddle_tpu.ops import pallas_short_conv as psc
    C = 2048
    assert psc.supports(seq, C, taps) and not psc.supports(seq, C + 64, taps)
    x = jax.ShapeDtypeStruct((batch * seq, 3 * C), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((C, taps), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((batch * seq, C), jnp.bfloat16,
                             sharding=one_chip)

    def conv(x, w):
        return psc.short_conv(x, w, seq, False)

    def grads(x, w, g):
        return jax.vjp(conv, x, w)[1](g)

    assert _kernels(jax.jit(conv).lower(x, w).compile()) == 1
    back = jax.jit(grads).lower(x, w, g)
    assert [tuple(o.shape) for o in back.out_info] == [
        (batch * seq, 3 * C), (C, taps)]
    assert _kernels(back.compile()) == 1


@pytest.mark.parametrize("batch,seq", [(1, 4096), (2, 2048)])
def test_ungated_conv_kernels_compile_for_v5e(one_chip, batch, seq):
    """A Mamba mixer's ``silu(conv(xBC) + bias)`` at the Granite cell's shape
    ([4096, 4352] bf16, 4 taps, 34 channel blocks): one kernel forward, the
    backward kernel alone in what a grad op lowers, gradients for the input,
    the filter and the bias."""
    from paddle_tpu.ops import pallas_short_conv as psc
    C, taps = 4352, 4
    assert psc.supports(seq, C, taps, True)
    x = jax.ShapeDtypeStruct((batch * seq, C), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((C, taps), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((C,), jnp.bfloat16, sharding=one_chip)

    def conv(x, w, b):
        return psc.short_conv(x, w, seq, False, b, False, "silu")

    def grads(x, w, b, g):
        return jax.vjp(conv, x, w, b)[1](g)

    assert _kernels(jax.jit(conv).lower(x, w, b).compile()) == 1
    back = jax.jit(grads).lower(x, w, b, x)
    assert [tuple(o.shape) for o in back.out_info] == [
        (batch * seq, C), (C, taps), (C,)]
    assert _kernels(back.compile()) == 1


@pytest.mark.parametrize("batch,seq,chunk", [(1, 4096, 256), (2, 1024, 128)])
def test_ssd_scan_kernels_compile_for_v5e(one_chip, batch, seq, chunk):
    """The chunked scan at the Granite cell's shape (64 heads of 64, state
    128, chunks of 256, one sequence of 4096, bf16 with float32 dt): one
    kernel forward; the backward a Program's grad op lowers holds the state
    pass and the reverse kernel and not the forward (its outputs are not
    residuals); no [.., 256, 256] array in the program around them."""
    from paddle_tpu.ops import pallas_ssd
    H, P, N = 64, 64, 128
    assert pallas_ssd.supports(seq, H, P, N, chunk)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (sds((batch, seq, H, P), jnp.bfloat16),
            sds((batch, seq, H), jnp.float32), sds((H,), jnp.float32),
            sds((batch, seq, N), jnp.bfloat16),
            sds((batch, seq, N), jnp.bfloat16), sds((H,), jnp.float32))

    def scan(*a):
        return pallas_ssd.ssd_scan(*a, chunk, False)

    def grads(*a):
        return jax.vjp(scan, *a[:-1])[1](a[-1])

    assert _kernels(jax.jit(scan).lower(*args).compile()) == 1
    back = jax.jit(grads).lower(*args, args[0])
    assert [tuple(o.shape) for o in back.out_info] == [
        tuple(a.shape) for a in args]
    compiled = back.compile()
    assert _kernels(compiled) == 2
    assert f"{chunk},{chunk}]" not in compiled.as_text().split(
        "ENTRY")[1].replace("custom_call", "")
    # the state a chunk, float32, and dB / dC a head block: well under the
    # composed form's 0.78 GB at this shape
    assert compiled.memory_analysis().temp_size_in_bytes < 200e6


@pytest.mark.parametrize("form", ["split", "packed"])
@pytest.mark.parametrize("batch,seq,chunk", [(2, 4096, 128), (1, 2048, 64)])
def test_gated_delta_rule_kernels_compile_for_v5e(one_chip, batch, seq,
                                                  chunk, form):
    """The chunked delta rule at the Qwen3-Next cell's shape (16 key and 32
    value heads of 128, chunks of 128, two sequences of 4096, bf16 with
    float32 g and beta): one kernel forward, which also writes the state
    entering each chunk; the backward the op's grad lowering calls is one
    kernel on those states and no forward. Raw q, k and v, as three arrays
    or (``packed``, the cell's) as the conv's one ``[B, S, 8192]`` array
    read in place: no array of q's, k's or v's shape is made around either
    kernel, and the per-head scalars go in unpadded."""
    from paddle_tpu.ops import pallas_delta
    n_k, n_v, d = 16, 32, 128
    assert pallas_delta.supports(seq, n_k, n_v, d, d, chunk)
    assert pallas_delta.packs(n_k, n_v)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    keys, values = sds((batch, seq, n_k * d), jnp.bfloat16), sds(
        (batch, seq, n_v * d), jnp.bfloat16)
    qkv = (keys, keys, values) if form == "split" else sds(
        (batch, seq, (2 * n_k + n_v) * d), jnp.bfloat16)
    scalars = sds((batch, seq, n_v), jnp.float32)
    states = sds((batch, seq // chunk, n_v, d, d), jnp.float32)

    def forward(*a):
        return pallas_delta._fwd_call(*a, chunk, False)

    def backward(*a):
        return pallas_delta._bwd_call(*a, chunk, False)
    fwd = jax.jit(forward).lower(qkv, scalars, scalars)
    assert [tuple(o.shape) for o in fwd.out_info] == [
        values.shape, states.shape]
    compiled = fwd.compile()
    assert _kernels(compiled) == 1
    # the scalars' rows (a value head each) alone: no operand of the kernel is
    # copied, cut out or normalised around it
    assert compiled.memory_analysis().temp_size_in_bytes < 20e6
    back = jax.jit(backward).lower(qkv, scalars, scalars, states, values)
    grads = jax.tree_util.tree_leaves(back.out_info)
    assert [tuple(o.shape) for o in grads] == [
        tuple(a.shape) for a in jax.tree_util.tree_leaves(qkv)] + [
        scalars.shape, scalars.shape]
    compiled = back.compile()
    assert _kernels(compiled) == 1
    # no [.., chunk, chunk] block of every batch, chunk and head around
    # them (the composed form's 3.5 GB at the cell's shape: chip run, PR
    # 41) and no lane-padded [.., chunk, 2] scalars (67 MB an array until
    # PR 43): the scalars' rows and their gradients', and for the packed
    # form dq, dk and dv before their concatenation (134 MB at the cell's
    # shape)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        20e6 if form == "split" else 160e6)
    text = compiled.as_text().split("ENTRY")[1]
    assert f",{chunk},2]" not in text and "f32[2,4096,2048]" not in text


@pytest.mark.parametrize("S", [384, 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_composed_below_1024(S, dtype):
    """The lengths 'auto' newly gives the kernels (interpreter, no dropout):
    forward and gradients against the composed lowering in float32, at the
    default block_q. Tolerances as tests/test_pallas_attention.py derives
    them."""
    import numpy as np
    ks = jax.random.split(jax.random.PRNGKey(S), 5)
    q, k, v, g = (jax.random.normal(kk, (2, 2, S, 64), dtype)
                  for kk in ks[:4])
    bias = jnp.where(jax.random.bernoulli(ks[4], 0.9, (2, 1, 1, S)),
                     0.0, -1e4).astype(dtype)
    f32 = lambda x: x.astype(jnp.float32)                   # noqa: E731
    assert S % pa.default_block_q(S) == 0

    def both(attend, *xs):
        out, vjp = jax.vjp(attend, *xs)
        return [np.asarray(f32(x)) for x in (out, *vjp(g.astype(out.dtype)))]

    ref = both(lambda q, k, v: pa.composed_attention(
        q, k, v, f32(bias), 0.125, 0.0, False, None), f32(q), f32(k), f32(v))
    got = both(lambda q, k, v: pa._flash(
        q, k, v, bias, jnp.int32(7), 0.125, 0.0, False, True), q, k, v)
    for r, x in zip(ref, got):
        if dtype == jnp.float32:
            np.testing.assert_allclose(x, r, atol=5e-5, rtol=1e-4)
        else:
            atol = 2.0 ** -9 * (np.sqrt(S) * np.sqrt((r * r).mean())
                                + np.abs(r).max())
            np.testing.assert_allclose(x, r, atol=atol, rtol=0)


def _expert_matmul(x, w, count):
    """``moe_expert_matmul``'s lowering, off a mesh."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import decoder_ops
    return decoder_ops.moe_expert_matmul(
        LowerCtx({}), {"X": [x], "W": [w], "Count": [count]})["Out"][0]


@pytest.mark.parametrize("rows,k,n", [(131072, 2048, 1024),
                                      (131072, 1024, 2048),
                                      (65536, 2048, 1024)])
def test_grouped_expert_matmul_compiles_for_v5e(one_chip, as_on_the_chip,
                                                rows, k, n):
    """OLMoE's expert products (64 experts, 4 x 4096 and 2 x 4096 tokens x
    top-8 rows) through the megablox kernels at ``GMM_TILING``: one kernel
    forward; and what a Program's grad op lowers -- the forward again under
    jax.vjp, its output unused -- holds the rows' and the weights' gradient
    kernels and nothing of the forward."""
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((64, k, n), jnp.bfloat16, sharding=one_chip)
    count = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((rows, n), jnp.bfloat16, sharding=one_chip)

    def grads(x, w, count, g):
        return jax.vjp(lambda x, w: _expert_matmul(x, w, count), x, w)[1](g)

    fwd = jax.jit(_expert_matmul).lower(x, w, count).compile()
    assert _kernels(fwd) == 1
    assert _kernels(jax.jit(grads).lower(x, w, count, g).compile()) == 2


@pytest.mark.parametrize("k,n", [(2048, 1792), (1792, 2048)])
def test_held_expert_matmul_compiles_for_v5e(one_chip, as_on_the_chip, k, n):
    """The LFM2 cell's expert products: 4 x 4096 tokens x top-4 rows (every
    assignment has a row), the sizes of all 32 groups, and the stacked
    weights of the 8 held experts only. The megablox kernels visit the held
    groups' row tiles and the rest of the output is zero-filled: the same
    kernel counts as with every expert held."""
    rows = 65536
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8, k, n), jnp.bfloat16, sharding=one_chip)
    count = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((rows, n), jnp.bfloat16, sharding=one_chip)

    def grads(x, w, count, g):
        return jax.vjp(lambda x, w: _expert_matmul(x, w, count), x, w)[1](g)

    fwd = jax.jit(_expert_matmul).lower(x, w, count).compile()
    assert _kernels(fwd) == 1
    back = jax.jit(grads).lower(x, w, count, g)
    assert [tuple(o.shape) for o in back.out_info] == [(rows, k), (8, k, n)]
    assert _kernels(back.compile()) == 2


@pytest.mark.parametrize("shape,rot", [
    ((4, 16, 4096, 128), 128),      # olmoe's q and k
    ((1, 48, 4096, 128), 64),       # laguna's full-attention q: half rotated
    ((4, 32, 4096, 64), 64),        # lfm2's q: half a vreg of lanes
    ((2, 16, 4096, 256), 64)])      # qwen3_next's q: a quarter of two vregs
def test_rotary_kernel_compiles_for_v5e(one_chip, shape, rot):
    """The one-pass rotary kernel at the four decoder cells' shapes: one
    kernel forward, one for the cotangent (the same pass, no forward under
    it), and nothing of the array's size beside them -- no float32 copy, no
    half-width array."""
    from paddle_tpu.ops import pallas_rope
    seq, dim = shape[-2:]
    assert pallas_rope.supports(seq, dim)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    table = jax.ShapeDtypeStruct((seq, rot), jnp.float32, sharding=one_chip)

    def forward(x, cos, sin):
        return pallas_rope.rotate(x, cos, sin, rot, False)

    def backward(g, cos, sin):
        x = jnp.zeros(shape, jnp.bfloat16)
        return jax.vjp(lambda v: forward(v, cos, sin), x)[1](g)[0]
    for fn in (forward, backward):
        compiled = jax.jit(fn).lower(x, table, table).compile()
        assert _kernels(compiled) == 1
        text = compiled.as_text()
        assert f"f32[{','.join(map(str, shape))}]" not in text
        assert f",{seq},{rot // 2}]" not in text


@pytest.mark.parametrize("wide", [True, False], ids=["wide", "as_x"])
def test_gated_norm_kernels_compile_for_v5e(one_chip, wide):
    """The gated ``rms_norm``'s kernels at qwen3_next's mixer (8,192 tokens,
    32 value heads of 128 = ``[262144, 128]`` rows): one kernel forward, one
    backward with no forward under it, no float32 array of the operands'
    size beside them, and (``wide``: the delta rule's flat output and the
    projection's ``[T, heads * D]`` gate, as the model hands them) no copy
    or relayout of an operand either: the kernels read the 2-D arrays in
    place, a head at its column offset."""
    from paddle_tpu.ops import pallas_norm
    T, heads, dim = 8192, 32, 128
    flat = jax.ShapeDtypeStruct((2, T // 2, heads * dim), jnp.bfloat16,
                                sharding=one_chip)
    z = jax.ShapeDtypeStruct((T, heads * dim) if wide else (T, heads, dim),
                             jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((dim,), jnp.float32, sharding=one_chip)
    assert pallas_norm.supports(pallas_norm.wide_view(
        (T, heads, dim), z.shape)[0], dim)

    def heads_of(o):            # as decoder_lm.delta_net reshapes the rule's
        return o.reshape(T, heads, dim) if wide else o.reshape(z.shape)

    # read as the model's neighbours read them: the output projection
    # flattens y, the delta rule's grad op flattens dx
    def forward(o, z, w):
        return pallas_norm._fwd_call(heads_of(o), z, w, 1e-6,
                                     False).reshape(T, -1)

    def backward(o, z, w, dy):
        dx, dz, dw = pallas_norm._bwd_call(heads_of(o), z, w, heads_of(dy),
                                           1e-6, False)
        return dx.reshape(o.shape), dz, dw
    for fn, args in ((forward, (flat, z, w)), (backward, (flat, z, w, flat))):
        compiled = jax.jit(fn).lower(*args).compile()
        assert _kernels(compiled) == 1
        text = compiled.as_text()
        assert f"f32[{T},{heads * dim}]" not in text
        assert f"f32[{T},{heads},{dim}]" not in text
        assert f"f32[{T * heads},{dim}]" not in text
        if wide:
            assert " copy(" not in text and " reshape(" not in text


@pytest.mark.parametrize("chunk", [64, 128])
def test_channel_decay_kernels_compile_for_v5e(one_chip, chunk):
    """Kimi Delta Attention's rule at the Kimi Linear cell's shape (2 x 4,096
    tokens, 32 heads of 128, q | k | v packed as the conv writes them, G a
    key channel in float32): one kernel forward (which writes the states),
    one backward on them; the sub-blocks' static slices, the pairwise
    passes' broadcasts and the 128 x 128 state in VMEM are Mosaic's to
    refuse, which the interpreter cannot say."""
    from paddle_tpu.ops import pallas_delta
    B, S, n = 2, 4096, 32

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    qkv = arg((B, S, 3 * n * 128), jnp.bfloat16)
    g, beta = arg((B, S, n, 128), jnp.float32), arg((B, S, n), jnp.float32)
    assert pallas_delta.supports(S, n, n, 128, 128, chunk, channel=True)
    forward = jax.jit(lambda a, b, c: pallas_delta._fwd_call(
        a, b, c, chunk, False)).lower(qkv, g, beta).compile()
    assert _kernels(forward) == 1
    states = arg((B, S // chunk, n, 128, 128), jnp.float32)
    do = arg((B, S, n * 128), jnp.bfloat16)
    backward = jax.jit(lambda a, b, c, d, e: pallas_delta._bwd_call(
        a, b, c, d, e, chunk, False)).lower(qkv, g, beta, states,
                                            do).compile()
    assert _kernels(backward) == 1
    text = backward.as_text()
    # dG leaves as [B, S, heads * 128] float32, a head a lane tile, and is
    # reshaped, not relaid
    assert f"f32[{B},{S},{n * 128}]" in text


@pytest.mark.parametrize("d", [192, 256])
def test_flash_kernels_with_a_narrow_value_head_compile_for_v5e(one_chip, d):
    """The Kimi Linear cell's latent attention: 32 heads, q / k 192 wide as
    published or 256 with 64 zero columns, v and the output 128, two
    sequences of 4,096, CAUSAL_BLOCKS' tiles: forward and backward."""
    B, H, S, dv = 2, 32, 4096, 128

    def arg(width):
        return jax.ShapeDtypeStruct((B, H, S, width), jnp.bfloat16,
                                    sharding=one_chip)
    q, v = arg(d), arg(dv)
    blocks = pa._blocks(S, True)
    forward = jax.jit(lambda q, k, v: pa._fwd_call(
        q, k, v, None, jnp.int32(3), 192 ** -0.5, 0.0, True, False,
        *blocks)).lower(q, q, v).compile()
    assert _kernels(forward) == 1
    assert f"bf16[{B},{H},{S},{dv}]" in forward.as_text()
    backward = jax.jit(lambda q, k, v, g, lse: pa._bwd_call(
        q, k, v, None, jnp.int32(3), g, lse, 192 ** -0.5, 0.0, True, False,
        *blocks)).lower(q, q, v, v, _lse(q, one_chip)).compile()
    assert _kernels(backward) == 1


def test_sigmoid_gated_norm_kernels_compile_for_v5e(one_chip):
    """The gated norm under ``activation="sigmoid"`` at the KDA mixer's
    shape: the silu kernels' plan, one kernel each way."""
    from paddle_tpu.ops import pallas_norm
    T, heads, dim = 8192, 32, 128

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x, z, w = arg((T, heads, dim)), arg((T, heads * dim)), arg(
        (dim,), jnp.float32)
    for fn, args in (
            (lambda x, z, w: pallas_norm._fwd_call(x, z, w, 1e-5, False,
                                                   "sigmoid"), (x, z, w)),
            (lambda x, z, w, dy: pallas_norm._bwd_call(
                x, z, w, dy, 1e-5, False, "sigmoid"), (x, z, w, x))):
        assert _kernels(jax.jit(fn).lower(*args).compile()) == 1


def _captured_step(main, feed, fetch, scope):
    """The jitted train step of ``main`` and its arguments, taken from the
    executor where it would compile them."""
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import Executor
    taken = {}

    class Captured(Exception):
        pass

    def capture(self, key, compiled, args):
        taken["fn"], taken["args"] = compiled.fn, args
        raise Captured()

    exe = fluid.Executor()
    real = Executor._aot_compile
    Executor._aot_compile = capture
    try:
        with pytest.raises(Captured):
            exe.run(main, feed=feed, fetch_list=fetch, scope=scope)
    finally:
        Executor._aot_compile = real
        exe.close()
    return taken["fn"], taken["args"]


_DECODER = {"hidden_size": 256, "num_hidden_layers": 1,
            "num_attention_heads": 2, "num_experts": 4,
            "num_experts_per_tok": 2, "intermediate_size": 128,
            "vocab_size": 512, "rms_norm_eps": 1e-5, "rope_theta": 10000,
            "dtype": "bfloat16"}


def _decoder_step_text(one_chip, model, batch, seq):
    """A small decoder_lm train step (one layer, widths Mosaic takes, S below
    the flash kernel's) compiled for the v5e: its optimized HLO text and the
    Program, whose lowering notes are that compile's."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_lm

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [batch, seq], "int64", **A)
        labels = fluid.data("labels", [batch * seq, 1], "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        fluid.optimizer.AdamW(4e-4, weight_decay=0.1).minimize(out["loss"])
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    exe.close()
    main._lowering_notes.clear()
    fn, args = _captured_step(main, {
        "ids": np.zeros((batch, seq), np.int32),
        "labels": np.zeros((batch * seq, 1), np.int32)}, [out["loss"]], scope)

    def spec(x):
        x = x if hasattr(x, "dtype") else np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    return fn.lower(
        *jax.tree_util.tree_map(spec, args)).compile().as_text(), main


# tokens, top-k, width, buffer rows, held groups of the five cells with
# expert layers (the held cells' buffers are their row budgets; LFM2's and
# OLMoE's hold every assignment)
TOKEN_SUMS = {"qwen3_next": (8192, 10, 2048, 20480, 32),
              "lfm2": (16384, 4, 2048, 65536, 8),
              "laguna": (4096, 10, 3072, 5120, 8),
              "glm": (4096, 4, 2048, 8192, 8),
              "olmoe": (16384, 8, 2048, 131072, 64)}


@pytest.mark.parametrize("cell", sorted(TOKEN_SUMS))
def test_token_sums_kernel_compiles_for_v5e(one_chip, cell):
    """The expert layers' token sums (ops/pallas_moe_rows.py: slabs of rows
    by DMA, a 0/1 product a 128 rows on the MXU) at the cells' shapes: one
    Mosaic call, the rows read where they are (no copy or reshape of the
    buffer's size beside it) and no scatter."""
    from paddle_tpu.ops import pallas_moe_rows
    T, k, H, R, G = TOKEN_SUMS[cell]
    assert pallas_moe_rows.supports(T, R, H, jnp.bfloat16)
    text = pallas_moe_rows.token_sums.lower(
        jax.ShapeDtypeStruct((R, H), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((T, k), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((G + 1,), jnp.int32, sharding=one_chip),
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    entry = text[text.index("\nENTRY "):]
    assert f"bf16[{R},{H}]" in entry and " scatter(" not in entry
    assert not [ln for ln in entry.splitlines()
                if f"[{R}," in ln.split(" = ")[-1].split("(")[0]
                and " parameter(" not in ln]


@pytest.mark.parametrize("width,dtype", [(2304, jnp.bfloat16),
                                         (128, jnp.float32)],
                         ids=["rows", "weights_as_lanes"])
def test_exchange_rows_kernel_compiles_for_v5e(one_chip, width, dtype):
    """The change of order of the exchange's received rows
    (ops/pallas_exchange_rows.py: slabs of the segments' rows by DMA, into
    place by a sublane rotate) at mellum2_12b_a2_5b.pretrain_s4096_ep4's
    buffer, 64 segments: one Mosaic call, no gather, the buffer read where
    it is."""
    from paddle_tpu.ops import pallas_exchange_rows
    R = 81920
    assert pallas_exchange_rows.supports(R, width, dtype)
    table = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    text = pallas_exchange_rows.move_segments.lower(
        jax.ShapeDtypeStruct((R, width), dtype, sharding=one_chip),
        table, table, table).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    entry = text[text.index("\nENTRY "):]
    assert " gather(" not in entry and " scatter(" not in entry


@pytest.mark.parametrize("held", [False, True], ids=["all_held", "a_part"])
def test_expert_layer_step_holds_nine_grouped_matmuls_and_one_sort_by_expert(
        one_chip, as_on_the_chip, held):
    """D11 on the decoder: every grad op re-lowers its forward under
    jax.vjp. A small decoder_lm train step (one layer, widths Mosaic takes,
    S below the flash kernel's) compiled for the v5e must hold, a layer,
    3 forward + 6 backward grouped-matmul kernels and one sort, and no more:
    the copies the grad ops trace are dropped or merged. The token sums are
    two calls of one kernel (``moe_combine``, ``moe_dispatch_grad``: PR 50),
    with all experts held or 4 of 16 under a row budget, and neither scope
    holds a scatter or a float32 array of the buffer's rows."""
    import re

    model = dict(_DECODER, router_aux_loss_coef=0.01, router_z_loss_coef=0.001)
    if held:
        model.update(num_experts_routed=16, moe_row_budget=256)
    text, main = _decoder_step_text(one_chip, model, batch=2, seq=128)
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    in_scope = lambda ln, scope: re.search(                 # noqa: E731
        r'op_name="[^"]*/' + scope + r'#\d+/', ln) is not None
    assert sum(in_scope(ln, "moe_expert_matmul") for ln in kernels) == 3
    assert sum(in_scope(ln, "moe_expert_matmul_grad") for ln in kernels) == 6
    # q's and k's rotation, and the same pass over their cotangents (PR 42)
    assert sum(in_scope(ln, "rotary_embedding") for ln in kernels) == 2
    assert sum(in_scope(ln, "rotary_embedding_grad") for ln in kernels) == 2
    assert sum(in_scope(ln, "moe_combine") for ln in kernels) == 1
    assert sum(in_scope(ln, "moe_dispatch_grad") for ln in kernels) == 1
    assert len(kernels) == 15           # S=128: attention is XLA's here
    sums = [ln for ln in text.splitlines() if in_scope(ln, "moe_combine")
            or in_scope(ln, "moe_dispatch_grad")]
    rows = model.get("moe_row_budget", 2 * 128 * 2)
    assert not [ln for ln in sums if re.search(r"\sscatter\(", ln)
                or f"f32[{rows},256]" in ln]
    assert lowering_reports.read(
        lowering_reports.publish(main), "moe_rows_lowering_total", "impl",
        "op", "bound") == {
            ("pallas", op, "held" if held else "all"): 1
            for op in ("combine", "dispatch_grad")}
    # the stable sort by expert, once; XLA's TPU top_k is a sort too (the
    # router's), and neither is traced a second time into the step by the
    # grad ops
    sorts = [ln for ln in text.splitlines() if re.search(r"\ssort\(", ln)]
    assert sum(in_scope(ln, "moe_dispatch") for ln in sorts) == 1
    assert sum(in_scope(ln, "moe_router") for ln in sorts) == 1
    assert not [ln for ln in sorts if in_scope(ln, "moe_dispatch_grad")
                or in_scope(ln, "moe_router_grad")]


@pytest.mark.parametrize("form,line", [("fused", 1 << 30), ("written", 1)])
def test_decoder_step_holds_no_float32_logits_in_either_form_of_the_loss_grad(
        one_chip, as_on_the_chip, monkeypatch, form, line):
    """The decoder hands ``softmax_with_cross_entropy`` its bfloat16 logits
    (PR 40). Compiled for the v5e, no float32 value of the logits' shape
    outlives a fusion, forward or backward; the grad op is noted ``fused``
    or, with the line patched down, ``written``: then the step holds the
    loop over row chunks of the logits' own buffer, no copy of a
    logits-shaped array beside it, and the head's weight-gradient product
    reads an array (no ``exponential`` in its fused computation)."""
    import re

    from paddle_tpu.observability.attribution import parse_hlo_computations
    from paddle_tpu.ops import math_ops

    monkeypatch.setattr(math_ops, "WRITTEN_GRAD_MIN_BYTES", line)
    model = dict(_DECODER, vocab_size=640)
    batch, seq = 4, 128     # 512 tokens: no size equals hidden
    text, main = _decoder_step_text(one_chip, model, batch, seq)
    assert lowering_reports.read(lowering_reports.publish(main),
                                 "loss_backward_total", "form") == {form: 1}
    comps, entry, _ = parse_hlo_computations(text)
    called = {c for ins in comps[entry]
              for c in re.findall(r"(?:body|condition)=%?([\w.\-]+)", ins.rest)}
    held = [ins for name in (entry, *called) for ins in comps[name]]
    logits = f"[{batch * seq},{model['vocab_size']}]"
    assert any(ins.shape.startswith("bf16" + logits) for ins in held)
    assert not [ins.name for ins in held if "f32" + logits in ins.shape]
    assert not [ins.name for ins in held
                if ins.opcode == "copy" and logits in ins.shape]
    loops = [ins for ins in comps[entry] if ins.opcode == "while"
             and "softmax_with_cross_entropy_grad#" in ins.op_name]
    assert len(loops) == (form == "written")
    if form == "written":
        dw = [ins for ins in comps[entry]
              if "mul_grad#" in ins.op_name and ins.opcode == "fusion"
              and f"[256,{model['vocab_size']}]" in ins.shape]
        assert dw
        for ins in dw:
            body = re.search(r"calls=%?([\w.\-]+)", ins.rest).group(1)
            assert not [i.name for i in comps[body]
                        if i.opcode == "exponential"]


def _bert_s512_step(strategy=None, with_lse=True):
    """A 12-layer BERT train step at S=512 (bert_base.pretrain_s512's op:
    bias, dropout 0.1, d=64; narrow otherwise) with ``impl='auto'`` and no
    tuning decision on disk, under ``strategy`` where one is given: the
    Program, the jitted step and its arguments. ``with_lse=False``: as a
    desc from before the op declared ``Lse`` reads (no such output on the
    op, no such input on its grad op)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    B, S, M, heads = 4, 512, 8, 2
    cfg = bert.BertConfig(vocab_size=128, hidden=64 * heads, n_layers=12,
                          n_heads=heads, max_seq_len=S, dropout=0.1,
                          attn_impl="auto", dtype="bfloat16")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        names = ("src_ids", "pos_ids", "sent_ids")
        ids = [fluid.data(n, [B, S], "int64", **A) for n in names]
        mask = fluid.data("input_mask", [B, S], "float32", **A)
        mpos = fluid.data("mask_pos", [M, 1], "int64", **A)
        mlabel = fluid.data("mask_label", [M, 1], "int64", **A)
        nsp = fluid.data("nsp_label", [B, 1], "int64", **A)
        total, _, _ = bert.pretrain(*ids, mask, mpos, mlabel, nsp, cfg)
        fluid.optimizer.Adam(1e-4).minimize(total)
    for op in [] if with_lse else main.global_block().ops:
        if op.type == "fused_attention":
            del op.outputs["Lse"]
        elif op.type == "fused_attention_grad":
            del op.inputs["Lse"]
            op.attrs["__fwd_out_slots__"] = ["Out"]
    scope = fluid.Scope()
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    exe.close()
    feed = {n: np.zeros((B, S), np.int32) for n in names}
    feed.update(input_mask=np.ones((B, S), np.float32),
                mask_pos=np.zeros((M, 1), np.int32),
                mask_label=np.zeros((M, 1), np.int32),
                nsp_label=np.zeros((B, 1), np.int32))
    run = main if strategy is None else \
        fluid.CompiledProgram(main).with_strategy(strategy)
    return (main,) + _captured_step(run, feed, [total], scope)


def _lowering_counts(main):
    """What the executor's counters would add for the trace just made:
    {"<impl>@<block_q>": ops} and, for the grad ops, {"<stats>": ops}."""
    registry = lowering_reports.publish(main, "step")
    counts = {"@".join(k): n for k, n in lowering_reports.read(
        registry, "attention_lowering_total", "impl", "block_q").items()}
    counts.update(lowering_reports.read(
        registry, "attention_backward_total", "stats"))
    return counts


def test_bert_s512_step_holds_24_kernels_lowered_once_and_no_score_matrix(
        one_chip, as_on_the_chip):
    """The step on one chip: the defaults give every layer the kernels. The
    compiled step holds 12 forward + 12 backward Mosaic calls and no
    [B, heads, S, S] array; the lowered module holds each kernel once (the
    layers share one trace and one lowered function: the set-up half of
    ISSUE 27) and, before XLA has dropped anything, calls the forward's 12
    times and the backward's 12: no grad op lowers a forward (it reads the
    forward op's Lse), so none is there for XLA to keep; the counters say
    12 x pallas at block_q 512 and 12 backwards on saved statistics."""
    import re

    import numpy as np

    main, fn, args = _bert_s512_step()

    def spec(x):
        x = x if hasattr(x, "dtype") else np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    lowered = fn.lower(*jax.tree_util.tree_map(spec, args))
    module = lowered.as_text()
    assert module.count("tpu_custom_call") == 2
    assert len(re.findall(r"call @_fwd_call\b", module)) == 12
    assert len(re.findall(r"call @_bwd_call\b", module)) == 12
    assert _lowering_counts(main) == {"pallas@512": 12, "saved": 12}
    text = lowered.compile().as_text()
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    in_scope = lambda ln, scope: re.search(                 # noqa: E731
        r'op_name="[^"]*/' + scope + r'#\d+/', ln) is not None
    assert sum(in_scope(ln, "fused_attention") for ln in kernels) == 12
    assert sum(in_scope(ln, "fused_attention_grad") for ln in kernels) == 12
    assert len(kernels) == 24
    assert "[4,2,512,512]" not in text


def test_bert_s512_step_from_a_desc_without_lse_still_holds_24_kernels(
        one_chip, as_on_the_chip):
    """A Program from before the op declared ``Lse``: its grad ops take the
    generic path (``recomputed``: the vjp lowers the forward kernel again
    for the statistics), and XLA merges that call with the forward op's own
    -- the same kernel on the same operands, both outputs written by both --
    so the compiled step still holds 12 + 12 Mosaic calls, not 36."""
    import numpy as np

    main, fn, args = _bert_s512_step(with_lse=False)

    def spec(x):
        x = x if hasattr(x, "dtype") else np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    lowered = fn.lower(*jax.tree_util.tree_map(spec, args))
    assert _lowering_counts(main) == {"pallas@512": 12, "recomputed": 12}
    assert _kernels(lowered.compile()) == 24


def test_bert_s512_step_under_a_dp_mesh_compiles_on_the_composed_lowering(
        v5e, as_on_the_chip, monkeypatch):
    """The same step data-parallel over the four described chips. A Mosaic
    call has no partitioning rule, so a jit over more than one device
    refuses to lower one outside a shard_map: 'auto' must read the mesh and
    keep XLA's composed lowering there (REVIEW of PR 27: with the crossover
    at 256 alone this step stopped compiling), and its grad op the generic
    vjp over that lowering. It compiles, holds no Mosaic call and the
    gradients' all-reduces, and the counters say 12 x xla, 12 x generic."""
    import numpy as np
    from jax.sharding import Mesh

    import paddle_tpu as fluid
    from paddle_tpu.compiler import DistributedStrategy

    monkeypatch.setattr(
        DistributedStrategy, "build_mesh",
        lambda self, devices=None: Mesh(np.array(v5e.devices[:4]), ("dp",)))
    strategy = fluid.DistributedStrategy(
        mesh_shape={"dp": 4},
        data_rules=[("mask_pos|mask_label", ()), ("nsp_label", ("dp",)),
                    ("src_ids|pos_ids|sent_ids|input_mask", ("dp",))])
    main, fn, args = _bert_s512_step(strategy)

    def spec(x):            # the layout's jit carries its own in_shardings
        x = x if hasattr(x, "dtype") else np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    lowered = fn.lower(*jax.tree_util.tree_map(spec, args))
    assert _lowering_counts(main) == {"xla@0": 12, "generic": 12}
    text = lowered.compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' not in text
    assert "all-reduce" in text
