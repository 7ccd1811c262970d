"""Fused multi-head attention as a Pallas TPU kernel ("flash attention").

The reference fuses transformer attention for inference with a graph pass
(reference: paddle/fluid/framework/ir/multihead_matmul_fuse_pass.cc:1 rewrites
mul/reshape/transpose/matmul/softmax chains into one multihead_matmul op). The
TPU-native version goes further: one Pallas kernel computes
softmax(Q K^T * scale + bias) V for both forward AND backward without ever
materializing the [B, heads, S, S] probability tensor in HBM -- the win is HBM
bandwidth, the usual TPU bottleneck (S=512 BERT-base: 48 MB of probs per layer
per step round-tripped, ~3x that in backward).

Design:
  * Per the registry's kernel-choice contract (core/registry.py:10), this is an
    *alternative lowering* for the `fused_attention` op: `impl=auto` picks the
    Pallas kernels on TPU from S >= AUTO_PALLAS_MIN_S up (256 since PR 27;
    XLA's own fusion wins at S=128; the measured table is at the constant)
    where the step is jitted for one device, the ring schedule under an
    sp>1 mesh, and the composed jnp lowering otherwise (a dp / mp mesh
    without sp: GSPMD cannot partition a Mosaic call) or for unsupported
    shapes. `impl='pallas'` forces the kernels
    at any supported S and raises off TPU (ops/pallas_mode.py: only the test
    harness may ask for the Pallas interpreter, so the CPU suite exercises
    the same kernel body).
  * Whole K/V rows for one (batch, head) are staged in VMEM (S*D*2 bytes
    each); Q is blocked at default_block_q(S) rows: the whole of S below
    1024, BLK_Q from there (the `fused_attention.block_sizes` choice's
    default). The softmax statistics (max, sum) and
    every accumulation are f32; the MXU is fed both operands of every product
    in the input's dtype with preferred_element_type=f32 (bf16 inputs: the
    probabilities and dS are rounded to bf16 once, as the composed lowering
    rounds p; f32 inputs keep f32 products). Nothing is divided per score:
    a power-of-two scale moves into the [BLK_Q, D] q block (exact), the
    softmax's 1/sum and the dropout's 1/(1-prob) scale [*, D] results.
    The [BLK_Q, S] f32 temporaries bound S: the forward fits Mosaic's 16 MiB
    of scoped VMEM to S=8192 at block_q 128, the backward takes
    BWD_VMEM_LIMIT_BYTES and compiles to S=4096 at BLK_Q (S=8192 at block_q
    128 only; PR 25, compiled for a described v5e); longer rows need the K
    axis blocked (ROADMAP S5).
  * Backward is a custom-VJP Pallas kernel that *recomputes* the probabilities
    per Q block from q, k, bias alone (flash-style: FLOPs are cheap, HBM is
    not; and a residual the forward kernel wrote would make a Program's grad
    op run that kernel a second time, see _flash_fwd). dK^T and dV^T [D, S]
    accumulate across the Q blocks in f32 VMEM scratch and leave in the
    input's dtype on the last one: grid axis 1 is declared "arbitrary"
    (sequential) for that, axis 0 "parallel".
  * Attention dropout uses the in-kernel PRNG (pltpu.prng_random_bits) seeded
    per (step, batch*head, 128-row block); the backward kernel reseeds
    identically so the mask matches without storing it, and the mask is the
    same under every block_q. In-kernel PRNG has no interpreter lowering, so
    dropout>0 takes the Pallas path only on a TPU (chip_smoke.py checks the
    two kernels' masks against each other there).
"""
from __future__ import annotations

import functools
import math

from ..core.registry import register

# The smallest Q block. Every S the kernel takes is a multiple of it
# (supports_pallas), every block_q too, and the dropout mask is drawn by
# blocks of it whatever block_q is.
_MIN_BLK_Q = 128

# Q rows a grid step from S=1024 up: 256 beats 128 forward + backward at
# S=1024, 2048 and 4096 (3.99 / 6.56 / 11.78 against 4.42 / 7.01 / 12.18 ms a
# layer of 16k tokens; chip runs, PR 25). 512 and 1024 gain 2-8% more at
# S=1024 and 2048 (PERF.md section 7) and 512 does not fit at 4096.
BLK_Q = 256


def default_block_q(S):
    """The Q block the kernels take at sequence length S where no tuning
    decision says otherwise; always divides S. Below 1024 one block a
    (batch, head): the whole [S, S] tile in one grid step beats every
    smaller block at S=256 ... 768 (2.43 against 2.67 ms at 256 and 3.33 at
    128, S=512, forward + backward of 16k tokens; table in PERF.md section 6,
    chip runs, PR 27) and compiles to S=896 in bf16 and f32. From 1024 up
    BLK_Q, or _MIN_BLK_Q where that does not divide S."""
    if S < 1024:
        return S
    return BLK_Q if S % BLK_Q == 0 else _MIN_BLK_Q


# Scoped VMEM the backward kernel may take. Mosaic's default (16 MiB of the
# v5e's 128) holds its [block_q, S] temporaries to S=2048 at BLK_Q; S=4096
# needs 20 MiB (28 with f32 inputs). The forward fits the default to S=8192
# and is 4% slower under a raised limit (chip runs, PR 25), so it keeps it.
BWD_VMEM_LIMIT_BYTES = 64 * 1024 * 1024

# 'auto' takes the Pallas kernels from this sequence length up; the default
# of the `fused_attention.backend` tunable choice (paddle_tpu/tuning/), which
# a persisted autotune decision overrides per (shape bucket, device).
# Measured on the v5e, forward + backward of 16k tokens, bf16, H=12 D=64, a
# [B,1,1,S] bias, dropout 0.1, ms (chip runs, PR 27; XLA's composed lowering
# against the kernels at default_block_q): S=128 2.13 / 3.13, 256 3.94 /
# 2.33, 384 5.56 / 2.39, 512 8.17 / 2.43, 1024 13.07 / 4.00, 2048 25.48 /
# 6.81; without dropout at S=512 4.97 / 2.14; causal, D=128, no bias, S=512
# 7.00 / 1.92. The composed lowering's S x S scores, probabilities and mask
# in HBM grow with S; the kernels' cost for 16k tokens hardly moves below
# S=512. XLA wins at S=128. Under a mesh of more than one device without
# sp the op keeps the composed lowering at every S (fused_attention: a
# Mosaic call has no partitioning rule). impl='pallas' forces the kernels.
AUTO_PALLAS_MIN_S = 256


def _pl():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


# --------------------------------------------------------------------------------------
# composed (XLA-fused) reference path
# --------------------------------------------------------------------------------------

def composed_attention(q, k, v, bias, scale, dropout, causal, rng,
                       bernoulli=None):
    """Plain jnp attention: the numerics oracle and the non-TPU lowering.
    ``bernoulli(key, keep, shape)`` draws the dropout mask: the op hands its
    ``LowerCtx.bernoulli_mask``, else ``jax.random.bernoulli``."""
    import jax
    import jax.numpy as jnp

    out_shape, grouped = q.shape, k.shape[1] != q.shape[1]
    scores, values = "bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd"
    if grouped:
        # grouped-query attention: query head i reads key/value head
        # i // group. The group is an axis of the products, so K and V are
        # never repeated.
        B, H, S, D = q.shape
        q = q.reshape(B, k.shape[1], H // k.shape[1], S, D)
        scores, values = "bhgqd,bhkd->bhgqk", "bhgqk,bhkd->bhgqd"
        if bias is not None:
            bias = bias[:, :, None]
    s = jnp.einsum(scores, q, k, preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        S_q, S_k = s.shape[-2], s.shape[-1]
        qi = jax.lax.broadcasted_iota(jnp.int32, (S_q, S_k), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (S_q, S_k), 1)
        s = jnp.where(ki <= qi, s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    if dropout:
        keep = (bernoulli or jax.random.bernoulli)(rng, 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    out = jnp.einsum(values, p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out.reshape(out_shape) if grouped else out


# --------------------------------------------------------------------------------------
# pallas kernels
# --------------------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims):
    """MXU product of two blocks in their own dtype, accumulated in f32."""
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _scale_is_exact(scale):
    """A power of two only moves the exponent: q * scale is exact in q's dtype,
    so the scale can leave the [block_q, S] scores for the [block_q, D] q."""
    return math.frexp(scale)[0] == 0.5


def _scores(q_blk, k_all, bias_row, iq, scale, causal):
    """[block_q, S] f32 scores of one Q block, and the q block that went into
    them (scaled when the scale is folded; the backward's dK needs it)."""
    import jax
    import jax.numpy as jnp

    blk_q = q_blk.shape[0]
    fold = _scale_is_exact(scale)
    if fold:
        q_blk = q_blk * jnp.asarray(scale, q_blk.dtype)
    s = _dot(q_blk, k_all, _NT)
    if not fold:
        s = s * scale
    if bias_row is not None:
        s = s + bias_row.astype(jnp.float32)                 # [1,S] broadcasts
    if causal:
        S_k = s.shape[-1]
        qi = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, S_k), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (blk_q, S_k), 1)
        s = jnp.where(ki <= qi, s, jnp.float32(-1e30))
    return s, q_blk


def _keep_mask(shape, seed_ref, iq, dropout, bh=None):
    """Bernoulli(1 - dropout) keep mask of one Q block, drawn by blocks of
    _MIN_BLK_Q rows, each seeded by (step seed, batch*head, its index in the
    sequence): the backward reseeds the same, and the mask does not depend on
    block_q. ``bh`` is the query's batch*head index where grid axis 0 is not
    it (the grouped backward)."""
    import jax.numpy as jnp
    pl, pltpu = _pl()
    n = shape[0] // _MIN_BLK_Q
    bits = []
    for j in range(n):
        pltpu.prng_seed(seed_ref[0]
                        + (pl.program_id(0) if bh is None else bh) * 1000003
                        + (iq * n + j) * 7919)
        bits.append(pltpu.prng_random_bits((_MIN_BLK_Q, shape[1])))
    bits = pltpu.bitcast(jnp.concatenate(bits, axis=0), jnp.uint32)
    return bits >= jnp.uint32(int(dropout * float(2**32)))


def _fwd_kernel(scale, dropout, causal, has_bias, *refs):
    import jax.numpy as jnp
    pl, _ = _pl()
    if has_bias:
        q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref = refs
        bias_row = bias_ref[0]                               # [1, S]
    else:
        q_ref, k_ref, v_ref, seed_ref, o_ref = refs
        bias_row = None
    iq = pl.program_id(1)
    s, _ = _scores(q_ref[0], k_ref[0], bias_row, iq, scale, causal)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    if dropout:
        e = jnp.where(_keep_mask(e.shape, seed_ref, iq, dropout), e, 0.0)
    # the softmax's 1/l and the dropout's 1/(1-prob) scale the [block_q, D]
    # product, one reciprocal a row, not the [block_q, S] probabilities
    o = _dot(e.astype(v_ref.dtype), v_ref[0], _NN)
    o_ref[0] = (o * (1.0 / (l * (1.0 - dropout)))).astype(o_ref.dtype)


def _bwd_kernel(scale, dropout, causal, has_bias, group, *refs):
    """``group`` query heads share a key/value head. At 1 grid axis 1 is the
    Q block; above 1 it runs over the group's heads and their Q blocks in
    turn, so that dK^T / dV^T accumulate over the whole group in VMEM and a
    key/value head's gradient is written once (no per-query-head dK, dV in
    HBM to sum afterwards)."""
    import jax.numpy as jnp
    pl, _ = _pl()
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, seed_ref, do_ref,
         dq_ref, dk_ref, dv_ref, dkt_acc, dvt_acc) = refs
        bias_row = bias_ref[0]                               # [1, S]
    else:
        (q_ref, k_ref, v_ref, seed_ref, do_ref,
         dq_ref, dk_ref, dv_ref, dkt_acc, dvt_acc) = refs
        bias_row = None
    step = iq = pl.program_id(1)
    bh = None
    if group > 1:
        n_q = pl.num_programs(1) // group
        iq = step % n_q
        bh = pl.program_id(0) * group + step // n_q
    dtype = q_ref.dtype
    s, q_s = _scores(q_ref[0], k_ref[0], bias_row, iq, scale, causal)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = e * (1.0 / jnp.sum(e, axis=-1, keepdims=True))       # [BLK_Q, S] f32
    do = do_ref[0]                                           # [BLK_Q, D]
    dpd = _dot(do, v_ref[0], _NT)                            # [BLK_Q, S] f32
    # Dropout multiplies p and its gradient by keep * c, c = 1/(1-prob). The
    # mask is applied here; c is a constant of every product below, so it
    # scales the [*, D] results (pk, dp, ds are the true values over c).
    c = 1.0 / (1.0 - dropout)
    if dropout:
        keep = _keep_mask(p.shape, seed_ref, iq, dropout, bh)
        pk = jnp.where(keep, p, 0.0)
        dp = jnp.where(keep, dpd, 0.0)
    else:
        pk, dp = p, dpd
    row = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = (p * (dp - row)).astype(dtype)
    dq_ref[0] = (_dot(ds, k_ref[0], _NN) * (c * scale)).astype(dq_ref.dtype)

    @pl.when(step == 0)
    def _():
        dkt_acc[...] = jnp.zeros_like(dkt_acc)
        dvt_acc[...] = jnp.zeros_like(dvt_acc)

    # dK^T, dV^T [D, S]: contracting the Q rows of both operands transposes
    # the [BLK_Q, D] block, not the [BLK_Q, S] one
    dkt_acc[...] += _dot(q_s, ds, _TN)
    dvt_acc[...] += _dot(do, pk.astype(dtype), _TN)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        k_scale = c if _scale_is_exact(scale) else c * scale  # q_s has it
        dk_ref[0] = (dkt_acc[...] * k_scale).T.astype(dk_ref.dtype)
        dv_ref[0] = (dvt_acc[...] * c).T.astype(dv_ref.dtype)


def _operands(q, k, v, bias, seed, block_q, by_kv_head=False):
    """The kernels' common operands and block specs, and the number of Q
    blocks (block_q divides S: _flash has seen to it). Grid axis 0 is the
    query's batch*head and axis 1 the Q block; with fewer key/value heads
    than query heads (k, v ``[B, Hkv, S, D]``) a query head's program reads
    its key/value head's rows in place. ``by_kv_head`` (the grouped
    backward): axis 0 is the key/value's batch*head, axis 1 the group's
    heads x Q blocks."""
    import jax.numpy as jnp
    pl, pltpu = _pl()
    B, H, S, D = q.shape
    kv = k.shape[1]
    group, n_q = H // kv, S // block_q
    args = [q.reshape(B * H, S, D), k.reshape(B * kv, S, D),
            v.reshape(B * kv, S, D)]
    if group == 1:
        q_at, kv_at, per_batch = (lambda b, i: (b, i, 0),
                                  lambda b, i: (b, 0, 0), H)
    elif by_kv_head:
        q_at, kv_at, per_batch = (
            lambda b, i: (b * group + i // n_q, i % n_q, 0),
            lambda b, i: (b, 0, 0), kv)
    else:
        q_at, kv_at, per_batch = (lambda b, i: (b, i, 0),
                                  lambda b, i: (b // group, 0, 0), H)
    qspec = pl.BlockSpec((1, block_q, D), q_at, memory_space=pltpu.VMEM)
    kvspec = pl.BlockSpec((1, S, D), kv_at, memory_space=pltpu.VMEM)
    in_specs = [qspec, kvspec, kvspec]
    if bias is not None:
        # [B,1,S] with block (1,1,S): the last two dims equal the array dims,
        # satisfying the TPU (8,128)-divisible-or-full block constraint.
        args.append(bias.reshape(B, 1, S))
        in_specs.append(pl.BlockSpec(
            (1, 1, S), lambda b, i: (b // per_batch, 0, 0),
            memory_space=pltpu.VMEM))
    args.append(jnp.asarray(seed, jnp.int32).reshape(1))
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    return args, in_specs, qspec, kvspec, n_q


def _compiler_params(interpret, vmem_limit_bytes=None):
    """Grid axis 0 (batch*head) is independent; axis 1 (Q blocks) must run
    in order -- the backward accumulates dK/dV over it in scratch and writes
    them on the last block. The interpreter takes no Mosaic parameters."""
    if interpret:
        return {}
    _, pltpu = _pl()
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes)}


import jax as _jax  # custom_vjp and jit must wrap at def time

# _flash's arguments after the arrays, all static
_STATIC = ("scale", "dropout", "causal", "interpret", "block_q")


def _flash(q, k, v, bias, seed, scale, dropout, causal, interpret,
           block_q=None):
    """The flash kernels, differentiable in q, k and v. ``block_q`` (Q rows a
    grid step) divides S; None takes ``default_block_q(S)``."""
    S = q.shape[2]
    if block_q is None:
        block_q = default_block_q(S)
    if S % block_q or block_q % _MIN_BLK_Q:
        raise ValueError(
            f"flash attention: block_q={block_q} must divide S={S} and be a "
            f"multiple of {_MIN_BLK_Q}")
    return _flash_vjp(q, k, v, bias, seed, scale, dropout, causal, interpret,
                      block_q)


@functools.partial(_jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_vjp(q, k, v, bias, seed, scale, dropout, causal, interpret,
               block_q):
    return _fwd_call(q, k, v, bias, seed, scale, dropout, causal, interpret,
                     block_q)


# Each kernel call sits behind a jit of its own. The layers of a model call
# it with the same shapes and static arguments: the first call traces the
# kernel body and lowers it to Mosaic, the others (and the forward a
# Program's grad op traces again under jax.vjp) find that trace, and the
# lowered module holds one function a kernel, called once a layer. Without
# it every call is traced and lowered by itself: 4 s of set-up at 12 layers
# (compile.trace_lower_s 8.3 against 4.4 s, ledger, PR 26).
@functools.partial(_jax.jit, static_argnames=_STATIC)
def _fwd_call(q, k, v, bias, seed, scale, dropout, causal, interpret,
              block_q):
    import jax
    pl, _ = _pl()
    B, H, S, D = q.shape
    args, in_specs, qspec, _, n_q = _operands(q, k, v, bias, seed, block_q)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale, dropout, causal,
                          bias is not None),
        grid=(B * H, n_q),
        in_specs=in_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=interpret,
        **_compiler_params(interpret),
    )(*args)
    return out.reshape(B, H, S, D)


def _flash_fwd(q, k, v, bias, seed, scale, dropout, causal, interpret,
               block_q):
    out = _fwd_call(q, k, v, bias, seed, scale, dropout, causal, interpret,
                    block_q)
    # Inputs only. A Program's generic grad op (core/registry.py) lowers the
    # forward again under jax.vjp: a kernel output among the residuals (a
    # log-sum-exp, say) keeps that second forward kernel alive, which costs
    # five times what it saves the backward (chip runs, PR 25).
    return out, (q, k, v, bias, seed)


@functools.partial(_jax.jit, static_argnames=_STATIC)
def _bwd_call(q, k, v, bias, seed, g, scale, dropout, causal, interpret,
              block_q):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    B, H, S, D = q.shape
    kv = k.shape[1]
    group = H // kv
    args, in_specs, qspec, kvspec, n_q = _operands(q, k, v, bias, seed,
                                                   block_q, by_kv_head=True)
    args.append(g.reshape(B * H, S, D))
    in_specs.append(qspec)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale, dropout, causal,
                          bias is not None, group),
        grid=(B * kv, group * n_q),
        in_specs=in_specs,
        out_specs=[qspec, kvspec, kvspec],
        out_shape=[jax.ShapeDtypeStruct((B * x.shape[1], S, D), x.dtype)
                   for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((D, S), jnp.float32),
                        pltpu.VMEM((D, S), jnp.float32)],
        interpret=interpret,
        **_compiler_params(interpret, BWD_VMEM_LIMIT_BYTES),
    )(*args)
    return (dq.reshape(B, H, S, D), dk.reshape(B, kv, S, D),
            dv.reshape(B, kv, S, D))


def _flash_bwd(scale, dropout, causal, interpret, block_q, res, g):
    import jax
    import jax.numpy as jnp
    import numpy as np
    q, k, v, bias, seed = res
    dq, dk, dv = _bwd_call(q, k, v, bias, seed, g, scale, dropout, causal,
                           interpret, block_q)
    return (dq, dk, dv, None if bias is None else jnp.zeros_like(bias),
            np.zeros(np.shape(seed), jax.dtypes.float0))


_flash_vjp.defvjp(_flash_fwd, _flash_bwd)


def supports_pallas(B, H, S, D, bias_shape, dropout, is_tpu):
    """Shape/placement gate for the Pallas lowering."""
    if S % _MIN_BLK_Q != 0 or S < _MIN_BLK_Q:
        return False
    if dropout and not is_tpu:
        return False  # in-kernel PRNG has no interpreter lowering
    if bias_shape is not None:
        # only [B,1,1,S]-broadcastable bias rows are supported fused
        if len(bias_shape) != 4 or bias_shape[1] != 1 or bias_shape[2] != 1:
            return False
    return True


# --------------------------------------------------------------------------------------
# registry op
# --------------------------------------------------------------------------------------

@register("fused_attention", nondiff_inputs=("Bias",))
def fused_attention(ctx, ins):
    """softmax(Q K^T * scale + Bias) V.

    Inputs: Q [B, heads, S, D], K/V [B, kv_heads, S, D] with kv_heads
    dividing heads (grouped-query attention: query head i reads key/value
    head i // (heads / kv_heads), in place -- no lowering repeats K or V);
    optional Bias [B, 1, 1, S] additive (already -inf-masked). Attrs: scale (default 1/sqrt(D)), dropout_prob, causal,
    is_test, impl ('auto' | 'pallas' | 'ring' | 'ulysses' | 'composed').

    Kernel choice: under a GSPMD jit whose mesh has an "sp" axis >1 (sequence
    parallelism), 'auto' opens the ring-attention shard_map island
    (parallel/ring_attention.py) so the sequence dim STAYS partitioned --
    GSPMD alone would all-gather K/V to every device; 'ulysses' instead does
    the all-to-all head-scatter schedule (parallel/ulysses.py, needs heads
    divisible by sp). Otherwise 'auto' is the Pallas flash kernel on
    TPU-supported shapes from S >= AUTO_PALLAS_MIN_S (at S=128 XLA's own
    fusion is measurably faster) where the jit spans one device, else the
    composed jnp path (a dp or mp mesh without sp: a Mosaic call cannot be
    partitioned by GSPMD). Which one an op took is counted at each compile
    (``ctx.note``; observability/attention.py).
    """
    import jax
    import jax.numpy as jnp

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = ins.get("Bias", [None])[0]
    B, H, S, D = q.shape
    kv_heads = k.shape[1]
    if H % kv_heads or v.shape[1] != kv_heads:
        raise ValueError(
            f"fused_attention: {H} query heads over {kv_heads} key and "
            f"{v.shape[1]} value heads; the query heads must be a multiple "
            f"of the key/value heads")
    scale = ctx.attr("scale") or (1.0 / math.sqrt(D))
    dropout = 0.0 if ctx.attr("is_test", False) else ctx.attr("dropout_prob", 0.0)
    causal = bool(ctx.attr("causal", False))
    impl = ctx.attr("impl", "auto")
    from . import pallas_mode
    is_tpu = pallas_mode.on_tpu()

    if ctx.abstract:
        # eval_shape inference: mesh/backend are unknown here, and every impl
        # produces the same output shape -- lower the composed path and defer
        # impl validation to the executor's real lowering
        return {"Out": [composed_attention(q, k, v, bias, float(scale), 0.0,
                                           causal, ctx.rng())]}

    gm = ctx.gspmd_mesh
    sp_n = gm.shape.get("sp", 1) if gm is not None else 1
    if kv_heads != H and (sp_n > 1 or impl in ("ring", "ulysses")):
        raise NotImplementedError(
            "fused_attention: grouped-query attention (fewer key/value than "
            "query heads) under sequence parallelism (ring / ulysses) is "
            "not built yet")
    ring_ok = sp_n > 1 and S % sp_n == 0 and (
        bias is None or (len(bias.shape) == 4 and bias.shape[1] == 1
                         and bias.shape[2] == 1))
    if impl == "ring" and not ring_ok:
        raise ValueError(
            f"fused_attention impl='ring' needs a GSPMD mesh with sp>1 "
            f"dividing S and a [B,1,1,S] bias; got sp={sp_n}, S={S}, "
            f"bias={None if bias is None else bias.shape}")
    if impl == "ulysses":
        mp_n = gm.shape.get("mp", 1) if gm is not None else 1
        h_local = H // mp_n if mp_n > 1 and H % mp_n == 0 else H
        if not (ring_ok and h_local % sp_n == 0):
            raise ValueError(
                f"fused_attention impl='ulysses' needs a GSPMD mesh with "
                f"sp>1 dividing S and the per-mp-shard head count, and a "
                f"[B,1,1,S] bias; got sp={sp_n}, S={S}, H={H} "
                f"({h_local} heads per mp shard), "
                f"bias={None if bias is None else bias.shape}")
        from ..parallel import ulysses as _uly
        ctx.note("fused_attention", ("ulysses", S, 0, kv_heads))
        seed = jax.random.randint(ctx.rng(), (), 0, 2**31 - 1, jnp.int32)
        return {"Out": [_uly.ulysses_attention(
            q, k, v, bias, float(scale), float(dropout), causal, seed, gm)]}
    if ring_ok and impl in ("auto", "ring"):
        from ..parallel import ring_attention as _ring
        ctx.note("fused_attention", ("ring", S, 0, kv_heads))
        seed = jax.random.randint(ctx.rng(), (), 0, 2**31 - 1, jnp.int32)
        return {"Out": [_ring.ring_attention(
            q, k, v, bias, float(scale), float(dropout), causal, seed, gm)]}

    bias_shape = None if bias is None else bias.shape
    if impl == "pallas":
        pallas_mode.require("fused_attention impl='pallas'")
        if not supports_pallas(B, H, S, D, bias_shape, dropout, is_tpu):
            raise ValueError(
                f"fused_attention impl='pallas' requires S % {_MIN_BLK_Q} "
                f"== 0, a [B,1,1,S] bias, and (for dropout>0) a TPU; got "
                f"S={S}, bias={bias_shape}, dropout={dropout}, "
                f"backend_tpu={is_tpu}. Use impl='auto' to let the op "
                f"choose the composed lowering.")
    # A Mosaic call has no partitioning rule: a jit over more than one device
    # refuses to lower one outside a shard_map ("Mosaic kernels cannot be
    # automatically partitioned"). Under such a mesh only the islands above
    # hold the kernels; 'auto' takes the composed lowering, which GSPMD
    # partitions by batch and heads, at every S and whatever a persisted
    # decision says (a batch/head island of its own: PERF.md section 7).
    one_device = gm is None or gm.size == 1
    # impl='auto' backend + block sizes are tunable choice points: a
    # persisted autotune decision (PADDLE_TPU_TUNE=cached/search) answers
    # where there is one, else the defaults measured on the v5e
    # (AUTO_PALLAS_MIN_S, default_block_q).
    from ..tuning import decide as _decide
    tune_params = {"b": B, "h": H, "s": S, "d": D, "dtype": str(q.dtype),
                   "has_bias": bias is not None, "dropout": float(dropout),
                   "causal": causal, "scale": float(scale)}
    use_pallas = impl == "pallas" or (
        impl == "auto" and one_device and pallas_mode.available() and
        supports_pallas(B, H, S, D, bias_shape, dropout, is_tpu) and
        _decide("fused_attention.backend", tune_params) == "pallas")
    if use_pallas:
        block_q, _ = _decide("fused_attention.block_sizes", tune_params)
        ctx.note("fused_attention", ("pallas", S, int(block_q), kv_heads))
        # The kernels read the seed for a dropout mask alone. A test-mode
        # op draws none, like the dropout op under is_test: an inference
        # program then holds no random op (0.5 s of set-up for a threefry
        # lowering nothing reads). A training op without dropout draws it
        # all the same: olmoe_1b_7b.pretrain_s4096, which has no other
        # random op, runs 0.8% slower without it (XLA's schedule; chip runs,
        # PR 27).
        if dropout or not ctx.attr("is_test", False):
            seed = jax.random.randint(ctx.rng(), (), 0, 2**31 - 1, jnp.int32)
        else:
            seed = jnp.int32(0)
        out = _flash(q, k, v, bias, seed, float(scale), float(dropout), causal,
                     pallas_mode.interpret(), block_q)
    else:
        ctx.note("fused_attention", ("xla", S, 0, kv_heads))
        out = composed_attention(q, k, v, bias, float(scale), float(dropout),
                                 causal, ctx.rng(), ctx.bernoulli_mask)
    return {"Out": [out]}
