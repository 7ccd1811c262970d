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
    Pallas kernel on TPU from S >= AUTO_PALLAS_MIN_S up (XLA's own fusion wins
    below; see the measured crossover at the constant), the ring schedule
    under an sp>1 mesh, and the composed jnp lowering otherwise or for
    unsupported shapes. `impl='pallas'` forces the kernel at any supported S
    and raises off TPU (ops/pallas_mode.py: only the test harness may ask for
    the Pallas interpreter, so the CPU suite exercises the same kernel body).
  * Whole K/V rows for one (batch, head) are staged in VMEM (S*D*2 bytes
    each); Q is blocked at BLK_Q rows. Softmax is computed in f32 in VMEM.
    Matmuls hit the MXU with preferred_element_type=f32. The backward's
    [BLK_Q, S] f32 temporaries and whole-row dK/dV blocks bound S: on a v5e
    (PR 21 chip runs) S=2048 and S=4096 compile, the S=8192 backward is
    refused (21.2 MB of scoped VMEM against the 16 MB limit); longer rows
    need the K axis blocked (ROADMAP S5).
  * Backward is a custom-VJP Pallas kernel that *recomputes* the probabilities
    per Q block (flash-style: FLOPs are cheap, HBM is not) and accumulates
    dK/dV across Q blocks by revisiting the same output block: grid axis 1
    is declared "arbitrary" (sequential) for that, axis 0 "parallel".
  * Attention dropout uses the in-kernel PRNG (pltpu.prng_random_bits) seeded
    per (step, batch*head, q-block); the backward kernel reseeds identically so
    the mask matches without storing it. In-kernel PRNG has no interpreter
    lowering, so dropout>0 takes the Pallas path only on a TPU.
"""
from __future__ import annotations

import functools
import math

from ..core.registry import register

BLK_Q = 128

# 'auto' uses the Pallas kernel only from this sequence length up: measured
# on TPU v5e (bf16, H=12 D=64, B*S fixed at 16k tokens), XLA's own fused
# attention wins below it (6.1 vs 7.3 ms at S=128) and flash wins above
# (7.4 vs 10.0 ms at S=2048) -- the online-softmax tiling pays off once the
# S x S score tile stops fitting cache-friendly shapes. impl='pallas' forces
# the kernel regardless. This crossover is now only the DEFAULT of the
# `fused_attention.backend` tunable choice (paddle_tpu/tuning/): a persisted
# autotune decision overrides it per (shape bucket, device).
AUTO_PALLAS_MIN_S = 1024


def _pl():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


# --------------------------------------------------------------------------------------
# composed (XLA-fused) reference path
# --------------------------------------------------------------------------------------

def composed_attention(q, k, v, bias, scale, dropout, causal, rng):
    """Plain jnp attention: the numerics oracle and the non-TPU lowering."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        S_q, S_k = s.shape[-2], s.shape[-1]
        qi = jax.lax.broadcasted_iota(jnp.int32, (S_q, S_k), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (S_q, S_k), 1)
        s = jnp.where(ki <= qi, s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    if dropout:
        keep = jax.random.bernoulli(rng, 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# --------------------------------------------------------------------------------------
# pallas kernels
# --------------------------------------------------------------------------------------

def _probs(q_blk, k_all, bias_row, seed_ref, iq, scale, dropout, causal):
    """[block_q, S] softmax probabilities (f32) + dropped variant for one Q
    block (block_q comes from the staged q_blk's leading dim)."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()

    blk_q = q_blk.shape[0]
    s = jax.lax.dot_general(
        q_blk, k_all, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale          # [block_q, S]
    if bias_row is not None:
        s = s + bias_row.astype(jnp.float32)                 # [1,S] broadcasts
    if causal:
        S_k = s.shape[-1]
        qi = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, S_k), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (blk_q, S_k), 1)
        s = jnp.where(ki <= qi, s, jnp.float32(-1e30))
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    if not dropout:
        return p, p
    # Deterministic per (step seed, batch*head, q block): backward reseeds the same.
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0) * 1000003 + iq * 7919)
    bits = pltpu.bitcast(pltpu.prng_random_bits(p.shape), jnp.uint32)
    thresh = jnp.uint32(int(dropout * float(2**32)))
    keep = bits >= thresh
    pd = jnp.where(keep, p / (1.0 - dropout), 0.0)
    return p, pd


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
    import jax
    _, pd = _probs(q_ref[0], k_ref[0], bias_row, seed_ref, iq, scale, dropout,
                   causal)
    o = jax.lax.dot_general(pd.astype(v_ref.dtype), v_ref[0],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0] = o.astype(o_ref.dtype)


def _bwd_kernel(scale, dropout, causal, has_bias, *refs):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, seed_ref, do_ref,
         dq_ref, dk_ref, dv_ref) = refs
        bias_row = bias_ref[0]                               # [1, S]
    else:
        q_ref, k_ref, v_ref, seed_ref, do_ref, dq_ref, dk_ref, dv_ref = refs
        bias_row = None
    iq = pl.program_id(1)
    p, pd = _probs(q_ref[0], k_ref[0], bias_row, seed_ref, iq, scale, dropout,
                   causal)
    do = do_ref[0].astype(jnp.float32)                       # [BLK_Q, D]
    v = v_ref[0].astype(jnp.float32)                         # [S, D]
    dv_blk = jax.lax.dot_general(pd, do, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [S, D]
    dpd = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)     # [BLK_Q, S]
    if dropout:
        # d(dropout(p))/dp: the same keep/(1-p) factor -- pd/p where p>0 encodes it,
        # but recompute from the mask-free relation: pd = p*keep/(1-prob)
        # => dp = dpd * keep/(1-prob) = dpd * (pd / jnp.where(p == 0, 1, p)).
        dp = dpd * (pd / jnp.where(p == 0.0, 1.0, p))
    else:
        dp = dpd
    row = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = p * (dp - row)                                      # [BLK_Q, S] f32
    dq_blk = jax.lax.dot_general(ds, k_ref[0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
    dk_blk = jax.lax.dot_general(ds, q_ref[0].astype(jnp.float32),
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
    dq_ref[0] = dq_blk.astype(dq_ref.dtype)

    @pl.when(iq == 0)
    def _():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    dk_ref[0] += dk_blk
    dv_ref[0] += dv_blk


def _specs(B, H, S, D, has_bias, block_q):
    import jax.numpy as jnp
    pl, pltpu = _pl()
    qspec = pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM)
    kvspec = pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0),
                          memory_space=pltpu.VMEM)
    in_specs = [qspec, kvspec, kvspec]
    if has_bias:
        # [B,1,S] with block (1,1,S): the last two dims equal the array dims,
        # satisfying the TPU (8,128)-divisible-or-full block constraint.
        in_specs.append(pl.BlockSpec((1, 1, S), lambda b, i: (b // H, 0, 0),
                                     memory_space=pltpu.VMEM))
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))  # seed
    return qspec, kvspec, in_specs


def _compiler_params(interpret):
    """Grid axis 0 (batch*head) is independent; axis 1 (Q blocks) must run
    in order -- the backward accumulates dK/dV into a revisited output
    block. The interpreter takes no Mosaic parameters."""
    if interpret:
        return {}
    _, pltpu = _pl()
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))}


import jax as _jax  # custom_vjp must wrap at def time

@functools.partial(_jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, bias, seed, scale, dropout, causal, interpret,
           block_q=BLK_Q):
    return _flash_fwd_impl(q, k, v, bias, seed, scale, dropout, causal,
                           interpret, block_q)


def _flash_fwd_impl(q, k, v, bias, seed, scale, dropout, causal, interpret,
                    block_q):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    B, H, S, D = q.shape
    BH = B * H
    qf = q.reshape(BH, S, D)
    kf = k.reshape(BH, S, D)
    vf = v.reshape(BH, S, D)
    has_bias = bias is not None
    args = [qf, kf, vf]
    if has_bias:
        args.append(bias.reshape(B, 1, S))
    args.append(jnp.asarray(seed, jnp.int32).reshape(1))
    qspec, _, in_specs = _specs(B, H, S, D, has_bias, block_q)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale, dropout, causal, has_bias),
        grid=(BH, S // block_q),
        in_specs=in_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        interpret=interpret,
        **_compiler_params(interpret),
    )(*args)
    return out.reshape(B, H, S, D)


def _flash_fwd(q, k, v, bias, seed, scale, dropout, causal, interpret,
               block_q=BLK_Q):
    out = _flash_fwd_impl(q, k, v, bias, seed, scale, dropout, causal,
                          interpret, block_q)
    return out, (q, k, v, bias, seed)


def _flash_bwd(scale, dropout, causal, interpret, block_q, res, g):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    q, k, v, bias, seed = res
    B, H, S, D = q.shape
    BH = B * H
    has_bias = bias is not None
    args = [q.reshape(BH, S, D), k.reshape(BH, S, D), v.reshape(BH, S, D)]
    if has_bias:
        args.append(bias.reshape(B, 1, S))
    args.append(jnp.asarray(seed, jnp.int32).reshape(1))
    args.append(g.reshape(BH, S, D))
    qspec, kvspec, in_specs = _specs(B, H, S, D, has_bias, block_q)
    in_specs.append(qspec)  # do
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale, dropout, causal, has_bias),
        grid=(BH, S // block_q),
        in_specs=in_specs,
        out_specs=[qspec, kvspec, kvspec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret),
    )(*args)
    shape = (B, H, S, D)
    import numpy as np
    return (dq.reshape(shape),
            dk.reshape(shape).astype(k.dtype),
            dv.reshape(shape).astype(v.dtype),
            None if bias is None else jnp.zeros_like(bias),
            np.zeros(np.shape(seed), jax.dtypes.float0))


_flash.defvjp(_flash_fwd, _flash_bwd)


def supports_pallas(B, H, S, D, bias_shape, dropout, is_tpu):
    """Shape/placement gate for the Pallas lowering."""
    if S % BLK_Q != 0 or S < BLK_Q:
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

    Inputs: Q/K/V [B, heads, S, D]; optional Bias [B, 1, 1, S] additive (already
    -inf-masked). Attrs: scale (default 1/sqrt(D)), dropout_prob, causal,
    is_test, impl ('auto' | 'pallas' | 'ring' | 'ulysses' | 'composed').

    Kernel choice: under a GSPMD jit whose mesh has an "sp" axis >1 (sequence
    parallelism), 'auto' opens the ring-attention shard_map island
    (parallel/ring_attention.py) so the sequence dim STAYS partitioned --
    GSPMD alone would all-gather K/V to every device; 'ulysses' instead does
    the all-to-all head-scatter schedule (parallel/ulysses.py, needs heads
    divisible by sp). Otherwise 'auto' is the Pallas flash kernel on
    TPU-supported shapes from S >= AUTO_PALLAS_MIN_S (below that XLA's own
    fusion is measurably faster), else the composed jnp path.
    """
    import jax
    import jax.numpy as jnp

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = ins.get("Bias", [None])[0]
    B, H, S, D = q.shape
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
        seed = jax.random.randint(ctx.rng(), (), 0, 2**31 - 1, jnp.int32)
        return {"Out": [_uly.ulysses_attention(
            q, k, v, bias, float(scale), float(dropout), causal, seed, gm)]}
    if ring_ok and impl in ("auto", "ring"):
        from ..parallel import ring_attention as _ring
        seed = jax.random.randint(ctx.rng(), (), 0, 2**31 - 1, jnp.int32)
        return {"Out": [_ring.ring_attention(
            q, k, v, bias, float(scale), float(dropout), causal, seed, gm)]}

    bias_shape = None if bias is None else bias.shape
    if impl == "pallas":
        pallas_mode.require("fused_attention impl='pallas'")
        if not supports_pallas(B, H, S, D, bias_shape, dropout, is_tpu):
            raise ValueError(
                f"fused_attention impl='pallas' requires S % {BLK_Q} == 0, "
                f"a [B,1,1,S] bias, and (for dropout>0) a TPU; got S={S}, "
                f"bias={bias_shape}, dropout={dropout}, "
                f"backend_tpu={is_tpu}. Use impl='auto' to let the op "
                f"choose the composed lowering.")
    # impl='auto' backend + block sizes are tunable choice points: with a
    # persisted autotune decision (PADDLE_TPU_TUNE=cached/search) the
    # measured winner is used; without one the default reproduces the
    # static S >= AUTO_PALLAS_MIN_S crossover and BLK_Q exactly.
    from ..tuning import decide as _decide
    tune_params = {"b": B, "h": H, "s": S, "d": D, "dtype": str(q.dtype),
                   "has_bias": bias is not None, "dropout": float(dropout),
                   "causal": causal, "scale": float(scale)}
    use_pallas = impl == "pallas" or (
        impl == "auto" and pallas_mode.available() and
        supports_pallas(B, H, S, D, bias_shape, dropout, is_tpu) and
        _decide("fused_attention.backend", tune_params) == "pallas")
    if use_pallas:
        block_q, _ = _decide("fused_attention.block_sizes", tune_params)
        seed = jax.random.randint(ctx.rng(), (), 0, 2**31 - 1, jnp.int32)
        out = _flash(q, k, v, bias, seed, float(scale), float(dropout), causal,
                     pallas_mode.interpret(), block_q)
    else:
        out = composed_attention(q, k, v, bias, float(scale), float(dropout),
                                 causal, ctx.rng())
    return {"Out": [out]}
