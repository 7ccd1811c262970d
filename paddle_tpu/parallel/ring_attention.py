"""Ring attention: blockwise attention over a sequence-parallel mesh axis.

The reference never shards sequence length (SURVEY.md §5.7 -- its long-sequence
story is LoD ragged batching + recompute). This is the TPU-native gap-fill:
Q/K/V are sharded over the "sp" axis; each device computes attention of its
local Q block against K/V blocks that rotate around the ring via
`jax.lax.ppermute` (one ICI hop per step), merging partial results with the
online-softmax rule (running max m, normalizer l, accumulator acc). Peak
activation memory is O(S/n) per device instead of O(S); the S x S probability
matrix never exists, locally or globally.

Implemented as a `shard_map` island that the `fused_attention` op lowering
opens inside the GSPMD-jitted training step when the compile strategy declares
an "sp" axis (GSPMD alone would all-gather K/V to every device -- a temporal
schedule like the ring must be written explicitly, same reasoning as
parallel/pipeline.py). The per-step body is `jax.checkpoint`-ed so backward
recomputes block probabilities instead of storing n steps of them, and the
whole function is differentiable (ppermute transposes to the reverse ring).
"""
from __future__ import annotations

import functools

# Incremented each time ring_attention is *traced*; tests and the driver dryrun
# read it to assert the ring path (not GSPMD all-gather) is what actually ran.
TRACE_COUNT = 0


def _ring_local(q, k, v, bias, seed, scale, dropout, causal, axis,
                vary_axes):
    """Local computation: q/k/v [B,H,Sl,D] shards, bias [B,1,1,Sl] shard.

    ``seed`` is a (1,) int32 array (raw PRNG seeds pass through shard_map on
    every jax version; typed key arrays historically did not)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed[0])
    # static axis size: psum of a Python int folds to size*1 at trace time
    n = jax.lax.psum(1, axis)
    my = jax.lax.axis_index(axis)
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    def varying(x):
        # scan carries must enter with the same varying-over-mesh-axes type
        # the body produces (jax vma typing for shard_map)
        return jax.lax.pcast(x, vary_axes, to="varying")

    m0 = varying(jnp.full((B, H, Sq, 1), -jnp.inf, jnp.float32))
    l0 = varying(jnp.zeros((B, H, Sq, 1), jnp.float32))
    acc0 = varying(jnp.zeros((B, H, Sq, D), jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, step):
        kb, vb, bb, m, l, acc = carry
        src = (my - step) % n                   # global block id held this step
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                       preferred_element_type=jnp.float32) * scale
        s = s + bb.astype(jnp.float32)
        if causal:
            qi = my * Sq + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
            ki = src * Sk + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
            s = jnp.where((ki <= qi)[None, None], s, jnp.float32(-1e30))
        bm = jnp.max(s, axis=-1, keepdims=True)
        nm = jnp.maximum(m, bm)
        p = jnp.exp(s - nm)
        if dropout:
            kk = jax.random.fold_in(jax.random.fold_in(key, my), src)
            keep = jax.random.bernoulli(kk, 1.0 - dropout, p.shape)
            pd = jnp.where(keep, p / (1.0 - dropout), 0.0)
        else:
            pd = p
        corr = jnp.exp(m - nm)
        # normalizer uses pre-dropout p (softmax denominator semantics match
        # the composed softmax->dropout->matmul chain)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", pd.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        bb = jax.lax.ppermute(bb, axis, perm)
        return (kb, vb, bb, nm, l, acc), None

    (_, _, _, m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (k, v, bias, m0, l0, acc0),
        jnp.arange(n))
    return (acc / l).astype(q.dtype)


def ring_attention(q, k, v, bias, scale, dropout, causal, seed, mesh,
                   seq_axis="sp", batch_axis="dp", head_axis="mp"):
    """softmax(QK^T*scale + bias)V with Q/K/V sequence-sharded over ``seq_axis``.

    q/k/v: [B, H, S, D] global views; bias: [B, 1, 1, S] additive or None;
    seed: scalar/(1,) int32 for attention dropout. Opens a shard_map over
    ``mesh``; batch rides ``batch_axis`` and heads ``head_axis`` when those
    axes exist and divide the dims, so no resharding is forced on them.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    global TRACE_COUNT
    TRACE_COUNT += 1
    B, H, S, _ = q.shape

    def ax(name, dim):
        n = mesh.shape.get(name, 1)
        return name if n > 1 and dim % n == 0 else None

    dp, mp, sp = ax(batch_axis, B), ax(head_axis, H), seq_axis
    if S % mesh.shape[sp] != 0:
        raise ValueError(f"ring_attention: S={S} not divisible by "
                         f"{sp}={mesh.shape[sp]}")
    if bias is None:
        bias = jnp.zeros((B, 1, 1, S), jnp.float32)
    seed = jnp.asarray(seed, jnp.int32).reshape(1)
    local = functools.partial(
        _ring_local, scale=scale, dropout=dropout, causal=causal, axis=sp,
        vary_axes=tuple(a for a in (dp, mp, sp) if a is not None))
    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, mp, sp, None), P(dp, mp, sp, None),
                  P(dp, mp, sp, None), P(dp, None, None, sp), P()),
        out_specs=P(dp, mp, sp, None))
    return f(q, k, v, bias, seed)
