"""Ulysses (DeepSpeed-style) sequence parallelism: all-to-all head scatter.

The second long-context schedule next to ring attention (SURVEY §5.7). Where
the ring keeps Q rows local and rotates K/V blocks (n-1 ICI hops, O(S/n)
memory), Ulysses does ONE all-to-all that re-shards [B, H, S/n, D] into
[B, H/n, S, D] -- every device then owns a full-sequence attention for a
slice of heads, computed with the ordinary fused kernel -- and one
all-to-all back. Two collective rounds total, so it wins over the ring when
S/n is small relative to the per-hop latency, and loses when H < n or the
full S x S score tile per head no longer fits; `fused_attention` keeps
'auto' on the ring and exposes impl='ulysses' for the head-rich regime.

Implemented, like the ring, as a shard_map island the fused_attention op
opens inside the GSPMD step: GSPMD would not derive the scatter-compute-
gather schedule on its own. Differentiable end to end (all_to_all is its own
transpose).
"""
from __future__ import annotations

import functools

# Traced-counter for tests/dryruns to assert the path actually ran.
TRACE_COUNT = 0


def _ulysses_local(q, k, v, bias, seed, scale, dropout, causal, axis):
    """q/k/v: [B, H, Sl, D] sequence shards; bias: [B, 1, 1, Sl] shard."""
    import jax
    import jax.numpy as jnp

    # scatter heads / gather sequence: [B, H, Sl, D] -> [B, H/n, S, D]
    qh = jax.lax.all_to_all(q, axis, split_axis=1, concat_axis=2, tiled=True)
    kh = jax.lax.all_to_all(k, axis, split_axis=1, concat_axis=2, tiled=True)
    vh = jax.lax.all_to_all(v, axis, split_axis=1, concat_axis=2, tiled=True)
    bf = jax.lax.all_gather(bias, axis, axis=3, tiled=True)  # [B,1,1,S]

    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32) * scale
    s = s + bf.astype(jnp.float32)
    S = s.shape[-1]
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        s = jnp.where((ki <= qi)[None, None], s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    if dropout:
        key = jax.random.fold_in(jax.random.PRNGKey(seed[0]),
                                 jax.lax.axis_index(axis))
        keep = jax.random.bernoulli(key, 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vh.dtype), vh,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    # gather heads / scatter sequence back: [B, H/n, S, D] -> [B, H, Sl, D]
    return jax.lax.all_to_all(out, axis, split_axis=2, concat_axis=1,
                              tiled=True)


def ulysses_attention(q, k, v, bias, scale, dropout, causal, seed, mesh,
                      seq_axis="sp", batch_axis="dp", head_axis="mp"):
    """softmax(QK^T*scale + bias)V, sequence-sharded over ``seq_axis`` via
    head-scatter all-to-all. Requires H divisible by the sp size."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    global TRACE_COUNT
    TRACE_COUNT += 1
    B, H, S, _ = q.shape
    n = mesh.shape[seq_axis]

    def ax(name, dim):
        m = mesh.shape.get(name, 1)
        return name if m > 1 and dim % m == 0 else None

    dp, mp = ax(batch_axis, B), ax(head_axis, H)
    # heads ride head_axis when model parallelism already shards them; the
    # all-to-all then subdivides each mp shard's heads over sp
    h_local = H // mesh.shape[mp] if mp else H
    if h_local % n != 0:
        raise ValueError(
            f"ulysses_attention: heads per {head_axis or 'device'} shard "
            f"({h_local}) not divisible by {seq_axis}={n} (use impl='ring' "
            f"instead)")
    if S % n != 0:
        raise ValueError(f"ulysses_attention: S={S} not divisible by "
                         f"{seq_axis}={n}")
    if bias is None:
        bias = jnp.zeros((B, 1, 1, S), jnp.float32)
    seed = jnp.asarray(seed, jnp.int32).reshape(1)
    local = functools.partial(_ulysses_local, scale=scale, dropout=dropout,
                              causal=causal, axis=seq_axis)
    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, mp, seq_axis, None), P(dp, mp, seq_axis, None),
                  P(dp, mp, seq_axis, None), P(dp, None, None, seq_axis),
                  P()),
        out_specs=P(dp, mp, seq_axis, None))
    return f(q, k, v, bias, seed)
