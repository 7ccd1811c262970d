"""Compressed gradient allreduce: quantize -> collective -> dequantize.

The EQuARX observation (arXiv:2506.17615): at scale the dp-axis gradient
allreduce is bandwidth-bound, and shipping narrower elements buys nearly
the full width reduction in step time -- IF the quantization error is kept
out of the optimizer's long-run trajectory.  Two modes:

- ``bf16``: cast to bfloat16, ``psum`` in bf16 (on-wire 2 bytes/elem),
  cast back.  Deterministic, byte-stable across runs.
- ``int8``: per-device symmetric int8 quantization, reduced by the
  two-phase quantized allreduce (the ring decomposition with int8 on the
  wire in BOTH phases):

    1. each device quantizes its full (error-compensated) vector with its
       own f32 scale and ``all_to_all``s the int8 shards -- device j ends
       up with everyone's j-th shard; scales ride a tiny ``all_gather``;
    2. device j dequantizes and sums its shards in f32 (full 8-bit
       precision per addend -- no quantized-accumulator wraparound),
       re-quantizes the reduced shard, and ``all_gather``s the int8
       result + scales; every device dequantizes the same broadcast
       bytes, so the output is bitwise identical on all ranks (SPMD-safe).

  On-wire: ``2 (n-1)/n * nbytes/4`` -- exactly 1/4 of the f32 ring.

**Error feedback** (the convergence insurance): each device keeps a
per-tensor residual ``r_t``; it transmits ``c(g_t + r_t)`` and carries
``r_{t+1} = (g_t + r_t) - c(g_t + r_t)`` forward, so quantization error
is re-submitted next step instead of accumulating as bias.  The residual
is *per-device* state (it depends on the local gradient), held as a
dp-sharded persistable (see ``rewrite.py``).  The phase-2 re-quantization
error of the int8 path is shared by all ranks and not fed back --
bounded at ~1/254 of the reduced shard's max per step (the EQuARX
two-stage loss).

Everything here is pure jax -- traceable inside ``shard_map``, no host
round trips.
"""
from __future__ import annotations

from typing import Optional, Tuple

#: suffix of the error-feedback residual persistable created per
#: compressed gradient tensor (rewrite.py); io.py excludes these from
#: checkpoint saves (advisory state: a fresh zero residual after restore
#: is harmless, a world-size-pinned shape in a checkpoint is not)
RESIDUAL_SUFFIX = "@comm_residual"

#: gradient dtypes the quantizer handles; anything else falls back to the
#: uncompressed path (PT048 makes the silent int8 fallback visible)
SUPPORTED_DTYPES = ("float32", "bfloat16", "float16")

#: compression modes the DistributedStrategy knob accepts
MODES = ("off", "bf16", "int8")

#: tensors below this many bytes never compress by default: the quantize/
#: dequantize arithmetic plus the extra scale traffic exceeds what a small
#: message saves (the per-tensor TunableChoice can only *widen* this gate,
#: never compress below it -- see tuning/choices.py CommCompress)
MIN_COMPRESS_BYTES = 65536


def is_residual(name: str) -> bool:
    return name.endswith(RESIDUAL_SUFFIX)


def residual_name(grad_name: str) -> str:
    return grad_name + RESIDUAL_SUFFIX


def quantize_int8(x) -> Tuple["object", "object"]:
    """Per-tensor symmetric int8: (q, scale) with x ~= q * scale.
    scale is a f32 scalar; an all-zero tensor quantizes to scale 1.0."""
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    scale = jnp.where(amax > 0, amax / 127.0, jnp.float32(1.0))
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    import jax.numpy as jnp
    return q.astype(jnp.float32) * scale


def _bf16_roundtrip(x):
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def axis_size(axis_name: str) -> int:
    """Static size of a bound mesh axis (psum of a literal 1 folds to a
    Python int under tracing -- what the collective lowerings use)."""
    import jax
    return int(jax.lax.psum(1, axis_name))


def _psum_int8(x, axis_name: str, n: int):
    """Two-phase int8 allreduce of ``x`` (any float dtype) over the bound
    axis; returns (sum_f32_cast_back, local_quantization_error)."""
    import jax
    import jax.numpy as jnp
    shape, dtype = x.shape, x.dtype
    xf = x.astype(jnp.float32).reshape(-1)
    size = xf.shape[0]
    pad = (-size) % n
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad,), jnp.float32)])
    q, scale = quantize_int8(xf)
    # phase 1: int8 shards to their owner + everyone's scale (tiny)
    recv = jax.lax.all_to_all(q.reshape(n, -1), axis_name,
                              split_axis=0, concat_axis=0, tiled=True)
    scales = jax.lax.all_gather(scale, axis_name)            # (n,) f32
    partial = jnp.sum(recv.astype(jnp.float32) * scales[:, None], axis=0)
    # phase 2: re-quantize the reduced shard, broadcast int8
    q2, s2 = quantize_int8(partial)
    all_q = jax.lax.all_gather(q2, axis_name, tiled=True)    # (size+pad,) i8
    all_s = jax.lax.all_gather(s2, axis_name)                # (n,) f32
    out = (all_q.reshape(n, -1).astype(jnp.float32)
           * all_s[:, None]).reshape(-1)
    err = (xf - dequantize_int8(q, scale))
    if pad:
        out, err = out[:size], err[:size]
    return out.reshape(shape).astype(dtype), err.reshape(shape).astype(dtype)


def compressed_allreduce(x, axis_name: str, mode: str,
                         residual: Optional["object"] = None,
                         mean: bool = False,
                         world: Optional[int] = None):
    """Quantize -> allreduce -> dequantize over a *bound* mesh axis, with
    optional error feedback.  Returns ``(reduced, new_residual)`` --
    ``new_residual`` is None when no residual was supplied (stateless use,
    e.g. the bench sweep).

    ``mean=True`` averages (the ``c_allreduce_avg`` semantics).  world=1
    (or an unbound axis -- the caller checks) must never reach here; the
    callers short-circuit to the uncompressed path, where compression is
    pure overhead.
    """
    import jax
    import jax.numpy as jnp
    if mode not in ("bf16", "int8"):
        raise ValueError(f"comm compression mode must be bf16|int8 here, "
                         f"got {mode!r}")
    n = int(world) if world is not None else axis_size(axis_name)
    local = x if residual is None else x + residual.astype(x.dtype)
    if mode == "bf16":
        sent = local.astype(jnp.bfloat16)
        out = jax.lax.psum(sent, axis_name).astype(x.dtype)
        err = (local - sent.astype(x.dtype)) if residual is not None else None
    else:
        out, err_all = _psum_int8(local, axis_name, n)
        err = err_all if residual is not None else None
    if mean:
        out = out / jnp.asarray(n, out.dtype)
    return out, err


# ----------------------------------------------------------- telemetry --

def record_collective(kind: str, dtype: str, raw_bytes: int,
                      on_wire_bytes: int):
    """Trace-time accounting: called by the collective lowerings once per
    compile (never per step), so the registry carries per-compiled-step
    wire bytes by collective kind and on-wire dtype, plus the cumulative
    compression ratio."""
    from ..observability.metrics import REGISTRY as _OBS
    _OBS.counter(
        "comm_bytes_total",
        "per-device interconnect bytes per compiled step, by collective "
        "kind and on-wire dtype (recorded at trace time)",
        kind=kind, dtype=dtype).inc(max(0, int(on_wire_bytes)))
    fam_raw = _OBS.counter(
        "comm_raw_bytes_total",
        "per-device interconnect bytes per compiled step BEFORE "
        "compression (the f32-equivalent traffic)",
        kind=kind, dtype=dtype)
    fam_raw.inc(max(0, int(raw_bytes)))
    # cumulative raw/wire over everything recorded so far
    raw = wire = 0.0
    for fname, accum in (("comm_raw_bytes_total", "raw"),
                         ("comm_bytes_total", "wire")):
        fam = _OBS.get(fname)
        if fam is None:
            continue
        total = sum(child.value for _, child in fam.items())
        if accum == "raw":
            raw = total
        else:
            wire = total
    if wire > 0:
        _OBS.gauge("comm_compress_ratio",
                   "cumulative pre-compression bytes / on-wire bytes over "
                   "all traced collectives (1.0 = nothing compressed)"
                   ).set(raw / wire)
