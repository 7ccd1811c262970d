"""Collective communication ops (reference: paddle/fluid/operators/collective/:
c_allreduce_{sum,max,min,prod}, c_broadcast, c_allgather, c_reducescatter;
operators/distributed_ops/allreduce_op.cc).

TPU-native: these lower to jax.lax collectives over *named mesh axes* -- compiled onto
ICI/DCN by XLA -- instead of NCCL ring calls. The reference's ``ring_id`` attr maps to
an axis name (attr ``axis_name``, default "dp"). Outside shard_map/pmap tracing (no
axis bound), they are identity/no-ops so the same program runs single-device --
mirroring the reference where collective ops exist only in multi-device programs.

c_gen_nccl_id / c_comm_init have no equivalent: device meshes need no runtime
bootstrap (SURVEY.md §5.8); multi-host init is jax.distributed (parallel/env.py).
"""
from __future__ import annotations

from typing import Dict, Optional

from ..core.registry import register

#: Communication metadata per op type, consumed by the static analyzer
#: (analysis/distributed.py, analysis/dataflow.py): which attr names the mesh
#: axis the op communicates over (and its default), plus the comm semantics
#: tag. Every rank of the axis must execute the SAME sequence of these ops --
#: they are synchronization points, never dead code, and never safe inside
#: control flow whose predicate/trip count can differ across ranks.
#: ``temporal_pipeline`` is included: its lowering is a shard_map of
#: ppermute/psum over ``axis`` (ops/pipeline_op.py), so to the analyzer it IS
#: a collective even though it never appears in this file.
COLLECTIVE_OPS: Dict[str, dict] = {
    "c_allreduce_sum": {"comm": "allreduce", "axis_attr": "axis_name",
                        "default_axis": "dp"},
    "c_allreduce_max": {"comm": "allreduce", "axis_attr": "axis_name",
                        "default_axis": "dp"},
    "c_allreduce_min": {"comm": "allreduce", "axis_attr": "axis_name",
                        "default_axis": "dp"},
    "c_allreduce_prod": {"comm": "allreduce", "axis_attr": "axis_name",
                         "default_axis": "dp"},
    "c_allreduce_avg": {"comm": "allreduce", "axis_attr": "axis_name",
                        "default_axis": "dp"},
    "c_allgather": {"comm": "allgather", "axis_attr": "axis_name",
                    "default_axis": "dp"},
    "c_reducescatter": {"comm": "reducescatter", "axis_attr": "axis_name",
                        "default_axis": "dp"},
    "c_broadcast": {"comm": "broadcast", "axis_attr": "axis_name",
                    "default_axis": "dp"},
    "alltoall": {"comm": "alltoall", "axis_attr": "axis_name",
                 "default_axis": "dp"},
    "collective_permute": {"comm": "permute", "axis_attr": "axis_name",
                           "default_axis": "dp"},
    "temporal_pipeline": {"comm": "pipeline", "axis_attr": "axis",
                          "default_axis": "pp"},
    "reshard": {"comm": "reshard", "axis_attr": "axis_name",
                "default_axis": "dp"},
}


def is_collective(op_type: str) -> bool:
    return op_type in COLLECTIVE_OPS


def collective_axis(op) -> Optional[str]:
    """The mesh-axis name an Operator (or anything with ``.type``/``.attr``)
    communicates over, or None for non-collective ops."""
    meta = COLLECTIVE_OPS.get(op.type)
    if meta is None:
        return None
    return op.attr(meta["axis_attr"], meta["default_axis"])


def _axis_bound(name):
    import jax
    try:
        jax.lax.axis_index(name)
        return True
    except Exception:
        return False


def _axis(ctx):
    return ctx.attr("axis_name", "dp")


def _coll(op_type, fn):
    @register(op_type, grad="auto")
    def lower(ctx, ins, fn=fn):
        import jax
        x = ins["X"][0]
        name = _axis(ctx)
        if ctx.mesh is None and not _axis_bound(name):
            return {"Out": [x]}
        return {"Out": [fn(x, name)]}
    return lower


def _lax():
    import jax.lax as lax
    return lax


def _record(kind: str, x, name: str, mode: str = "off"):
    """Trace-time wire-byte accounting (once per compile, never per
    step): per-device bytes by collective kind and on-wire dtype through
    the observability registry.  Payload is the tensor as the op sees it
    (for the gradient allreduce that IS the logical tensor)."""
    try:
        from ..comm import compress as _compress
        from ..comm import cost as _cost
        n = _compress.axis_size(name)
        if n <= 1:
            return n
        raw = int(x.size) * _cost.dtype_wire_bytes(str(x.dtype))
        raw_wire = _cost.wire_bytes(kind, raw, n)
        if mode in ("bf16", "int8"):
            wire = _cost.wire_bytes(
                kind, _cost.compressed_bytes(raw, str(x.dtype), mode, n), n)
            dtype = mode if mode == "int8" else "bfloat16"
        else:
            wire, dtype = raw_wire, str(x.dtype)
        _compress.record_collective(kind, dtype, raw_wire, wire)
        return n
    except Exception:
        return 0   # telemetry must never fail a trace


def _allreduce_compressed(ctx, ins, name, mean):
    """The quantize -> psum -> dequantize path of c_allreduce_sum/avg
    (DistributedStrategy.comm_compression via the comm.rewrite attr, or a
    hand-set ``comm_compress`` attr -- the bench sweep door), with the
    error-feedback residual threaded through the ResidualIn/ResidualOut
    slots when the rewrite materialized one.  The residual persistable is
    dp-sharded (ndp, *shape); its local block carries a leading 1-dim."""
    from ..comm import compress as _compress
    x = ins["X"][0]
    mode = ctx.attr("comm_compress", "off")
    res_in = (ins.get("ResidualIn") or [None])[0]
    # resolve the EFFECTIVE mode before recording: an unsupported dtype
    # ships full-width, and the telemetry must say so (PT048 surfaces it)
    if mode in ("bf16", "int8") \
            and str(x.dtype) not in _compress.SUPPORTED_DTYPES:
        mode = "off"
    n = _record("allreduce", x, name, mode)
    if mode not in ("bf16", "int8") or n <= 1:
        # unsupported dtype / unbound axis: the silent fallback PT048
        # makes visible at lint time
        import jax
        out = (jax.lax.pmean if mean else jax.lax.psum)(x, name)
        outs = {"Out": [out]}
        if res_in is not None:
            outs["ResidualOut"] = [res_in]
        return outs
    res_local = None
    if res_in is not None:
        import jax.numpy as jnp
        res_local = jnp.squeeze(res_in, axis=0)
    out, err = _compress.compressed_allreduce(
        x, name, mode, residual=res_local, mean=mean, world=n)
    outs = {"Out": [out]}
    if res_in is not None:
        import jax.numpy as jnp
        outs["ResidualOut"] = [jnp.expand_dims(err, 0)]
    return outs


def _coll_allreduce(op_type, mean):
    @register(op_type, grad="auto")
    def lower(ctx, ins, mean=mean):
        import jax
        x = ins["X"][0]
        name = _axis(ctx)
        if ctx.mesh is None and not _axis_bound(name):
            outs = {"Out": [x]}
            res_in = (ins.get("ResidualIn") or [None])[0]
            if res_in is not None:
                outs["ResidualOut"] = [res_in]
            return outs
        if ctx.attr("comm_compress", "off") != "off" \
                or "ResidualIn" in ins:
            return _allreduce_compressed(ctx, ins, name, mean)
        _record("allreduce", x, name)
        return {"Out": [(jax.lax.pmean if mean else jax.lax.psum)(x, name)]}
    return lower


_coll_allreduce("c_allreduce_sum", mean=False)
_coll_allreduce("c_allreduce_avg", mean=True)
_coll("c_allreduce_max", lambda x, n: _lax().pmax(x, n))
_coll("c_allreduce_min", lambda x, n: _lax().pmin(x, n))
def _pprod(x, name):
    # Exact cross-device product: all_gather then reduce on the gathered axis.
    # (XLA has no product all-reduce primitive; gather+prod keeps bit-exactness
    # vs the sign/log trick, and these tensors are small in practice.)
    import jax
    import jax.numpy as jnp
    return jnp.prod(jax.lax.all_gather(x, name), axis=0)


_coll("c_allreduce_prod", _pprod)


@register("c_allgather")
def c_allgather(ctx, ins):
    import jax
    x = ins["X"][0]
    name = _axis(ctx)
    if not _axis_bound(name):
        return {"Out": [x]}
    _record("allgather", x, name)
    return {"Out": [jax.lax.all_gather(x, name, tiled=True)]}


@register("c_reducescatter")
def c_reducescatter(ctx, ins):
    import jax
    x = ins["X"][0]
    name = _axis(ctx)
    if not _axis_bound(name):
        return {"Out": [x]}
    _record("reducescatter", x, name)
    return {"Out": [jax.lax.psum_scatter(x, name, tiled=True)]}


@register("c_broadcast")
def c_broadcast(ctx, ins):
    """Broadcast from root rank over the axis: implemented as select+psum (XLA lowers
    this to an efficient collective broadcast)."""
    import jax
    import jax.numpy as jnp
    x = ins["X"][0]
    name = _axis(ctx)
    if not _axis_bound(name):
        return {"Out": [x]}
    _record("broadcast", x, name)
    root = ctx.attr("root", 0)
    idx = jax.lax.axis_index(name)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return {"Out": [jax.lax.psum(masked, name)]}


@register("alltoall")
def alltoall(ctx, ins):
    """Ulysses-style all-to-all: split axis 'split_axis', concat on 'concat_axis'."""
    import jax
    x = ins["X"][0]
    name = _axis(ctx)
    if not _axis_bound(name):
        return {"Out": [x]}
    _record("alltoall", x, name)
    return {"Out": [jax.lax.all_to_all(x, name, ctx.attr("split_axis", 0),
                                       ctx.attr("concat_axis", 0), tiled=True)]}


@register("collective_permute")
def collective_permute(ctx, ins):
    """Ring shift by 'offset' along the axis (ring-attention building block)."""
    import jax
    x = ins["X"][0]
    name = _axis(ctx)
    if not _axis_bound(name):
        return {"Out": [x]}
    _record("permute", x, name)
    # static axis size via psum-of-1
    n = jax.lax.psum(1, name)
    off = ctx.attr("offset", 1)
    perm = [(i, (i + off) % n) for i in range(n)]
    return {"Out": [jax.lax.ppermute(x, name, perm)]}


@register("reshard")
def reshard_op(ctx, ins):
    """Spec-to-spec redistribution: apply the comm.reshard planner's
    minimal collective sequence to the local block of a sharded value.
    Attrs: ``src_dim``/``dst_dim`` (-1 = replicated), ``axis_name``.  The
    SAME decomposition the PT046 lint prices and the elastic host-chunk
    reshard executes -- here lowered onto live device values inside
    shard_map (the ZeRO param re-gather door: src_dim=k, dst_dim=-1 is
    the priced all-gather)."""
    import numpy as np
    from ..comm import reshard as _reshard
    x = ins["X"][0]
    name = _axis(ctx)
    if not _axis_bound(name):
        return {"Out": [x]}
    from ..comm import compress as _compress
    n = _compress.axis_size(name)
    src_dim = int(ctx.attr("src_dim", -1))
    dst_dim = int(ctx.attr("dst_dim", -1))
    src = _reshard.ShardSpec(None if src_dim < 0 else src_dim, n, name)
    dst = _reshard.ShardSpec(None if dst_dim < 0 else dst_dim, n, name)
    gshape = list(np.shape(x))
    if src.sharded:
        gshape[src.dim] *= n   # x is the local block of the source spec
    plan = _reshard.plan_transfer(gshape, str(x.dtype), src, dst, axis=name)
    for s in plan.steps:
        if s.wire_bytes:
            try:
                # the plan already priced this step from the GLOBAL shape;
                # record it as-is (re-deriving from the local block would
                # undercount by the world size)
                _compress.record_collective(s.collective, str(x.dtype),
                                            s.wire_bytes, s.wire_bytes)
            except Exception:
                pass   # telemetry must never fail a trace
    return {"Out": [_reshard.apply_transfer(x, plan, axis_name=name)]}


@register("c_sync_calc_stream", grad="auto")
def c_sync_calc_stream(ctx, ins):
    # No-op under XLA's static schedule (reference needed explicit stream sync).
    return {"Out": [ins["X"][0]]}


@register("c_sync_comm_stream", grad="auto")
def c_sync_comm_stream(ctx, ins):
    return {"Out": [ins["X"][0]]}
