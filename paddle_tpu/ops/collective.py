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

import functools
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


# --------------------------------------------------------------------------------------
# An expert layer's exchange (ops/decoder_ops.py: moe_dispatch / moe_combine
# under attr ``expert_axis``)
# --------------------------------------------------------------------------------------

def exchange_impl() -> str:
    """The wire an expert layer's exchange lowers here: "ragged"
    (``jax.lax.ragged_all_to_all``: the live rows and nothing else cross,
    into one receive buffer a device) on a TPU, "padded"
    (``jax.lax.all_to_all`` of a fixed part of the buffer a pair of devices)
    off one -- XLA's CPU backend has no ragged-all-to-all, so the CPU tests
    run the padded wire. On the chip the two move a crossing in the same
    time, and the padded wire's budget a PAIR of devices drops rows where a
    device's one pool does not (PERF.md section 6, PR 55)."""
    from . import pallas_mode
    return "ragged" if pallas_mode.on_tpu() else "padded"


class RowExchange:
    """One device's plan of an expert layer's exchange over the ``n``
    devices of mesh axis ``axis`` (built inside a ``shard_map`` island over
    it), from ``cnt [n, E]``: the rows device j's sorted buffer holds for
    expert e (every device's counts, all-gathered: each device computes the
    whole plan and reads its own part). Device c holds experts ``[c E / n,
    (c + 1) E / n)``. A sender's buffer is sorted by expert, so the rows for
    device c are one run of it (``send_off``, ``sent``); the receive buffer
    holds ``budget`` rows, source by source (``in_off``), and the grouped
    products want them expert by expert: what arrived from source s for
    held expert e is one contiguous *segment* of either order
    (``segments``: its start source by source, its start expert by expert,
    its length), so the change of order that follows the wire on the way
    out (``by_expert``) and precedes it on the way back (``by_source``)
    moves ``n x E / n`` segments. ``kernel`` (the ``interpret`` flag of its
    call, or None) says whether the kernel of ``ops/pallas_exchange_rows.py``
    moves them, from the three tables; the composed form builds one index a
    row (``to_expert_major`` / ``to_source_major``, computed where it is
    read) and gathers.

    Rows over the budget are dropped and counted (``dropped``, this
    device's): under ``ragged`` the buffer is one pool, filled source by
    source, and the overflow is cut from the last sources' runs; under
    ``padded`` a pair of devices has ``budget / n`` rows, and a run over
    that is cut. A cut run loses its last rows: its highest experts'.
    ``kept [n]`` (of my run to each device) and ``group [E / n]`` (the rows
    of each expert I hold, after the cuts) are what the layer's other ops
    need; ``out`` and ``back`` are each other's transpose."""

    def __init__(self, cnt, axis: str, n: int, budget: int, impl: str,
                 kernel=None, me=None):
        import jax
        import jax.numpy as jnp
        if impl not in ("ragged", "padded"):
            raise ValueError(f"exchange impl {impl!r}")
        if impl == "padded" and budget % n:
            raise ValueError(f"a padded exchange cuts its buffer of "
                             f"{budget} rows in {n} equal parts")
        self.axis, self.n, self.budget, self.impl = axis, n, budget, impl
        self.kernel = kernel
        per = cnt.shape[1] // n
        # ``me``: this device's place on the axis, for a plan built outside
        # an island over it (tools/mellum2_probe.py passes)
        me = self.me = jax.lax.axis_index(axis) if me is None else me
        by_owner = cnt.reshape(n, n, per)               # [source, owner, e]
        sent = by_owner.sum(-1)                         # [source, owner]
        if impl == "padded":
            self.part = budget // n
            keep = jnp.minimum(sent, self.part)
            in_off = jnp.broadcast_to(
                (jnp.arange(n, dtype=jnp.int32) * self.part)[:, None], (n, n))
        else:
            ends = jnp.minimum(jnp.cumsum(sent, axis=0), budget)
            keep = jnp.diff(ends, axis=0, prepend=0)
            in_off = ends - keep
        send_off = jnp.cumsum(sent, axis=1) - sent      # [source, owner]
        self.send_off = send_off[me]                    # my runs' starts
        self.send_off_there = send_off[:, me]   # each source's run for me
        self.sent, self.kept = sent[me], keep[me]               # [owner]
        self.in_off_there = in_off[me]      # my run's place in each owner's
        self.in_off, self.taken = in_off[:, me], keep[:, me]    # [source]
        self.dropped = jnp.sum(sent[:, me] - keep[:, me])
        # the rows of (source, held expert) that arrive here, after the cuts
        ends = jnp.minimum(jnp.cumsum(by_owner[:, me], axis=-1),
                           keep[:, me][:, None])
        mine = jnp.diff(ends, axis=-1, prepend=0)               # [source, e]
        self.group = mine.sum(0).astype(jnp.int32)
        self.live = jnp.sum(self.group)
        # where a (source, expert) segment starts in the receive buffer's
        # order and in the experts' order, and its rows: each [source, e]
        sm_start = (self.in_off[:, None] + jnp.cumsum(mine, axis=1)
                    - mine).astype(jnp.int32)
        em_end = jnp.cumsum(mine.T.reshape(-1)).reshape(per, n).T
        self.segments = (sm_start, (em_end - mine).astype(jnp.int32),
                         mine.astype(jnp.int32))

    @functools.cached_property
    def to_expert_major(self):
        """int32 ``[budget]``: the received row each expert-major row is
        (the composed form's index; the rows behind ``live`` name clipped
        copies of real rows)."""
        import jax.numpy as jnp
        sm_start, em_start, mine = (v.T.reshape(-1) for v in self.segments)
        rows = jnp.arange(self.budget, dtype=jnp.int32)
        seg = jnp.minimum(
            jnp.searchsorted(em_start + mine, rows, side="right",
                             method="compare_all"), mine.shape[0] - 1)
        return jnp.clip(sm_start[seg] + rows - em_start[seg], 0,
                        self.budget - 1)

    @functools.cached_property
    def to_source_major(self):
        """int32 ``[budget]``: the expert-major row each received row is."""
        import jax.numpy as jnp
        sm_start, em_start, _ = (v.reshape(-1) for v in self.segments)
        rows = jnp.arange(self.budget, dtype=jnp.int32)
        seg = jnp.maximum(
            jnp.searchsorted(sm_start, rows, side="right",
                             method="compare_all") - 1, 0)
        return jnp.clip(em_start[seg] + rows - sm_start[seg], 0,
                        self.budget - 1)

    def _moved(self, x, index: str, src, dst, length):
        """``x [budget, ...]`` in the other order: the kernel over the
        segment tables (ascending in ``dst``) where it runs -- rows as they
        are, a vector (the router weights) as the lanes of ``[budget,
        128]``: 0.34 ms against the gather's 0.89 at 81,920 float32 (chip
        runs, PR 56) --, else the gather by the index array ``index``."""
        import jax.numpy as jnp
        from . import pallas_exchange_rows as rows
        if self.kernel is None or x.ndim > 2 or not rows.supports(
                self.budget, x.shape[-1] if x.ndim == 2 else rows.LANES,
                x.dtype):
            return x[getattr(self, index)]
        wide = x if x.ndim == 2 else jnp.broadcast_to(
            x[:, None], (self.budget, rows.LANES))
        moved = rows.move_segments(
            wide, src.reshape(-1), dst.reshape(-1), length.reshape(-1),
            interpret=self.kernel)
        return moved if x.ndim == 2 else moved[:, 0]

    def by_expert(self, got):
        """Received rows ``[budget, ...]``, source by source -> expert by
        expert (within an expert source by source), the rows behind
        ``live`` padding: zero from the kernel, copies of real rows from
        the gather; nothing reads them."""
        sm_start, em_start, mine = (v.T for v in self.segments)
        return self._moved(got, "to_expert_major", sm_start, em_start, mine)

    def by_source(self, y):
        """``by_expert``'s transpose: rows expert by expert -> source by
        source, as the wire back sends them."""
        sm_start, em_start, mine = self.segments
        return self._moved(y, "to_source_major", em_start, sm_start, mine)

    def out(self, take, sorted_rows: int):
        """The sorted buffer's rows to the devices that hold their experts:
        ``take(idx)`` gives the buffer's rows at the sorted positions
        ``idx`` (so that a gather of the tokens' rows into sorted order and
        the wire's own packing are one pass); returns ``[budget, ...]``
        expert by expert, the rows behind ``live`` padding."""
        import jax
        import jax.numpy as jnp
        n = self.n
        if self.impl == "ragged":
            buf = take(jnp.arange(sorted_rows, dtype=jnp.int32))
            got = jax.lax.ragged_all_to_all(
                buf, jnp.zeros((self.budget,) + buf.shape[1:], buf.dtype),
                self.send_off.astype(jnp.int32), self.kept.astype(jnp.int32),
                self.in_off_there.astype(jnp.int32),
                self.taken.astype(jnp.int32), axis_name=self.axis)
        else:
            part = jnp.arange(self.part, dtype=jnp.int32)
            idx = jnp.clip(self.send_off[:, None] + part[None, :], 0,
                           sorted_rows - 1)
            buf = take(idx.reshape(-1))
            got = jax.lax.all_to_all(
                buf.reshape((n, self.part) + buf.shape[1:]), self.axis, 0, 0
            ).reshape((self.budget,) + buf.shape[1:])
        return self.by_expert(got)

    def back(self, y, sorted_rows: int):
        """``y [budget, ...]`` expert by expert back to the devices the rows
        came from: ``[sorted_rows, ...]`` in the sender's sorted order, a
        dropped row's place zero."""
        import jax
        import jax.numpy as jnp
        n = self.n
        by_source = self.by_source(y)
        if self.impl == "ragged":
            return jax.lax.ragged_all_to_all(
                by_source,
                jnp.zeros((sorted_rows,) + y.shape[1:], y.dtype),
                self.in_off.astype(jnp.int32), self.taken.astype(jnp.int32),
                self.send_off_there.astype(jnp.int32),
                self.kept.astype(jnp.int32), axis_name=self.axis)
        got = jax.lax.all_to_all(
            by_source.reshape((n, self.part) + y.shape[1:]), self.axis, 0, 0
        ).reshape((self.budget,) + y.shape[1:])
        rows = jnp.arange(sorted_rows, dtype=jnp.int32)
        owner = jnp.maximum(
            jnp.searchsorted(self.send_off, rows, side="right") - 1, 0)
        at = rows - self.send_off[owner]
        kept = (at < self.kept[owner]).reshape((-1,) + (1,) * (y.ndim - 1))
        picked = got[owner * self.part + jnp.minimum(at, self.part - 1)]
        return jnp.where(kept, picked, jnp.zeros((), y.dtype))
