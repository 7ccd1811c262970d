"""Tensor-manipulation ops: reshape/transpose/concat/split/slice/gather/embedding/...

Reference: paddle/fluid/operators/{reshape_op, transpose_op, concat_op, split_op,
slice_op, gather_op, scatter_op, lookup_table_op, expand_op, stack_op, squeeze_op,
unsqueeze_op, flatten_op, pad_op, topk_op, arg_min_max_op, argsort_op, unstack_op}.*
"""
from __future__ import annotations

import numpy as np

from ..core.registry import register


def _jnp():
    import jax.numpy as jnp
    return jnp


def _resolve_shape(shape, x):
    """Fluid reshape semantics: 0 copies the input dim, one -1 is inferred."""
    shape = list(shape)
    total = int(np.prod(x.shape)) if x.shape else 1
    out = []
    for i, s in enumerate(shape):
        if s == 0:
            out.append(x.shape[i])
        else:
            out.append(int(s))
    if -1 in out:
        known = int(np.prod([s for s in out if s != -1])) or 1
        out[out.index(-1)] = total // known
    return tuple(out)


def _reshape_lower(ctx, ins):
    x = ins["X"][0]
    shape = _resolve_shape(ctx.attr("shape", []), x)
    return {"Out": [x.reshape(shape)],
            "XShape": [None]}


register("reshape")( _reshape_lower)
register("reshape2")(_reshape_lower)


def _transpose_lower(ctx, ins):
    x = ins["X"][0]
    return {"Out": [_jnp().transpose(x, ctx.attr("axis"))], "XShape": [None]}


register("transpose")(_transpose_lower)
register("transpose2")(_transpose_lower)


def _flatten_lower(ctx, ins):
    x = ins["X"][0]
    axis = ctx.attr("axis", 1)
    lead = int(np.prod(x.shape[:axis])) if axis > 0 else 1
    return {"Out": [x.reshape((lead, -1))], "XShape": [None]}


register("flatten")(_flatten_lower)
register("flatten2")(_flatten_lower)


def _squeeze_lower(ctx, ins):
    jnp = _jnp()
    x = ins["X"][0]
    axes = ctx.attr("axes", [])
    if axes:
        axes = tuple(a % x.ndim for a in axes if x.shape[a % x.ndim] == 1)
        out = jnp.squeeze(x, axis=axes) if axes else x
    else:
        out = jnp.squeeze(x)
    return {"Out": [out], "XShape": [None]}


register("squeeze")(_squeeze_lower)
register("squeeze2")(_squeeze_lower)


def _unsqueeze_lower(ctx, ins):
    jnp = _jnp()
    x = ins["X"][0]
    for a in sorted(ctx.attr("axes", [])):
        x = jnp.expand_dims(x, a)
    return {"Out": [x], "XShape": [None]}


register("unsqueeze")(_unsqueeze_lower)
register("unsqueeze2")(_unsqueeze_lower)


@register("concat")
def concat(ctx, ins):
    jnp = _jnp()
    xs = [x for x in ins["X"] if x is not None]
    return {"Out": [jnp.concatenate(xs, axis=ctx.attr("axis", 0))]}


@register("split")
def split(ctx, ins):
    jnp = _jnp()
    x = ins["X"][0]
    axis = ctx.attr("axis", 0)
    num = ctx.attr("num", 0)
    sections = ctx.attr("sections", [])
    if sections:
        idx = np.cumsum(sections)[:-1].tolist()
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    return {"Out": list(outs)}


@register("stack")
def stack(ctx, ins):
    jnp = _jnp()
    return {"Y": [jnp.stack([x for x in ins["X"] if x is not None],
                            axis=ctx.attr("axis", 0))]}


@register("unstack")
def unstack(ctx, ins):
    jnp = _jnp()
    x = ins["X"][0]
    axis = ctx.attr("axis", 0)
    n = x.shape[axis]
    return {"Y": [jnp.squeeze(s, axis=axis) for s in jnp.split(x, n, axis=axis)]}


@register("slice")
def slice_op(ctx, ins):
    x = ins["Input"][0]
    axes = ctx.attr("axes", [])
    starts = ctx.attr("starts", [])
    ends = ctx.attr("ends", [])
    sl = [slice(None)] * x.ndim
    for a, s, e in zip(axes, starts, ends):
        dim = x.shape[a]
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        sl[a] = slice(s, e)
    return {"Out": [x[tuple(sl)]]}


@register("strided_slice")
def strided_slice(ctx, ins):
    x = ins["Input"][0]
    axes = ctx.attr("axes", [])
    starts, ends, strides = (ctx.attr("starts", []), ctx.attr("ends", []),
                             ctx.attr("strides", []))
    sl = [slice(None)] * x.ndim
    for a, s, e, st in zip(axes, starts, ends, strides):
        sl[a] = slice(s, e, st)
    return {"Out": [x[tuple(sl)]]}


@register("gather", nondiff_inputs=("Index",))
def gather(ctx, ins):
    jnp = _jnp()
    x, idx = ins["X"][0], ins["Index"][0].astype("int32")
    axis = ctx.attr("axis", 0)
    if axis == 0:
        n = 1
        if jnp.issubdtype(x.dtype, jnp.number):     # rows that psum can add
            n = ctx.data_shards(*x.shape[:1], *idx.shape[:1])
        ctx.report("gather_layout_total",
                   layout="shard" if n > 1 else "global", shards=n)
        if n > 1:
            return {"Out": [_rows_over_data_axis(
                ctx.gspmd_mesh, ctx.data_axis, n, x, idx)]}
    return {"Out": [jnp.take(x, idx, axis=axis)]}


def _rows_over_data_axis(mesh, axis, n, x, idx):
    """``jnp.take(x, idx, axis=0)`` under a GSPMD mesh, the rows leaving laid
    over the mesh's data ``axis`` (``n`` devices): device r holds rows
    ``[r M / n, (r + 1) M / n)`` of the M gathered. XLA's SPMD partitioner
    gathers from a data-sharded ``x`` (activations ``[B * S, H]``) by
    replicated indices (BERT's flat ``mask_pos``) under a mask, all-reduces
    and leaves the rows replicated, so every device runs every row-wise
    consumer, and its backward, at all M rows. The island does the same
    masked take and all-reduce and hands each device its part, whichever way
    ``idx`` is laid out; its backward pads and all-reduces the rows'
    gradient. All-reduces only, by design: an all-gather (of sharded indices,
    of the cotangent; what a sharding constraint on the output or
    ``psum_scatter`` gets from XLA) is run asynchronously across the
    neighbouring fusions on a TPU and cost BERT's 24 feed-forward products
    their VMEM-resident operands, 3 ms of a 98.6 ms step (PERF.md section 6,
    PR 36). An index outside ``x`` gives a row of zeros (``jnp.take``: NaN)."""
    import jax
    from jax.sharding import PartitionSpec as P
    jnp = _jnp()
    rows, m = x.shape[0] // n, idx.shape[0] // n

    def local(x_here, idx_here):
        r = jax.lax.axis_index(axis)
        # every device's indices: mine in their place, summed
        i = jax.lax.psum(jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros((m * n, *idx_here.shape[1:]), idx_here.dtype),
            idx_here, r * m, axis=0), axis)
        i = jnp.where(i < 0, i + rows * n, i) - r * rows
        mine = (i >= 0) & (i < rows)
        got = jnp.take(x_here, jnp.clip(i, 0, rows - 1), axis=0)
        got = jnp.where(mine.reshape(mine.shape + (1,) * (x.ndim - 1)), got,
                        jnp.zeros((), got.dtype))
        return jax.lax.dynamic_slice_in_dim(
            jax.lax.psum(got, axis), r * m, m, axis=0)

    return jax.shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis)),
                         out_specs=P(axis))(x, idx)


@register("gather_nd", nondiff_inputs=("Index",))
def gather_nd(ctx, ins):
    x, idx = ins["X"][0], ins["Index"][0]
    idx = idx.astype("int32")
    nd = idx.shape[-1]
    out = x[tuple(idx[..., i] for i in range(nd))]
    return {"Out": [out]}


@register("scatter", nondiff_inputs=("Ids",))
def scatter(ctx, ins):
    x, ids, updates = ins["X"][0], ins["Ids"][0], ins["Updates"][0]
    ids = ids.astype("int32").reshape(-1)
    if ctx.attr("overwrite", True):
        out = x.at[ids].set(updates)
    else:
        out = x.at[ids].add(updates)
    return {"Out": [out]}


@register("scatter_nd_add", nondiff_inputs=("Index",))
def scatter_nd_add(ctx, ins):
    x, idx, updates = ins["X"][0], ins["Index"][0], ins["Updates"][0]
    idx = idx.astype("int32")
    nd = idx.shape[-1]
    return {"Out": [x.at[tuple(idx[..., i] for i in range(nd))].add(updates)]}


def _lookup(ctx, ins):
    """Embedding lookup (reference lookup_table_op.cc). padding_idx rows produce zeros
    in forward and receive no gradient.

    TPU note: grads are dense (one big scatter-add fused by XLA); the reference's
    SelectedRows sparse grad is an optimization for CPU/pserver paths -- the sharded
    (EP) embedding path lives in parallel/ and layers.sparse_embedding."""
    jnp = _jnp()
    w, ids = ins["W"][0], ins["Ids"][0]
    squeeze_last = ids.ndim > 1 and ids.shape[-1] == 1
    if squeeze_last:
        ids = ids.squeeze(-1)
    ids = ids.astype("int32")
    out = jnp.take(w, ids, axis=0)
    pad = ctx.attr("padding_idx", -1)
    if pad is not None and pad >= 0:
        mask = (ids != pad)[..., None].astype(out.dtype)
        out = out * mask
    return {"Out": [out]}


register("lookup_table", nondiff_inputs=("Ids",))(_lookup)
register("lookup_table_v2", nondiff_inputs=("Ids",))(_lookup)


@register("embedding_bag", nondiff_inputs=("Ids",))
def embedding_bag(ctx, ins):
    jnp = _jnp()
    w, ids = ins["W"][0], ins["Ids"][0]
    out = jnp.take(w, ids.astype("int32"), axis=0)
    mode = ctx.attr("mode", "sum")
    return {"Out": [jnp.sum(out, axis=1) if mode == "sum" else jnp.mean(out, axis=1)]}


@register("expand")
def expand(ctx, ins):
    jnp = _jnp()
    x = ins["X"][0]
    times = ctx.attr("expand_times", [])
    return {"Out": [jnp.tile(x, tuple(times))]}


@register("expand_as")
def expand_as(ctx, ins):
    jnp = _jnp()
    x, target = ins["X"][0], ins["target_tensor"][0]
    times = tuple(t // s for t, s in zip(target.shape, x.shape))
    return {"Out": [jnp.tile(x, times)]}


@register("tile")
def tile(ctx, ins):
    return {"Out": [_jnp().tile(ins["X"][0], tuple(ctx.attr("repeat_times", [])))]}


@register("pad")
def pad(ctx, ins):
    jnp = _jnp()
    x = ins["X"][0]
    p = ctx.attr("paddings", [])
    pairs = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": [jnp.pad(x, pairs, constant_values=ctx.attr("pad_value", 0.0))]}


@register("pad2d")
def pad2d(ctx, ins):
    jnp = _jnp()
    x = ins["X"][0]
    p = ctx.attr("paddings", [0, 0, 0, 0])  # top, bottom, left, right
    mode = ctx.attr("mode", "constant")
    fmt = ctx.attr("data_format", "NCHW")
    if fmt == "NCHW":
        pairs = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    else:
        pairs = [(0, 0), (p[0], p[1]), (p[2], p[3]), (0, 0)]
    if mode == "constant":
        return {"Out": [jnp.pad(x, pairs, constant_values=ctx.attr("pad_value", 0.0))]}
    jmode = {"reflect": "reflect", "edge": "edge"}[mode]
    return {"Out": [jnp.pad(x, pairs, mode=jmode)]}


@register("top_k", nondiff_outputs=("Indices",))
def top_k(ctx, ins):
    import jax
    x = ins["X"][0]
    k = ctx.attr("k", 1)
    vals, idx = jax.lax.top_k(x, k)
    return {"Out": [vals], "Indices": [idx.astype("int64")]}


@register("arg_max", grad=None, nondiff_inputs=("X",))
def arg_max(ctx, ins):
    jnp = _jnp()
    return {"Out": [jnp.argmax(ins["X"][0], axis=ctx.attr("axis", -1))
                    .astype(np.dtype(ctx.attr("dtype_str", "int64")))]}


@register("arg_min", grad=None, nondiff_inputs=("X",))
def arg_min(ctx, ins):
    jnp = _jnp()
    return {"Out": [jnp.argmin(ins["X"][0], axis=ctx.attr("axis", -1))
                    .astype("int64")]}


@register("argsort", nondiff_outputs=("Indices",))
def argsort(ctx, ins):
    jnp = _jnp()
    x = ins["X"][0]
    axis = ctx.attr("axis", -1)
    descending = ctx.attr("descending", False)
    idx = jnp.argsort(-x if descending else x, axis=axis)
    out = jnp.take_along_axis(x, idx, axis=axis)
    return {"Out": [out], "Indices": [idx.astype("int64")]}


@register("index_select", nondiff_inputs=("Index",))
def index_select(ctx, ins):
    jnp = _jnp()
    return {"Out": [jnp.take(ins["X"][0], ins["Index"][0].astype("int32"),
                             axis=ctx.attr("dim", 0))]}


@register("roll")
def roll(ctx, ins):
    jnp = _jnp()
    return {"Out": [jnp.roll(ins["X"][0], ctx.attr("shifts", [0]),
                             axis=tuple(ctx.attr("axis", [0])))]}


@register("flip")
def flip(ctx, ins):
    jnp = _jnp()
    return {"Out": [jnp.flip(ins["X"][0], axis=tuple(ctx.attr("axis", [0])))]}


@register("reverse")
def reverse(ctx, ins):
    jnp = _jnp()
    return {"Out": [jnp.flip(ins["X"][0], axis=tuple(ctx.attr("axis", [0])))]}


@register("label_smooth", nondiff_inputs=("PriorDist",))
def label_smooth(ctx, ins):
    x = ins["X"][0]
    eps = ctx.attr("epsilon", 0.0)
    k = x.shape[-1]
    prior = ins.get("PriorDist", [None])
    if prior and prior[0] is not None:
        return {"Out": [(1 - eps) * x + eps * prior[0]]}
    return {"Out": [(1 - eps) * x + eps / k]}


@register("diag", grad=None)
def diag(ctx, ins):
    return {"Out": [_jnp().diag(ins["Diagonal"][0])]}


@register("eye", grad=None)
def eye(ctx, ins):
    jnp = _jnp()
    return {"Out": [jnp.eye(ctx.attr("num_rows"), ctx.attr("num_columns"),
                            dtype=np.dtype(ctx.attr("dtype", "float32")))]}


@register("meshgrid", grad=None)
def meshgrid(ctx, ins):
    jnp = _jnp()
    outs = jnp.meshgrid(*[x for x in ins["X"]], indexing="ij")
    return {"Out": list(outs)}


@register("shard_index", grad=None, nondiff_inputs=("X",))
def shard_index(ctx, ins):
    jnp = _jnp()
    x = ins["X"][0]
    index_num = ctx.attr("index_num")
    nshards = ctx.attr("nshards")
    shard_id = ctx.attr("shard_id")
    ignore = ctx.attr("ignore_value", -1)
    size = (index_num + nshards - 1) // nshards
    in_shard = (x // size) == shard_id
    return {"Out": [jnp.where(in_shard, x % size, ignore)]}
