"""Operator-library tail (round 5): the remaining user-facing math/NN ops
from the reference's registry that are neither scoped infrastructure
(PS/RPC/LoD/engine/fake-quant rows in SCOPE.md) nor niche kernels.

Each op cites its reference implementation. All are jnp/lax lowerings --
fixed shapes, differentiable through the registry's auto-vjp unless marked
grad=None.
"""
from __future__ import annotations

import numpy as np

from ..core.registry import register, simple_op


def _jnp():
    import jax.numpy as jnp
    return jnp


def _lax():
    import jax.lax as lax
    return lax


# -- activations / losses ----------------------------------------------------

@simple_op("selu")
def selu(ctx, x):
    """Reference selu_op.cc: scale * (x > 0 ? x : alpha * (exp(x) - 1))."""
    jnp = _jnp()
    scale = ctx.attr("scale", 1.0507009873554805)
    alpha = ctx.attr("alpha", 1.6732632423543772)
    return scale * jnp.where(x > 0, x, alpha * (jnp.exp(x) - 1.0))


@register("hinge_loss")
def hinge_loss(ctx, ins):
    """Reference hinge_loss_op.cc: max(1 - pred * (2*label - 1), 0)."""
    jnp = _jnp()
    pred, label = ins["Logits"][0], ins["Labels"][0]
    return {"Loss": [jnp.maximum(
        1.0 - pred * (2.0 * label.astype(pred.dtype) - 1.0), 0.0)]}


@register("modified_huber_loss")
def modified_huber_loss(ctx, ins):
    """Reference modified_huber_loss_op.cc over z = pred * (2y - 1):
    z >= -1 -> max(0, 1-z)^2 ; z < -1 -> -4z. IntermediateVal carries z
    (the reference saves it for backward; auto-vjp recomputes, the output
    exists for parity)."""
    jnp = _jnp()
    pred, label = ins["X"][0], ins["Y"][0]
    z = pred * (2.0 * label.astype(pred.dtype) - 1.0)
    loss = jnp.where(z >= -1.0, jnp.square(jnp.maximum(1.0 - z, 0.0)),
                     -4.0 * z)
    import jax
    return {"Out": [loss], "IntermediateVal": [jax.lax.stop_gradient(z)]}


@register("squared_l2_distance")
def squared_l2_distance(ctx, ins):
    """Reference squared_l2_distance_op.cc: per-row sum of squared
    differences; sub_result is saved for backward (parity output)."""
    import jax
    jnp = _jnp()
    x, y = ins["X"][0], ins["Y"][0]
    sub = x - y   # y may be [1, K]: broadcast like the reference
    return {"Out": [jnp.sum(jnp.square(sub), axis=-1, keepdims=True)],
            "sub_result": [jax.lax.stop_gradient(sub)]}


@simple_op("l1_norm")
def l1_norm(ctx, x):
    """Reference l1_norm_op.cc: sum of absolute values (scalar [1])."""
    jnp = _jnp()
    return jnp.sum(jnp.abs(x)).reshape(1)


# -- elementwise / tensor utilities ------------------------------------------

@register("minus")
def minus(ctx, ins):
    """Reference minus_op.cc: Out = X - Y."""
    return {"Out": [ins["X"][0] - ins["Y"][0]]}


@register("norm")
def norm(ctx, ins):
    """Reference norm_op.cc: l2-normalize along ``axis``; Norm holds
    sqrt(sum(x^2) + eps) (saved for backward in the reference)."""
    import jax
    jnp = _jnp()
    x = ins["X"][0]
    axis = ctx.attr("axis", 1)
    eps = ctx.attr("epsilon", 1e-10)
    n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / n], "Norm": [jax.lax.stop_gradient(n)]}


@register("size", grad=None)
def size(ctx, ins):
    """Reference size_op.cc: number of elements. The reference emits int64;
    this framework runs with x64 disabled so integer outputs are int32
    (the repo-wide int convention -- fine below 2^31 elements)."""
    jnp = _jnp()
    return {"Out": [jnp.asarray([int(np.prod(ins["Input"][0].shape))],
                                jnp.int32)]}


@register("fill", grad=None)
def fill(ctx, ins):
    """Reference fill_op.cc: materialize attr ``value`` (flat float list)
    as a tensor of attr shape/dtype."""
    jnp = _jnp()
    from ..framework import convert_dtype
    shape = ctx.attr("shape", [])
    dtype = convert_dtype(ctx.attr("dtype", "float32"))
    vals = np.asarray(ctx.attr("value", []), dtype="float64")
    return {"Out": [jnp.asarray(vals.reshape(shape), dtype=dtype)]}


@register("fill_zeros_like2", grad=None)
def fill_zeros_like2(ctx, ins):
    """Reference fill_zeros_like_op.cc (v2: explicit dtype attr)."""
    jnp = _jnp()
    from ..framework import convert_dtype
    dt = ctx.attr("dtype", None)
    x = ins["X"][0]
    return {"Out": [jnp.zeros(x.shape,
                              convert_dtype(dt) if dt is not None
                              else x.dtype)]}


@register("crop")
def crop(ctx, ins):
    """Reference crop_op.cc: static-offset crop to ``shape`` (or Y's
    shape). The runtime-Offsets input variant is served by crop_tensor."""
    lax = _lax()
    x = ins["X"][0]
    y = ins.get("Y", [None])[0]
    shape = list(y.shape) if y is not None else list(ctx.attr("shape", []))
    offsets = list(ctx.attr("offsets", []) or [0] * x.ndim)
    return {"Out": [lax.slice(x, offsets,
                              [o + s for o, s in zip(offsets, shape)])]}


@register("fc")
def fc(ctx, ins):
    """Reference operators/fc_op.cc (the fused inference op; the Python
    layers.fc builds mul+add instead): flatten to in_num_col_dims, matmul,
    optional bias."""
    jnp = _jnp()
    x, w = ins["Input"][0], ins["W"][0]
    ncol = ctx.attr("in_num_col_dims", 1)
    x2 = x.reshape((int(np.prod(x.shape[:ncol])), -1))
    out = jnp.dot(x2, w)
    b = ins.get("Bias", [None])[0]
    if b is not None:
        out = out + b.reshape(1, -1)
    return {"Out": [out.reshape(tuple(x.shape[:ncol]) + (w.shape[1],))]}


@register("cvm")
def cvm(ctx, ins):
    """Reference cvm_op.cc: X rows are [show, click, features...];
    use_cvm=True keeps width D with Y[0]=log(show+1),
    Y[1]=log(click+1)-log(show+1); False drops the two CVM columns."""
    jnp = _jnp()
    x = ins["X"][0]
    if ctx.attr("use_cvm", True):
        show = jnp.log(x[:, :1] + 1.0)
        click = jnp.log(x[:, 1:2] + 1.0) - show
        return {"Y": [jnp.concatenate([show, click, x[:, 2:]], axis=1)]}
    return {"Y": [x[:, 2:]]}


@register("conv_shift")
def conv_shift(ctx, ins):
    """Reference conv_shift_op.cc (circular convolution, NTM-style):
    out[b, i] = sum_j x[b, (i + j - (M-1)//2) mod N] * y[b, j]."""
    jnp = _jnp()
    x, y = ins["X"][0], ins["Y"][0]
    m = y.shape[1]
    half = (m - 1) // 2
    out = 0.0
    for j in range(m):   # M is small (the shift kernel), static unroll
        out = out + jnp.roll(x, -(j - half), axis=1) * y[:, j:j + 1]
    return {"Out": [out]}


# -- pooling tail ------------------------------------------------------------

@register("max_pool2d_with_index", nondiff_outputs=("Mask",))
def max_pool2d_with_index(ctx, ins):
    """Reference pool_with_index_op.cc: max pool + flat argmax indices into
    each input feature map (consumed by unpool). Non-overlapping windows
    (stride == ksize, the unpool use case); overlapping windows raise."""
    jnp = _jnp()
    import jax
    x = ins["X"][0]
    k = ctx.attr("ksize", [2, 2])
    s = ctx.attr("strides", k) or k
    p = ctx.attr("paddings", [0, 0]) or [0, 0]
    n, c, h, w = x.shape
    kh, kw = int(k[0]), int(k[1])
    if list(k) != list(s) or any(p) or h % kh or w % kw:
        raise NotImplementedError(
            "max_pool2d_with_index: non-overlapping unpadded windows over "
            "divisible maps only (stride == ksize, H % kh == W % kw == 0); "
            "use pool2d for plain max pooling")
    xb = x.reshape(n, c, h // kh, kh, w // kw, kw)
    xb = xb.transpose(0, 1, 2, 4, 3, 5).reshape(n, c, h // kh, w // kw,
                                                kh * kw)
    out = jnp.max(xb, axis=-1)
    win = jnp.argmax(xb, axis=-1)                    # index inside window
    rows = (jax.lax.broadcasted_iota(jnp.int32, out.shape, 2) * kh
            + win // kw)
    cols = (jax.lax.broadcasted_iota(jnp.int32, out.shape, 3) * kw
            + win % kw)
    return {"Out": [out],
            "Mask": [jax.lax.stop_gradient(rows * w + cols)]}


@register("unpool", nondiff_inputs=("Indices",))
def unpool(ctx, ins):
    """Reference unpool_op.cc: scatter pooled values back to the argmax
    positions recorded by max_pool2d_with_index (zeros elsewhere)."""
    jnp = _jnp()
    x, idx = ins["X"][0], ins["Indices"][0]
    n, c, h, w = x.shape
    out_size = ctx.attr("unpool_size", None) or ctx.attr("output_size", None)
    if out_size is None:
        # reference unpool_op.cc default: out = (in - 1) * stride + ksize
        k = ctx.attr("ksize", [2, 2])
        st = ctx.attr("strides", k) or k
        out_size = [(h - 1) * int(st[0]) + int(k[0]),
                    (w - 1) * int(st[1]) + int(k[1])]
    hs, ws = int(out_size[0]), int(out_size[1])
    flat = jnp.zeros((n, c, hs * ws), x.dtype)
    flat = flat.at[
        jnp.arange(n)[:, None, None],
        jnp.arange(c)[None, :, None],
        idx.reshape(n, c, -1)].set(x.reshape(n, c, -1))
    return {"Out": [flat.reshape(n, c, hs, ws)]}


@register("spp")
def spp(ctx, ins):
    """Reference spp_op.h:35 (spatial pyramid pooling): level l pools to
    2^l x 2^l bins with kernel=ceil(size/bins), stride=kernel,
    pad=(kernel*bins-size+1)//2 -- window extents match the reference's
    Pool2dFunctor exactly (windows clipped to the map; avg divides by the
    valid count, i.e. exclusive)."""
    jnp = _jnp()
    x = ins["X"][0]
    height = ctx.attr("pyramid_height", 1)
    ptype = ctx.attr("pooling_type", "max")
    n, c, h, w = x.shape
    pieces = []
    for level in range(height):
        bins = 2 ** level
        kh = -(-h // bins)
        kw = -(-w // bins)
        ph = (kh * bins - h + 1) // 2
        pw = (kw * bins - w + 1) // 2
        for i in range(bins):
            h0 = min(max(0, i * kh - ph), h - 1)
            h1 = max(h0 + 1, min(h, i * kh - ph + kh))
            for j in range(bins):
                w0 = min(max(0, j * kw - pw), w - 1)
                w1 = max(w0 + 1, min(w, j * kw - pw + kw))
                cell = x[:, :, h0:h1, w0:w1]
                red = jnp.max(cell, axis=(2, 3)) if ptype == "max"                     else jnp.mean(cell, axis=(2, 3))
                pieces.append(red.reshape(n, c, 1))
    return {"Out": [jnp.concatenate(pieces, axis=2).reshape(n, -1)]}


# -- conv tail ---------------------------------------------------------------

@register("depthwise_conv2d_transpose")
def depthwise_conv2d_transpose(ctx, ins):
    """Reference conv_transpose_op.cc depthwise registration: groups ==
    channels transpose conv; reuses the grouped path of conv2d_transpose.
    The groups override rides a COPIED ctx -- ctx.attrs is the program's
    own attr dict and must not be mutated by lowering."""
    from . import nn_ops
    from ..core.registry import LowerCtx
    x = ins["Input"][0]
    sub = LowerCtx({**ctx.attrs, "groups": int(x.shape[1])},
                   ctx._base_key, ctx._salt, ctx.block_runner, ctx.program,
                   ctx.mesh, gspmd_mesh=ctx.gspmd_mesh,
                   abstract=ctx.abstract, data_axis=ctx.data_axis)
    return nn_ops.conv2d_transpose(sub, ins)


# -- optimizer tail ----------------------------------------------------------

@register("proximal_adagrad", grad=None)
def proximal_adagrad(ctx, ins):
    """Reference proximal_adagrad_op.h:52: m_out = m + g^2;
    prox = p - lr * g / sqrt(m_out); the l1 threshold and l2 denominator
    use the RAW scalar lr (only the gradient term is moment-scaled)."""
    jnp = _jnp()
    p, g = ins["Param"][0], ins["Grad"][0]
    m = ins["Moment"][0]
    lr = ins["LearningRate"][0].reshape(())
    l1 = ctx.attr("l1", 0.0)
    l2 = ctx.attr("l2", 0.0)
    m_out = m + g * g
    prox = p - lr * g / jnp.sqrt(m_out)
    if l1 > 0.0:
        p_out = (jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
                 / (1.0 + lr * l2))
    else:
        p_out = prox / (1.0 + lr * l2)
    return {"ParamOut": [p_out.astype(p.dtype)], "MomentOut": [m_out]}


# -- aliases: reference op names for capabilities registered under this
#    repo's naming -------------------------------------------------------

def _register_aliases():
    from ..core.registry import _REGISTRY, OpDef

    def alias(name, target, doc):
        t = _REGISTRY[target]
        if name in _REGISTRY:
            return
        d = OpDef(name, t.lower, infer_shape=t.custom_infer_shape,
                  grad=t.grad, nondiff_inputs=t.nondiff_inputs,
                  nondiff_outputs=t.nondiff_outputs)
        d.lower.__dict__.setdefault("_alias_doc", doc)
        _REGISTRY[name] = d

    # sync_batch_norm: under the GSPMD whole-program jit the batch dim is
    # sharded over 'dp' and batch_norm's jnp.mean reductions ARE global --
    # GSPMD inserts the cross-replica collectives the reference implements
    # by hand in sync_batch_norm_op.cu. The alias makes that explicit.
    alias("sync_batch_norm", "batch_norm",
          "global-batch statistics fall out of GSPMD reductions")
    # reference v2 names for ops this repo registered once
    alias("multiclass_nms2", "multiclass_nms",
          "nms2 = nms + Index output (already produced)")
    alias("generate_mask_labels", "generate_mask_targets",
          "reference name for the mask-target op")


_register_aliases()


# -- deformable convolution ---------------------------------------------------

def _bilinear_sample_nchw(x, py, px):
    """Bilinear sample x [N, C, H, W] at float coords py/px [N, S] per
    batch; out-of-bounds contributes zero (the reference's im2col border
    rule). Returns [N, C, S]."""
    jnp = _jnp()
    import jax
    n, c, h, w = x.shape
    y0 = jnp.floor(py)
    x0 = jnp.floor(px)
    wy = py - y0
    wx = px - x0
    out = 0.0
    for dy, dx in ((0, 0), (0, 1), (1, 0), (1, 1)):
        yy = y0 + dy
        xx = x0 + dx
        valid = ((yy >= 0) & (yy <= h - 1) & (xx >= 0) & (xx <= w - 1))
        yc = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xc = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
        flat = x.reshape(n, c, h * w)
        idx = yc * w + xc                              # [N, S]
        vals = jnp.take_along_axis(flat, idx[:, None, :].repeat(c, axis=1),
                                   axis=2)
        wgt = ((wy if dy else (1.0 - wy)) * (wx if dx else (1.0 - wx))
               * valid.astype(x.dtype))
        out = out + vals * wgt[:, None, :]
    return out


@register("deformable_conv")
def deformable_conv(ctx, ins):
    """Reference deformable_conv_op.cc (v2, modulated): each kernel tap k
    samples the input at p0 + p_k + offset[n, 2k:2k+2, p0] with bilinear
    interpolation, scaled by Mask, then contracts with the filter. The
    CUDA modulated_deformable_im2col collapses into one vectorized
    bilinear-gather + einsum."""
    jnp = _jnp()
    x, off, w = ins["Input"][0], ins["Offset"][0], ins["Filter"][0]
    mask = ins.get("Mask", [None])[0]
    strides = ctx.attr("strides", [1, 1]) or [1, 1]
    pads = ctx.attr("paddings", [0, 0]) or [0, 0]
    dil = ctx.attr("dilations", [1, 1]) or [1, 1]
    groups = int(ctx.attr("groups", 1) or 1)
    dg = int(ctx.attr("deformable_groups", 1) or 1)
    n, cin, h, wd = x.shape
    cout, cpg, kh, kw = w.shape
    ho = (h + 2 * pads[0] - (dil[0] * (kh - 1) + 1)) // strides[0] + 1
    wo = (wd + 2 * pads[1] - (dil[1] * (kw - 1) + 1)) // strides[1] + 1
    K = kh * kw
    import jax
    base_y = (jax.lax.broadcasted_iota(jnp.float32, (ho, wo), 0)
              * strides[0] - pads[0])
    base_x = (jax.lax.broadcasted_iota(jnp.float32, (ho, wo), 1)
              * strides[1] - pads[1])
    off = off.reshape(n, dg, K, 2, ho, wo).astype(jnp.float32)
    cols = []
    cg = cin // dg
    for g in range(dg):
        xg = x[:, g * cg:(g + 1) * cg]
        taps = []
        for ki in range(kh):
            for kj in range(kw):
                k = ki * kw + kj
                py = base_y[None] + ki * dil[0] + off[:, g, k, 0]
                px = base_x[None] + kj * dil[1] + off[:, g, k, 1]
                s = _bilinear_sample_nchw(xg, py.reshape(n, -1),
                                          px.reshape(n, -1))
                if mask is not None:
                    m = mask.reshape(n, dg, K, ho, wo)[:, g, k]
                    s = s * m.reshape(n, 1, -1).astype(s.dtype)
                taps.append(s)                        # [N, cg, Ho*Wo]
        cols.append(jnp.stack(taps, axis=2))          # [N, cg, K, S]
    col = jnp.concatenate(cols, axis=1)               # [N, Cin, K, S]
    # grouped contraction with the filter; full-f32 accumulation (the
    # reference kernel is f32 -- TPU's default multi-pass bf16 matmul would
    # cost ~1e-3 here)
    out = jnp.einsum("ngcks,gock->ngos",
                     col.reshape(n, groups, cin // groups, K, ho * wo),
                     w.reshape(groups, cout // groups, cin // groups, K),
                     precision="highest")
    return {"Output": [out.reshape(n, cout, ho, wo).astype(x.dtype)]}


@register("deformable_conv_v1")
def deformable_conv_v1(ctx, ins):
    """Reference deformable_conv_v1_op.cc: the unmodulated form (no Mask)."""
    ins = dict(ins)
    ins.pop("Mask", None)
    return deformable_conv(ctx, ins)


# -- similarity focus ---------------------------------------------------------

@register("similarity_focus", grad=None)
def similarity_focus(ctx, ins):
    """Reference similarity_focus_op.h:29: for each batch and each channel
    in ``indexes`` (along ``axis``), walk the 2-D slice's cells in
    descending value order and select each cell whose row AND column are
    both unused (greedy bipartite pick); the output mask is 1 at selected
    cells, broadcast over the axis dim, OR-ed across indexes.

    The sequential greedy walk is a fixed-length lax.scan over the sorted
    cell order (once min(rows, cols) cells are picked every later cell is
    blocked, reproducing the reference's early break). Ties sort by cell
    index (deterministic; the reference's std::sort leaves tie order
    unspecified).
    """
    import jax
    jnp = _jnp()
    x = ins["X"][0]
    axis = int(ctx.attr("axis", 1))
    indexes = list(ctx.attr("indexes", []))
    if x.ndim != 4 or axis not in (1, 2, 3):
        raise ValueError("similarity_focus: X must be 4-D with axis in "
                         "{1,2,3} (reference contract)")
    if not indexes:
        raise ValueError("similarity_focus: Indexes' size can not be 0")
    perm = [0, axis] + [d for d in (1, 2, 3) if d != axis]
    xp = jnp.transpose(x, perm)                  # [B, A, R, C]
    B, A, R, C = xp.shape

    def pick(slice2d):                           # [R, C] -> [R, C] 0/1 mask
        flat = slice2d.reshape(-1)
        order = jnp.argsort(-flat)               # stable: ties by index

        def body(carry, idx):
            rows, cols, mask = carry
            r = idx // C
            c = idx % C
            free = jnp.logical_and(~rows[r], ~cols[c])
            rows = rows.at[r].set(rows[r] | free)
            cols = cols.at[c].set(cols[c] | free)
            mask = mask.at[idx].set(mask[idx] | free)
            return (rows, cols, mask), None

        init = (jnp.zeros(R, bool), jnp.zeros(C, bool),
                jnp.zeros(R * C, bool))
        (_, _, mask), _ = jax.lax.scan(body, init, order)
        return mask.reshape(R, C)

    mask = jnp.zeros((B, R, C), bool)
    for index in indexes:
        if not 0 <= index < A:
            raise ValueError("similarity_focus: Index exceeds tensor shape "
                             "limit")
        mask = mask | jax.vmap(pick)(xp[:, index])
    out = jnp.broadcast_to(mask[:, None, :, :], (B, A, R, C))
    inv = [perm.index(d) for d in range(4)]
    return {"Out": [jnp.transpose(out, inv).astype(x.dtype)]}
