"""NN ops: conv / pool / normalization / dropout / interpolate.

Reference: paddle/fluid/operators/{conv_op, conv_cudnn_op.cu.cc, depthwise_conv_op,
conv_transpose_op, pool_op, batch_norm_op, layer_norm_op, group_norm_op,
instance_norm_op, data_norm_op, dropout_op, interpolate_op, prelu_op}.*

Convs lower to lax.conv_general_dilated (MXU path); there are no separate cuDNN
variants -- XLA targets the TPU convolution directly. Data layout is NCHW like the
reference's default; XLA relayouts internally for the MXU.
"""
from __future__ import annotations

import numpy as np

from ..core.registry import register


def _jnp():
    import jax.numpy as jnp
    return jnp


def _lax():
    import jax.lax as lax
    return lax


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


def _conv_pads(pads):
    """[ph, pw] (symmetric) or [top, bottom, left, right] (asymmetric)."""
    if len(pads) == 4:
        return [(pads[0], pads[1]), (pads[2], pads[3])]
    return [(pads[0], pads[0]), (pads[1], pads[1])]


def conv_in_layout(x, w, strides, pads, dil, groups, fmt, layout):
    """Run a 2D conv over ``x`` (declared layout ``fmt``) *computing* in
    ``layout``, returning the output back in ``fmt``. Filter stays OIHW in
    every combination (parameter shapes/checkpoints are layout-independent).
    When ``layout != fmt`` the activations are transposed at the op boundary;
    XLA cancels adjacent inverse transposes between consecutive convs, so a
    consistent tuned layout costs one transpose pair at the network edges."""
    lax = _lax()
    import jax.numpy as jnp
    if layout != fmt:
        x = jnp.transpose(x, (0, 2, 3, 1) if fmt == "NCHW" else (0, 3, 1, 2))
    out = lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=_conv_pads(pads),
        rhs_dilation=dil, feature_group_count=groups,
        dimension_numbers=(layout, "OIHW", layout),
        preferred_element_type=None)
    if layout != fmt:
        out = jnp.transpose(out,
                            (0, 3, 1, 2) if fmt == "NCHW" else (0, 2, 3, 1))
    return out


def _conv(ctx, ins, depthwise=False):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = _pair(ctx.attr("paddings", [0, 0]))  # 2-elem symmetric or 4-elem
    dil = _pair(ctx.attr("dilations", [1, 1]))
    groups = ctx.attr("groups", 1) or 1
    # data_format: activations NCHW (reference default) or NHWC (TPU-preferred;
    # channels-minor keeps XLA from inserting relayout transposes around the MXU
    # conv). Filter stays OIHW in both cases so parameter shapes/checkpoints are
    # layout-independent.
    fmt = ctx.attr("data_format", "NCHW") or "NCHW"
    if depthwise:
        groups = x.shape[1] if fmt == "NCHW" else x.shape[-1]
    # The COMPUTE layout is a tunable choice point: a persisted autotune
    # decision may run the conv in the other layout (transposing at the
    # boundary); the default is the declared format, i.e. exactly the old
    # lowering. Abstract (eval_shape) lowering never consults the tuner.
    layout = fmt
    if not ctx.abstract and len(getattr(x, "shape", ())) == 4:
        from ..tuning import decide as _decide
        layout = _decide("conv2d.layout", {
            "x_shape": tuple(x.shape), "w_shape": tuple(w.shape),
            "strides": tuple(strides), "pads": list(pads),
            "dils": tuple(dil), "groups": groups, "fmt": fmt,
            "dtype": str(x.dtype)})
    out = conv_in_layout(x, w, strides, pads, dil, groups, fmt, layout)
    return {"Output": [out]}


register("conv2d")(lambda ctx, ins: _conv(ctx, ins))
register("depthwise_conv2d")(lambda ctx, ins: _conv(ctx, ins, depthwise=True))


def _grouped_conv_transpose(x, w, groups, conv1):
    """lax.conv_transpose has no feature_group_count: split channels, conv
    each group, concat outputs. w: [in_c, out_c/groups, ...]."""
    import jax.numpy as jnp
    if groups <= 1:
        return conv1(x, w)
    icg = x.shape[1] // groups
    outs = [conv1(x[:, g * icg:(g + 1) * icg], w[g * icg:(g + 1) * icg])
            for g in range(groups)]
    return jnp.concatenate(outs, axis=1)


@register("conv2d_transpose")
def conv2d_transpose(ctx, ins):
    lax = _lax()
    x, w = ins["Input"][0], ins["Filter"][0]  # w: [in_c, out_c/groups, kh, kw]
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = _pair(ctx.attr("paddings", [0, 0]))
    dil = _pair(ctx.attr("dilations", [1, 1]))
    groups = ctx.attr("groups", 1) or 1

    def conv1(xg, wg):
        # paddle/torch kernel layout [in_c, out_c, kh, kw]: with
        # transpose_kernel=True jax wants it marked as the FORWARD conv's
        # kernel, i.e. O=in_c I=out_c -> "OIHW" (IOHW only shape-checks when
        # in_c == out_c, and silently computes the wrong transpose even then).
        # lax padding = d*(k-1) - p (paddle/torch p crops the output; the
        # effective dilated kernel is d*(k-1)+1). The two only coincide at
        # p == (k-1)/2, d=1 -- why odd-kernel same-pad tests used to pass.
        # Verified vs torch for k in {2,3,4,5} and dilation {1,2}.
        kh, kw = wg.shape[2], wg.shape[3]
        ph = dil[0] * (kh - 1) - pads[0]
        pw = dil[1] * (kw - 1) - pads[1]
        return lax.conv_transpose(
            xg, wg, strides=strides,
            padding=[(ph, ph), (pw, pw)],
            rhs_dilation=dil,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            transpose_kernel=True)

    return {"Output": [_grouped_conv_transpose(x, w, groups, conv1)]}


@register("conv3d")
def conv3d(ctx, ins):
    lax = _lax()
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = tuple(ctx.attr("strides", [1, 1, 1]))
    pads = tuple(ctx.attr("paddings", [0, 0, 0]))
    dil = tuple(ctx.attr("dilations", [1, 1, 1]))
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=[(p, p) for p in pads],
        rhs_dilation=dil, feature_group_count=ctx.attr("groups", 1) or 1,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    return {"Output": [out]}


@register("pool2d")
def pool2d(ctx, ins):
    lax = _lax()
    jnp = _jnp()
    x = ins["X"][0]
    ptype = ctx.attr("pooling_type", "max")
    k = _pair(ctx.attr("ksize", [2, 2]))
    s = _pair(ctx.attr("strides", [2, 2]))
    p = _pair(ctx.attr("paddings", [0, 0]))
    fmt = ctx.attr("data_format", "NCHW") or "NCHW"
    sp_axes = (2, 3) if fmt == "NCHW" else (1, 2)
    if ctx.attr("global_pooling", False):
        if ptype == "max":
            return {"Out": [jnp.max(x, axis=sp_axes, keepdims=True)]}
        return {"Out": [jnp.mean(x, axis=sp_axes, keepdims=True)]}
    if ctx.attr("adaptive", False):
        # adaptive pooling to output k: split H/W into k bins (requires divisibility)
        red = jnp.max if ptype == "max" else jnp.mean
        if fmt == "NCHW":
            n, c, h, w_ = x.shape
            xb = x.reshape(n, c, k[0], h // k[0], k[1], w_ // k[1])
            return {"Out": [red(xb, axis=(3, 5))]}
        n, h, w_, c = x.shape
        xb = x.reshape(n, k[0], h // k[0], k[1], w_ // k[1], c)
        return {"Out": [red(xb, axis=(2, 4))]}
    if fmt == "NCHW":
        window = (1, 1) + k
        strides = (1, 1) + s
        pads = ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]))
    else:
        window = (1,) + k + (1,)
        strides = (1,) + s + (1,)
        pads = ((0, 0), (p[0], p[0]), (p[1], p[1]), (0, 0))
    if ptype == "max":
        init = -jnp.inf if np.issubdtype(np.dtype(str(x.dtype)) if str(x.dtype) !=
                                         "bfloat16" else np.float32, np.floating) else 0
        out = lax.reduce_window(x, np.asarray(init, x.dtype), lax.max, window,
                                strides, pads)
        return {"Out": [out]}
    summed = lax.reduce_window(x, np.asarray(0, x.dtype), lax.add, window, strides,
                               pads)
    if ctx.attr("exclusive", True) and (p[0] or p[1]):
        ones = jnp.ones_like(x)
        cnt = lax.reduce_window(ones, np.asarray(0, x.dtype), lax.add, window,
                                strides, pads)
        return {"Out": [summed / cnt]}
    return {"Out": [summed / (k[0] * k[1])]}


@register("batch_norm", nondiff_inputs=("Mean", "Variance"),
          nondiff_outputs=("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"))
def batch_norm(ctx, ins):
    """Reference batch_norm_op.cc. Training mode computes batch stats over (N, spatial)
    and exponentially updates the running stats (which alias Mean/Variance in the
    program -- functional state threading makes this explicit)."""
    import jax
    jnp = _jnp()
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean_in, var_in = ins["Mean"][0], ins["Variance"][0]
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    layout = ctx.attr("data_layout", "NCHW")
    c_axis = 1 if layout == "NCHW" else x.ndim - 1
    red_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]

    if ctx.attr("is_test", False) or ctx.attr("use_global_stats", False):
        mean, var = mean_in, var_in
        saved_mean, saved_var = mean_in, var_in
        mean_out, var_out = mean_in, var_in
    else:
        cdt = jnp.float32
        xf = x.astype(cdt)
        mean = jnp.mean(xf, axis=red_axes)
        var = jnp.mean(jnp.square(xf), axis=red_axes) - jnp.square(mean)
        saved_mean, saved_var = mean, var
        mean_out = mean_in * momentum + mean * (1 - momentum)
        var_out = var_in * momentum + var * (1 - momentum)
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    y = (x.astype(jnp.float32) - mean.reshape(bshape)) * inv.reshape(bshape)
    y = y * scale.reshape(bshape) + bias.reshape(bshape)
    sg = jax.lax.stop_gradient
    return {"Y": [y.astype(x.dtype)],
            "MeanOut": [sg(mean_out)], "VarianceOut": [sg(var_out)],
            "SavedMean": [sg(saved_mean)], "SavedVariance": [sg(inv)]}


@register("layer_norm", nondiff_outputs=("Mean", "Variance"))
def layer_norm(ctx, ins):
    """Reference layer_norm_op.cc: normalize over dims >= begin_norm_axis."""
    import jax
    jnp = _jnp()
    x = ins["X"][0]
    eps = ctx.attr("epsilon", 1e-5)
    bna = ctx.attr("begin_norm_axis", 1)
    axes = tuple(range(bna, x.ndim))
    cdt = jnp.float32
    xf = x.astype(cdt)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    scale = ins.get("Scale", [None])
    if scale and scale[0] is not None:
        y = y * scale[0].reshape((1,) * bna + x.shape[bna:]).astype(cdt)
    bias = ins.get("Bias", [None])
    if bias and bias[0] is not None:
        y = y + bias[0].reshape((1,) * bna + x.shape[bna:]).astype(cdt)
    sg = jax.lax.stop_gradient
    return {"Y": [y.astype(x.dtype)],
            "Mean": [sg(mean.reshape(x.shape[:bna]))],
            "Variance": [sg(var.reshape(x.shape[:bna]))]}


@register("group_norm", nondiff_outputs=("Mean", "Variance"))
def group_norm(ctx, ins):
    import jax
    jnp = _jnp()
    x = ins["X"][0]
    g = ctx.attr("groups", 1)
    eps = ctx.attr("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, g, c // g) + x.shape[2:]).astype(jnp.float32)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=axes, keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    scale = ins.get("Scale", [None])
    if scale and scale[0] is not None:
        y = y * scale[0].reshape(bshape)
    bias = ins.get("Bias", [None])
    if bias and bias[0] is not None:
        y = y + bias[0].reshape(bshape)
    sg = jax.lax.stop_gradient
    return {"Y": [y.astype(x.dtype)], "Mean": [sg(mean.reshape(n, g))],
            "Variance": [sg(var.reshape(n, g))]}


@register("instance_norm", nondiff_outputs=("SavedMean", "SavedVariance"))
def instance_norm(ctx, ins):
    import jax
    jnp = _jnp()
    x = ins["X"][0]
    eps = ctx.attr("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    c = x.shape[1]
    bshape = (1, c) + (1,) * (x.ndim - 2)
    scale = ins.get("Scale", [None])
    if scale and scale[0] is not None:
        y = y * scale[0].reshape(bshape)
    bias = ins.get("Bias", [None])
    if bias and bias[0] is not None:
        y = y + bias[0].reshape(bshape)
    sg = jax.lax.stop_gradient
    return {"Y": [y.astype(x.dtype)], "SavedMean": [sg(mean.squeeze())],
            "SavedVariance": [sg(var.squeeze())]}


@register("dropout", nondiff_outputs=("Mask",))
def dropout(ctx, ins):
    """Reference dropout_op.cc. dropout_implementation: 'downgrade_in_infer' (default:
    scale output by (1-p) at inference) or 'upscale_in_train' (scale kept units by
    1/(1-p) during training)."""
    import jax
    jnp = _jnp()
    x = ins["X"][0]
    p = ctx.attr("dropout_prob", 0.5)
    impl = ctx.attr("dropout_implementation", "downgrade_in_infer")
    if ctx.attr("is_test", False):
        out = x if impl == "upscale_in_train" else x * (1.0 - p)
        # Declared outputs are always produced (clone(for_test) keeps grad ops that
        # list Mask as input); an all-ones mask is free after XLA DCE.
        return {"Out": [out], "Mask": [jnp.ones_like(x)]}
    keep = ctx.bernoulli_mask(ctx.rng(ctx.attr("seed", 0) or 0), 1.0 - p,
                              x.shape)
    mask = keep.astype(x.dtype)
    if impl == "upscale_in_train":
        out = jnp.where(p >= 1.0, jnp.zeros_like(x), x * mask / (1.0 - p))
    else:
        out = x * mask
    return {"Out": [out], "Mask": [jax.lax.stop_gradient(mask)]}


@register("prelu")
def prelu(ctx, ins):
    jnp = _jnp()
    x, alpha = ins["X"][0], ins["Alpha"][0]
    mode = ctx.attr("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    elif mode == "element":
        alpha = alpha.reshape((1,) + x.shape[1:])
    return {"Out": [jnp.where(x > 0, x, alpha * x)]}


@register("interpolate")
def interpolate(ctx, ins):
    import jax
    x = ins["X"][0]
    method = ctx.attr("interp_method", "nearest")
    out_h = ctx.attr("out_h", 0)
    out_w = ctx.attr("out_w", 0)
    scale = ctx.attr("scale", 0.0)
    n, c, h, w = x.shape
    if scale and scale > 0:
        out_h, out_w = int(h * scale), int(w * scale)
    jmethod = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic"}[method]
    out = jax.image.resize(x, (n, c, out_h, out_w), method=jmethod)
    return {"Out": [out.astype(x.dtype)]}


def _interp_as(method):
    def lower(ctx, ins):
        ctx.attrs = dict(ctx.attrs, interp_method=method)
        return interpolate(ctx, ins)
    return lower


register("nearest_interp")(_interp_as("nearest"))
register("bilinear_interp")(_interp_as("bilinear"))


def _triple(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * 3


@register("pool3d")
def pool3d(ctx, ins):
    """3D pooling (pool_op.cc NCDHW); same knobs as pool2d."""
    lax = _lax()
    jnp = _jnp()
    x = ins["X"][0]
    ptype = ctx.attr("pooling_type", "max")
    k = _triple(ctx.attr("ksize", [2, 2, 2]))
    s = _triple(ctx.attr("strides", [2, 2, 2]))
    p = _triple(ctx.attr("paddings", [0, 0, 0]))
    if ctx.attr("global_pooling", False):
        red = jnp.max if ptype == "max" else jnp.mean
        return {"Out": [red(x, axis=(2, 3, 4), keepdims=True)]}
    if ctx.attr("adaptive", False):
        n, c, d, h, w = x.shape
        xb = x.reshape(n, c, k[0], d // k[0], k[1], h // k[1], k[2], w // k[2])
        red = jnp.max if ptype == "max" else jnp.mean
        return {"Out": [red(xb, axis=(3, 5, 7))]}
    window = (1, 1) + k
    strides = (1, 1) + s
    pads = ((0, 0), (0, 0)) + tuple((pp, pp) for pp in p)
    if ptype == "max":
        out = lax.reduce_window(x, np.asarray(-np.inf, x.dtype), lax.max,
                                window, strides, pads)
        return {"Out": [out]}
    summed = lax.reduce_window(x, np.asarray(0, x.dtype), lax.add, window,
                               strides, pads)
    if ctx.attr("exclusive", True) and any(p):
        cnt = lax.reduce_window(jnp.ones_like(x), np.asarray(0, x.dtype),
                                lax.add, window, strides, pads)
        return {"Out": [summed / cnt]}
    return {"Out": [summed / (k[0] * k[1] * k[2])]}


@register("conv3d_transpose")
def conv3d_transpose(ctx, ins):
    lax = _lax()
    x, w = ins["Input"][0], ins["Filter"][0]   # w: [in_c, out_c/g, kd, kh, kw]
    strides = _triple(ctx.attr("strides", [1, 1, 1]))
    pads = _triple(ctx.attr("paddings", [0, 0, 0]))
    dil = _triple(ctx.attr("dilations", [1, 1, 1]))
    groups = ctx.attr("groups", 1) or 1

    def conv1(xg, wg):
        ks = wg.shape[2:]
        return lax.conv_transpose(
            xg, wg, strides=strides,
            padding=[(d * (k - 1) - p, d * (k - 1) - p)
                     for k, p, d in zip(ks, pads, dil)],
            rhs_dilation=dil, dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
            transpose_kernel=True)

    return {"Output": [_grouped_conv_transpose(x, w, groups, conv1)]}


@register("trilinear_interp")
def trilinear_interp(ctx, ins):
    import jax
    x = ins["X"][0]                            # [B, C, D, H, W]
    out_d = int(ctx.attr("out_d"))
    out_h = int(ctx.attr("out_h"))
    out_w = int(ctx.attr("out_w"))
    out = jax.image.resize(x, x.shape[:2] + (out_d, out_h, out_w),
                           method="trilinear")
    return {"Out": [out.astype(x.dtype)]}
