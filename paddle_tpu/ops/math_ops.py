"""Matmul family + softmax/cross-entropy + norms.

Reference: paddle/fluid/operators/{matmul_op, mul_op, softmax_op,
softmax_with_cross_entropy_op, cross_entropy_op, log_softmax}.* and math/blas.h.
Matmuls are the MXU path: lowerings keep them as single large dots (no scalar loops),
letting XLA tile onto the systolic array; bf16 flows through unchanged.
"""
from __future__ import annotations

import math

import numpy as np

from ..core.registry import register, register_grad


def _jnp():
    import jax.numpy as jnp
    return jnp


@register("matmul")
def matmul(ctx, ins):
    jnp = _jnp()
    x, y = ins["X"][0], ins["Y"][0]
    if ctx.attr("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if ctx.attr("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    out = jnp.matmul(x, y)
    alpha = ctx.attr("alpha", 1.0)
    if alpha != 1.0:
        out = out * np.asarray(alpha, dtype=out.dtype)
    return {"Out": [out]}


@register("mul")
def mul(ctx, ins):
    """Flattening matmul (reference mul_op.cc): X flattened to 2D at x_num_col_dims."""
    jnp = _jnp()
    x, y = ins["X"][0], ins["Y"][0]
    xn = ctx.attr("x_num_col_dims", 1)
    yn = ctx.attr("y_num_col_dims", 1)
    xlead = x.shape[:xn]
    x2 = x.reshape((int(np.prod(xlead or (1,))), -1))
    y2 = y.reshape((int(np.prod(y.shape[:yn] or (1,))), -1))
    out = x2 @ y2
    return {"Out": [out.reshape(tuple(xlead) + tuple(y.shape[yn:]))]}


@register("bmm")
def bmm(ctx, ins):
    return {"Out": [_jnp().matmul(ins["X"][0], ins["Y"][0])]}


@register("dot")
def dot(ctx, ins):
    jnp = _jnp()
    return {"Out": [jnp.sum(ins["X"][0] * ins["Y"][0], axis=-1, keepdims=True)]}


@register("softmax")
def softmax(ctx, ins):
    import jax
    return {"Out": [jax.nn.softmax(ins["X"][0], axis=ctx.attr("axis", -1))]}


@register("log_softmax")
def log_softmax(ctx, ins):
    import jax
    return {"Out": [jax.nn.log_softmax(ins["X"][0], axis=ctx.attr("axis", -1))]}


# A written loss gradient must earn a pass over the logits. Offline compile
# for a described v5e (PR 40; XLA's estimated_cycles, temp_gb against the
# parent's), the head's dW product / the step's temporaries:
#   olmoe  [16384, 50304] bf16, 1.65 GB: fused 66.6 M cycles, written 30.4 M
#          (the forward product's 31.7 M), temp 4.975 -> 3.89 either way;
#   lfm2   [16384, 16384], 0.54 GB: XLA lays the head's output class-major
#          there, so a row-chunked write costs a transposing copy of the
#          logits (6.95 M cycles, +0.54 GB live) for a dW that falls 20.8 ->
#          9.9 M: temp 7.873 -> 8.02, over the peak's bound;
#   laguna [4096, 12544], 0.10 GB: the same layout, less to gain.
WRITTEN_GRAD_MIN_BYTES = 1 << 30
# rows of the logits a trip of the written form's loop rewrites: 16 trips in
# olmoe, each 1024 rows (103 MB read and written)
WRITTEN_GRAD_CHUNKS = 16


def _label_column(label, logits):
    """Hard labels of the last axis, [N..., 1] or [N...], as int32 [N..., 1]."""
    if label.ndim < logits.ndim:
        label = label[..., None]
    return label.astype("int32")


def _lean_loss(ctx, logits) -> bool:
    """What the lean form of ``softmax_with_cross_entropy`` and its written
    gradient need to see: hard labels along the last axis, no active
    ``ignore_index``, and logits narrower than float32 (what a model gets by
    not casting its head's output). Float32 logits keep the lowering they
    had, to the jaxpr."""
    jnp = _jnp()
    return (not ctx.attr("soft_label", False)
            and ctx.attr("axis", -1) in (-1, logits.ndim - 1)
            and ctx.attr("ignore_index", -100) < 0
            and jnp.issubdtype(logits.dtype, jnp.floating)
            and logits.dtype.itemsize < 4)


@register("softmax_with_cross_entropy", nondiff_inputs=("Label",),
          nondiff_outputs=("Softmax", "Lse"))
def softmax_with_cross_entropy(ctx, ins):
    """Fused stable softmax + CE (reference softmax_with_cross_entropy_op.cc).

    Hard labels: Label int [N...,1]; soft labels: Label same shape as Logits.
    Outputs: Softmax (no grad flow), Loss [N...,1], and Lse [N...,1], the
    rows' ``logsumexp``: the statistic the op's own grad lowering reads
    (``softmax_with_cross_entropy_grad``), float32 in the lean form.
    NOTE: Softmax marked nondiff so the vjp grad comes only from Loss -- matching the
    reference, whose grad kernel uses only the saved Softmax.

    Lean form (``_lean_loss``): float32 inside, and no float32 value of the
    logits' shape outlives a reduction -- the label's logit is gathered from
    the logits themselves, so ``Loss = lse - f32(x[label])``, the same
    float32 number as ``-(f32(x) - lse)[label]``.
    """
    import jax
    jnp = _jnp()
    logits, label = ins["Logits"][0], ins["Label"][0]
    axis = ctx.attr("axis", -1)
    if _lean_loss(ctx, logits):
        x32 = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(x32, axis=-1, keepdims=True)
        picked = jnp.take_along_axis(
            logits, _label_column(label, logits), axis=-1)
        return {"Softmax": [jnp.exp(x32 - lse).astype(logits.dtype)],
                "Loss": [lse - picked.astype(jnp.float32)], "Lse": [lse]}
    lse = jax.scipy.special.logsumexp(logits, axis=axis, keepdims=True)
    log_probs = logits - lse
    softmax_out = jnp.exp(log_probs)
    if ctx.attr("soft_label", False):
        loss = -jnp.sum(label.astype(log_probs.dtype) * log_probs, axis=axis,
                        keepdims=True)
    else:
        lab = label
        if lab.ndim == logits.ndim and lab.shape[axis] == 1:
            lab = jnp.squeeze(lab, axis=axis)
        picked = jnp.take_along_axis(log_probs, lab[..., None].astype("int32"),
                                     axis=axis)
        loss = -picked
        ignore = ctx.attr("ignore_index", -100)
        if ignore >= 0:
            mask = (lab[..., None] != ignore)
            loss = jnp.where(mask, loss, jnp.zeros_like(loss))
    return {"Softmax": [jax.lax.stop_gradient(softmax_out)], "Loss": [loss],
            "Lse": [lse]}


def _loss_grad_rows(x, lse, lab, g):
    """``(exp(f32(x) - lse) - onehot(lab)) * g`` over rows ``x [R, V]``,
    float32 inside, rounded once to the logits' dtype (the rounding the
    cast's grad applied while the model cast its logits)."""
    import jax
    jnp = _jnp()
    cls = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    p = jnp.exp(x.astype(jnp.float32) - lse)
    return ((p - (cls == lab).astype(jnp.float32)) * g).astype(x.dtype)


@register_grad("softmax_with_cross_entropy")
def softmax_with_cross_entropy_grad(ctx, ins, generic):
    """dLogits. Where the forward took the lean form and declared ``Lse``,
    the closed form over the logits in one elementwise pass
    (``_loss_grad_rows``: no forward lowered, no reduction of the logits'
    shape). From ``WRITTEN_GRAD_MIN_BYTES`` of logits on one device it is
    ``written``: a loop over row chunks rewrites the logits' own buffer, so
    the head's two gradient products read an array and neither re-derives
    the softmax in its operand; below that it is ``fused``, the same
    expression left to XLA. Every other case (soft labels, another axis, an
    ``ignore_index``, float32 logits, a desc from before the op had ``Lse``)
    is ``generic``. Which it was is reported as ``loss_backward_total``."""
    import jax
    logits, label = ins["Logits"][0], ins["Label"][0]
    lse, g = ins.get("Lse", [None])[0], ins.get("Loss@GRAD", [None])[0]
    if not _lean_loss(ctx, logits) or lse is None or g is None:
        ctx.report("loss_backward_total", form="generic")
        return generic()
    V = logits.shape[-1]
    x = logits.reshape((-1, V))
    lse, g = lse.reshape((-1, 1)), g.reshape((-1, 1)).astype(lse.dtype)
    lab = _label_column(label, logits).reshape((-1, 1))
    trips = math.gcd(x.shape[0], WRITTEN_GRAD_CHUNKS)
    written = (x.size * x.dtype.itemsize >= WRITTEN_GRAD_MIN_BYTES
               and trips > 1 and ctx.mesh is None and ctx.gspmd_mesh is None)
    ctx.report("loss_backward_total", form="written" if written else "fused")
    if not written:
        dx = _loss_grad_rows(x, lse, lab, g)
        return {"Logits@GRAD": [dx.reshape(logits.shape)]}
    rows = x.shape[0] // trips

    def chunk(i, buf):
        at = lambda a: jax.lax.dynamic_slice_in_dim(a, i * rows, rows, 0)
        return jax.lax.dynamic_update_slice_in_dim(
            buf, _loss_grad_rows(at(buf), at(lse), at(lab), at(g)),
            i * rows, 0)

    dx = jax.lax.fori_loop(0, trips, chunk, x)
    return {"Logits@GRAD": [dx.reshape(logits.shape)]}


@register("cross_entropy", nondiff_inputs=("Label",))
def cross_entropy(ctx, ins):
    jnp = _jnp()
    x, label = ins["X"][0], ins["Label"][0]
    if ctx.attr("soft_label", False):
        loss = -jnp.sum(label.astype(x.dtype) * jnp.log(x), axis=-1, keepdims=True)
    else:
        lab = label
        if lab.ndim == x.ndim and lab.shape[-1] == 1:
            lab = jnp.squeeze(lab, axis=-1)
        picked = jnp.take_along_axis(x, lab[..., None].astype("int32"), axis=-1)
        loss = -jnp.log(picked)
        ignore = ctx.attr("ignore_index", -100)
        if ignore >= 0:
            loss = jnp.where(lab[..., None] != ignore, loss, jnp.zeros_like(loss))
    return {"Y": [loss]}


@register("cross_entropy2", nondiff_inputs=("Label",))
def cross_entropy2(ctx, ins):
    """Reference cross_entropy2_op.cc: hard-label CE over probabilities,
    additionally emitting the matched probability MatchX (its grad kernel's
    saved value; XShape is the reference's reshape bookkeeping, not needed
    here)."""
    import jax
    jnp = _jnp()
    x, label = ins["X"][0], ins["Label"][0]
    lab = label
    if lab.ndim == x.ndim and lab.shape[-1] == 1:
        lab = jnp.squeeze(lab, axis=-1)
    ignore = ctx.attr("ignore_index", -100)
    li = lab[..., None]
    # rows are kept only when the label is both not-ignored AND in range:
    # out-of-range labels (e.g. a -1 ignore convention while ignore_index
    # stays at the -100 default) would otherwise be clipped by the gather to
    # the last class and silently train toward it
    keep = (li != ignore) & (li >= 0) & (li < x.shape[-1])
    safe = jnp.where(keep, li, 0).astype("int32")
    picked = jnp.take_along_axis(x, safe, axis=-1)
    loss = jnp.where(keep, -jnp.log(picked), jnp.zeros_like(picked))
    return {"Y": [loss], "MatchX": [jax.lax.stop_gradient(picked)]}


@register("sigmoid_cross_entropy_with_logits")
def sigmoid_ce(ctx, ins):
    jnp = _jnp()
    x, label = ins["X"][0], ins["Label"][0]
    # stable: max(x,0) - x*z + log(1+exp(-|x|))
    loss = jnp.maximum(x, 0) - x * label.astype(x.dtype) + jnp.log1p(
        jnp.exp(-jnp.abs(x)))
    ignore = ctx.attr("ignore_index", -100)
    if ignore >= 0:
        loss = jnp.where(label != ignore, loss, jnp.zeros_like(loss))
    if ctx.attr("normalize", False):
        n = jnp.maximum(jnp.sum((label != ignore).astype(x.dtype)), 1.0)
        loss = loss / n
    return {"Out": [loss]}


@register("mean")
def mean(ctx, ins):
    return {"Out": [_jnp().mean(ins["X"][0]).reshape((1,))]}


@register("huber_loss", nondiff_outputs=("Residual",))
def huber_loss(ctx, ins):
    import jax
    jnp = _jnp()
    x, y = ins["X"][0], ins["Y"][0]
    d = ctx.attr("delta", 1.0)
    r = y - x
    loss = jnp.where(jnp.abs(r) <= d, 0.5 * r * r, d * (jnp.abs(r) - 0.5 * d))
    return {"Out": [loss], "Residual": [jax.lax.stop_gradient(r)]}


@register("square_error_cost")
def square_error_cost(ctx, ins):
    x, y = ins["X"][0], ins["Y"][0]
    d = x - y
    return {"Out": [d * d]}


@register("smooth_l1_loss", nondiff_outputs=("Diff",))
def smooth_l1_loss(ctx, ins):
    import jax
    jnp = _jnp()
    x, y = ins["X"][0], ins["Y"][0]
    sigma = ctx.attr("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    if len(ins.get("InsideWeight", [])) and ins["InsideWeight"][0] is not None:
        d = d * ins["InsideWeight"][0]
    a = jnp.abs(d)
    loss = jnp.where(a < 1.0 / s2, 0.5 * d * d * s2, a - 0.5 / s2)
    if len(ins.get("OutsideWeight", [])) and ins["OutsideWeight"][0] is not None:
        loss = loss * ins["OutsideWeight"][0]
    loss = jnp.sum(loss.reshape(loss.shape[0], -1), axis=1, keepdims=True)
    return {"Out": [loss], "Diff": [jax.lax.stop_gradient(d)]}


@register("cos_sim")
def cos_sim(ctx, ins):
    jnp = _jnp()
    x, y = ins["X"][0], ins["Y"][0]
    xn = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True))
    out = jnp.sum(x * y, axis=-1, keepdims=True) / (xn * yn)
    return {"Out": [out], "XNorm": [xn], "YNorm": [yn]}


@register("l2_normalize")
def l2_normalize(ctx, ins):
    jnp = _jnp()
    x = ins["X"][0]
    axis = ctx.attr("axis", -1)
    eps = ctx.attr("epsilon", 1e-12)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


@register("p_norm")
def p_norm(ctx, ins):
    jnp = _jnp()
    x = ins["X"][0]
    p = ctx.attr("porder", 2.0)
    axis = ctx.attr("axis", -1)
    keepdim = ctx.attr("keepdim", False)
    out = jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=keepdim) ** (1.0 / p)
    return {"Out": [out]}


@register("log_loss")
def log_loss(ctx, ins):
    jnp = _jnp()
    p, label = ins["Predicted"][0], ins["Labels"][0]
    eps = ctx.attr("epsilon", 1e-4)
    loss = -label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps)
    return {"Loss": [loss]}
