"""Decoder-LM ops: RMSNorm, rotary embedding (whole or partial, plain or
YaRN-scaled), the SiLU-gated product, attention's per-head output gate, the
gated short convolution, and a dropless mixture-of-experts layer in four ops
(``moe_router`` -> ``moe_dispatch`` -> ``moe_expert_matmul`` x3 around
``swiglu`` -> ``moe_combine``) plus ``moe_bias_update`` for a router that
balances by a selection bias.

The expert layer never drops an assignment and has no capacity: the
tokens x top-k assignments are sorted by expert (one stable sort in
``moe_dispatch``), the experts run as grouped matmuls over the sorted rows
against stacked ``[experts, in, out]`` weights, and ``moe_combine`` sums each
token's k expert outputs. The router's weight of an assignment multiplies its
row of the gated product, before the down projection (``W (w h) = w (W h)``):
the combine is then a plain sum, its gradient needs no expert output, and
the ``[assignments, hidden]`` output of the last matmul is never kept for
the backward. The sorted buffer holds expert 0's rows first, then expert
1's, ..., with ``Count`` giving each expert's rows: an all-to-all over an
``ep`` axis can split it by expert range without sorting again. A layer that
holds a part of its experts (``moe_dispatch``'s ``first_expert``, stacked
weights for the held experts only) starts the sort at its first expert, so
its rows lead the buffer, and the grouped products stop after them: the
rows of experts held elsewhere stay zero through the layer and add nothing.
Such a layer may state a row budget (``moe_dispatch``'s ``rows``): the
buffers then hold that many sorted rows and not all tokens x top-k, and the
held experts' rows beyond it are dropped and counted (``Dropped``) -- the one
case in which an assignment is lost, and the layer says how often.

What moves rows is written so that forward and backward are both gathers
(``_movers``: a permutation's transpose is the inverse permutation, which
the sort already gave), never a scatter-add of wide rows -- but, in the
composed form, under a row budget, where the few rows kept are added to
their tokens. The direction that sums (``moe_combine``'s forward,
``moe_dispatch``'s registered grad lowering) is on a TPU one kernel, budget
or none, that reads the held experts' rows and nothing behind them
(``ops/pallas_moe_rows.py``, PR 50). The three matmuls
are separate ops so that what the backward needs (sorted rows, gate, up, the
weighted gated product) are Program variables: a grad op re-lowers its
forward under ``jax.vjp`` (core/registry.py), a grouped matmul's own output
is not needed for its gradients, and XLA drops the copy.

Kernel choice for the grouped matmul, from what the code can see: on a TPU
the megablox Pallas kernels that ship with JAX (``gmm`` forward and for the
rows' gradient, ``tgmm`` for the weights'); elsewhere ``jax.lax.ragged_dot``.
XLA's own TPU expansion of ``ragged_dot`` is a Mosaic kernel too, but it
discards the instruction's ``op_name`` metadata, so its time cannot be joined
to a Program op (PERF.md section 6, PR 26, has both timed on the chip).
"""
from __future__ import annotations

import functools

from ..core.registry import register, register_grad

# m, k, n tile of the megablox kernels (chip runs, PR 26: PERF.md section 6)
GMM_TILING = (512, 1024, 1024)


@register("rms_norm")
def rms_norm(ctx, ins):
    """y = x / sqrt(mean(x^2, last axis) + epsilon) * Scale (attr
    ``zero_centered``: ``* (1 + Scale)``), computed in float32 whatever x's
    dtype, returned in x's dtype. Given ``Gate`` (X's element count: X's
    shape, or ``[T, heads * D]`` beside ``X [T, heads, D]``) the result
    times ``silu(Gate)`` (attr ``gate_activation`` ``"sigmoid"``: times
    ``sigmoid(Gate)``), with no rounding between the norm and the gate:
    ``_gated_norm``."""
    import jax
    import jax.numpy as jnp
    x = ins["X"][0]
    scale, gate = (ins.get(s, [None])[0] for s in ("Scale", "Gate"))
    if gate is not None:
        return {"Y": [_gated_norm(ctx, x, gate, scale)]}
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + ctx.attr("epsilon", 1e-5))
    if scale is not None:
        scale = scale.astype(jnp.float32)
        y = y * (1.0 + scale if ctx.attr("zero_centered", False) else scale)
    return {"Y": [y.astype(x.dtype)]}


def _gated_norm(ctx, x, gate, scale, dy=None):
    """The gated ``rms_norm``'s one pass over its operands: forward, or
    (given the cotangent ``dy``) backward, which reads X, Gate, Scale and dy
    and returns (dX, dGate, dScale) in closed form. The Pallas kernels of
    ``ops/pallas_norm.py`` where ``pallas_mode.lowers_kernels`` says so and
    they take the shape, else the same expressions composed in
    ``jax.numpy``. Which it was is reported as
    ``rms_norm_gated_lowering_total``."""
    import jax.numpy as jnp
    from . import pallas_mode, pallas_norm
    dim = x.shape[-1]
    if gate.size != x.size:
        raise ValueError(f"rms_norm: Gate {gate.shape} has not X's "
                         f"{x.shape} element count")
    eps = float(ctx.attr("epsilon", 1e-5))
    act = ctx.attr("gate_activation", "silu")
    factor = (jnp.ones((dim,), jnp.float32) if scale is None
              else scale.astype(jnp.float32))
    if ctx.attr("zero_centered", False):
        factor = 1.0 + factor
    kernels = pallas_mode.lowers_kernels(
        ctx, ctx.attr("impl", "auto"),
        pallas_norm.supports(
            pallas_norm.wide_view(x.shape, gate.shape)[0], dim), "rms_norm",
        f"needs a Gate, a last axis of a multiple of {pallas_norm.LANES} "
        f"and rows that tile by 16; got X {x.shape}")
    ctx.report("rms_norm_gated_lowering_total",
               impl="pallas" if kernels else "composed",
               direction="forward" if dy is None else "backward",
               head_dim=dim, activation=act)
    if dy is None:
        if kernels:
            return pallas_norm.gated_norm(x, gate, factor, eps,
                                          pallas_mode.interpret(), act)
        return pallas_norm.forward(
            x.astype(jnp.float32), gate.reshape(x.shape).astype(jnp.float32),
            factor, eps, act).astype(x.dtype)
    if kernels:
        dx, dz, dscale = pallas_norm._bwd_call(
            x, gate, factor, dy.astype(x.dtype), eps, pallas_mode.interpret(),
            act)
    else:
        dx, dz, terms = pallas_norm.backward(
            x.astype(jnp.float32), gate.reshape(x.shape).astype(jnp.float32),
            factor, dy.astype(jnp.float32), eps, act)
        dx, dz = dx.astype(x.dtype), dz.astype(gate.dtype).reshape(gate.shape)
        dscale = jnp.sum(terms, axis=tuple(range(x.ndim - 1)))
    return dx, dz, None if scale is None else dscale.astype(scale.dtype)


@register_grad("rms_norm")
def rms_norm_grad(ctx, ins, generic):
    """Of the gated form: dX, dGate and dScale from X, Gate, Scale and
    ``Y@GRAD`` in closed form (``_gated_norm``); it keeps no residual
    (a row's ``rsqrt`` is one reduction again) and lowers no forward. An
    op without a ``Gate``, or a grad op without a cotangent, is
    ``generic``."""
    gate, dy = (ins.get(s, [None])[0] for s in ("Gate", "Y@GRAD"))
    if gate is None or dy is None:
        return generic()
    scale = ins.get("Scale", [None])[0]
    dx, dz, dscale = _gated_norm(ctx, ins["X"][0], gate, scale, dy)
    grads = {"X@GRAD": [dx], "Gate@GRAD": [dz]}
    if scale is not None:
        grads["Scale@GRAD"] = [dscale]
    return grads


def yarn_inv_freq(theta, dim, factor, original_max_position, beta_fast,
                  beta_slow):
    """YaRN's blended rotary frequencies ``[dim / 2]`` (Peng et al.,
    arXiv:2309.00071, as HF's ``_compute_yarn_parameters`` computes them):
    with ``base_i = theta^(-2i/dim)``, ``inv_freq_i = (1 - m_i) base_i /
    factor + m_i base_i`` where ``m_i = 1 - clip((i - low) / (high - low), 0,
    1)`` and ``low`` / ``high`` are the floor / ceil of ``dim ln(original_
    max_position / (n 2 pi)) / (2 ln theta)`` at ``n`` = ``beta_fast`` /
    ``beta_slow`` rotations, held to ``[0, dim - 1]``: the fast dimensions
    keep their frequency, the slow ones are interpolated by ``factor``.
    float64 numpy, from static numbers."""
    import math
    import numpy as np

    def correction(rotations):
        return dim * math.log(original_max_position / (
            rotations * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    base = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    keep = 1.0 - np.clip(
        (np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return base / factor * (1.0 - keep) + base * keep


#: widest head whose grad op lowers the kernel: at D=256 (`qwen3_next`) XLA
#: schedules the layer's weight-gradient products after the next layer's
#: backward when the cotangent's rotation is a kernel, and the step's
#: temporaries grow by 0.29 GB (PERF.md section 6, PR 42)
ROTARY_GRAD_KERNEL_MAX_DIM = 128


def _rotary_tables(ctx, S, D):
    """float32 ``cos``, ``sin [S, rotary_dim]`` and ``rotary_dim`` from the
    op's attrs: angle ``pos * inv_freq_i`` for both halves' element i, the
    sign of the rotate-half in ``sin``'s first half."""
    import jax.numpy as jnp
    rot = int(ctx.attr("rotary_dim", 0)) or D
    if rot > D or rot % 2:
        raise ValueError(f"rotary_embedding: rotary_dim={rot} of {D}")
    scaling = ctx.attr("scaling", "")
    if scaling == "yarn":
        inv_freq = jnp.asarray(yarn_inv_freq(
            float(ctx.attr("theta", 10000.0)), rot,
            float(ctx.attr("factor")), float(ctx.attr("original_max_position")),
            float(ctx.attr("beta_fast", 32.0)),
            float(ctx.attr("beta_slow", 1.0))), jnp.float32)
    elif scaling:
        raise NotImplementedError(f"rotary_embedding: scaling={scaling!r}")
    else:
        inv_freq = ctx.attr("theta", 10000.0) ** (
            -jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], axis=-1))         # [S, rot]
    sin = jnp.sin(jnp.concatenate([-ang, ang], axis=-1))        # signed
    if scaling:
        factor = float(ctx.attr("attention_factor", 1.0))
        cos, sin = cos * factor, sin * factor
    return cos, sin, rot


def _rotary_pass(ctx, x, backward):
    """The op's one pass over ``x [..., S, D]``, forward or (``backward``:
    x is the cotangent, sin's sign turned) the transpose of it; float32
    inside, one rounding to x's dtype. The Pallas kernel of
    ``ops/pallas_rope.py`` where ``pallas_mode.lowers_kernels`` says so and
    it takes the shape (``supports``; a grad op up to
    ``ROTARY_GRAD_KERNEL_MAX_DIM``), else the rotation composed in
    ``jax.numpy``. Which it was is reported as ``rotary_lowering_total``."""
    import jax.numpy as jnp
    from . import pallas_mode, pallas_rope
    S, D = x.shape[-2:]
    cos, sin, rot = _rotary_tables(ctx, S, D)
    if backward:
        sin = -sin
    fits = (pallas_rope.supports(S, D)
            and not (backward and D > ROTARY_GRAD_KERNEL_MAX_DIM))
    # under a mesh: each device's own batch rows, in an island (ctx.island)
    shards = ctx.data_shards(x.shape[0]) if x.ndim > 2 else 1
    kernel = pallas_mode.lowers_kernels(ctx, "auto", fits, shards=shards)
    ctx.report("rotary_lowering_total",
               direction="backward" if backward else "forward",
               form="kernel" if kernel else "composed",
               impl="pallas" if kernel else "composed",
               mesh="island" if kernel and shards > 1 else "none")
    if kernel:
        interpret = pallas_mode.interpret()
        return ctx.island(
            lambda x: pallas_rope.rotate(x, cos, sin, rot, interpret),
            (x,), (True,), shards)
    # a slice, a roll of its lanes by half, a concatenate for the tail: what
    # XLA fuses best of the forms of this expression (PERF.md section 6)
    xf = (x if rot == D else x[..., :rot]).astype(jnp.float32)
    out = (xf * cos + jnp.roll(xf, rot // 2, axis=-1) * sin).astype(x.dtype)
    return out if rot == D else jnp.concatenate([out, x[..., rot:]], axis=-1)


@register("rotary_embedding")
def rotary_embedding(ctx, ins):
    """Rotary position embedding in the rotate-half convention over
    ``X [..., S, D]``, positions 0..S-1 along axis -2:
    ``x * cos + concat(-x[D/2:], x[:D/2]) * sin`` with angle
    ``pos * theta^(-2i/D)`` for both halves' element i. float32 inside.

    Attr ``rotary_dim`` (0: all of D): only the first ``rotary_dim`` values
    of a row are rotated, among themselves (D stands for ``rotary_dim``
    above), and the rest pass through. Attr ``scaling="yarn"``: the
    frequencies are ``yarn_inv_freq``'s (attrs ``factor``,
    ``original_max_position``, ``beta_fast``, ``beta_slow``) and cos and sin
    are multiplied by ``attention_factor``. One pass over X:
    ``_rotary_pass``."""
    return {"Out": [_rotary_pass(ctx, ins["X"][0], backward=False)]}


@register_grad("rotary_embedding")
def rotary_embedding_grad(ctx, ins, generic):
    """dX. The op is linear in X and orthogonal a position, so its transpose
    is the same pass over the cotangent with the sign of sin turned: it
    reads ``Out@GRAD`` alone (not X, not Out) and lowers no forward. A grad
    op without a cotangent is ``generic``."""
    g = ins.get("Out@GRAD", [None])[0]
    if g is None:
        ctx.report("rotary_lowering_total", direction="backward",
                   form="generic")
        return generic()
    return {"X@GRAD": [_rotary_pass(ctx, g, backward=True)]}


def _latent_sizes(ctx):
    """(batch, seq, heads, nope, rope, v's head width, the q / k head's
    width as written, whether the rotary parts are rotated)."""
    B, S, h, d_n, d_r = (int(ctx.attr(k)) for k in (
        "batch", "seq", "heads", "nope_dim", "rope_dim"))
    return (B, S, h, d_n, d_r, int(ctx.attr("value_dim", 0)) or d_n + d_r,
            int(ctx.attr("head_dim", 0)) or d_n + d_r,
            bool(ctx.attr("rotate", True)))


def _rope_rows(ctx, x, backward):
    """The rotation over the last axis of ``x [B, S, ..., rope_dim]``
    (positions along axis 1), float32 in and out; ``backward``: its
    transpose, the sign of sin turned."""
    import jax.numpy as jnp
    S, r = x.shape[1], x.shape[-1]
    cos, sin, _ = _rotary_tables(ctx, S, r)
    shape = (1, S) + (1,) * (x.ndim - 3) + (r,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    return x * cos + jnp.roll(x, r // 2, axis=-1) * (-sin if backward else sin)


@register("latent_qkv")
def latent_qkv(ctx, ins):
    """Latent attention's q, k and v for ``fused_attention``, out of its
    three up-projections (attrs ``batch``, ``seq``, ``heads``, ``nope_dim``,
    ``rope_dim``, ``theta``; T = batch x seq): ``Q [T, heads x (nope +
    rope)]`` laid out ``[every head's q_n | every head's q_r]``, ``KV [T,
    heads x (nope + v)]`` laid out ``[every head's k_n | every head's v]``,
    ``KRope [T, rope]`` the one rotary key head. ``OutQ`` a head ``[q_n |
    RoPE(q_r)]``, ``OutK`` a head ``[k_n | RoPE(k_r)]`` with the one rotated
    key head in every head, each ``[batch, heads, seq, nope + rope]``,
    ``OutV`` the values ``[batch, heads, seq, v]``. Attr ``value_dim``
    (default nope + rope): v's head width. Attr ``rotate`` (default true):
    false leaves q_r and k_r as projected (Kimi Linear's ``mla_use_nope``:
    positions are the linear layers' business). Attr ``head_dim`` (default
    nope + rope): the q / k head's width as written, zero columns behind the
    rope part (whole lane tiles for the flash kernels; a zero column adds
    nothing to a score). Attr ``scaling="yarn"``: the rotation's frequencies
    are ``yarn_inv_freq``'s over the rotary head (attrs ``factor``,
    ``original_max_position``, ``beta_fast``, ``beta_slow``) and cos and sin
    are multiplied by ``attention_factor`` (``rotary_embedding``'s attrs,
    read by the same ``_rotary_tables``). Rotate-half, positions 0..seq-1,
    float32 inside the rotation only: the parts that are not rotated move in
    their own dtype."""
    import jax.numpy as jnp
    q, kv, k_r = ins["Q"][0], ins["KV"][0], ins["KRope"][0]
    B, S, h, d_n, d_r, d_v, d, rotate = _latent_sizes(ctx)
    if q.shape[-1] != h * (d_n + d_r) or kv.shape[-1] != h * (d_n + d_v) \
            or k_r.shape[-1] != d_r or d < d_n + d_r:
        raise ValueError(
            f"latent_qkv: Q {q.shape}, KV {kv.shape} and KRope {k_r.shape} "
            f"are not {h} heads of [{d_n} | {d_r}], [{d_n} | {d_v}] and one "
            f"of {d_r} (head_dim {d})")
    ctx.report("latent_qkv_lowering_total", rotated=int(rotate), heads=h,
               head_dim=d, value_dim=d_v,
               frequencies=ctx.attr("scaling", "") or "default")

    def heads_of(x, width):         # [T, h * width] -> [B, h, S, width]
        return x.reshape(B, S, h, width).transpose(0, 2, 1, 3)
    q_r, k_r = q[:, h * d_n:].reshape(B, S, h, d_r), k_r.reshape(B, S, d_r)
    if rotate:
        q_r = _rope_rows(ctx, q_r.astype(jnp.float32), False).astype(q.dtype)
        k_r = _rope_rows(ctx, k_r.astype(jnp.float32), False).astype(kv.dtype)
    pad = [jnp.zeros((B, h, S, d - d_n - d_r), q.dtype)] \
        if d > d_n + d_r else []
    out_q = jnp.concatenate([heads_of(q[:, :h * d_n], d_n),
                             q_r.transpose(0, 2, 1, 3)] + pad, axis=-1)
    out_k = jnp.concatenate(
        [heads_of(kv[:, :h * d_n], d_n),
         jnp.broadcast_to(k_r[:, None], (B, h, S, d_r))] + pad, axis=-1)
    return {"OutQ": [out_q], "OutK": [out_k],
            "OutV": [heads_of(kv[:, h * d_n:], d_v)]}


@register_grad("latent_qkv")
def latent_qkv_grad(ctx, ins, generic):
    """dQ, dKV and dKRope from the three cotangents alone: the op is linear,
    a cut, a rotation and a broadcast, so its transpose is the parts put
    back where they came from, the rotary parts turned back (the sign of
    sin; not under ``rotate=False``) and the one key head's gradient the sum
    over the heads of dK's rotary part (summed in float32, then turned back
    once). It reads no forward input's values and lowers no forward; a grad
    op that lacks a cotangent is ``generic``."""
    import jax.numpy as jnp
    dq, dk, dv = (ins.get(s + "@GRAD", [None])[0]
                  for s in ("OutQ", "OutK", "OutV"))
    if dq is None or dk is None or dv is None:
        return generic()
    B, S, h, d_n, d_r, _, _, rotate = _latent_sizes(ctx)
    rope = slice(d_n, d_n + d_r)

    def flat(x):                    # [B, h, S, width] -> [T, h * width]
        return x.transpose(0, 2, 1, 3).reshape(B * S, -1)
    dq_r = dq[..., rope].transpose(0, 2, 1, 3)
    dk_r = jnp.sum(dk[..., rope], axis=1, dtype=jnp.float32)
    if rotate:
        dq_r = _rope_rows(ctx, dq_r.astype(jnp.float32), True).astype(dq.dtype)
        dk_r = _rope_rows(ctx, dk_r, True)
    return {"Q@GRAD": [jnp.concatenate(
                [flat(dq[..., :d_n]), dq_r.reshape(B * S, h * d_r)], axis=-1)],
            "KV@GRAD": [jnp.concatenate([flat(dk[..., :d_n]), flat(dv)],
                                        axis=-1)],
            "KRope@GRAD": [dk_r.astype(dk.dtype).reshape(B * S, d_r)]}


def _hc_sizes(ctx):
    """(streams, Sinkhorn-Knopp iterations, eps, the clamp's two ends)."""
    return (int(ctx.attr("streams")), int(ctx.attr("iters")),
            float(ctx.attr("eps")), float(ctx.attr("clamp_min")),
            float(ctx.attr("clamp_max")))


def _hc_report(ctx, part: str, backward: bool, x=None) -> None:
    """``x``: the state a read side multiplies by Phi (``product`` says how:
    ``_phi_product``); the write side has no product."""
    ctx.report("hyper_connection_lowering_total", part=part,
               direction="backward" if backward else "forward",
               streams=int(ctx.attr("streams")), iters=int(ctx.attr("iters")),
               product="none" if x is None else
               "pieces" if _bf16_exact(x) else "highest")


def _hc_streams(x, n: int):
    """The ``n`` streams of ``x [T, n * C]``, float32 ``[T, C]`` each: whole
    lane tiles of the last axis where C is a multiple of 128."""
    import jax.numpy as jnp
    C = x.shape[-1] // n
    return [x[:, j * C:(j + 1) * C].astype(jnp.float32) for j in range(n)]


def _bf16_exact(x) -> bool:
    import jax.numpy as jnp
    return x.dtype == jnp.bfloat16


def bf16_pieces(a):
    """A float32 array as three bfloat16 arrays, largest first: each the
    rounding of what the ones before it left (a remainder is exact in
    float32). Three hold all 24 bits of a mantissa: they add up to ``a``
    bit for bit."""
    import jax.numpy as jnp
    out = []
    for _ in range(3):
        out.append(a.astype(jnp.bfloat16))
        a = a - out[-1].astype(jnp.float32)
    return out


def _phi_product(xf, phi, exact: bool):
    """``(X Phi)^T [c, T]`` from the float32 ``xf [T, k]`` and ``phi [k,
    c]``: a product at precision ``highest``, which on the MXU cuts both
    operands into three bfloat16 pieces and multiplies the six largest
    pairs, six passes over c of an array's 128 columns. ``exact``: xf holds
    a bfloat16 state. It is then its own first piece and has no other, so
    three pairs are left and they share their left operand: Phi's pieces
    side by side, 3 c columns, are ONE bfloat16 pass with float32
    accumulation that keeps every term ``highest`` keeps. Its gradient
    likewise: ``dPhi = X^T g`` with g's pieces side by side, and ``dX = g
    Phi^T``, both operands float32, with ``highest``'s own six pairs side by
    side on the contraction (6 c deep)."""
    import jax
    import jax.numpy as jnp
    f32, bf16, c = jnp.float32, jnp.bfloat16, phi.shape[1]
    if not exact:
        return jnp.einsum("tk,kc->ct", xf, phi,
                          precision=jax.lax.Precision.HIGHEST)

    def side_by_side(a):
        """bfloat16 ``[3 c, m]``: the pieces of the float32 ``a [c, m]``,
        one under the other."""
        return jnp.concatenate(bf16_pieces(a), axis=0)

    def repeated(a, order):
        """bfloat16 ``[len(order) c, m]``: the pieces ``order`` (0 the
        largest) of ``a``, one under the other, as a select over a
        broadcast: a concatenation that names a piece twice XLA lowers as
        a chain of small updates, one fusion an entry, and the step's
        memory-space assignment then loses what the layer gains (PERF.md
        section 6, PR 62)."""
        hi, mid, lo = (p.astype(f32) for p in bf16_pieces(a))
        which = jnp.asarray(order)[:, None, None]
        rows = jnp.where(which == 0, hi, jnp.where(which == 1, mid, lo))
        return rows.reshape(-1, a.shape[1]).astype(bf16)

    def groups(a):                  # a's groups of c rows added, last first
        return functools.reduce(jnp.add, jnp.split(a, a.shape[0] // c)[::-1])

    @jax.custom_vjp
    def product(xf, phi):
        return groups(jnp.einsum(
            "tk,ck->ct", xf.astype(bf16), side_by_side(phi.T),
            preferred_element_type=f32))

    def backward(kept, g):          # g [c, T]
        xf, phi = kept
        dphi = groups(jnp.einsum(
            "tk,ct->ck", xf.astype(bf16), side_by_side(g),
            preferred_element_type=f32))
        # highest's six pairs, the smallest first: (lo, hi) (hi, lo)
        # (mid, mid) (mid, hi) (hi, mid) (hi, hi)
        dxf = jnp.einsum(
            "ct,ck->tk", repeated(g, (2, 0, 1, 1, 0, 0)),
            repeated(phi.T, (0, 2, 1, 0, 1, 0)),
            preferred_element_type=f32)
        return dxf, dphi.T
    product.defvjp(lambda xf, phi: (product(xf, phi), (xf, phi)), backward)
    return product(xf, phi)


def hyper_connection_coefficients(x, phi, b, alpha, n: int, iters: int,
                                  eps: float, lo: float, hi: float):
    """The per-token coefficients of one hyper-connection, float32
    throughout, as ``[2 n + n^2, T]`` (tokens along the lanes: the twenty
    normalisations then run over whole registers): from the state ``x [T, n
    C]``, ``xbar = x / sqrt(mean(x^2) + eps)`` over all ``n C`` values,
    ``z = xbar Phi`` (the division after the product: one number a token
    scales the product's ``2 n + n^2`` and not the state's ``n C``, as the
    mHC report's own kernels order it; ``_phi_product``: by bfloat16 pieces
    under a bfloat16 state, at precision ``highest`` under another), rows
    ``[0, n)`` ``H_pre = sigmoid(alpha_0 z + b)``, rows ``[n, 2 n)`` ``H_post
    = 2 sigmoid(alpha_1 z + b)``, the others ``H_res`` row-major: ``M =
    exp(clip(alpha_2 z + b, lo, hi))``, then ``iters`` times ``M / (its
    rows' sums + eps)`` and ``M / (its columns' sums + eps)``
    (Sinkhorn-Knopp: ``H_res`` ends doubly stochastic to the iteration's
    error)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    xf = x.astype(f32)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1) + eps)      # [T]
    z = _phi_product(xf, phi.astype(f32), _bf16_exact(x)) * r[None, :]
    b, alpha = b.astype(f32)[:, None], alpha.astype(f32)
    pre = jax.nn.sigmoid(alpha[0] * z[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * z[2 * n:] + b[2 * n:], lo, hi))

    def normalise(m, _):            # [row, column, T]
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=0, keepdims=True) + eps), None
    # one loop, not its iterations written out: forty reductions over axes
    # of 4 unrolled were ~170 small fusions a grad op, and as elementwise
    # chains over the 16 entries XLA's fusion grew them past compiling
    m, _ = jax.lax.scan(normalise, m.reshape(n, n, -1), None, length=iters)
    return jnp.concatenate([pre, post, m.reshape(n * n, -1)], axis=0)


def _hc_read(x, pre, n: int):
    """``u = H_pre X``: ``pre [n, T]`` over the streams of ``x [T, n C]``,
    float32 ``[T, C]``."""
    return sum(pre[j][:, None] * xj for j, xj in enumerate(_hc_streams(x, n)))


def _hc_pre(ctx, x, phi, b, alpha):
    import jax.numpy as jnp
    n, iters, eps, lo, hi = _hc_sizes(ctx)
    coef = hyper_connection_coefficients(x, phi, b, alpha, n, iters, eps, lo,
                                         hi)
    return _hc_read(x, coef[:n], n).astype(x.dtype), coef.T


def _hc_grad_descs(op, grad_out_map):
    """A hyper-connection op's grad maker: the generic desc without the
    forward's outputs, which its grad lowering never reads, so that the
    Program says what the backward keeps: the op's inputs."""
    from ..core import registry
    descs = registry.generic_grad_op_descs(op, grad_out_map)
    for slot in op.outputs:
        del descs[0]["inputs"][slot]
    return descs


@register("hyper_connection_pre", grad=_hc_grad_descs)
def hyper_connection_pre(ctx, ins):
    """The read side of a manifold-constrained hyper-connection (DeepSeek-AI,
    mHC, arXiv:2512.24880, over Hyper-Connections, Zhu et al.,
    arXiv:2409.19606). ``X [T, n C]``: a token's residual state of ``n`` =
    attr ``streams`` streams of width C side by side (``vec(X)``; stream j
    the columns ``[j C, (j + 1) C)``); ``Phi [n C, 2 n + n^2]``, ``B [2 n +
    n^2]``, ``Alpha [3]`` float32. ``Coef [T, 2 n + n^2]`` float32 are the
    token's ``[H_pre | H_post | H_res row-major]``
    (``hyper_connection_coefficients``; attrs ``iters``, ``eps``,
    ``clamp_min``, ``clamp_max``) and ``U [T, C] = H_pre X`` the branch's
    input, in X's dtype. float32 inside; composed ``jax.numpy``."""
    x, phi, b, alpha = (ins[s][0] for s in ("X", "Phi", "B", "Alpha"))
    _hc_report(ctx, "pre", backward=False, x=x)
    u, coef = _hc_pre(ctx, x, phi, b, alpha)
    return {"U": [u], "Coef": [coef]}


@register_grad("hyper_connection_pre")
def hyper_connection_pre_grad(ctx, ins, generic):
    """dX, dPhi, dB and dAlpha from X, the three parameters and the two
    cotangents: the norm, the product, the exponentials and the iterations
    are computed again (the mHC report's recipe: a kept float32 ``xbar`` is
    twice X) and differentiated in place; neither ``U`` nor ``Coef`` is
    read. A cotangent that is absent (a ``Coef`` no write side read) is
    zero."""
    import jax
    import jax.numpy as jnp
    x, phi, b, alpha = (ins[s][0] for s in ("X", "Phi", "B", "Alpha"))
    _hc_report(ctx, "pre", backward=True, x=x)
    (u, coef), pullback = jax.vjp(
        lambda *a: _hc_pre(ctx, *a), x, phi, b, alpha)
    du, dcoef = (ins.get(s + "@GRAD", [None])[0] for s in ("U", "Coef"))
    dx, dphi, db, dalpha = pullback((
        jnp.zeros_like(u) if du is None else du.astype(u.dtype),
        jnp.zeros_like(coef) if dcoef is None else dcoef.astype(coef.dtype)))
    return {"X@GRAD": [dx], "Phi@GRAD": [dphi], "B@GRAD": [db],
            "Alpha@GRAD": [dalpha]}


@register("hyper_connection_post", grad=_hc_grad_descs)
def hyper_connection_post(ctx, ins):
    """The write side of a hyper-connection: ``Out = H_res X + H_post^T Y``,
    a stream ``Out_i = sum_j H_res[i, j] X_j + H_post[i] Y``, from ``X [T, n
    C]``, the branch's output ``Y [T, C]`` and ``Coef [T, 2 n + n^2]``
    (``hyper_connection_pre``'s); attrs ``streams`` and, for the counter,
    ``iters``. float32 inside, X's dtype out."""
    import jax.numpy as jnp
    _hc_report(ctx, "post", backward=False)
    x, y, coef = (ins[s][0] for s in ("X", "Y", "Coef"))
    n = int(ctx.attr("streams"))
    xs, yf, coef = _hc_streams(x, n), y.astype(jnp.float32), coef.T
    post, res = coef[n:2 * n], coef[2 * n:]
    return {"Out": [jnp.concatenate([
        (post[i][:, None] * yf + sum(
            res[i * n + j][:, None] * xs[j] for j in range(n))).astype(x.dtype)
        for i in range(n)], axis=-1)]}


@register_grad("hyper_connection_post")
def hyper_connection_post_grad(ctx, ins, generic):
    """dX, dY and dCoef in closed form from X, Y, Coef and ``Out@GRAD``
    (``Out`` is not read): ``dX_j = sum_i H_res[i, j] g_i``, ``dY = sum_i
    H_post[i] g_i``, ``dH_res[i, j] = g_i . X_j``, ``dH_post[i] = g_i . Y``
    over the channels, nothing to ``H_pre``'s columns; float32 sums."""
    import jax.numpy as jnp
    g = ins.get("Out@GRAD", [None])[0]
    if g is None:
        return generic()
    _hc_report(ctx, "post", backward=True)
    x, y, coef = (ins[s][0] for s in ("X", "Y", "Coef"))
    n = int(ctx.attr("streams"))
    xs, gs, yf = _hc_streams(x, n), _hc_streams(g, n), y.astype(jnp.float32)
    post, res = coef.T[n:2 * n], coef.T[2 * n:]
    dx = jnp.concatenate([
        sum(res[i * n + j][:, None] * gs[i] for i in range(n)).astype(x.dtype)
        for j in range(n)], axis=-1)
    dy = sum(post[i][:, None] * gs[i] for i in range(n)).astype(y.dtype)
    dcoef = jnp.stack(
        [jnp.zeros(x.shape[:1], jnp.float32)] * n
        + [jnp.sum(gs[i] * yf, axis=-1) for i in range(n)]
        + [jnp.sum(gs[i] * xs[j], axis=-1)
           for i in range(n) for j in range(n)], axis=-1)
    return {"X@GRAD": [dx], "Y@GRAD": [dy],
            "Coef@GRAD": [dcoef.astype(coef.dtype)]}


@register("swiglu")
def swiglu(ctx, ins):
    """silu(X) * Y, the gated product of a gated feed-forward layer; with
    ``Scale [rows]`` each row of it times its scale (an expert layer's router
    weights). float32 inside."""
    import jax
    import jax.numpy as jnp
    g, u = ins["X"][0], ins["Y"][0]
    gf = g.astype(jnp.float32)
    out = gf * jax.nn.sigmoid(gf) * u.astype(jnp.float32)
    scale = ins.get("Scale", [None])[0]
    if scale is not None:
        out = out * scale.astype(jnp.float32)[:, None]
    return {"Out": [out.astype(g.dtype)]}


@register("attention_gate")
def attention_gate(ctx, ins):
    """Attention's output gate: ``X [B, heads, S, D]``, the heads' outputs
    as ``fused_attention`` leaves them, times ``sigmoid(Gate)``: ``Gate [B *
    S, heads]`` one gate a token and head (the gate's projection is by
    token: the small array is the one turned), ``Gate [B * S, heads * D]``
    one a token, head and channel. float32 inside. Gating the
    kernels' layout keeps the op one pass over X: on the token-major
    ``[B * S, heads * D]`` XLA joined it with the transpose before it and
    copied float32 arrays of X's size (7.3% of the Laguna step against
    PERF.md section 6, PR 39)."""
    import jax
    import jax.numpy as jnp
    x, gate = ins["X"][0], ins["Gate"][0]
    B, heads, S, D = x.shape
    g = jax.nn.sigmoid(gate.astype(jnp.float32))
    if gate.shape[-1] == heads * D:
        g = g.reshape(B, S, heads, D).transpose(0, 2, 1, 3)
    else:
        g = g.reshape(B, S, heads).transpose(0, 2, 1)[..., None]
    out = x.astype(jnp.float32) * g
    return {"Out": [out.astype(x.dtype)]}


@register("exit_gate_loss")
def exit_gate_loss(ctx, ins):
    """The exit gate of a looped model and the expected loss over its exit
    distribution (Ouro / LoopLM, arXiv:2510.25741, the stage-I objective),
    float32 inside. ``X [steps * T, H]``: the state after each of ``steps``
    passes, pass-major; ``W [H, 1]``, ``B [1]``: one gate shared by the
    passes; ``CE [steps * T, 1]``: every position's cross-entropy under
    each pass's logits. A position ``i``: ``lambda_r = sigmoid(w . x_r +
    b)``; ``p_1 = lambda_1``, ``p_r = lambda_r prod_{j<r} (1 - lambda_j)``,
    ``p_steps = prod_{j<steps} (1 - lambda_j)`` (the last pass takes what is
    left: its own gate is not read); outputs ``P [steps * T, 1]``,
    ``ExpectedCE [1] = mean_i sum_r p_r ce_r`` and ``Loss [1] = mean_i
    [sum_r p_r ce_r - entropy_coef H(p_i)]``. The products of ``1 -
    lambda`` are sums of ``log sigmoid(-z)`` and the entropy reads ``log p``
    from the same sums, so a saturated gate gives 0 and no NaN; the gate's
    product is a float32 multiply and row sum, not a matrix product (which
    a TPU would round to bfloat16 passes). Gradients flow to X, W, B and
    CE."""
    import jax
    import jax.numpy as jnp
    x, w, b, ce = (ins[s][0] for s in ("X", "W", "B", "CE"))
    steps = int(ctx.attr("steps"))
    f32 = jnp.float32
    z = (jnp.sum(x.astype(f32) * w.astype(f32).reshape(1, -1), axis=-1)
         + b.astype(f32).reshape(())).reshape(steps, -1)
    log_stay = jax.nn.log_sigmoid(-z)                   # log(1 - lambda)
    before = jnp.cumsum(log_stay, axis=0) - log_stay    # log prod_{j<r}
    log_p = jnp.concatenate(
        [before[:-1] + jax.nn.log_sigmoid(z[:-1]), before[-1:]], axis=0)
    prob = jnp.exp(log_p)
    expected = jnp.sum(prob * ce.astype(f32).reshape(steps, -1), axis=0)
    # p log p -> 0 as p -> 0: a -inf of log_p never meets its 0
    entropy = -jnp.sum(jnp.where(prob > 0, prob * log_p, 0.0), axis=0)
    loss = jnp.mean(expected - float(ctx.attr("entropy_coef", 0.0)) * entropy)
    return {"Loss": [loss.reshape(1)],
            "ExpectedCE": [jnp.mean(expected).reshape(1)],
            "P": [prob.reshape(-1, 1)]}


@register("moe_router", nondiff_inputs=("Bias",), nondiff_outputs=("Index",))
def moe_router(ctx, ins):
    """Router of a mixture-of-experts layer, in float32 throughout (the
    product too: at default precision a TPU multiplies float32 in bfloat16
    passes). ``X [T, H]`` any float dtype, ``W [H, E]`` ->
    ``Prob [T, E]`` = softmax(X W), ``Weight`` / ``Index [T, k]`` its k
    largest entries as they are -- under attr ``norm_topk`` over their sum
    (no epsilon: a softmax's k largest do not vanish), times attr ``scale``
    --, ``LogZ [T]`` = logsumexp(X W) for the router z-loss. ``Index``
    carries no gradient. Attr ``scoring="sigmoid"``: ``_sigmoid_routing``."""
    import jax
    import jax.numpy as jnp
    x, w = ins["X"][0], ins["W"][0]
    logits = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if ctx.attr("scoring", "softmax") == "sigmoid":
        return _sigmoid_routing(ctx, logits, ins.get("Bias", [None])[0])
    logz = jax.nn.logsumexp(logits, axis=-1)
    prob = jnp.exp(logits - logz[:, None])
    weight, index = jax.lax.top_k(prob, int(ctx.attr("k")))
    if ctx.attr("norm_topk", False):
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    if float(ctx.attr("scale", 1.0)) != 1.0:
        weight = weight * float(ctx.attr("scale"))
    return {"Weight": [weight], "Index": [index.astype(jnp.int32)],
            "Prob": [prob], "LogZ": [logz]}


def _sigmoid_routing(ctx, logits, bias):
    """``scoring="sigmoid"``: each expert's score is sigmoid(logit) by
    itself; the k experts are chosen by score + ``Bias [E]`` (a balancing
    state, no gradient through it or through the choice) and weighed by the
    score without the bias, divided by the chosen scores' sum + 1e-6 under
    ``norm_topk``, times ``scale``. ``Prob`` is the scores; no ``LogZ``."""
    import jax
    import jax.numpy as jnp
    score = jax.nn.sigmoid(logits)
    chosen_by = score if bias is None else score + bias.astype(jnp.float32)
    _, index = jax.lax.top_k(jax.lax.stop_gradient(chosen_by),
                             int(ctx.attr("k")))
    weight = jnp.take_along_axis(score, index, axis=-1)
    if ctx.attr("norm_topk", False):
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6)
    weight = weight * float(ctx.attr("scale", 1.0))
    return {"Weight": [weight], "Index": [index.astype(jnp.int32)],
            "Prob": [score]}


def _moved(fwd_rows, bwd_rows):
    """A row movement whose transpose is another row movement: ``f(x, order,
    slot, bounds, live)`` with integer ``order [A]`` (the flat assignment
    held by each sorted row), ``slot [T, k]`` (the sorted row of each
    assignment), ``bounds [G + 1]`` (the first sorted row of each group the
    layer holds and the end of the last) and ``live`` (that end, under
    ``held``: sorted rows from it on are padding; None where every row of
    the buffer is some held expert's)."""
    import jax

    @jax.custom_vjp
    def f(x, order, slot, bounds, live):
        return fwd_rows(x, order, slot, bounds, live)

    def fwd(x, order, slot, bounds, live):
        return fwd_rows(x, order, slot, bounds, live), (order, slot, bounds,
                                                        live)

    def bwd(res, g):        # every movement keeps its argument's dtype
        return bwd_rows(g, *res), None, None, None, None

    f.defvjp(fwd, bwd)
    return f


def _gather_tokens(x, order, slot, bounds, live):       # [T, ...] -> [A, ...]
    # the padding's rows too (from ``live`` on): zeroing them is a second
    # pass over the buffer that XLA does not fuse into the gather (0.23 M
    # cycles an op at [5120, 3072], 2.4 M at [65536, 2048]; PR 50)
    return x[order // slot.shape[1]]


def _sum_slots(g, order, slot, bounds=None, live=None):  # [A, H] -> [T, H]
    import jax.numpy as jnp
    rows = g[slot]
    if live is not None:
        rows = jnp.where((slot < live)[..., None], rows,
                         jnp.zeros((), g.dtype))
    return jnp.sum(rows, axis=1, dtype=jnp.float32).astype(g.dtype)


def _add_rows(g, order, slot, bounds=None, live=None):
    # [R, H] -> [T, H], R rows kept by a row budget: the rows kept are far
    # fewer than the tokens' k slots, so each is added to its token (a gather
    # of [T, k] rows, nearly all of them the fill, cost 2 ms a layer at 4096
    # x 10 x 3072; chip runs, PR 39)
    import jax.numpy as jnp
    kept = g.astype(jnp.float32)
    if live is not None:
        kept = jnp.where(jnp.arange(g.shape[0])[:, None] < live, kept, 0.0)
    return jnp.zeros((slot.shape[0], g.shape[1]), jnp.float32).at[
        order // slot.shape[1]].add(kept).astype(g.dtype)


def _kernel_sums(interpret, g, order, slot, bounds, live):
    # [R, H] -> [T, H], budget or none: ops/pallas_moe_rows.py
    from . import pallas_moe_rows
    return pallas_moe_rows.token_sums(g, slot, bounds, interpret)


def _gather_assignments(w, order, slot, bounds, live):  # [T, k] -> [A]
    return w.reshape(-1)[order]


def _gather_slots(g, order, slot, bounds, live):        # [A] -> [T, k]
    return g[slot]


def _gather_kept_slots(g, order, slot, bounds, live):
    # [R] -> [T, k], R rows kept by a row budget: an assignment whose row
    # was not kept reads zero
    return g.at[slot].get(mode="fill", fill_value=0)


def _sums(budgeted: bool, kernel):
    """Sorted rows -> token sums: the kernel of ``ops/pallas_moe_rows.py``,
    budget or none (``kernel``: the call's ``interpret`` flag), or (None)
    the composed form, additions of the kept rows under a row budget and a
    sum over each token's gathered slots without one."""
    if kernel is not None:
        return functools.partial(_kernel_sums, kernel)
    return _add_rows if budgeted else _sum_slots


@functools.lru_cache(maxsize=None)
def _movers(budgeted: bool = False, kernel=None):
    """(token rows -> sorted rows, sorted rows -> token sums, router weights
    -> sorted weights): each one's transpose is another of these movements.
    ``budgeted`` (the ops' ``rows`` attr): the sorted side holds the rows a
    budget kept, and a slot without a row reads zero; ``kernel``:
    ``_sums``."""
    sums = _sums(budgeted, kernel)
    return (_moved(_gather_tokens, sums), _moved(sums, _gather_tokens),
            _moved(_gather_assignments,
                   _gather_kept_slots if budgeted else _gather_slots))


def _bounds(count):
    """``[G + 1]`` int32: the first sorted row of each of the groups whose
    rows ``count [G]`` gives, and the end of the last."""
    import jax.numpy as jnp
    ends = jnp.cumsum(count)
    return jnp.concatenate([jnp.zeros((1,), ends.dtype),
                            ends]).astype(jnp.int32)


def _held_rows(ctx, count):
    """(``bounds``, ``live``) of the movers from ``count [E]``, the rows of
    each group of the sorted buffer in its order (``GroupCount``, which a
    row budget has cut already): under attr ``held`` the bounds of the held
    groups and the end of their rows, the padding's start; without it every
    group's bounds and no padding (None)."""
    held = int(ctx.attr("held", 0))
    bounds = _bounds(count[:held] if held else count)
    return bounds, (bounds[-1] if held else None)


def _sums_kernel(ctx, op, rows, slot, count, shards=1):
    """How the token sums over the sorted ``rows [R, H]`` lower in the op
    that computes them (``op``: combine / dispatch_grad): the ``interpret``
    flag of the kernel's call where ``pallas_mode.lowers_kernels`` says so,
    the kernel takes the shapes and the op was given the groups' counts,
    else None (``_sums``); reported as ``moe_rows_lowering_total``.
    ``shards`` > 1: the sums run inside the exchange's island, each device
    over its own tokens and the sorted rows that came back to it (``rows``
    and ``slot`` are then one device's, as shapes)."""
    from . import pallas_mode, pallas_moe_rows
    kernel = pallas_mode.lowers_kernels(
        ctx, "auto", count is not None and pallas_moe_rows.supports(
            slot.shape[0], rows.shape[0], rows.shape[1], rows.dtype),
        shards=shards)
    ctx.report("moe_rows_lowering_total",
               impl="pallas" if kernel else "composed", op=op,
               bound="held" if int(ctx.attr("held", 0)) else "all",
               mesh="island" if kernel and shards > 1 else "none")
    return pallas_mode.interpret() if kernel else None


def sort_by_expert(index, n_experts: int, first: int = 0):
    """The stable sort of the ``index [T, k]`` assignments by expert,
    counted from expert ``first`` on and wrapping around: ``order [T * k]``
    the flat assignment of each sorted row, ``slot [T, k]`` the sorted row
    of each assignment, ``count [n_experts]`` the rows of each expert in
    the sorted order."""
    import jax.numpy as jnp
    flat = index.reshape(-1).astype(jnp.int32)
    if first:
        flat = (flat - first) % n_experts
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    rows = jnp.arange(flat.shape[0], dtype=jnp.int32)
    slot = jnp.zeros_like(rows).at[order].set(
        rows, unique_indices=True).reshape(index.shape)
    count = jnp.sum(flat[:, None] == jnp.arange(n_experts, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    return order, slot, count


def _exchange_shards(ctx, tokens: int) -> int:
    """The devices an expert layer's exchange crosses here: those of the
    mesh axis attr ``expert_axis`` names, where the op is lowered under a
    GSPMD mesh on which that axis has several devices that divide the
    tokens and the experts; else 1 (no such attr, one device, shape
    inference, inside another island), and the op is the layer without an
    exchange."""
    axis = ctx.attr("expert_axis", "")
    return ctx.axis_shards(axis, tokens, int(ctx.attr("num_experts", 0))
                           or tokens) if axis else 1


def _exchange_scope(ctx, grad: bool, way: str):
    """The trace scope of one crossing: ``moe_exchange.<way>#<op>`` in a
    forward op, ``moe_exchange_grad.<way>#<op>`` in a backward (``way``: out,
    the rows to their experts, or back)."""
    import jax
    return jax.named_scope(
        f"moe_exchange{'_grad' if grad else ''}.{way}#{ctx.op_idx or 0}")


def _exchange(ctx, n: int, tokens: int, k: int):
    """(``axis``, ``n``, rows a device's receive buffer holds, the wire) of
    the exchange of an op over ``tokens`` tokens (all devices') choosing
    ``k`` experts each: attr ``recv_rows``, a device's row budget, or
    without one every assignment of every device (nothing can be dropped)."""
    from . import collective
    budget = int(ctx.attr("recv_rows", 0)) or tokens * k
    return ctx.attr("expert_axis"), n, budget, collective.exchange_impl()


def _under_grad(ctx) -> bool:
    """Whether the lowering is a grad op's: its own registered one (only a
    grad op's desc carries the forward's first output's name), or the
    forward a generic grad op lowers again under ``jax.vjp``."""
    return ctx.under_grad or bool(ctx.attr("__fwd_out0__", ""))


def _sums_kernel_here(ctx, op, n: int, order, slot, width: int, dtype, cnt):
    """``_sums_kernel`` for the token sums inside an exchange's island: one
    device's ``order.shape[0] / n`` sorted rows of ``width`` for its ``slot
    .shape[0] / n`` tokens; returns the rows' shape beside the answer."""
    import jax
    import jax.numpy as jnp
    here = jax.ShapeDtypeStruct((order.shape[0] // n, width), dtype)
    return here, _sums_kernel(
        ctx, op, here, jax.ShapeDtypeStruct(
            (slot.shape[0] // n, slot.shape[1]), jnp.int32), cnt, n)


def _report_exchange(ctx, plan, rows: int, way: str, crossing: str) -> None:
    """One crossing compiled (``crossing``: dispatch / combine and their
    ``_grad``s, four a layer and step): the wire it took, and the rows of a
    device's ``rows`` assignments that leave it when the router is even
    (``moe_exchange_even_rows``: a constant of the shapes)."""
    axis, n, _, impl = plan
    ctx.report("moe_exchange_lowering_total", axis=axis, impl=impl,
               crossing=crossing)
    ctx.report("moe_exchange_even_rows", rows * (n - 1) // n, direction=way)


def _rows_kernel(ctx, plan, width: int, dtype, way: str):
    """How the rows an op's crossing of the exchange ``plan`` receives
    change order (``RowExchange.by_expert`` / ``by_source``; ``way``: out /
    back), for rows ``width`` wide: the ``interpret`` flag of the kernel of
    ``ops/pallas_exchange_rows.py`` where ``pallas_mode.lowers_kernels``
    says so for the exchange's island and the kernel takes the buffer, else
    None (an index a row and a gather); reported as
    ``moe_exchange_rows_lowering_total``."""
    from . import pallas_exchange_rows, pallas_mode
    _, n, budget, _ = plan
    kernel = pallas_mode.lowers_kernels(
        ctx, "auto", pallas_exchange_rows.supports(budget, width, dtype),
        shards=n)
    ctx.report("moe_exchange_rows_lowering_total",
               impl="pallas" if kernel else "composed",
               mesh="island" if kernel else "none", way=way)
    return pallas_mode.interpret() if kernel else None


def _exchange_movers(ctx, plan, k: int, sums_interpret, grad: bool,
                     rows_kernel=None):
    """The three movements of ``_movers`` across the exchange ``plan``
    (``_exchange``), for use inside its island: token rows ``[T, H]`` ->
    the rows the held experts received ``[budget, H]``, those rows -> token
    sums, router weights ``[T, k]`` -> the received rows' weights; each
    ``f(x, order, slot, cnt)`` with the device's own sort (``order``,
    ``slot``) and every device's counts ``cnt [n, E]``, and each one's
    transpose another of them. ``sums_interpret``: ``_sums_kernel``'s
    answer for the token sums over the rows that came back;
    ``rows_kernel``: ``_rows_kernel``'s for the received rows' change of
    order. ``grad``: the op being lowered is a backward's (the scopes'
    names)."""
    import jax
    from .collective import RowExchange
    sums = _sums(False, sums_interpret)

    def crossing(cnt):
        return RowExchange(cnt, *plan, kernel=rows_kernel)

    def mine(cnt):          # the bounds of my own sorted buffer's groups
        return _bounds(cnt[jax.lax.axis_index(plan[0])])

    def rows_out(x, order, slot, cnt, back: bool):
        with _exchange_scope(ctx, grad or back, "out"):
            return crossing(cnt).out(lambda at: x[order[at] // k],
                                     order.shape[0])

    def rows_back(y, order, slot, cnt, back: bool):
        with _exchange_scope(ctx, grad or back, "back"):
            home = crossing(cnt).back(y, order.shape[0])
        return sums(home, order, slot, mine(cnt), None)

    def weights_out(w, order, slot, cnt, back: bool):
        with _exchange_scope(ctx, grad or back, "out"):
            return crossing(cnt).out(lambda at: w.reshape(-1)[order[at]],
                                     order.shape[0])

    def weights_back(g, order, slot, cnt, back: bool):
        with _exchange_scope(ctx, grad or back, "back"):
            return crossing(cnt).back(g, order.shape[0])[slot]

    def moved(fwd_rows, bwd_rows):
        @jax.custom_vjp
        def f(x, order, slot, cnt):
            return fwd_rows(x, order, slot, cnt, False)

        def fwd(x, order, slot, cnt):
            return fwd_rows(x, order, slot, cnt, False), (order, slot, cnt)

        def bwd(res, g):
            return bwd_rows(g, *res, True), None, None, None

        f.defvjp(fwd, bwd)
        return f
    return (moved(rows_out, rows_back), moved(rows_back, rows_out),
            moved(weights_out, weights_back), weights_back)


def _dispatch_exchange(ctx, x, index, weight, n: int):
    """``moe_dispatch`` under attr ``expert_axis`` on a mesh whose axis of
    that name has ``n`` > 1 devices: an island over the axis in which each
    device sorts its own tokens' assignments over all the experts, sends
    each row and its weight to the device that holds its expert (device c
    holds experts ``[c E / n, (c + 1) E / n)``) and lays what it received
    out expert by expert. What the outputs then are is in ``moe_dispatch``'s
    docstring."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from .collective import RowExchange
    E, k = int(ctx.attr("num_experts")), index.shape[1]
    plan = axis, _, budget, _ = _exchange(ctx, n, x.shape[0], k)
    ctx.report("moe_row_budget", budget * n)
    _report_exchange(ctx, plan, x.shape[0] // n * k, "out", "dispatch")
    to_owners, _, weights_to_owners, _ = _exchange_movers(
        ctx, plan, k, None, False,
        _rows_kernel(ctx, plan, x.shape[1], x.dtype, "out"))

    def local(x, index, weight):
        order, slot, count = sort_by_expert(index, E)
        cnt = jax.lax.all_gather(count, axis)
        crossing = RowExchange(cnt, *plan)
        return (to_owners(x, order, slot, cnt),
                weights_to_owners(weight, order, slot, cnt), order, slot,
                jax.lax.psum(count, axis), crossing.group, cnt,
                jax.lax.psum(crossing.dropped, axis).reshape(1)
                .astype(jnp.int32))

    cut, whole = P(axis), P()
    out, row_weight, order, slot, load, group, cnt, dropped = jax.shard_map(
        local, mesh=ctx.gspmd_mesh, in_specs=(cut, cut, cut),
        out_specs=(cut, cut, cut, cut, whole, cut, whole, whole),
        check_vma=False)(x, index, weight)
    return {"Out": [out], "RowWeight": [row_weight], "Order": [order],
            "Slot": [slot], "Count": [load], "GroupCount": [group],
            "SendCount": [cnt], "Dropped": [dropped]}


@register("moe_dispatch", nondiff_inputs=("Index",),
          nondiff_outputs=("Order", "Slot", "Count", "GroupCount",
                           "Dropped", "SendCount"))
def moe_dispatch(ctx, ins):
    """Sort the T x k assignments by expert (stable: within an expert, by
    token) and bring each one's token row and router weight into place.
    ``X [T, H]``, ``Index`` / ``Weight [T, k]`` -> ``Out [T*k, H]`` (expert
    0's rows first) and ``RowWeight [T*k]``, ``Order [T*k]`` the flat
    assignment (token * k + choice) of each sorted row, ``Slot [T, k]`` the
    sorted row of each assignment, ``Count [E]`` the rows of each expert.
    Every assignment has a row: nothing is dropped whatever the routing.

    Attr ``first_expert`` (default 0) is the first expert this layer holds
    of the ``num_experts`` routed over (``layers.moe_ffn``'s
    ``experts_held``): the sort then starts at that expert and wraps
    around, so the held experts' rows are the first of the buffer whatever
    the range, and ``GroupCount [E]`` counts the rows in the sorted order
    (expert ``first_expert`` first) while ``Count`` stays by expert id. The
    buffers keep all T*k rows -- the worst case, every assignment local --
    and the grouped products stop after the held experts' rows.

    Attr ``held`` (0: all): the experts held here. The sorted rows from the
    held experts' end on (``live`` = the sum of ``GroupCount``'s first
    ``held`` entries, at most the buffer) are *padding*: no held expert
    multiplies them. ``Out`` holds their tokens' rows there as everywhere
    (zeroing them would be a second pass over the buffer), but as
    constants: ``Out = where(row < live, X[token], stop_gradient(X[token]))``.
    So the gradient ignores the cotangent's rows there -- ``X@GRAD`` sums,
    for each token, the cotangent's rows of its slots below ``live`` -- and
    nothing past ``live`` is read in the backward. ``RowWeight`` is the
    router's weight on every row.

    Attr ``rows`` (0: none), with ``held``: a row
    budget. ``Out``, ``RowWeight`` and ``Order`` keep the first ``rows``
    sorted rows only (the held experts' lead the buffer), ``Slot`` still
    names every assignment's sorted row, those from ``rows`` up being rows
    no buffer has (they add nothing in ``moe_combine``), ``GroupCount`` is
    cut so that it sums to ``rows``, and ``Dropped [1]`` int32 counts the
    held experts' rows beyond the budget, which this step lost.

    Attr ``expert_axis`` (with all the experts held, no ``rows``): the
    experts are split over the mesh axis of that name, device c of its n
    holding experts ``[c E / n, (c + 1) E / n)``, and the tokens are laid
    over the same axis. Lowered under a mesh with n > 1 such devices
    dividing tokens and experts, the op is an island over the axis
    (``_dispatch_exchange``): each device sorts its own tokens' assignments
    and sends every row, and its weight, to the device that holds its
    expert. ``Out [n * R, H]`` and ``RowWeight [n * R]`` are then the rows
    each device *received*, laid over the axis, expert by expert within a
    device, R = attr ``recv_rows`` (a device's receive buffer; 0: every
    assignment of every device, so that nothing can overflow) and the rows
    behind a device's live ones padding; ``Order`` / ``Slot`` each device's
    own sort of its own tokens; ``Count`` the assignments of each expert
    over all the devices; ``GroupCount [E]`` the rows each expert received
    after the budget's cuts (device c's part its experts'); ``SendCount [n,
    E]`` every device's count by expert, from which ``moe_combine`` and the
    grad ops rebuild the crossing (``collective.RowExchange``); ``Dropped
    [1]`` the rows the receive buffers lost, all devices. The wire is
    ``collective.exchange_impl()``. Where the axis has one device (or there
    is no mesh) nothing crosses: the outputs are the layer's without the
    attr, ``SendCount`` = ``Count [1, E]``, ``Dropped`` zero."""
    import jax.numpy as jnp
    x, index, weight = ins["X"][0], ins["Index"][0], ins["Weight"][0]
    n_experts = int(ctx.attr("num_experts"))
    shards = _exchange_shards(ctx, x.shape[0])
    if shards > 1:
        return _dispatch_exchange(ctx, x, index, weight, shards)
    first = int(ctx.attr("first_expert", 0))
    order, slot, count = sort_by_expert(index, n_experts, first)
    budget = int(ctx.attr("rows", 0))
    ctx.report("moe_row_budget", budget or index.size)
    outs = {"Slot": [slot], "GroupCount": [count],
            "Count": [jnp.roll(count, first) if first else count]}
    if ctx.attr("expert_axis", ""):     # one device on that axis: no wire
        ctx.report("moe_exchange_lowering_total",
                   axis=ctx.attr("expert_axis"), impl="none",
                   crossing="dispatch")
        outs.update(SendCount=[count[None]],
                    Dropped=[jnp.zeros((1,), jnp.int32)])
    if budget:
        if budget > index.size:
            raise ValueError(f"moe_dispatch: a budget of {budget} rows for "
                             f"{index.size} assignments")
        order = order[:budget]
        ends = jnp.minimum(jnp.cumsum(count), budget)
        held_rows = jnp.sum(count[:int(ctx.attr("held"))])
        outs.update(
            GroupCount=[jnp.diff(ends, prepend=0).astype(jnp.int32)],
            Dropped=[jnp.maximum(held_rows - budget, 0).reshape(1)
                     .astype(jnp.int32)])
    # the registered grad lowering chooses how the transpose's sums lower;
    # what differentiates this lowering directly takes the composed form
    to_rows, _, to_row_weights = _movers(bool(budget))
    where = _held_rows(ctx, outs["GroupCount"][0])
    return {"Out": [to_rows(x, order, slot, *where)],
            "RowWeight": [to_row_weights(weight, order, slot, *where)],
            "Order": [order], **outs}


@register_grad("moe_dispatch")
def moe_dispatch_grad(ctx, ins, generic):
    """``X@GRAD``: each token's sum of ``Out@GRAD``'s rows at its slots
    (below ``live`` under ``held``: the vjp of what ``moe_dispatch``
    documents) -- the token sums ``moe_combine`` computes, on the same
    kernel; ``Weight@GRAD``: ``RowWeight@GRAD`` gathered back to ``[T,
    k]``. It reads the forward op's ``Order`` / ``Slot`` / ``GroupCount``
    and sorts nothing. A grad op without both cotangents, or a desc from
    before the op had its sort as outputs, is ``generic``."""
    g, gw = (ins.get(s, [None])[0] for s in ("Out@GRAD", "RowWeight@GRAD"))
    order, slot = (ins.get(s, [None])[0] for s in ("Order", "Slot"))
    count = ins.get("GroupCount", ins.get("Count", [None]))[0]
    if any(v is None for v in (g, gw, order, slot, count)):
        return generic()
    shards = _exchange_shards(ctx, ins["X"][0].shape[0])
    if shards > 1:
        return _dispatch_grad_exchange(ctx, ins, g, gw, order, slot, shards)
    budgeted = bool(int(ctx.attr("rows", 0)))
    sums = _sums(budgeted, _sums_kernel(ctx, "dispatch_grad", g, slot, count))
    to_slots = _gather_kept_slots if budgeted else _gather_slots
    return {"X@GRAD": [sums(g.astype(ins["X"][0].dtype), order, slot,
                            *_held_rows(ctx, count))],
            "Weight@GRAD": [to_slots(gw.astype(ins["Weight"][0].dtype),
                                     order, slot, None, None)]}


def _dispatch_grad_exchange(ctx, ins, g, gw, order, slot, n: int):
    """``moe_dispatch_grad`` across the exchange: the cotangent's rows (and
    the weights') go back to the devices their tokens are on, and each
    device sums its tokens' rows with the forward's own sort."""
    import jax
    from jax.sharding import PartitionSpec as P
    x, weight, cnt = ins["X"][0], ins["Weight"][0], ins["SendCount"][0]
    k = weight.shape[1]
    plan = _exchange(ctx, n, x.shape[0], k)
    axis = plan[0]
    here, interpret = _sums_kernel_here(ctx, "dispatch_grad", n, order, slot,
                                        g.shape[1], x.dtype, cnt)
    _report_exchange(ctx, plan, here.shape[0], "back", "dispatch_grad")
    _, to_tokens, _, weights_back = _exchange_movers(
        ctx, plan, k, interpret, True,
        _rows_kernel(ctx, plan, g.shape[1], x.dtype, "back"))

    def local(g, gw, order, slot, cnt):
        return (to_tokens(g, order, slot, cnt),
                weights_back(gw, order, slot, cnt, True))

    cut = P(axis)
    dx, dw = jax.shard_map(
        local, mesh=ctx.gspmd_mesh, in_specs=(cut, cut, cut, cut, P()),
        out_specs=(cut, cut), check_vma=False)(
            g.astype(x.dtype), gw.astype(weight.dtype), order, slot, cnt)
    return {"X@GRAD": [dx], "Weight@GRAD": [dw]}


def grouped_matmul(x, w, count, kernels: bool, tiling=None):
    """``x [A, K]`` rows sorted by group, ``w [G, K, N]``, ``count`` rows a
    group (summing to A) -> ``[A, N]`` in x's dtype: row a times the
    weight of its group, accumulated in float32. With weights for the first
    G of ``count``'s groups only (an expert layer that holds a part of its
    experts), the later groups' rows are not computed and come out zero, in
    the product and in both gradients: megablox's kernels (``kernels``)
    visit the groups they have weights for and zero the rest,
    ``ragged_dot`` leaves rows beyond its group sizes zero. ``tiling``: the
    kernels' (m, k, n) tile in ``GMM_TILING``'s place."""
    import jax
    if kernels:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        tiling = tuple(min(int(t), d) for t, d in
                       zip(tiling or GMM_TILING,
                           (x.shape[0], x.shape[1], w.shape[2])))
        return megablox.gmm(x, w, count, x.dtype, tiling)
    if w.shape[0] < count.shape[0]:
        count = count[:w.shape[0]]
    return jax.lax.ragged_dot(x, w, count)


@register("moe_expert_matmul", nondiff_inputs=("Count",))
def moe_expert_matmul(ctx, ins):
    """One of an expert layer's products over the sorted rows: ``X [A, K]``,
    stacked ``W [E, K, N]``, ``Count [E]`` -> ``Out [A, N]``; with ``W``
    stacking the first G < E groups only, the rows after theirs are zero
    (``grouped_matmul``): megablox's kernels where
    ``pallas_mode.lowers_kernels`` says so (they take every shape, but are
    not run in the test harness' interpreter), ``ragged_dot`` elsewhere.

    Attr ``expert_axis`` (``layers.moe_ffn``): the experts, and the rows
    ``moe_dispatch`` brought them, are split over the mesh axis of that
    name; under a mesh with several devices on it the product runs in an
    island over the axis (``ctx.island``), each device over its own experts
    and the rows it received, ``W``'s split being the one the parameter
    declares. Attr ``tiling`` (``layers.moe_ffn``'s ``matmul_tiling``): the
    kernels' tile. Which lowering an op took:
    ``moe_expert_matmul_lowering_total``."""
    from . import pallas_mode
    x, w, count = ins["X"][0], ins["W"][0], ins["Count"][0]
    axis = ctx.attr("expert_axis", "")
    shards = ctx.axis_shards(axis, x.shape[0], w.shape[0]) if axis else 1
    kernels = pallas_mode.lowers_kernels(
        ctx, "auto", not pallas_mode.interpret(), shards=shards)
    ctx.report("moe_expert_matmul_lowering_total",
               impl="pallas" if kernels else "composed",
               mesh="island" if shards > 1 else "none")

    def held(x, w, count):
        # the rows behind the held experts' are one more group, without
        # weights: not computed, zero (grouped_matmul)
        import jax.numpy as jnp
        if shards > 1:
            count = jnp.concatenate(
                [count, (x.shape[0] - jnp.sum(count)).reshape(1)
                 .astype(count.dtype)])
        return grouped_matmul(x, w, count, kernels, ctx.attr("tiling", None))
    return {"Out": [ctx.island(held, (x, w, count), (True, True, True),
                               shards, axis or None)]}


def _combine_exchange(ctx, x, order, slot, cnt, n: int):
    """``moe_combine`` across the exchange: the experts' results go back to
    the devices their tokens are on (``RowExchange.back``), and each sums
    its tokens' rows with its own sort, on the kernel where it runs."""
    import jax
    from jax.sharding import PartitionSpec as P
    k = slot.shape[1]
    plan = _exchange(ctx, n, slot.shape[0], k)
    here, interpret = _sums_kernel_here(ctx, "combine", n, order, slot,
                                        x.shape[1], x.dtype, cnt)
    grad = _under_grad(ctx)     # the generic grad: the transpose goes out
    way = "out" if grad else "back"
    _report_exchange(ctx, plan, here.shape[0], way,
                     "combine_grad" if grad else "combine")
    _, to_tokens, _, _ = _exchange_movers(
        ctx, plan, k, interpret, grad,
        _rows_kernel(ctx, plan, x.shape[1], x.dtype, way))
    cut = P(plan[0])
    return {"Out": [jax.shard_map(
        to_tokens, mesh=ctx.gspmd_mesh, in_specs=(cut, cut, cut, P()),
        out_specs=cut, check_vma=False)(x, order, slot, cnt)]}


@register("moe_combine", nondiff_inputs=("Order", "Slot", "GroupCount",
                                         "SendCount"))
def moe_combine(ctx, ins):
    """Each token's output: the sum of its k assignments' rows (already
    weighted, see ``swiglu``'s ``Scale``). ``X [T*k, H]`` sorted rows,
    ``Order`` / ``Slot`` from ``moe_dispatch`` -> ``Out [T, H]`` in X's
    dtype, summed in float32. Attr ``rows`` (``moe_dispatch``'s row budget;
    0: none): ``X`` has that many rows, each added to its token, and an
    assignment whose row was not kept adds nothing.

    ``GroupCount`` (``moe_dispatch``'s, optional, no gradient) and attr
    ``held`` (0: all) say where the held experts' rows end (``live``, as in
    ``moe_dispatch``): the sum is over a token's slots *below* ``live``,
    and the rows from there on are padding that is not read. ``X@GRAD`` is
    the cotangent's row of each sorted row's token; in the padding, where
    the vjp is zero, it holds that row all the same and is padding in its
    turn (the grouped products' grad ops read the held groups' rows only;
    zeroing it would be a second pass). Given ``GroupCount`` the sums lower
    as the kernel of ``ops/pallas_moe_rows.py`` where it runs, budget or
    none (``moe_rows_lowering_total``).

    Attr ``expert_axis`` with the input ``SendCount`` (``moe_dispatch``'s):
    where ``moe_dispatch`` crossed the exchange, ``X`` is the rows each
    device's experts received, laid over the axis; they go back to the
    devices their tokens are on and are summed there
    (``_combine_exchange``)."""
    x, order, slot = ins["X"][0], ins["Order"][0], ins["Slot"][0]
    shards = _exchange_shards(ctx, slot.shape[0])
    if shards > 1:
        return _combine_exchange(ctx, x, order, slot, ins["SendCount"][0],
                                 shards)
    count = ins.get("GroupCount", [None])[0]
    if count is None and int(ctx.attr("held", 0)):
        raise ValueError("moe_combine: attr held needs the input GroupCount")
    _, to_tokens, _ = _movers(bool(int(ctx.attr("rows", 0))), _sums_kernel(
        ctx, "combine", x, slot, count))
    where = (None, None) if count is None else _held_rows(ctx, count)
    return {"Out": [to_tokens(x, order, slot, *where)]}


@register("moe_bias_update", grad=None)
def moe_bias_update(ctx, ins):
    """Auxiliary-loss-free load balancing (Wang et al., arXiv:2408.15664):
    ``BiasOut = Bias + rate * sign(mean(Load) - Load)`` over ``Load [E]``,
    this step's assignments by expert. The router's selection bias is a
    state variable of the training program that no optimizer owns; the op
    runs after the backward (``models/decoder_lm.py:balance_experts``), so
    every op of a step reads the bias the step began with."""
    import jax.numpy as jnp
    bias, load = ins["Bias"][0], ins["Load"][0].astype(jnp.float32)
    step = float(ctx.attr("rate")) * jnp.sign(jnp.mean(load) - load)
    return {"BiasOut": [bias + step.astype(bias.dtype)]}


@register("short_conv")
def short_conv(ctx, ins):
    """The short causal convolution between a hybrid decoder layer's two
    projections, ``W [C, L]`` one depthwise filter of length L a channel:
    ``conv(z)[t] = sum_j W[:, j] * z[t - (L-1) + j]``. Gated (attr ``gated``,
    the default; LFM2): ``X [T, 3C]`` holds ``B | C | u`` side by side (the
    input projection's output) and ``Out [T, C] = C * conv(B * u)``. Not
    gated (a Mamba mixer): ``X [T, C]`` and ``Out = conv(X)``. Either way
    ``Bias [C]``, where given, is added to the filter's output and attr
    ``activation`` (``"silu"`` or none) applied to that, before the gate
    ``C``. The T rows are sequences of ``seq`` (attr) consecutive positions:
    positions before a sequence's start count as zero, nothing crosses from
    one sequence into the next. float32 inside.

    Attr ``impl``: ``auto`` (default) lowers the Pallas kernels of
    ``ops/pallas_short_conv.py`` where they can run (a TPU, or the test
    harness' interpreter) and take the shapes, else the composed form below;
    ``pallas`` / ``composed`` force one. The kernels are 8 to 13 times
    faster than XLA's fusion of the composed form on a v5e (timed at
    ``[16384, 3 x 2048]``: PERF.md section 6, PR 32)."""
    import jax
    import jax.numpy as jnp
    from . import pallas_mode, pallas_short_conv
    x, w = ins["X"][0], ins["W"][0]
    bias = ins.get("Bias", [None])[0]
    gated, act = bool(ctx.attr("gated", True)), ctx.attr("activation", "")
    if act not in ("", "silu"):
        raise ValueError(f"short_conv: activation {act!r} (only 'silu')")
    seq, (rows, wide) = int(ctx.attr("seq")), x.shape
    chan, taps = wide // 3 if gated else wide, w.shape[1]
    impl = ctx.attr("impl", "auto")
    kernels = pallas_mode.lowers_kernels(
        ctx, impl, pallas_short_conv.supports(seq, chan, taps,
                                              bias is not None),
        "short_conv",
        f"needs channels % {pallas_short_conv.BLK_C} == 0, seq % 16 == 0 "
        f"and at most {pallas_short_conv.MAX_SEQ}; got seq={seq}, "
        f"channels={chan}, taps={taps}")
    ctx.report("short_conv_lowering_total",
               impl="pallas" if kernels else "composed",
               form="gated" if gated else "plain", activation=act or "none",
               taps=taps)
    if kernels:
        return {"Out": [pallas_short_conv.short_conv(
            x, w, seq, pallas_mode.interpret(), bias, gated, act)]}
    xf = x.astype(jnp.float32)
    z = (xf[:, :chan] * xf[:, 2 * chan:] if gated else xf).reshape(
        rows // seq, seq, chan)
    wf = w.astype(jnp.float32)
    conv = z * wf[:, taps - 1]
    for back in range(1, taps):             # z[t - back], zeros before t=0
        past = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :seq]
        conv = conv + past * wf[:, taps - 1 - back]
    conv = conv.reshape(rows, chan)
    if bias is not None:
        conv = conv + bias.astype(jnp.float32)
    if act:
        conv = jax.nn.silu(conv)
    if gated:
        conv = xf[:, chan:2 * chan] * conv
    return {"Out": [conv.astype(x.dtype)]}


def composed_ssd_scan(x, dt, a, bm, cm, d, chunk):
    """The chunked scan in plain ``jax.numpy`` (Dao & Gu, arXiv:2405.21060,
    listing 1), float32 throughout: per chunk the ``[Q, Q]`` decay block of
    every head, the chunk's state, the recurrence over the chunks'
    states, and the entering state's part of the output. Shapes as the op's;
    ``[batch, chunks, Q, Q, heads]`` arrays exist whole, which is what the
    kernels are for."""
    import jax
    import jax.numpy as jnp
    batch, seq, heads, p = x.shape
    n, c = bm.shape[-1], seq // chunk
    f32 = jnp.float32
    xf = x.astype(f32)
    dt = dt.astype(f32)
    xd = (xf * dt[..., None]).reshape(batch, c, chunk, heads, p)
    bm = bm.astype(f32).reshape(batch, c, chunk, n)
    cm = cm.astype(f32).reshape(batch, c, chunk, n)
    cum = jnp.cumsum((dt * a.astype(f32)).reshape(batch, c, chunk, heads),
                     axis=2)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None]
    decay = jnp.exp(jnp.where(
        lower, cum[:, :, :, None, :] - cum[:, :, None, :, :], -jnp.inf))
    y = jnp.einsum("bcin,bcjn,bcijh,bcjhp->bcihp", cm, bm, decay, xd)
    end = cum[:, :, -1:, :]
    made = jnp.einsum("bcjn,bcjh,bcjhp->bchnp", bm, jnp.exp(end - cum), xd)

    def carry(h, inp):                  # h: the state entering the chunk
        made_c, keep_c = inp
        return keep_c[..., None, None] * h + made_c, h

    _, entering = jax.lax.scan(
        carry, jnp.zeros((batch, heads, n, p), f32),
        (made.transpose(1, 0, 2, 3, 4),
         jnp.exp(end[:, :, 0]).transpose(1, 0, 2)))
    y = y + jnp.einsum("bcin,cbhnp,bcih->bcihp", cm, entering, jnp.exp(cum))
    y = y.reshape(x.shape) + xf * d.astype(f32)[:, None]
    return y.astype(x.dtype)


@register("ssd_scan")
def ssd_scan(ctx, ins):
    """The state-space scan of a Mamba-2 layer (Dao & Gu, arXiv:2405.21060),
    a head at a time with state ``h [N, P]``: ``h_t = exp(dt_t A) h_{t-1} +
    B_t (x) (dt_t x_t)``, ``y_t = C_t h_t + D x_t``, the state zero before each
    sequence's start. ``X [B, S, heads, P]``, ``Dt [B, S, heads]`` (positive:
    after its softplus), ``A [heads]`` (negative), ``B`` / ``C [B, S, N]``
    (one group: shared by the heads), ``D [heads]`` -> ``Y`` like ``X``.
    Computed in chunks of ``chunk`` (attr; the sequence where that is
    shorter) positions, which equals the recurrence in exact arithmetic; the
    decay, its running sums, the exps and the state in float32.

    Attr ``impl``: ``auto`` (default) lowers the Pallas kernels of
    ``ops/pallas_ssd.py`` where they can run (a TPU, or the test harness'
    interpreter) and take the shapes, else ``composed_ssd_scan``; ``pallas``
    / ``composed`` force one. Which one an op took is counted at each compile
    (``ssd_lowering_total``; observability/lowerings.py)."""
    from . import pallas_mode, pallas_ssd
    x, dt, a, bm, cm, d = (ins[k][0] for k in ("X", "Dt", "A", "B", "C", "D"))
    _, seq, heads, p = x.shape
    n = bm.shape[-1]
    chunk = min(int(ctx.attr("chunk", 256)), seq)
    if seq % chunk:
        raise ValueError(f"ssd_scan: chunk {chunk} must divide seq {seq}")
    impl = ctx.attr("impl", "auto")
    kernels = pallas_mode.lowers_kernels(
        ctx, impl, pallas_ssd.supports(seq, heads, p, n, chunk), "ssd_scan",
        f"needs heads of {pallas_ssd.HEAD_DIM}, heads % "
        f"{pallas_ssd.HEAD_BLOCK} == 0, state % 128 == 0 and chunk % 128 == "
        f"0; got heads={heads} of {p}, state={n}, chunk={chunk}")
    ctx.report("ssd_lowering_total",
               impl="pallas" if kernels else "composed", chunk=chunk,
               heads=heads, state=n)
    if kernels:
        return {"Y": [pallas_ssd.ssd_scan(x, dt, a, bm, cm, d, chunk,
                                          pallas_mode.interpret())]}
    return {"Y": [composed_ssd_scan(x, dt, a, bm, cm, d, chunk)]}


def _chunk_sums(g, chunk):
    """The running sum of ``g [B, S, heads]`` (or ``[B, S, heads, d_k]``)
    inside each chunk of ``chunk`` positions, float32."""
    import jax.numpy as jnp
    b, s = g.shape[:2]
    return jnp.cumsum(
        g.astype(jnp.float32).reshape(b, s // chunk, chunk, *g.shape[2:]),
        axis=2).reshape(g.shape)


def _delta_operands(q, k, g, chunk, dtype):
    """What the composed chunk form reads beside ``v`` and ``beta``: q and k
    each over its l2 norm (``pallas_delta.unit``, float32), q also over
    ``sqrt(key dim)``, cast to ``dtype``, and the running sum of ``g`` inside
    each chunk. The kernels form the same unit q and k in VMEM."""
    import jax.numpy as jnp
    from . import pallas_delta
    f32 = jnp.float32
    return (pallas_delta.unit(q.astype(f32), q.shape[-1] ** -0.5)
            .astype(dtype),
            pallas_delta.unit(k.astype(f32)).astype(dtype),
            _chunk_sums(g, chunk))


def composed_gated_delta_rule(qn, kn, v, cum, beta, chunk):
    """The chunk form of the gated delta rule in plain ``jax.numpy`` (HF's
    ``torch_chunk_gated_delta_rule``; ``ops/pallas_delta.py`` has the
    algebra), float32 throughout: ``qn`` / ``kn [B, S, key heads, d_k]`` as
    ``_delta_operands`` leaves them, ``v [B, S, heads, d_v]``, ``cum`` /
    ``beta [B, S, heads]`` -> ``o`` like ``v`` (float32) and the state
    entering each chunk ``[B, chunks, heads, d_k, d_v]``. The ``[C, C]``
    decay block and the triangular inverse of every batch, chunk and head
    exist whole, which is what the kernels are for."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular
    f32 = jnp.float32
    batch, seq, heads, dv = v.shape
    rep, dk, c = heads // qn.shape[2], qn.shape[-1], seq // chunk

    def chunks_first(x, repeat=1):      # [B, S, h, ...] -> [c, B, h, C, ...]
        x = jnp.repeat(x.astype(f32), repeat, axis=2) if repeat > 1 \
            else x.astype(f32)
        x = x.reshape(batch, c, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)
    qn, kn = chunks_first(qn, rep), chunks_first(kn, rep)   # [c, B, h, C, dk]
    v, cum, beta = chunks_first(v), chunks_first(cum), chunks_first(beta)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    d = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                          -jnp.inf))                        # [c, B, h, C, C]
    kk = jnp.einsum("cbhik,cbhjk->cbhij", kn, kn)
    m = jnp.where(jnp.tril(lower, -1), kk * d, 0.0) * beta[..., None]
    eye = jnp.eye(chunk, dtype=f32)
    t = solve_triangular(eye + m, jnp.broadcast_to(eye, m.shape), lower=True,
                         unit_diagonal=True)
    p = jnp.einsum("cbhik,cbhjk->cbhij", qn, kn) * d
    eg = jnp.exp(cum)[..., None]
    end = cum[..., -1:, None]
    kf = kn * jnp.exp(end - cum[..., None])

    def one(s, inp):                    # s [B, h, dk, dv] enters the chunk
        qn_c, kn_c, v_c, t_c, p_c, eg_c, kf_c, beta_c, e_end = inp
        z = v_c - eg_c * jnp.einsum("bhik,bhkv->bhiv", kn_c, s)
        vp = jnp.einsum("bhij,bhjv->bhiv", t_c, beta_c[..., None] * z)
        o = eg_c * jnp.einsum("bhik,bhkv->bhiv", qn_c, s) + jnp.einsum(
            "bhij,bhjv->bhiv", p_c, vp)
        return e_end * s + jnp.einsum("bhik,bhiv->bhkv", kf_c, vp), (o, s)

    _, (o, states) = jax.lax.scan(
        one, jnp.zeros((batch, heads, dk, dv), f32),
        (qn, kn, v, t, p, eg, kf, beta, jnp.exp(end)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)           # [B, c, C, h, dv]
    return o.reshape(batch, seq, heads, dv), jnp.moveaxis(states, 0, 1)


def composed_channel_delta_rule(qn, kn, v, cum, beta, chunk):
    """``composed_gated_delta_rule`` under a decay a key channel (``cum [B,
    S, heads, d_k]``; one value head a key head): ``pallas_delta.
    channel_chunk``, the kernels' own arithmetic a chunk, mapped over batch
    and head and scanned over the chunks. No exponent of a positive number
    is taken (the decayed ``[C, C]`` blocks are built by halving, ``log2 C``
    levels of one ``exp`` pass over ``[C, d_k]`` and one product each), so
    the result stays finite however steep the decay, and nothing wider than
    a chunk's ``[C, d_k]`` and ``[C, C]`` arrays exists a batch and head."""
    import jax
    import jax.numpy as jnp
    from . import pallas_delta
    f32 = jnp.float32
    batch, seq, heads, dv = v.shape
    dk, c = qn.shape[-1], seq // chunk
    if chunk & (chunk - 1):
        raise ValueError(
            f"gated_delta_rule: under a decay a key channel the chunk must "
            f"be a power of two (the halving's levels, the inverse's "
            f"merges); got {chunk}")

    def chunks_first(x):                # [B, S, h, ...] -> [c, B, h, C, ...]
        x = x.reshape(batch, c, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)
    a_chunk = jax.vmap(jax.vmap(pallas_delta.channel_chunk))

    def one(s, inp):
        o, s_next = a_chunk(*inp, s)
        return s_next, (o, s)
    _, (o, states) = jax.lax.scan(
        one, jnp.zeros((batch, heads, dk, dv), f32),
        tuple(chunks_first(x) for x in (qn, kn, v, cum.astype(f32),
                                        beta.astype(f32))))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)           # [B, c, C, h, dv]
    return o.reshape(batch, seq, heads, dv), jnp.moveaxis(states, 0, 1)


def _delta_inputs(ctx, ins):
    """(q, k [B, S, key heads, d_k], v [B, S, heads, d_v], g, beta, the
    packed ``q | k | v [B, S, 2 keys + values]`` or None): an op given
    ``QKV`` (attrs ``key_heads``, ``key_dim``; the value heads are ``G``'s)
    reads q, k and v as column ranges of it."""
    g, beta = ins["G"][0], ins["Beta"][0]
    if "QKV" not in ins:
        return ins["Q"][0], ins["K"][0], ins["V"][0], g, beta, None
    qkv = ins["QKV"][0]
    n_k, d_k = int(ctx.attr("key_heads")), int(ctx.attr("key_dim"))
    (b, s, wide), heads, keys = qkv.shape, g.shape[2], n_k * d_k
    d_v, rest = divmod(wide - 2 * keys, heads)
    if rest or d_v <= 0:
        raise ValueError(
            f"gated_delta_rule: a packed q | k | v of {wide} columns is not "
            f"2 x {n_k} key heads of {d_k} and {heads} value heads")
    return (qkv[..., :keys].reshape(b, s, n_k, d_k),
            qkv[..., keys:2 * keys].reshape(b, s, n_k, d_k),
            qkv[..., 2 * keys:].reshape(b, s, heads, d_v), g, beta, qkv)


def _delta_plan(ctx, q, k, v, qkv, g):
    """(what the kernels read of q, k and v where the op lowers them here,
    else None -- the packed array itself where they can address it in
    place, else three flat operands --, the op's chunk): the forward op and
    its grad op ask alike. ``g``'s rank says which decay: a value head's
    scalar, or (``[B, S, heads, d_k]``) a key channel's."""
    from . import pallas_delta, pallas_mode
    _, seq, heads, dv = v.shape
    key_heads, dk = q.shape[2], q.shape[3]
    if heads % key_heads or k.shape != q.shape:
        raise ValueError(
            f"gated_delta_rule: {heads} value heads over {key_heads} key "
            f"heads (q {q.shape}, k {k.shape}); the value heads must be a "
            f"multiple of the key heads")
    channel = g.ndim == 4
    if channel and (g.shape[3] != dk or heads != key_heads):
        raise ValueError(
            f"gated_delta_rule: a decay a key channel (G {g.shape}) needs "
            f"G's last axis to be the key dim {dk} and one value head a key "
            f"head; got {heads} over {key_heads}")
    chunk = min(int(ctx.attr("chunk", 64)), seq)
    if seq % chunk:
        raise ValueError(
            f"gated_delta_rule: chunk {chunk} must divide seq {seq}")
    impl = ctx.attr("impl", "auto")
    if not pallas_mode.lowers_kernels(
            ctx, impl,
            pallas_delta.supports(seq, key_heads, heads, dk, dv, chunk,
                                  channel),
            "gated_delta_rule",
            f"needs key and value heads of {pallas_delta.HEAD_DIM} and a "
            f"chunk of {pallas_delta.CHUNKS} that divides seq; got heads of "
            f"{dk} / {dv}, chunk={chunk}, seq={seq}"):
        return None, chunk
    if qkv is not None and pallas_delta.packs(key_heads, heads):
        return qkv, chunk
    return (_flat(q), _flat(k), _flat(v)), chunk


def _flat(x):           # [B, S, heads, d] -> [B, S, heads * d], as projected
    return x.reshape(*x.shape[:2], -1)


@register("gated_delta_rule", nondiff_outputs=("States",))
def gated_delta_rule(ctx, ins):
    """The gated delta rule of a Gated DeltaNet layer (Yang et al.,
    arXiv:2412.06464; HF's ``torch_recurrent_gated_delta_rule``), a value
    head at a time with state ``S [d_k, d_v]``, zero before each sequence's
    start: ``S' = exp(g_t) S_{t-1}``, ``u_t = beta_t (v_t - S'^T k_t)``, ``S_t
    = S' + k_t u_t^T``, ``o_t = S_t^T q_t``, with ``k_t = K_t / sqrt(sum(K_t^2)
    + 1e-6)`` and ``q_t`` likewise over ``sqrt(d_k)``. ``Q`` / ``K [B, S, key
    heads, d_k]``, ``V [B, S, heads, d_v]`` (value head j reads key head ``j
    // (heads / key heads)``), or the three as one ``QKV [B, S, 2 keys +
    values]`` (q | k | v along the columns, as a projection and a short
    convolution write them; attrs ``key_heads``, ``key_dim``), ``G`` (<= 0)
    and ``Beta [B, S, heads]`` -> ``Out [B, S, heads, d_v]``. ``G [B, S,
    heads, d_k]`` is a decay a key channel (Kimi Delta Attention,
    arXiv:2510.26692; one value head a key head): ``S' = diag(exp(g_t))
    S_{t-1}``, row c of the state times ``exp(g_t[c])``, the rest as above;
    the same op, grad lowering and counter, G's rank deciding. Computed in
    chunks of ``chunk`` (attr; the sequence where that is shorter)
    positions, which equals the recurrence in exact arithmetic; the norms,
    the decays, their running sums and the state in float32. ``States [B,
    chunks, heads, d_k, d_v]`` float32, the state entering each chunk, is
    for the op's own backward and carries no gradient.

    Attr ``impl``: ``auto`` (default) lowers the Pallas kernels of
    ``ops/pallas_delta.py`` where they can run (a TPU, or the test harness'
    interpreter) and take the shapes, else ``composed_gated_delta_rule``;
    ``pallas`` / ``composed`` force one. The kernels read raw q and k and,
    given ``QKV``, that array in place (``packed``; ``split`` is three
    operands: ``Q`` / ``K`` / ``V``, or column ranges cut out of a ``QKV``
    the kernels' blocks cannot address, and always the composed form).
    Which lowering and which operand form an op took, and how many key
    heads a grid step of its kernels (``step_heads``: what ``pallas_delta.
    step_heads`` took of the op's heads, each with all its value heads), is
    counted at each compile (``delta_lowering_total``;
    observability/lowerings.py)."""
    import jax.numpy as jnp
    from . import pallas_delta, pallas_mode
    q, k, v, g, beta, qkv = _delta_inputs(ctx, ins)
    operands, chunk = _delta_plan(ctx, q, k, v, qkv, g)
    ctx.report(
        "delta_lowering_total",
        impl="composed" if operands is None else "pallas", chunk=chunk,
        heads=v.shape[2], key_dim=q.shape[3], value_dim=v.shape[3],
        operands=("split" if operands is None or operands is not qkv
                  else "packed"),
        decay="channel" if g.ndim == 4 else "head",
        step_heads=(1 if operands is None
                    else pallas_delta.step_heads(q.shape[2], v.shape[2])))
    if operands is not None:
        o, states = pallas_delta.chunked(
            operands, _chunk_sums(g, chunk), beta.astype(jnp.float32), chunk,
            pallas_mode.interpret())
        return {"Out": [o.reshape(v.shape)], "States": [states]}
    qn, kn, cum = _delta_operands(q, k, g, chunk, jnp.float32)
    o, states = (composed_channel_delta_rule if g.ndim == 4
                 else composed_gated_delta_rule)(qn, kn, v, cum, beta, chunk)
    return {"Out": [o.astype(v.dtype)], "States": [states]}


@register_grad("gated_delta_rule")
def gated_delta_rule_grad(ctx, ins, generic):
    """dQ, dK, dV (or dQKV), dG, dBeta. Where the forward op took the
    kernels and declared ``States``, the backward kernel alone on the states
    the forward wrote, with the norms' vjp inside it: no forward is lowered
    here but the running sums again, which XLA shares with the forward's.
    Every other case is the generic grad (``jax.vjp`` over the forward's
    lowering)."""
    import jax
    import jax.numpy as jnp
    from . import pallas_delta, pallas_mode
    q, k, v, g, beta, qkv = _delta_inputs(ctx, ins)
    states, do = ins.get("States", [None])[0], ins.get("Out@GRAD", [None])[0]
    operands, chunk = _delta_plan(ctx, q, k, v, qkv, g)
    if operands is None or states is None or do is None:
        return generic()
    cum, back = jax.vjp(lambda g: _chunk_sums(g, chunk), g)
    dqkv, dcum, dbeta = pallas_delta._bwd_call(
        operands, cum, beta.astype(jnp.float32), states,
        _flat(do.astype(v.dtype)), chunk, pallas_mode.interpret())
    (dg,) = back(dcum)
    grads = {"G@GRAD": [dg.astype(g.dtype)],
             "Beta@GRAD": [dbeta.astype(beta.dtype)]}
    if qkv is None:
        dq, dk, dv = dqkv
        grads.update({"Q@GRAD": [dq.reshape(q.shape)],
                      "K@GRAD": [dk.reshape(k.shape)],
                      "V@GRAD": [dv.reshape(v.shape)]})
    else:
        grads["QKV@GRAD"] = [dqkv if operands is qkv
                             else jnp.concatenate(dqkv, axis=-1)]
    return grads
