"""The short convolution (``ops/decoder_ops.py:short_conv``) as a pair of
Pallas TPU kernels, forward and backward, in the forms the op is told: LFM2's
``C * conv(B * u)`` over ``[T, 3C]`` and a Mamba mixer's ``silu(conv(x) +
bias)`` over ``[T, C]`` share the bodies; the gates, the bias and the
activation are Python branches of them.

The op is bound by HBM: gated, 4 arrays of ``[T, C]`` forward (the three
parts of X in, the output out), 7 backward; ungated 2 and 3. XLA's fusion of the composed form moves them
at 75 / 48 GB/s on a v5e (the shifted reads along the sequence defeat its
tiling); these kernels at 636 / 619 GB/s, 8.5 and 12.8 times faster at
``[16384, 3 x 2048]`` (chip runs, PR 32: PERF.md section 6). So on a TPU the
op lowers them, wherever the shapes allow.

One grid step holds a whole sequence of ``BLK_C`` channels: the filter runs
along the rows of the block, a position's taps are row shifts of the block
(``pltpu.roll`` along the sublanes, the rows that wrapped around masked to
zero), and no block needs another's rows. The three parts of X are three
column ranges of the one ``[B, S, 3C]`` array, read in place. The backward
recomputes ``B u`` and the filter's output from X (cheaper than keeping
them), writes the three parts' gradients as three arrays (one block an
operand a grid step; the caller joins them) and each sequence's part of the
filter's gradient (and the bias', in the row after the taps), summed over
the sequences outside. float32 inside.
"""
from __future__ import annotations

import functools

import jax as _jax  # custom_vjp and jit must wrap at def time

BLK_C = 128
# Scoped VMEM: a [S, BLK_C] block is 1 MiB in bf16 at S=4096; the backward
# holds 7 of them double-buffered and about as much again in float32
# temporaries.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
MAX_SEQ = 8192
MAX_TAPS = 8


def supports(seq: int, channels: int, taps: int, bias: bool = False) -> bool:
    """Whether the kernels take these shapes (else the composed form); the
    bias takes a row of the eight beside the taps."""
    return (channels % BLK_C == 0 and seq % 16 == 0 and seq <= MAX_SEQ
            and taps + bool(bias) <= MAX_TAPS)


def _pl():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


def _behind(z, k):
    """z[t - k] along the rows, zeros before the start."""
    import jax
    import jax.numpy as jnp
    _, pltpu = _pl()
    if k == 0:
        return z
    rows = jax.lax.broadcasted_iota(jnp.int32, z.shape, 0)
    return jnp.where(rows >= k, pltpu.roll(z, k, axis=0), 0.0)


def _ahead(z, k):
    """z[t + k] along the rows, zeros past the end."""
    import jax
    import jax.numpy as jnp
    _, pltpu = _pl()
    if k == 0:
        return z
    n = z.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, z.shape, 0)
    return jnp.where(rows < n - k, pltpu.roll(z, n - k, axis=0), 0.0)


def _silu(pre):
    """silu(pre) and its derivative."""
    import jax
    s = jax.nn.sigmoid(pre)
    return pre * s, s * (1.0 + pre * (1.0 - s))


def _fwd_kernel(taps, gated, bias, act, *refs):
    import jax.numpy as jnp
    if gated:
        b_ref, c_ref, u_ref, w_ref, o_ref = refs
        z = b_ref[0].astype(jnp.float32) * u_ref[0].astype(jnp.float32)
    else:
        u_ref, w_ref, o_ref = refs
        z = u_ref[0].astype(jnp.float32)
    w = w_ref[...]                 # [8, BLK_C]: row j = tap j, then the bias
    conv = sum(_behind(z, taps - 1 - j) * w[j:j + 1] for j in range(taps))
    if bias:
        conv = conv + w[taps:taps + 1]
    if act:
        conv = _silu(conv)[0]
    if gated:
        conv = c_ref[0].astype(jnp.float32) * conv
    o_ref[0] = conv.astype(o_ref.dtype)


def _bwd_kernel(taps, gated, bias, act, *refs):
    import jax.numpy as jnp
    if gated:
        b_ref, c_ref, u_ref, w_ref, g_ref, db_ref, dc_ref, du_ref, dw_ref = \
            refs
        bf, cf, uf, g = (r[0].astype(jnp.float32)
                         for r in (b_ref, c_ref, u_ref, g_ref))
        z = bf * uf
    else:
        u_ref, w_ref, g_ref, du_ref, dw_ref = refs
        z, g = u_ref[0].astype(jnp.float32), g_ref[0].astype(jnp.float32)
    w = w_ref[...]
    past = [_behind(z, taps - 1 - j) for j in range(taps)]
    conv = sum(p * w[j:j + 1] for j, p in enumerate(past))
    if bias:
        conv = conv + w[taps:taps + 1]
    slope = None
    if act:
        conv, slope = _silu(conv)
    if gated:
        dc_ref[0] = (g * conv).astype(dc_ref.dtype)
        g = g * cf
    dconv = g if slope is None else g * slope
    dz = sum(_ahead(dconv, taps - 1 - j) * w[j:j + 1] for j in range(taps))
    if gated:
        db_ref[0] = (dz * uf).astype(db_ref.dtype)
        du_ref[0] = (dz * bf).astype(du_ref.dtype)
    else:
        du_ref[0] = dz.astype(du_ref.dtype)
    rows = [jnp.sum(dconv * p, axis=0, keepdims=True) for p in past]
    if bias:
        rows.append(jnp.sum(dconv, axis=0, keepdims=True))
    dw_ref[0] = jnp.concatenate(
        rows + [jnp.zeros((8 - len(rows), z.shape[1]), jnp.float32)], axis=0)


def _specs(batch, seq, channels, gated):
    pl, pltpu = _pl()
    n = channels // BLK_C

    def part(p):        # column range p of the [B, S, 3C] array
        return pl.BlockSpec((1, seq, BLK_C), lambda b, j: (b, 0, p * n + j),
                            memory_space=pltpu.VMEM)
    one = pl.BlockSpec((1, seq, BLK_C), lambda b, j: (b, 0, j),
                       memory_space=pltpu.VMEM)
    taps8 = pl.BlockSpec((8, BLK_C), lambda b, j: (0, j),
                         memory_space=pltpu.VMEM)
    ins = [part(0), part(1), part(2)] if gated else [one]
    return ins + [taps8], one, (batch, n)


def _params(interpret):
    if interpret:
        return {}
    _, pltpu = _pl()
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)}


def _taps8(w, bias=None):
    """``w [C, taps]`` as float32 ``[8, C]``, tap j in row j and the bias
    ``[C]``, where there is one, in the row after the taps."""
    import jax.numpy as jnp
    taps = w.shape[1]
    out = jnp.zeros((8, w.shape[0]), jnp.float32).at[:taps].set(
        w.astype(jnp.float32).T)
    if bias is not None:
        out = out.at[taps].set(bias.astype(jnp.float32))
    return out


@functools.partial(_jax.custom_vjp, nondiff_argnums=(2, 3, 5, 6))
def short_conv(x, w, seq, interpret, bias=None, gated=True, act=""):
    """``w [C, taps]``, ``bias [C]`` or None. ``gated``: ``x [T, 3C]`` holds
    ``B | C | u`` and the result is ``C * act(conv(B * u) + bias)``; else
    ``x [T, C]`` and it is ``act(conv(x) + bias)``. ``act``: ``"silu"`` or
    none. ``[T, C]`` (the op's contract), differentiable in x, w and bias."""
    return _fwd_call(x, w, bias, seq, interpret, gated, act)


# behind a jit of its own, like the flash kernels: the layers of a model
# (and the forward a grad op traces again) share one trace and one lowering
@functools.partial(_jax.jit,
                   static_argnames=("seq", "interpret", "gated", "act"))
def _fwd_call(x, w, bias, seq, interpret, gated, act):
    import jax
    pl, _ = _pl()
    rows, wide = x.shape
    batch, channels = rows // seq, wide // 3 if gated else wide
    x3 = x.reshape(batch, seq, wide)
    in_specs, one, grid = _specs(batch, seq, channels, gated)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, w.shape[1], gated, bias is not None,
                          act),
        grid=grid, in_specs=in_specs, out_specs=one,
        out_shape=jax.ShapeDtypeStruct((batch, seq, channels), x.dtype),
        interpret=interpret, **_params(interpret),
    )(*([x3] * (3 if gated else 1)), _taps8(w, bias))
    return out.reshape(rows, channels)


@functools.partial(_jax.jit,
                   static_argnames=("seq", "interpret", "gated", "act"))
def _bwd_call(x, w, bias, g, seq, interpret, gated, act):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    rows, wide = x.shape
    batch, channels = rows // seq, wide // 3 if gated else wide
    taps = w.shape[1]
    x3 = x.reshape(batch, seq, wide)
    in_specs, one, grid = _specs(batch, seq, channels, gated)
    part = jax.ShapeDtypeStruct((batch, seq, channels), x.dtype)
    n_parts = 3 if gated else 1
    *dparts, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, taps, gated, bias is not None, act),
        grid=grid, in_specs=in_specs + [one],
        out_specs=[one] * n_parts + [
            pl.BlockSpec((1, 8, BLK_C), lambda b, j: (b, 0, j),
                         memory_space=pltpu.VMEM)],
        out_shape=[part] * n_parts + [
            jax.ShapeDtypeStruct((batch, 8, channels), jnp.float32)],
        interpret=interpret, **_params(interpret),
    )(*([x3] * n_parts), _taps8(w, bias), g.reshape(batch, seq, channels))
    dx = (jnp.concatenate(dparts, axis=-1) if gated else dparts[0]).reshape(
        rows, wide)
    dw = jnp.sum(dw, axis=0)
    return (dx, dw[:taps].T.astype(w.dtype),
            None if bias is None else dw[taps].astype(bias.dtype))


def _vjp_fwd(x, w, seq, interpret, bias, gated, act):
    # inputs only: a kernel output among the residuals would keep alive the
    # forward that a Program's grad op lowers again (pallas_attention.py)
    return _fwd_call(x, w, bias, seq, interpret, gated, act), (x, w, bias)


def _vjp_bwd(seq, interpret, gated, act, res, g):
    x, w, bias = res
    return _bwd_call(x, w, bias, g, seq, interpret, gated, act)


short_conv.defvjp(_vjp_fwd, _vjp_bwd)
