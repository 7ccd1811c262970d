"""The gated per-head RMSNorm (``ops/decoder_ops.py:rms_norm`` given a
``Gate``) as two Pallas TPU kernels, one pass over its operands in each
direction: ``y = x * rsqrt(mean(x^2) + eps) * scale * silu(gate)`` over the
last axis, float32 in registers, x's dtype out; under ``activation=
"sigmoid"`` the gate is ``sigmoid(gate)`` (Kimi Delta Attention's output
norm; HF ``FusedRMSNormGated(activation='sigmoid')``).

As two Program ops (``rms_norm`` then ``swiglu``), each float32 inside and
each lowered again under ``jax.vjp`` by its generic grad op, XLA kept float32
copies of the operands as residuals and relaid them between its fusions: 11
to 14 ms of Qwen3-Next's 185 ms step for 2 ms of HBM traffic (PR 49: PERF.md
section 6). The work is bound by HBM (two arrays in and one out forward,
three in and two out backward) and a kernel's boundary is opaque to the
simplifier, as with ``ops/pallas_rope.py``, whose mould this follows.

``X [..., D]`` and ``Gate`` are viewed as ``[T, heads * D]`` where Gate is
that wide (``wide_view``: the shape a projection writes; on a TPU its tiles
are not those of ``[T * heads, D]``, and a reshape between the two is a copy
of the array), else as ``[rows, D]``. A grid step takes a block of rows of
one head's columns and works through it in chunks of ``CHUNK_ROWS`` rows. ``forward`` / ``backward`` are the mathematics on
float32 values, the one expression the kernels' bodies and the composed form
both use. The backward recomputes a row's ``rsqrt`` (one reduction) and
keeps no residual; the scale's gradient leaves as one float32 partial a
block, summed outside.
"""
from __future__ import annotations

import functools
import math

import jax as _jax  # custom_vjp and jit must wrap at def time

from .pallas_rope import LANES, block_rows_of
from .pallas_short_conv import _pl, _silu

VMEM_LIMIT_BYTES = 32 * 1024 * 1024
# rows of a block the kernels' bodies take at a time. A row's reduction,
# rsqrt and broadcast are a serial chain, hidden only behind the chunk's
# other rows. Chip runs, PR 49, ms a call forward / backward over [8192, 32 x
# 128] bfloat16 at 4096-row blocks, by rows a chunk: 64 0.592 / 1.081, 128
# 0.420 / 0.745, 256 0.352 / 0.591, 512 0.314 / 0.561, 1024 0.307 / 0.572,
# 2048 0.298 / 0.573 (2048-row blocks: 0.327 / 0.621 at 512)
CHUNK_ROWS = 512


def wide_view(x_shape, gate_shape):
    """(rows, heads) of the 2-D view ``[rows, heads * D]`` the kernels read
    ``X [..., D]`` and ``Gate`` in: Gate's own ``[T, heads * D]`` where it
    is that, else ``[X's rows, D]``."""
    dim = x_shape[-1]
    if len(gate_shape) == 2 and tuple(gate_shape) != tuple(x_shape) \
            and gate_shape[-1] % dim == 0:
        return gate_shape[0], gate_shape[-1] // dim
    return math.prod(x_shape[:-1]), 1


def supports(rows: int, dim: int) -> bool:
    """Whether the kernels take rows of ``dim``, ``rows`` of them in the
    view's columns: whole vregs of lanes, and a count that tiles by 16 (a
    packed bfloat16 vreg holds 16 rows)."""
    return dim % LANES == 0 and rows % 16 == 0


def _unit(x, eps):
    """x over its root mean square along the last axis, and the reciprocal
    root: float32 values."""
    import jax
    import jax.numpy as jnp
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r, r


ACTIVATIONS = ("silu", "sigmoid")


def _gate(gate, activation):
    """The gate's factor and its derivative: ``silu`` or ``sigmoid`` of
    float32 ``gate``."""
    import jax
    if activation == "silu":
        return _silu(gate)
    if activation != "sigmoid":
        raise ValueError(f"rms_norm: gate_activation={activation!r} is not "
                         f"one of {ACTIVATIONS}")
    s = jax.nn.sigmoid(gate)
    return s, s * (1.0 - s)


def forward(x, gate, scale, eps, activation="silu"):
    """``rmsnorm(x) * scale * silu(gate)`` (or ``sigmoid(gate)``) on float32
    ``x``, ``gate [..., D]`` and ``scale [D]`` (or ``[1, D]``)."""
    return _unit(x, eps)[0] * scale * _gate(gate, activation)[0]


def backward(x, gate, scale, dy, eps, activation="silu"):
    """(dx, dgate, the terms of dscale: summed over every axis but the last
    they are the scale's gradient) of ``forward`` under the cotangent
    ``dy``, in closed form on float32 values."""
    import jax.numpy as jnp
    xh, r = _unit(x, eps)
    s, ds = _gate(gate, activation)
    u = dy * s * scale
    dx = r * (u - xh * jnp.mean(xh * u, axis=-1, keepdims=True))
    return dx, dy * xh * scale * ds, dy * s * xh


def _chunks(block):
    """(rows a chunk, chunks a block): ``CHUNK_ROWS`` where it divides the
    block, else the block whole."""
    chunk = CHUNK_ROWS if block % CHUNK_ROWS == 0 else block
    return chunk, block // chunk


def _fwd_kernel(eps, activation, x_ref, z_ref, scale_ref, y_ref):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    chunk, n = _chunks(x_ref.shape[0])

    def one(i, carry):
        at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        y_ref[at, :] = forward(
            x_ref[at, :].astype(jnp.float32), z_ref[at, :].astype(jnp.float32),
            scale_ref[...], eps, activation).astype(y_ref.dtype)
        return carry
    jax.lax.fori_loop(0, n, one, 0)


def _bwd_kernel(eps, activation, x_ref, z_ref, scale_ref, dy_ref, dx_ref,
                dz_ref, dscale_ref):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    chunk, n = _chunks(x_ref.shape[0])

    def one(i, acc):
        at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        dx, dz, terms = backward(
            x_ref[at, :].astype(jnp.float32), z_ref[at, :].astype(jnp.float32),
            scale_ref[...], dy_ref[at, :].astype(jnp.float32), eps,
            activation)
        dx_ref[at, :] = dx.astype(dx_ref.dtype)
        dz_ref[at, :] = dz.astype(dz_ref.dtype)
        return acc + terms
    acc = jax.lax.fori_loop(
        0, n, one, jnp.zeros((chunk, x_ref.shape[1]), jnp.float32))
    dscale_ref[...] = jnp.sum(acc, axis=0, keepdims=True)[None]


@functools.partial(_jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def gated_norm(x, gate, scale, eps, interpret, activation="silu"):
    """``x [..., D]`` normed over its last axis, times float32 ``scale [D]``
    and ``silu(gate)`` or ``sigmoid(gate)`` (``gate``: x's element count),
    in x's dtype: the forward kernel, and under ``jax.vjp`` the backward
    kernel on x, gate and the cotangent alone."""
    return _fwd_call(x, gate, scale, eps, interpret, activation)


def _gated_norm_fwd(x, gate, scale, eps, interpret, activation):
    return (_fwd_call(x, gate, scale, eps, interpret, activation),
            (x, gate, scale))


def _gated_norm_bwd(eps, interpret, activation, res, dy):
    return _bwd_call(*res, dy, eps, interpret, activation)


gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def _plan(x, gate, interpret):
    """What both calls share for ``x [..., D]`` and ``gate``: the view's
    shape ``(rows, heads * D)``, the grid (row blocks, heads), the block
    spec of the operands of the view's shape, the scale's, and the
    compiler's parameters."""
    pl, pltpu = _pl()
    dim = x.shape[-1]
    rows, heads = wide_view(x.shape, gate.shape)
    block = block_rows_of(rows, dim)
    by_head = pl.BlockSpec((block, dim), lambda i, h: (i, h),
                           memory_space=pltpu.VMEM)
    whole = pl.BlockSpec((1, dim), lambda i, h: (0, 0),
                         memory_space=pltpu.VMEM)
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)}
    return (rows, heads * dim), (rows // block, heads), by_head, whole, params


# each behind a jit of its own, like the flash kernels: the layers of a
# model share one trace and one lowering
@functools.partial(_jax.jit,
                   static_argnames=("eps", "interpret", "activation"))
def _fwd_call(x, gate, scale, eps, interpret, activation="silu"):
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    view, grid, by_head, whole, params = _plan(x, gate, interpret)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, eps, activation), grid=grid,
        in_specs=[by_head, by_head, whole], out_specs=by_head,
        out_shape=jax.ShapeDtypeStruct(view, x.dtype),
        interpret=interpret, **params,
    )(x.reshape(view), gate.reshape(view),
      scale.astype(jnp.float32).reshape(1, -1))
    return y.reshape(x.shape)


@functools.partial(_jax.jit,
                   static_argnames=("eps", "interpret", "activation"))
def _bwd_call(x, gate, scale, dy, eps, interpret, activation="silu"):
    """(dx, dgate, dscale float32 ``[D]``) in x's, gate's shape and dtype."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    view, grid, by_head, whole, params = _plan(x, gate, interpret)
    dim = x.shape[-1]
    partial = pl.BlockSpec((1, 1, dim), lambda i, h: (i * grid[1] + h, 0, 0),
                           memory_space=pltpu.VMEM)
    dx, dz, dscale = pl.pallas_call(
        functools.partial(_bwd_kernel, eps, activation), grid=grid,
        in_specs=[by_head, by_head, whole, by_head],
        out_specs=[by_head, by_head, partial],
        out_shape=[jax.ShapeDtypeStruct(view, x.dtype),
                   jax.ShapeDtypeStruct(view, gate.dtype),
                   jax.ShapeDtypeStruct((grid[0] * grid[1], 1, dim),
                                        jnp.float32)],
        interpret=interpret, **params,
    )(x.reshape(view), gate.reshape(view),
      scale.astype(jnp.float32).reshape(1, -1), dy.reshape(view))
    return (dx.reshape(x.shape), dz.reshape(gate.shape),
            jnp.sum(dscale, axis=(0, 1)).reshape(scale.shape))
