"""The rotary embedding (``ops/decoder_ops.py:rotary_embedding``) as one
Pallas TPU kernel: a block of rows is read once in its own dtype, rotated in
float32 in registers and written once.

The op is bound by HBM (one array in, one out) and nothing in it is
arithmetic, but XLA's lowering of the composed form moved ten times that on
a v5e (PR 42: PERF.md section 6): its ``astype(float32)`` was hoisted across
the ``transpose2`` in front of the op, so q and k crossed as float32 copies,
and the rotate-half, a slice + concatenate at half a vreg of lanes, became
two half-width arrays padded to 128 lanes each. A kernel's boundary is
opaque to the simplifier, and inside it the half-swap is ``pltpu.roll`` along
the lanes (the XLU rotates a vreg).

``X [..., S, D]`` is viewed as ``[rows, D]``, a row a position of a head. A
grid step takes a block of rows of one head-sequence; the position blocks
are the outer grid axis, so a block of cos / sin is fetched once and stays
while the heads pass under it. Heads of 64 are half a vreg of lanes, as
their arrays are in HBM (lane-padded tiles): the block is 64 wide too.
"""
from __future__ import annotations

import functools

import jax as _jax  # custom_vjp and jit must wrap at def time

from .pallas_short_conv import _pl

LANES = 128
# float32 bytes of one block: the kernel holds the block in float32 a few
# times over beside the double-buffered input, output, cos and sin blocks.
# Chip runs, PR 42, ms a call over [4, 16, 4096, 128] bfloat16 by rows a
# block: 256 0.454, 512 0.334, 1024 0.275, 2048 0.244, 4096 0.239
BLOCK_F32_BYTES = 2 << 20
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def supports(seq: int, dim: int) -> bool:
    """Whether the kernel takes ``[..., seq, dim]``: rows of whole vregs of
    lanes, or of the half vreg of a head of 64 (Mosaic rotates it within its
    64 lanes), and positions that tile by 16 (a packed bfloat16 vreg holds
    16 rows)."""
    return (dim % LANES == 0 or dim == 64) and seq % 16 == 0


def block_rows_of(seq: int, dim: int) -> int:
    """The largest power-of-two split of a head-sequence's ``seq`` rows
    whose float32 block stays within ``BLOCK_F32_BYTES`` (a multiple of
    16)."""
    block = seq
    while block % 32 == 0 and block * max(dim, LANES) * 4 > BLOCK_F32_BYTES:
        block //= 2
    return block


def _kernel(rot, x_ref, cos_ref, sin_ref, o_ref):
    """``x * cos + swap(x) * sin`` over a block's rows in float32: ``swap``
    exchanges the halves of a row's first ``rot`` values (the sign of the
    rotate-half lives in ``sin``) and the values past ``rot`` pass through."""
    import jax
    import jax.numpy as jnp
    _, pltpu = _pl()
    xf = x_ref[...].astype(jnp.float32)
    dim, half = xf.shape[-1], rot // 2
    if rot == dim:          # both halves' partners are half a row away
        out = xf * cos_ref[...] + pltpu.roll(xf, half, axis=1) * sin_ref[...]
    else:
        lane = jax.lax.broadcasted_iota(jnp.int32, xf.shape, 1)
        swapped = jnp.where(lane < half, pltpu.roll(xf, dim - half, axis=1),
                            pltpu.roll(xf, half, axis=1))
        out = jnp.where(lane < rot,
                        xf * cos_ref[...] + swapped * sin_ref[...], xf)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(_jax.custom_vjp, nondiff_argnums=(3, 4))
def rotate(x, cos, sin, rot, interpret):
    """``x [..., S, D]`` rotated by float32 ``cos`` / ``sin [S, rot]`` (sin
    signed: its first half carries the rotate-half's minus) in its first
    ``rot`` values a position; in x's dtype. Linear in x and orthogonal a
    position: the cotangent is the same pass with ``-sin``."""
    return _call(x, cos, sin, rot, interpret)


def _rotate_fwd(x, cos, sin, rot, interpret):
    return _call(x, cos, sin, rot, interpret), (cos, sin)


def _rotate_bwd(rot, interpret, res, g):
    cos, sin = res
    return _call(g, cos, -sin, rot, interpret), None, None


rotate.defvjp(_rotate_fwd, _rotate_bwd)


# behind a jit of its own, like the flash kernels: the layers of a model
# share one trace and one lowering
@functools.partial(_jax.jit, static_argnames=("rot", "interpret"))
def _call(x, cos, sin, rot, interpret):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    seq, dim = x.shape[-2:]
    block = block_rows_of(seq, dim)
    steps = seq // block
    x2 = x.reshape(-1, dim)
    # the tables at the block's width; the kernel passes the tail through
    cos, sin = (jnp.pad(t, ((0, 0), (0, dim - rot))) for t in (cos, sin))
    by_rows = pl.BlockSpec((block, dim), lambda p, h: (h * steps + p, 0),
                           memory_space=pltpu.VMEM)
    by_position = pl.BlockSpec((block, dim), lambda p, h: (p, 0),
                               memory_space=pltpu.VMEM)
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)}
    out = pl.pallas_call(
        functools.partial(_kernel, rot),
        grid=(steps, x2.shape[0] // seq),
        in_specs=[by_rows, by_position, by_position], out_specs=by_rows,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret, **params,
    )(x2, cos, sin)
    return out.reshape(x.shape)
