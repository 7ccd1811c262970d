"""The change of order of the rows an expert layer's exchange received
(``ops/collective.py:RowExchange``) as one Pallas TPU kernel: a buffer ``[R,
W]`` whose rows lie source by source is written expert by expert, or the
other way round, for the grouped products on the way out and for the wire on
the way back.

The composed form is ``got[to_expert_major]``: one index a row of the buffer
(two ``searchsorted`` over R rows and three more element gathers build it,
anew in each op) and an XLA gather that moves the rows one index at a time
(PR 56: PERF.md section 6 has the passes' times). But a sender's buffer is
sorted by expert, so what a device receives from source s for its expert e
is one contiguous run: the whole permutation is ``n x E / n`` contiguous
*segments* that swap between ``[source][expert]`` and ``[expert][source]``
order, and the plan already has their starts and lengths. The kernel is
given those three small tables and no per-row index.

As in ``ops/pallas_moe_rows.py`` (read its docstring first) a row cannot be
brought by itself: a DMA's slice of the rows has to be aligned to the
dtype's tile (``slab_rows``: 8 rows of 32 bits, 16 of 16). So a grid step
owns a block of *destination* rows, written once as whole tiles, and walks
the segments that meet it ``SUB_ROWS`` destination rows at a time: the
scalar core starts the DMAs of the *source* rows of such a piece, aligned
down to the tile (a few rows either side come along), into one of two VMEM
buffers while the piece before it is placed. The source rows sit in their
buffer ``shift = (src - dst) mod tile`` rows behind their place, one shift a
piece, and come into place by a sublane rotate of 32-bit words (the XLU; a
tile's words and the next tile's, selected by sublane): rows of 16 bits are
packed in pairs in a word, so an odd shift takes the high half of one word
and the low half of the next. No arithmetic touches a value: the result is
the gather's bit for bit, whatever the bits. A piece's first and last tile
are merged into the block under a mask; rows no segment covers (behind the
live rows, between a padded wire's parts) are zero.

Chip runs, PR 56 (PERF.md section 6), ``[81920, 2304]`` bfloat16, 64
segments of 952-1,100 rows (a uniform router) or 913-3,244 (a skewed one):
1.17-1.24 ms a pass either way, 610-640 GB/s read and written, against 3.1-
3.2 ms for the indices and the gather; placing the rows by PR 50's 0/1
product on the MXU instead took 1.28-1.32 ms and turns a negative zero
positive, so the rotate stayed.
"""
from __future__ import annotations

import functools

import jax as _jax  # jit must wrap at def time

from .pallas_moe_rows import slab_rows
from .pallas_rope import LANES
from .pallas_short_conv import _pl

# destination rows a grid step writes, at most, and the bytes of them: a
# step's first piece is brought before anything can be placed, so fewer,
# larger steps expose less (512 / 1024 / 2048 rows of 2,304 bfloat16: 1.26 /
# 1.21 / 1.17 ms a pass over 81,920 rows; chip runs, PR 56)
BLOCK_ROWS = 2048
BLOCK_BYTES = 8 * 1024 * 1024
# destination rows a piece covers at most: the next piece's DMAs run while
# this one is placed (256: 1.18 ms at 1024 rows a step, no better)
SUB_ROWS = 128
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def block_rows_of(rows: int, width: int, dtype):
    """Destination rows a grid step: the largest multiple of ``SUB_ROWS``
    within ``BLOCK_ROWS`` and ``BLOCK_BYTES`` that divides ``rows``, None
    where there is none."""
    import jax.numpy as jnp
    most = min(BLOCK_ROWS, rows,
               BLOCK_BYTES // (width * jnp.dtype(dtype).itemsize))
    for block in range(most // SUB_ROWS * SUB_ROWS, 0, -SUB_ROWS):
        if rows % block == 0:
            return block
    return None


def supports(rows: int, width: int, dtype) -> bool:
    """Whether the kernel takes a buffer of ``rows`` rows ``width`` wide:
    whole vregs of lanes, rows in whole blocks, 2 or 4 bytes an element."""
    import jax.numpy as jnp
    return (jnp.dtype(dtype).itemsize in (2, 4) and width % LANES == 0
            and block_rows_of(rows, width, dtype) is not None)


def _kernel(segments, slab, first_ref, src_ref, dst_ref, len_ref, x_ref,
            out_ref, buf, sems):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    block, width = out_ref.shape
    pack = slab // 8                            # rows a 32-bit word
    b0 = pl.program_id(0) * block
    whole = SUB_ROWS // slab                    # slabs a full piece

    def piece(g, j):
        """Segment g's rows in the block's ``j``-th ``SUB_ROWS`` rows:
        (their first row in the block, how many (<= 0: none), the first's
        source row, their shift)."""
        at = jnp.minimum(g, segments - 1)
        d, n, s = dst_ref[at], len_ref[at], src_ref[at]
        n = jnp.where((g < segments) & (j < block // SUB_ROWS), n, 0)
        lo = b0 + j * SUB_ROWS
        d0 = jnp.maximum(d, lo)
        m = jnp.minimum(d + n, lo + SUB_ROWS) - d0
        r0, s0 = d0 - b0, s + d0 - d
        # the block starts on a tile, so this is (src - dst) mod tile
        return r0, m, s0, jax.lax.rem(s0 + block - r0, slab)

    def after(g, j):
        """The piece after (g, j): the next segment where g ends inside
        these ``SUB_ROWS`` rows, else g's rows in the next."""
        at = jnp.minimum(g, segments - 1)
        ends = dst_ref[at] + len_ref[at] <= b0 + (j + 1) * SUB_ROWS
        return jnp.where(ends, g + 1, g), jnp.where(ends, j, j + 1)

    def copies(side, cursor, go):
        """Start (``go(copy)``) or await the DMAs of piece ``cursor`` into
        buffer ``side``: the tiles of the source that hold its rows, one
        DMA where they are a full piece's, a tile each else."""
        r0, m, s0, shift = piece(*cursor)
        lead = jax.lax.rem(s0, slab)
        base, place = s0 - lead, r0 - cursor[1] * SUB_ROWS + shift - lead
        n = jnp.where(m > 0, (lead + m + slab - 1) // slab, 0)

        def copy(rows, i):
            return pltpu.make_async_copy(
                x_ref.at[pl.ds(pl.multiple_of(base + i * slab, slab),
                               rows), :],
                buf.at[side, pl.ds(pl.multiple_of(place + i * slab, slab),
                                   rows), :],
                sems.at[side])
        full = n >= whole

        @pl.when(full)
        def _():
            go(copy(SUB_ROWS, 0))

        def one(i, _):
            go(copy(slab, i))
            return _
        jax.lax.fori_loop(jnp.where(full, whole, 0), n, one, 0)

    def words(rows):
        return pltpu.bitcast(rows, jnp.uint32)              # [8, width]

    sublane = jax.lax.broadcasted_iota(jnp.int32, (8, width), 0)

    def place(side, cursor):
        """Piece ``cursor``, in buffer ``side``, into the block."""
        r0, m, _, shift = piece(*cursor)
        r1 = r0 + m
        near = cursor[1] * SUB_ROWS         # the buffer's row 0, in the block
        q, odd = shift // pack, jax.lax.rem(shift, pack)

        def behind(a, b, k):
            # word j + k of the words a (8 sublanes) and, after them, b
            turn = jax.lax.rem(16 - k, 8)
            return jnp.where(sublane + k < 8, pltpu.roll(a, turn, 0),
                             pltpu.roll(b, turn, 0))

        def tile(t, halves: bool, masked: bool):
            at = pl.multiple_of(t * slab, slab)
            here = pl.multiple_of(at - near, slab)
            a = words(buf[side, pl.ds(here, slab), :])
            b = words(buf[side, pl.ds(here + slab, slab), :])
            new = behind(a, b, q)
            if halves:      # 16-bit rows, an odd shift: row 2j + 1 of the
                new = (new >> 16) | (behind(a, b, q + 1) << 16)  # words on
            if masked:
                old = words(out_ref[pl.ds(at, slab), :])
                row = at + sublane * pack
                keep = jnp.where((row >= r0) & (row < r1),
                                 jnp.uint32(0xFFFFFFFF >> (16 * (pack - 1))),
                                 jnp.uint32(0))
                if pack == 2:
                    keep |= jnp.where((row + 1 >= r0) & (row + 1 < r1),
                                      jnp.uint32(0xFFFF0000), jnp.uint32(0))
                new = (new & keep) | (old & ~keep)
            out_ref[pl.ds(at, slab), :] = pltpu.bitcast(new, out_ref.dtype)

        def tiles(halves: bool):
            first, last = r0 // slab, (r1 - 1) // slab
            tile(first, halves, True)

            @pl.when(last > first)
            def _():
                tile(last, halves, True)

            def inner(t, _):
                tile(t, halves, False)
                return _
            jax.lax.fori_loop(first + 1, last, inner, 0)

        @pl.when((m > 0) & (odd == 0))
        def _():
            tiles(False)

        if pack == 2:
            @pl.when((m > 0) & (odd == 1))
            def _():
                tiles(True)

    out_ref[...] = jnp.zeros_like(out_ref)

    def more(state):
        g, j, _ = state
        return (g < segments) & (j < block // SUB_ROWS)

    def step(state):
        g, j, side = state
        ahead = after(g, j)
        copies(1 - side, ahead, lambda c: c.start())
        copies(side, (g, j), lambda c: c.wait())
        place(side, (g, j))
        return (*ahead, 1 - side)
    begin = (first_ref[pl.program_id(0)], jnp.int32(0))
    copies(0, begin, lambda c: c.start())
    # the piece after the last is past the block or the segments: empty
    jax.lax.while_loop(more, step, (*begin, jnp.int32(0)))


@functools.partial(_jax.jit, static_argnames=("interpret",))
def move_segments(x, src, dst, length, interpret=False):
    """``x [R, W]``; ``src``, ``dst``, ``length`` int32 ``[S]``: segment i
    is the rows ``[src_i, src_i + length_i)`` of ``x`` and the rows
    ``[dst_i, dst_i + length_i)`` of the result, ``dst`` ascending and the
    segments apart in both -> ``[R, W]``: every segment's rows at their
    place, bit for bit, and zero where no segment lies."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    R, W = x.shape
    block, slab = block_rows_of(R, W, x.dtype), slab_rows(x.dtype)
    segments = src.shape[0]
    src, dst = (jnp.clip(v.astype(jnp.int32), 0, R) for v in (src, dst))
    length = jnp.clip(length.astype(jnp.int32), 0,
                      R - jnp.maximum(src, dst))   # no DMA past the buffer
    # the first segment that reaches into each block
    first = jnp.sum((dst + length)[None, :] <= (
        jnp.arange(R // block, dtype=jnp.int32) * block)[:, None],
        axis=1, dtype=jnp.int32)
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES)}
    return pl.pallas_call(
        functools.partial(_kernel, segments, slab),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(R // block,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, W), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, SUB_ROWS + slab, W), x.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((R, W), x.dtype),
        interpret=interpret, **params,
    )(first, src, dst, length, x)
