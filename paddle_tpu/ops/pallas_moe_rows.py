"""An expert layer's token sums (``ops/decoder_ops.py``: ``moe_combine``'s
forward, ``moe_dispatch``'s backward) as one Pallas TPU kernel: sorted rows
``[R, H]`` -> ``[T, H]``, each token the float32 sum of those of its k slots
whose sorted row belongs to a group the layer holds, written once in the
rows' dtype.

The composed forms move far more than they need (PR 50: PERF.md section 6).
Under a row budget XLA expands ``zeros([T, H], f32).at[token].add(rows)``
into a float32 copy of the rows, a gather of that copy into the order of its
own sort of the indices, the scatter and a copy back; without one it fuses
``rows[slot]`` into the reduce at 100 cycles a gathered row. And a layer that
holds a part of its experts sorts their rows to the front of the buffer: the
rows behind them are zero through the layer, three of four in the cells, and
no XLA form can skip a count that is a device value. The kernel is given
where the held groups' rows lie and brings nothing else from HBM.

One row of ``[R, H]`` cannot be brought by itself: a DMA's slice of a tiled
dimension has to be aligned to the tile (8 rows; 16-bit rows are packed in
pairs besides), and a view with a row on a leading axis is a relayout of the
whole buffer that XLA prices above the sums it would save. But the sort is
by expert and then by token, so the rows a block of consecutive tokens sent
to one expert are *consecutive* sorted rows: a block's rows are one run a
held group, and a run is brought in aligned slabs of ``slab_rows`` rows (a
few rows either side of a run come along and are masked). Where each run
starts is a small table (``run_starts``) the caller computes from the slots
and the groups' counts, no sort.

A grid step takes a block of tokens. The scalar core walks the block's runs
and starts one DMA a slab into a VMEM buffer of ``PASS_ROWS`` rows, two
buffers in turn, noting each slab's first row and its run's ends in SMEM.
What moves a row to its token is the MXU: for 128 buffered rows at a time,
``P [tokens, 128]`` is 1 where one of the token's k slots names the buffered
row (k compares of the block's slots against the rows' numbers), and ``P @
rows`` is added to the block's float32 sums. A 0/1 factor is exact, so a
sum differs from the slot-order sum by the order of its float32 additions
only. The buffered rows that are none of the block's (beside a run in
its slabs, or left by an earlier pass) are zeroed before the product, so a
non-finite value in another token's row stays out of the block's sums.

Chip runs, PR 50 (PERF.md section 6), ms an op against the composed form, at
``BLOCK_TOKENS`` x ``PASS_ROWS`` = 256 x 1024: 8192 tokens x top-10 of 2048,
32 of 512 experts held under a budget of 20480 rows, 0.58 against 2.32 (128
x 1024: 0.68, 512 x 1024: 0.55, 256 x 2048: 0.58); 16384 x top-4 of 2048, 8
of 32 held, no budget, 0.64 against 3.85 (0.67, 0.70, 0.65); 16384 x top-8
of 2048, all 64 held, 2.86 against 5.50 (2.60, 3.83, 2.99); the masking of
the buffered rows costs nothing that shows (0.579 with, 0.584 without).
"""
from __future__ import annotations

import functools

import jax as _jax  # jit must wrap at def time

from .pallas_rope import LANES
from .pallas_short_conv import _pl

# tokens a grid step: the MXU's work grows with it (every buffered row meets
# every token of the block), the rows brought beside the runs shrink with it
BLOCK_TOKENS = 256
# rows a buffer holds; a block whose runs need more takes several passes
PASS_ROWS = 1024
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def slab_rows(dtype) -> int:
    """Rows a DMA brings: a whole tile of the rows' dtype in VMEM."""
    import jax.numpy as jnp
    return 8 * 4 // jnp.dtype(dtype).itemsize


def block_tokens_of(tokens: int):
    """Tokens a grid step: the largest power-of-two multiple of 16 up to
    ``BLOCK_TOKENS`` that divides ``tokens``, None where there is none."""
    if tokens % 16:
        return None
    block = 16
    while block * 2 <= BLOCK_TOKENS and tokens % (block * 2) == 0:
        block *= 2
    return block


def supports(tokens: int, rows: int, width: int, dtype) -> bool:
    """Whether the kernel takes ``rows`` sorted rows ``width`` wide for
    ``tokens`` tokens: whole vregs of lanes, whole slabs of rows, tokens in
    whole blocks, rows of 2 or 4 bytes an element."""
    import jax.numpy as jnp
    return (jnp.dtype(dtype).itemsize in (2, 4) and width % LANES == 0
            and rows % slab_rows(dtype) == 0
            and block_tokens_of(tokens) is not None)


def run_starts(slot, bounds, block):
    """``slot [T, k]``, ``bounds [G + 1]`` the first sorted row of each held
    group and the end of the last -> int32 ``[(T / block + 1) * G]``: entry
    ``b * G + g`` is the first sorted row of group g that a token from
    ``b * block`` on sent (a group's rows are in token order), so block b's
    rows of group g are entries ``[b * G + g, (b + 1) * G + g)``."""
    import jax.numpy as jnp
    T, k = slot.shape
    by_block = slot.reshape(T // block, block * k, 1)
    sent = jnp.sum((by_block >= bounds[:-1]) & (by_block < bounds[1:]),
                   axis=1, dtype=jnp.int32)
    before = jnp.concatenate([jnp.zeros_like(sent[:1]),
                              jnp.cumsum(sent, axis=0)])
    return (bounds[:-1] + before).astype(jnp.int32).reshape(-1)


def _kernel(groups, slab, exact, starts_ref, slot_ref, rows_ref, out_ref,
            buf, acc, base_ref, lo_ref, hi_ref, sems):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    block, k = slot_ref.shape
    per_pass = buf.shape[1] // slab              # slabs a buffer
    per_tile = LANES // slab                     # slabs 128 buffered rows
    b = pl.program_id(0)

    def slab_copy(base, side, place):
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(pl.multiple_of(base, slab), slab), :],
            buf.at[side, pl.ds(pl.multiple_of(place * slab, slab), slab), :],
            sems.at[side])

    def start(side, cursor):
        """Start the DMAs of the block's slabs from ``cursor`` (run, next
        slab's first row or -1 at a run's start) on, as many as a buffer
        holds; returns the cursor reached and how many."""
        def more(state):
            g, _, n = state
            return (g < groups) & (n < per_pass)

        def one(state):
            g, base, n = state
            lo, hi = starts_ref[b * groups + g], starts_ref[(b + 1) * groups
                                                            + g]
            base = jnp.where(base < 0, lo // slab * slab, base)

            def bring():
                slab_copy(base, side, n).start()
                base_ref[side, n], lo_ref[side, n] = base, lo
                hi_ref[side, n] = hi
                done = base + slab >= hi
                return (jnp.where(done, g + 1, g),
                        jnp.where(done, -1, base + slab), n + 1)
            return jax.lax.cond(base < hi, bring,
                                lambda: (g + 1, jnp.int32(-1), n))
        g, base, n = jax.lax.while_loop(more, one, (*cursor, jnp.int32(0)))
        return (g, base), n

    def numbered(at_rows, first, n, side):
        """The sorted row held by each of 128 buffered rows from slab
        ``first`` on (``at_rows``: their places 0 .. 127, along the lanes
        or the sublanes), -1 where it is none of the block's: a slab no DMA
        of this pass wrote, a row beside its run."""
        row = jnp.full(at_rows.shape, -1, jnp.int32)
        for s in range(per_tile):
            at = first + s
            r = base_ref[side, at] + at_rows - s * slab
            mine = ((at_rows // slab == s) & (at < n)
                    & (r >= lo_ref[side, at]) & (r < hi_ref[side, at]))
            row = jnp.where(mine, r, row)
        return row

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    sublane = jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0)

    def finish(side, n):
        """Wait for buffer ``side``'s n slabs and add their rows to the
        block's sums, 128 buffered rows a product."""
        def wait(i, _):
            slab_copy(0, side, 0).wait()
            return _
        jax.lax.fori_loop(0, n, wait, 0)

        def tile(i, _):
            row = numbered(lane, i * per_tile, n, side)
            owns = slot_ref[:, 0:1] == row
            for j in range(1, k):
                owns |= slot_ref[:, j:j + 1] == row
            rows = buf[side, pl.ds(pl.multiple_of(i * LANES, LANES), LANES), :]
            # a row beside its run is another token's, or nobody's
            rows = jnp.where(numbered(sublane, i * per_tile, n, side) >= 0,
                             rows, jnp.zeros((), rows.dtype))
            acc[...] += jnp.dot(
                owns.astype(rows.dtype), rows,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST if exact else None)
            return _
        jax.lax.fori_loop(0, (n + per_tile - 1) // per_tile, tile, 0)

    acc[...] = jnp.zeros_like(acc)

    def step(state):
        side, n, cursor = state
        cursor, ahead = start(1 - side, cursor)
        finish(side, n)
        return 1 - side, ahead, cursor
    cursor, n = start(0, (jnp.int32(0), jnp.int32(-1)))
    jax.lax.while_loop(lambda state: state[1] > 0, step,
                       (jnp.int32(0), n, cursor))
    out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(_jax.jit, static_argnames=("interpret",))
def token_sums(rows, slot, bounds, interpret=False):
    """``rows [R, H]`` sorted rows, ``slot [T, k]`` int32 the sorted row of
    each assignment, ``bounds [G + 1]`` int32 ascending, the first row of
    each of the G leading groups and the end of the last (at most R) ->
    ``[T, H]`` in rows' dtype: token t the float32 sum of ``rows[slot[t,
    j]]`` over its slots below ``bounds[-1]``. Rows from there on are not
    read; a slot past the buffer adds nothing."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    (R, H), (T, k) = rows.shape, slot.shape
    block, slab = block_tokens_of(T), slab_rows(rows.dtype)
    groups = bounds.shape[0] - 1
    slot = slot.astype(jnp.int32)
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES)}
    per_pass = PASS_ROWS // slab
    return pl.pallas_call(
        functools.partial(_kernel, groups, slab,
                          jnp.dtype(rows.dtype).itemsize == 4),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T // block,),
            in_specs=[pl.BlockSpec((block, k), lambda i, starts: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, H), lambda i, starts: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, PASS_ROWS, H), rows.dtype),
                pltpu.VMEM((block, H), jnp.float32),
                pltpu.SMEM((2, per_pass), jnp.int32),
                pltpu.SMEM((2, per_pass), jnp.int32),
                pltpu.SMEM((2, per_pass), jnp.int32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((T, H), rows.dtype),
        interpret=interpret, **params,
    )(run_starts(slot, jnp.minimum(bounds, R).astype(jnp.int32), block),
      slot, rows)
