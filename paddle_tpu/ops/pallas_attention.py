"""Fused multi-head attention as a Pallas TPU kernel ("flash attention").

The reference fuses transformer attention for inference with a graph pass
(reference: paddle/fluid/framework/ir/multihead_matmul_fuse_pass.cc:1 rewrites
mul/reshape/transpose/matmul/softmax chains into one multihead_matmul op). The
TPU-native version goes further: one Pallas kernel computes
softmax(Q K^T * scale + bias) V for both forward AND backward without ever
materializing the [B, heads, S, S] probability tensor in HBM -- the win is HBM
bandwidth, the usual TPU bottleneck (S=512 BERT-base: 48 MB of probs per layer
per step round-tripped, ~3x that in backward).

Design:
  * Per the registry's kernel-choice contract (core/registry.py:10), this is an
    *alternative lowering* for the `fused_attention` op: `impl=auto` picks the
    Pallas kernels on TPU from S >= AUTO_PALLAS_MIN_S up (256 since PR 27;
    XLA's own fusion wins at S=128; the measured table is at the constant)
    where the step is jitted for one device, the ring schedule under an
    sp>1 mesh, and the composed jnp lowering otherwise (a dp / mp mesh
    without sp: GSPMD cannot partition a Mosaic call) or for unsupported
    shapes. `impl='pallas'` forces the kernels
    at any supported S and raises off TPU (ops/pallas_mode.py: only the test
    harness may ask for the Pallas interpreter, so the CPU suite exercises
    the same kernel body).
  * Whole K/V rows for one (batch, head) are staged in VMEM (S*D*2 bytes
    each); Q is blocked at default_block_q rows and the K axis is tiled at
    default_block_k columns (the `fused_attention.block_sizes` choice's
    default). Without `causal` one tile is the row (block_k = S) and a Q
    block makes a single pass over [block_q, S] scores. Under `causal` from
    CAUSAL_TILES_MIN_S a Q block loops over the K tiles at or under its
    diagonal: a tile wholly above it costs no product, no exp and no
    compare, a tile wholly under it takes no mask, and only the tiles the
    diagonal crosses keep the `where` (_KTiles; at S=4096 and 512 x 1024
    tiles 20 of 32 tiles are visited). The softmax statistics (max, sum) and
    every accumulation are f32; the MXU is fed both operands of every
    product in the input's dtype with preferred_element_type=f32 (bf16
    inputs: the probabilities and dS are rounded to bf16 once, as the
    composed lowering rounds p; f32 inputs keep f32 products). Nothing is
    divided per score: a power-of-two scale moves into the [block_q, D] q
    block (exact), the softmax's 1/sum and the dropout's 1/(1-prob) scale
    [*, D] results.
    What bounds S is VMEM: the staged rows and the [block_q, S] f32 of score
    tiles a Q block keeps between its passes (temporaries with one tile, a
    scratch with more). The forward fits Mosaic's 16 MiB of scoped VMEM to
    S=4096 at block_q 512 and to S=8192 at block_q 128 and one tile; the
    backward takes BWD_VMEM_LIMIT_BYTES and compiles to S=4096, and at
    S=8192 at block_q 128 or 256 and one tile and in CAUSAL_BLOCKS' tiles
    (where the forward, its stage 16 MB, does not; compiled for a described
    v5e). Longer rows need K/V staged by tile too (ROADMAP S1 (b)).
  * The forward kernel writes, beside the output, each row's softmax
    statistic ``lse = max + log(sum)``: one float32 a row, [B, H, 1, S],
    compact in HBM (a [block_q, 1] column is turned into a [1, block_q] row
    in the kernel: _as_row). The backward kernel reads it and forms a
    tile's probabilities as ``exp(s - lse)``: no pass for the row's max,
    none for its sum, no 1/sum (PR 38; the three passes it made before, max
    / exp, sum and dP / dS and the products, are two: p, dP and the row's
    sum of dP P; dS and the products -- with one tile a row a single pass
    with one cross-lane reduction). One exp a score and five products;
    the p and dP tiles of a Q block stay in VMEM between the passes (a
    second pass that recomputes them pays two exps and seven products: 15%
    and 11% slower at the two decoders' shapes, chip runs, PR 34). dK^T and
    dV^T [D, block_k] a K tile accumulate across the Q blocks in f32 VMEM
    scratch and leave in the input's dtype on the last one: grid axis 1 is
    declared "arbitrary" (sequential) for that, axis 0 "parallel".
    How the statistic reaches the backward: the op declares it as an
    output (``Lse``) and registers its own grad lowering
    (fused_attention_grad), which calls the backward kernel on the forward
    op's Lse and lowers no forward. The registry's generic grad op lowers
    the forward a second time under jax.vjp, and XLA drops that copy only
    while none of its outputs is read: a statistic handed over as a
    custom-VJP residual made a Program run 24 forward kernels for 12 layers
    (chip runs, PR 25). The custom VJP (_flash: a direct jax.vjp, the
    tuner) keeps lse as its residual all the same: nothing lowers a second
    forward there. A row every key of which is biased by -1e30 reads
    lse = -1e30 (log S is under float32's spacing there), so the backward
    forms p = 1 for it, not 1/S: such a row has no key to attend to.
  * Attention dropout uses the in-kernel PRNG (pltpu.prng_random_bits) seeded
    per (step, batch*head, 128-row block, K tile); the backward kernel
    reseeds identically so the mask matches without storing it, and the mask
    is the same under every block_q (under every block_k it is not: a tile's
    draw is [128, block_k] wide). In-kernel PRNG has no interpreter
    lowering, so dropout>0 takes the Pallas path only on a TPU
    (chip_smoke.py checks the two kernels' masks against each other there).
"""
from __future__ import annotations

import functools
import math

from ..core.registry import register, register_grad

# The smallest Q block. Every S the kernel takes is a multiple of it
# (supports_pallas), every block_q too, and the dropout mask is drawn by
# blocks of it whatever block_q is.
_MIN_BLK_Q = 128

# lanes of a vector register: a row statistic's partials (_KTiles)
_LANES = 128

# Q rows a grid step from S=1024 up where one K tile is the row: 256 beats 128
# forward + backward at S=1024, 2048 and 4096 (3.99 / 6.56 / 11.78 against
# 4.42 / 7.01 / 12.18 ms a layer of 16k tokens; chip runs, PR 25). 512 and
# 1024 gain 2-8% more at S=1024 and 2048 (PERF.md section 7) and 512 does not
# fit at 4096 with the whole row's scores in one block.
BLK_Q = 256

# (block_q, block_k) under `causal` from CAUSAL_TILES_MIN_S up, where both
# divide S. Forward / backward kernel ms a layer of 4 x 4096 tokens, bf16
# (chip runs, PR 34), at olmoe's shape (16 heads of 128) and at lfm2's (32
# query over 8 key/value heads of 64); the first row is one tile a row at
# BLK_Q, what a causal op takes below CAUSAL_TILES_MIN_S:
#   (block_q, block_k)   olmoe fwd / bwd    lfm2 fwd / bwd
#   (256, 4096) 1 tile   3.99 / 9.22        8.73 / 16.62
#   (256, 256)           4.27 / 9.24 (*)    9.19 / 17.61 (*)
#   (512, 256)           3.72 / 8.49 (*)    8.05 / 16.02 (*)
#   (256, 512)           3.29 / 7.33 (*)    7.21 / 14.00 (*)
#   (512, 512)           2.88 / 6.40        6.43 / 11.68
#   (256, 1024)          2.94 / 6.50        6.56 / 12.14
#   (512, 1024)          2.74 / 6.28        6.09 / 11.40
#   (1024, 1024)         no fit / 6.79 (*)  no fit / 13.13 (*)
# (*) before the backward's dQ product moved ahead of its dK^T / dV^T
# accumulations, which took 8% (d=128) and 12% (d=64) off the backward at
# (512, 512). A loop iteration costs about 0.2 us whatever the tile holds
# (three iterations a tile in the backward then, two since it reads the
# forward's lse, PR 38: 6.37 -> 5.60 and 11.22 -> 9.85 ms at (512, 1024)):
# small tiles visit 53% of the square and lose it again; 512 x 1024 visits
# 62.5%.
CAUSAL_BLOCKS = (512, 1024)
CAUSAL_TILES_MIN_S = 2048

# (block_q, block_k) of a causal op with a sliding window shorter than S,
# from WINDOW_TILES_MIN_S up where both divide S: a Q block then visits the
# tiles its window reaches and no others, so what it costs no longer grows
# with its index. Forward / backward kernel ms a layer of 1 x 4096 tokens at
# 72 query over 8 key/value heads of 128, window 512, bf16, and the K tiles
# visited of all (chip runs, PR 39); the same op without the window reads
# 3.33 / 6.30 at CAUSAL_BLOCKS, XLA's composed lowering 34.9 / 30.3:
#   (512, 512)   2.09 / 3.25   15 of 64     (256, 512)   2.25 / 3.55   30 of 128
#   (512, 256)   2.49 / 3.73   30 of 128    (256, 256)   2.38 / 3.68   45 of 256
#   (512, 1024)  2.34 / 4.02   11 of 32     (256, 1024)  2.44 / 4.28   22 of 64
#   (1024, 512)  2.61 / 4.14   11 of 32     (128, 512)   2.98 / 4.55   60 of 256
# Every visited tile is masked at these sizes, and at (512, 512) half the
# pairs a Q block computes lie outside its rows' windows.
WINDOW_BLOCKS = (512, 512)
WINDOW_TILES_MIN_S = 1024


def _window_tiled(S, causal, window):
    q, k = WINDOW_BLOCKS
    return bool(causal and window and window < S and S >= WINDOW_TILES_MIN_S
                and S % q == 0 and S % k == 0)


def default_block_q(S, causal=False, window=None):
    """The Q block the kernels take at sequence length S where no tuning
    decision says otherwise; always divides S. Below 1024 one block a
    (batch, head): the whole [S, S] tile in one grid step beats every
    smaller block at S=256 ... 768 (2.43 against 2.67 ms at 256 and 3.33 at
    128, S=512, forward + backward of 16k tokens; table in PERF.md section 6,
    chip runs, PR 27) and compiles to S=896 in bf16 and f32. From 1024 up
    BLK_Q, or _MIN_BLK_Q where that does not divide S; with K tiles
    (default_block_k) the Q block of CAUSAL_BLOCKS, or of WINDOW_BLOCKS
    under a sliding window."""
    if _window_tiled(S, causal, window):
        return WINDOW_BLOCKS[0]
    if S < 1024:
        return S
    if default_block_k(S, causal) != S:
        return CAUSAL_BLOCKS[0]
    return BLK_Q if S % BLK_Q == 0 else _MIN_BLK_Q


def default_block_k(S, causal=False, window=None):
    """The K tile at sequence length S; always divides S. Without ``causal``
    the row: every tile would be visited, a narrower one only adds loop
    iterations, and the dropout mask is drawn a tile at a time. With it, the
    K tile of CAUSAL_BLOCKS from CAUSAL_TILES_MIN_S up where the pair divides
    S (at S=4096 32% less kernel time than one tile, table above), chosen
    from what the op sees: ``causal`` and S. Under a sliding ``window``
    shorter than S, the K tile of WINDOW_BLOCKS from WINDOW_TILES_MIN_S
    up."""
    if _window_tiled(S, causal, window):
        return WINDOW_BLOCKS[1]
    q, k = CAUSAL_BLOCKS
    if causal and S >= CAUSAL_TILES_MIN_S and S % q == 0 and S % k == 0:
        return k
    return S


def _under_diagonal(iq, block_q, block_k):
    """(clear, visited) of Q block ``iq`` under a causal mask: K tiles
    [0, clear) lie wholly at or under its diagonal, [clear, visited) are
    crossed by it, the tiles from ``visited`` up lie wholly above it. Python
    ints or traced values, as ``iq`` is."""
    return ((iq * block_q) // block_k,
            ((iq + 1) * block_q - 1) // block_k + 1)


def _behind_window(iq, block_q, block_k, window):
    """(first, edge) of Q block ``iq`` under a sliding window (row i sees
    the columns i - window < j <= i): K tiles [0, first) lie wholly behind
    the window of every row of the block and are not visited, [first, edge)
    hold a column that some row's window no longer reaches and take the
    mask. Python ints or traced values, as ``iq`` is (``//`` floors both)."""
    lo = iq * block_q - window + 1          # first column the first row sees
    first, edge = lo // block_k, (lo + block_q - 2) // block_k + 1
    if isinstance(first, int):
        return max(first, 0), max(edge, 0)
    import jax.numpy as jnp
    return jnp.maximum(first, 0), jnp.maximum(edge, 0)


def _tiles_by_block(S, block_q, block_k, window=None):
    """The K tiles each Q block of a causal op visits, a count a block."""
    return [_under_diagonal(iq, block_q, block_k)[1]
            - (_behind_window(iq, block_q, block_k, window)[0] if window
               else 0) for iq in range(S // block_q)]


def k_tiles(S, block_q, block_k, causal, window=None):
    """(visited, skipped): the K tiles the Q blocks of one (batch, head)
    pass over in the forward kernel, and those they leave out because they
    lie wholly above the diagonal or, under a sliding ``window``, wholly
    behind it (_KTiles, summed over the Q blocks)."""
    n_q, n_k = S // block_q, S // block_k
    visited = sum(_tiles_by_block(S, block_q, block_k, window)) \
        if causal else n_q * n_k
    return visited, n_q * n_k - visited


# Scoped VMEM the backward kernel may take. Mosaic's default (16 MiB of the
# v5e's 128) holds its [block_q, S] temporaries to S=2048 at BLK_Q; S=4096
# needs 20 MiB (28 with f32 inputs). The forward fits the default to S=8192
# and is 4% slower under a raised limit (chip runs, PR 25), so it keeps it.
BWD_VMEM_LIMIT_BYTES = 64 * 1024 * 1024

# The forward kernel's own, for heads wider than 128 only. At d=256 in the
# causal 512 x 1024 tiles its stage, the double-buffered q, o and K/V rows
# and the score tile stand at the edge of the default: the call compiles
# inside one train step and not alone, nor in a test clone without the ops
# that were around it (16.39 M asked of 16.00 M: PERF.md section 7 (u), PR
# 43). Up to d=128 the call keeps Mosaic's default, under which it is 4%
# faster (above), and those cells' programs stay as they were.
FWD_VMEM_LIMIT_BYTES_WIDE = 32 * 1024 * 1024


def fwd_vmem_limit_bytes(head_dim):
    """``vmem_limit_bytes`` of the forward call: None (Mosaic's default) up
    to heads of 128, ``FWD_VMEM_LIMIT_BYTES_WIDE`` above."""
    return FWD_VMEM_LIMIT_BYTES_WIDE if head_dim > 128 else None

# 'auto' takes the Pallas kernels from this sequence length up; the default
# of the `fused_attention.backend` tunable choice (paddle_tpu/tuning/), which
# a persisted autotune decision overrides per (shape bucket, device).
# Measured on the v5e, forward + backward of 16k tokens, bf16, H=12 D=64, a
# [B,1,1,S] bias, dropout 0.1, ms (chip runs, PR 27; XLA's composed lowering
# against the kernels at default_block_q): S=128 2.13 / 3.13, 256 3.94 /
# 2.33, 384 5.56 / 2.39, 512 8.17 / 2.43, 1024 13.07 / 4.00, 2048 25.48 /
# 6.81; without dropout at S=512 4.97 / 2.14; causal, D=128, no bias, S=512
# 7.00 / 1.92. The composed lowering's S x S scores, probabilities and mask
# in HBM grow with S; the kernels' cost for 16k tokens hardly moves below
# S=512. XLA wins at S=128. Under a mesh of more than one device without
# sp the op keeps the composed lowering at every S (fused_attention: a
# Mosaic call has no partitioning rule). impl='pallas' forces the kernels.
AUTO_PALLAS_MIN_S = 256


def _pl():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


# --------------------------------------------------------------------------------------
# composed (XLA-fused) reference path
# --------------------------------------------------------------------------------------

def composed_attention(q, k, v, bias, scale, dropout, causal, rng,
                       bernoulli=None, window=None):
    """Plain jnp attention: the numerics oracle and the non-TPU lowering.
    ``bernoulli(key, keep, shape)`` draws the dropout mask: the op hands its
    ``LowerCtx.bernoulli_mask``, else ``jax.random.bernoulli``. ``window``
    (with ``causal``): query i sees the keys i - window < j <= i."""
    import jax
    import jax.numpy as jnp

    out_shape = q.shape[:-1] + v.shape[-1:]
    grouped = k.shape[1] != q.shape[1]
    scores, values = "bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd"
    if grouped:
        # grouped-query attention: query head i reads key/value head
        # i // group. The group is an axis of the products, so K and V are
        # never repeated.
        B, H, S, D = q.shape
        q = q.reshape(B, k.shape[1], H // k.shape[1], S, D)
        scores, values = "bhgqd,bhkd->bhgqk", "bhgqk,bhkd->bhgqd"
        if bias is not None:
            bias = bias[:, :, None]
    s = jnp.einsum(scores, q, k, preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        S_q, S_k = s.shape[-2], s.shape[-1]
        qi = jax.lax.broadcasted_iota(jnp.int32, (S_q, S_k), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (S_q, S_k), 1)
        seen = ki <= qi
        if window:
            seen = seen & (ki > qi - window)
        s = jnp.where(seen, s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    if dropout:
        keep = (bernoulli or jax.random.bernoulli)(rng, 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    out = jnp.einsum(values, p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out.reshape(out_shape) if grouped else out


# --------------------------------------------------------------------------------------
# pallas kernels
# --------------------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims):
    """MXU product of two blocks in their own dtype, accumulated in f32."""
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _scale_is_exact(scale):
    """A power of two only moves the exponent: q * scale is exact in q's dtype,
    so the scale can leave the [block_q, S] scores for the [block_q, D] q."""
    return math.frexp(scale)[0] == 0.5


def _fold_scale(q_blk, scale):
    """The q block that goes into the scores: scaled where the scale is a
    power of two (the backward's dK needs the same block)."""
    import jax.numpy as jnp
    if _scale_is_exact(scale):
        return q_blk * jnp.asarray(scale, q_blk.dtype)
    return q_blk


def _rows(ref, t, block_k):
    """K tile ``t`` of a staged [1, S, D] row block: [block_k, D]."""
    pl, _ = _pl()
    if isinstance(t, int):
        return ref[0, t * block_k:(t + 1) * block_k, :]
    return ref[0, pl.ds(pl.multiple_of(t * block_k, block_k), block_k), :]


def _scores(q_s, k_ref, bias_ref, iq, t, block_k, scale, masked,
            window=None):
    """[block_q, block_k] f32 scores of Q block ``iq`` against K tile ``t``.
    ``q_s`` comes from _fold_scale; ``masked``: the diagonal crosses the
    tile (a tile wholly under it takes no mask, one above it is not
    visited: _KTiles) or, under a sliding ``window``, the window's far edge
    does; a masked tile of a window op takes both edges."""
    import jax
    import jax.numpy as jnp

    blk_q = q_s.shape[0]
    s = _dot(q_s, _rows(k_ref, t, block_k), _NT)
    if not _scale_is_exact(scale):
        s = s * scale
    if bias_ref is not None:
        s = s + bias_ref[0, t].astype(jnp.float32)           # [1, block_k]
    if masked:
        # column - row inside the tile, against where the tile lies
        rel = (jax.lax.broadcasted_iota(jnp.int32, (blk_q, block_k), 1)
               - jax.lax.broadcasted_iota(jnp.int32, (blk_q, block_k), 0))
        off = iq * blk_q - t * block_k
        seen = rel <= off
        if window is not None:
            seen = seen & (rel > off - window)
        s = jnp.where(seen, s, jnp.float32(-1e30))
    return s


def _keep_mask(shape, seed_ref, iq, dropout, bh=None, t=0):
    """Bernoulli(1 - dropout) keep mask of one Q block against K tile ``t``,
    drawn by blocks of _MIN_BLK_Q rows, each seeded by (step seed,
    batch*head, its index in the sequence, the tile): the backward reseeds
    the same, and the mask does not depend on block_q (on block_k it does:
    the two kernels take the same). ``bh`` is the query's batch*head index
    where grid axis 0 is not it (the grouped backward)."""
    import jax.numpy as jnp
    pl, pltpu = _pl()
    n = shape[0] // _MIN_BLK_Q
    bits = []
    for j in range(n):
        pltpu.prng_seed(seed_ref[0]
                        + (pl.program_id(0) if bh is None else bh) * 1000003
                        + (iq * n + j) * 7919 + t * 104729)
        bits.append(pltpu.prng_random_bits((_MIN_BLK_Q, shape[1])))
    bits = pltpu.bitcast(jnp.concatenate(bits, axis=0), jnp.uint32)
    return bits >= jnp.uint32(int(dropout * float(2**32)))


def _plus(acc, x):
    """``acc + x``; ``x`` where nothing has been carried yet."""
    return x if acc is None else acc + x


def _as_row(col):
    """A row statistic [block_q, 1] (a value a sublane) as [1, block_q] (a
    value a lane): how it lies in HBM, ``[B * H, 1, S]`` float32, compact. A
    128-lane copy a row is what Mosaic would store without a relayout, 512
    bytes for 4; the transpose of one [block_q, 128] tile is nothing beside
    a Q block's [block_q, S] scores."""
    import jax.numpy as jnp
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1]


def _as_col(row):
    """[1, block_q] as it lies in HBM back to [block_q, 1]."""
    import jax.numpy as jnp
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T[:, :1]


class _KTiles:
    """The K tiles one Q block visits, in passes. Tiles [0, clear) lie wholly
    at or under Q block ``iq``'s diagonal and take no mask, [clear, visited)
    are crossed by it, and the tiles from ``visited`` up lie wholly above it:
    no pass goes there. Without ``causal`` every tile is clear. Under a
    sliding ``window`` (row i sees i - window < j <= i) the tiles [0, first)
    lie wholly behind every row's window and are not visited either, and
    [first, edge) are crossed by the window's far edge and take the mask
    (_behind_window): the tiles a Q block visits no longer grow with its
    index, and a stage holds the most one block visits, indexed from
    ``first``.

    A score tile lives from one pass to a later one in a stage (``put`` /
    ``get``), a [n_k, block_q, block_k] f32 VMEM scratch. A row statistic
    (max, sum) is carried from tile to tile as its [block_q, _LANES]
    partials, elementwise work; the cross-lane reduction is made once a pass
    (``total``), not once a tile.

    ``whole``: one tile is the row (block_k = S). Then nothing loops, nothing
    is carried (a body's ``carry`` is None), a staged tile is the value
    itself and a statistic is reduced where it is formed: a single pass over
    [block_q, S], the BERT cells' path
    (tests/test_pallas_attention.py keeps its plain body as an oracle)."""

    def __init__(self, iq, block_q, block_k, n_k, causal, stage_refs=(),
                 window=None):
        self.whole, self.causal, self.block_q = n_k == 1, causal, block_q
        self.clear, self.visited = _under_diagonal(
            iq, block_q, block_k) if causal else (n_k, n_k)
        self.window = window
        if window is not None and not self.whole:
            self.first, self.edge = _behind_window(iq, block_q, block_k,
                                                   window)
        self.stages = list(stage_refs)
        self.staged = {}

    def passes(self, body, init):
        """``body(masked, t, carry)`` over the clear, then the crossed
        tiles; ``init()`` is the carry before the first tile of a loop."""
        import jax
        if self.whole:
            return body(self.causal, 0, None)
        if self.window is not None:
            # behind the diagonal's tiles: those the window's edge crosses,
            # then the clear ones (none where the window is no wider than
            # a tile)
            import jax.numpy as jnp
            carry = jax.lax.fori_loop(
                self.first, jnp.minimum(self.edge, self.clear),
                functools.partial(body, True), init())
            carry = jax.lax.fori_loop(
                jnp.maximum(self.edge, self.first), self.clear,
                functools.partial(body, False), carry)
            return jax.lax.fori_loop(self.clear, self.visited,
                                     functools.partial(body, True), carry)
        carry = jax.lax.fori_loop(0, self.clear,
                                  functools.partial(body, False), init())
        if not self.causal:
            return carry
        return jax.lax.fori_loop(self.clear, self.visited,
                                 functools.partial(body, True), carry)

    def _slot(self, t):
        """Where tile ``t`` lies in a stage."""
        return t if self.window is None else t - self.first

    def put(self, i, t, x):
        if self.whole:
            self.staged[i] = x
        else:
            self.stages[i][self._slot(t)] = x

    def get(self, i, t):
        return self.staged[i] if self.whole else self.stages[i][self._slot(t)]

    def stat(self, x, op):
        """Tile ``x``'s part in a row statistic; ``op`` is jnp.max or
        jnp.sum."""
        import jax
        import jax.numpy as jnp
        if self.whole:
            return op(x, axis=-1, keepdims=True)
        # lax, not jnp: a kernel has some 200 of these slices and pairs, and
        # a jnp call costs a millisecond of tracing each
        both = jax.lax.max if op is jnp.max else jax.lax.add
        return functools.reduce(both, [
            jax.lax.slice_in_dim(x, j, j + _LANES, axis=1)
            for j in range(0, x.shape[1], _LANES)])

    def total(self, x, op):
        """[block_q, 1] of a statistic carried over a pass."""
        return x if self.whole else op(x, axis=-1, keepdims=True)

    def stat_init(self, value):
        """A statistic's carry before a pass's first tile."""
        import jax.numpy as jnp
        return jnp.full((self.block_q, _LANES), value, jnp.float32)

    def row_max(self, scores):
        """Pass 1 of the forward kernel: every visited tile's scores into
        stage 0, and the row maxima [block_q, 1]. ``scores(masked, t)``."""
        import jax.numpy as jnp

        def body(masked, t, m):
            s = scores(masked, t)
            self.put(0, t, s)
            m_t = self.stat(s, jnp.max)
            return m_t if m is None else jnp.maximum(m, m_t)

        return self.total(self.passes(
            body, lambda: self.stat_init(-jnp.inf)), jnp.max)


def _fwd_kernel(scale, dropout, causal, has_bias, block_k, *refs,
                window=None):
    """One Q block against its K tiles in two passes: (1) scores, row max;
    (2) exp, row sum and the product with V. Beside the output it writes the
    rows' softmax statistic, ``lse = max + log(sum)``, one float32 a row: all
    the backward needs to form the probabilities again."""
    import jax.numpy as jnp
    pl, _ = _pl()
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    seed_ref, o_ref, lse_ref = refs[3 + has_bias:6 + has_bias]
    iq = pl.program_id(1)
    blk_q, D = q_ref.shape[1:]
    tiles = _KTiles(iq, blk_q, block_k, k_ref.shape[1] // block_k, causal,
                    refs[6 + has_bias:], window)
    q_s = _fold_scale(q_ref[0], scale)
    m = tiles.row_max(lambda masked, t: _scores(
        q_s, k_ref, bias_ref, iq, t, block_k, scale, masked, window))

    def product(masked, t, carry):
        e = jnp.exp(tiles.get(0, t) - m)
        l_t = tiles.stat(e, jnp.sum)
        if dropout:
            e = jnp.where(_keep_mask(e.shape, seed_ref, iq, dropout, t=t),
                          e, 0.0)
        o_t = _dot(e.astype(v_ref.dtype), _rows(v_ref, t, block_k), _NN)
        l, o = carry or (None, None)
        return _plus(l, l_t), _plus(o, o_t)

    l, o = tiles.passes(product, lambda: (
        tiles.stat_init(0.0),
        jnp.zeros((blk_q, v_ref.shape[-1]), jnp.float32)))
    l = tiles.total(l, jnp.sum)
    # the softmax's 1/l and the dropout's 1/(1-prob) scale the [block_q, D]
    # product, one reciprocal a row, not the [block_q, S] probabilities
    o_ref[0] = (o * (1.0 / (l * (1.0 - dropout)))).astype(o_ref.dtype)
    lse_ref[0] = _as_row(m + jnp.log(l))


def _bwd_kernel(scale, dropout, causal, has_bias, group, block_k, *refs,
                window=None):
    """``group`` query heads share a key/value head. At 1 grid axis 1 is the
    Q block; above 1 it runs over the group's heads and their Q blocks in
    turn, so that dK^T / dV^T accumulate over the whole group in VMEM and a
    key/value head's gradient is written once (no per-query-head dK, dV in
    HBM to sum afterwards).

    The rows' ``lse`` comes from the forward kernel, so a tile's
    probabilities are ``exp(s - lse)`` as soon as its scores are: no pass
    for the row's max, none for its sum, no 1/sum. Two passes over the Q
    block's K tiles: (1) scores, p, dP = dO V^T and the row's sum of dP P;
    (2) dS and the three gradient products. With one tile a row that is a
    single pass with one cross-lane reduction."""
    import jax.numpy as jnp
    pl, _ = _pl()
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    (seed_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref, dkt_acc,
     dvt_acc) = refs[3 + has_bias:11 + has_bias]
    step = iq = pl.program_id(1)
    bh = None
    if group > 1:
        n_q = pl.num_programs(1) // group
        iq = step % n_q
        bh = pl.program_id(0) * group + step // n_q
    dtype = q_ref.dtype
    blk_q, D = q_ref.shape[1:]
    n_k = k_ref.shape[1] // block_k
    tiles = _KTiles(iq, blk_q, block_k, n_k, causal, refs[11 + has_bias:],
                    window)
    q_s = _fold_scale(q_ref[0], scale)
    do = do_ref[0]                                           # [BLK_Q, D]
    lse = _as_col(lse_ref[0])                                # [BLK_Q, 1]

    # Dropout multiplies p and its gradient by keep * c, c = 1/(1-prob). The
    # mask is applied to dp and to p; c is a constant of every product, so
    # it scales the [*, D] results (pk, dp, ds are the true values over c).
    c = 1.0 / (1.0 - dropout)

    def keep(t):
        return _keep_mask((blk_q, block_k), seed_ref, iq, dropout, bh, t)
    if tiles.whole:
        keep = functools.lru_cache(None)(keep)               # drawn once
    # (with more tiles each pass draws a tile's mask: the draw is cheaper
    # than a third staged tile)

    def softmax(masked, t, r):
        p = jnp.exp(_scores(q_s, k_ref, bias_ref, iq, t, block_k, scale,
                            masked, window) - lse)
        dp = _dot(do, _rows(v_ref, t, block_k), _NT)         # [BLK_Q, blk_k]
        if dropout:
            dp = jnp.where(keep(t), dp, 0.0)
        tiles.put(0, t, p)
        tiles.put(1, t, dp)
        return _plus(r, tiles.stat(dp * p, jnp.sum))

    row = tiles.total(tiles.passes(
        softmax, lambda: tiles.stat_init(0.0)), jnp.sum)     # sum of dp * p

    @pl.when(step == 0)
    def _():
        dkt_acc[...] = jnp.zeros_like(dkt_acc)
        dvt_acc[...] = jnp.zeros_like(dvt_acc)

    def grads(masked, t, dq):
        p = tiles.get(0, t)                                  # f32
        pk = jnp.where(keep(t), p, 0.0) if dropout else p
        ds = (p * (tiles.get(1, t) - row)).astype(dtype)
        dq = _plus(dq, _dot(ds, _rows(k_ref, t, block_k), _NN))
        # dK^T, dV^T [D, block_k]: contracting the Q rows of both operands
        # transposes the [BLK_Q, D] block, not the [BLK_Q, block_k] one
        dkt_acc[t] += _dot(q_s, ds, _TN)
        dvt_acc[t] += _dot(do, pk.astype(dtype), _TN)
        return dq

    dq = tiles.passes(grads, lambda: jnp.zeros((blk_q, D), jnp.float32))
    dq_ref[0] = (dq * (c * scale)).astype(dq_ref.dtype)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        k_scale = c if _scale_is_exact(scale) else c * scale  # q_s has it
        for t in range(n_k):
            cols = slice(t * block_k, (t + 1) * block_k)
            dk_ref[0, cols, :] = (dkt_acc[t] * k_scale).T.astype(dk_ref.dtype)
            dv_ref[0, cols, :] = (dvt_acc[t] * c).T.astype(dv_ref.dtype)


def _operands(q, k, v, bias, seed, block_q, block_k, by_kv_head=False):
    """The kernels' common operands and block specs, the spec of a row
    statistic and the number of Q blocks (block_q and block_k divide S:
    _flash_stats has seen to it). K and V rows are staged whole, a row's
    bias by K tile. Grid axis 0 is the query's batch*head and axis 1 the Q
    block; with fewer key/value heads than query heads (k, v ``[B, Hkv, S,
    D]``) a query head's program reads its key/value head's rows in place.
    ``by_kv_head`` (the grouped backward): axis 0 is the key/value's
    batch*head, axis 1 the group's heads x Q blocks. ``v`` (and with it the
    output) may have a head width of its own, so the specs come in pairs:
    ``(q's, the output's)`` and ``(k's, v's)``."""
    import jax.numpy as jnp
    pl, pltpu = _pl()
    B, H, S, D = q.shape
    kv, Dv = k.shape[1], v.shape[3]
    group, n_q = H // kv, S // block_q
    args = [q.reshape(B * H, S, D), k.reshape(B * kv, S, D),
            v.reshape(B * kv, S, Dv)]
    if group == 1:
        q_at, kv_at, per_batch = (lambda b, i: (b, i, 0),
                                  lambda b, i: (b, 0, 0), H)
    elif by_kv_head:
        q_at, kv_at, per_batch = (
            lambda b, i: (b * group + i // n_q, i % n_q, 0),
            lambda b, i: (b, 0, 0), kv)
    else:
        q_at, kv_at, per_batch = (lambda b, i: (b, i, 0),
                                  lambda b, i: (b // group, 0, 0), H)
    qspec = pl.BlockSpec((1, block_q, D), q_at, memory_space=pltpu.VMEM)
    kvspec = pl.BlockSpec((1, S, D), kv_at, memory_space=pltpu.VMEM)
    # a Q block's rows of lse [B * H, 1, S]: q's block with the rows along
    # the lanes (the middle 1 is the array's whole dim, as Mosaic wants)
    lse_spec = pl.BlockSpec(
        (1, 1, block_q), lambda b, i: (q_at(b, i)[0], 0, q_at(b, i)[1]),
        memory_space=pltpu.VMEM)
    # the output's and v's blocks: q's and k's unless v has its own width
    ospec, vspec = qspec, kvspec
    if Dv != D:
        ospec = pl.BlockSpec((1, block_q, Dv), q_at, memory_space=pltpu.VMEM)
        vspec = pl.BlockSpec((1, S, Dv), kv_at, memory_space=pltpu.VMEM)
    in_specs = [qspec, kvspec, vspec]
    if bias is not None:
        # [B, n_k, 1, block_k] with block (1, n_k, 1, block_k): the last two
        # dims equal the array dims, satisfying the TPU (8,128)-divisible-
        # or-full block constraint, and a tile's row is a leading index.
        n_k = S // block_k
        args.append(bias.reshape(B, n_k, 1, block_k))
        in_specs.append(pl.BlockSpec(
            (1, n_k, 1, block_k), lambda b, i: (b // per_batch, 0, 0, 0),
            memory_space=pltpu.VMEM))
    args.append(jnp.asarray(seed, jnp.int32).reshape(1))
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    return args, in_specs, (qspec, ospec), (kvspec, vspec), lse_spec, n_q


def _stages(n, S, block_q, block_k, window=None):
    """Scratch for ``n`` staged [block_q, block_k] f32 tiles a K tile
    (_KTiles); none where one tile is the row. Under a sliding window a
    stage holds the most tiles one Q block visits."""
    import jax.numpy as jnp
    _, pltpu = _pl()
    n_k = S // block_k
    held = n_k if window is None or n_k == 1 else max(_tiles_by_block(
        S, block_q, block_k, window))
    return [pltpu.VMEM((held, block_q, block_k), jnp.float32)] * (
        n if n_k > 1 else 0)


def _compiler_params(interpret, vmem_limit_bytes=None):
    """Grid axis 0 (batch*head) is independent; axis 1 (Q blocks) must run
    in order -- the backward accumulates dK/dV over it in scratch and writes
    them on the last block. The interpreter takes no Mosaic parameters."""
    if interpret:
        return {}
    _, pltpu = _pl()
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes)}


import jax as _jax  # custom_vjp and jit must wrap at def time

# the kernel calls' arguments after the arrays, all static
_STATIC = ("scale", "dropout", "causal", "interpret", "block_q", "block_k",
           "window")


def _flash(q, k, v, bias, seed, scale, dropout, causal, interpret,
           block_q=None, block_k=None, window=None):
    """The flash kernels, differentiable in q, k and v: _flash_stats' output
    alone."""
    return _flash_stats(q, k, v, bias, seed, scale, dropout, causal,
                        interpret, block_q, block_k, window)[0]


def sliding_window(window, S, causal):
    """The window a lowering applies: None for none, and for one that
    reaches every key of every query (``window >= S``: plain causal
    attention, and lowered as that). A window needs ``causal``."""
    if not window:
        return None
    if not causal:
        raise ValueError("fused_attention: a sliding window needs causal")
    return int(window) if window < S else None


def _blocks(S, causal, block_q=None, block_k=None, window=None):
    """(block_q, block_k) as given, None taking ``default_block_q`` /
    ``default_block_k``; both must divide S in multiples of _MIN_BLK_Q."""
    if block_q is None:
        block_q = default_block_q(S, causal, window)
    if block_k is None:
        block_k = default_block_k(S, causal, window)
    for name, block in (("block_q", block_q), ("block_k", block_k)):
        if S % block or block % _MIN_BLK_Q:
            raise ValueError(
                f"flash attention: {name}={block} must divide S={S} and be "
                f"a multiple of {_MIN_BLK_Q}")
    return block_q, block_k


def _flash_stats(q, k, v, bias, seed, scale, dropout, causal, interpret,
                 block_q=None, block_k=None, window=None, kept=None):
    """(out, lse) of the flash kernels: the output, differentiable in q, k
    and v, and the rows' softmax statistic ``lse`` [B, H, 1, S] float32 (the
    log of the sum of exp over a row's scores, bias and mask included),
    which carries no gradient: it is what _bwd_call reads in place of a
    pass for the max and the sum. ``block_q`` (Q rows a grid step) and
    ``block_k`` (columns a K tile, both kernels) divide S. ``window``:
    ``sliding_window``. ``kept``: called (at trace time) where JAX
    differentiates this call itself, through ``_flash_fwd``: the backward
    kernel then reads the ``lse`` that call kept."""
    window = sliding_window(window, q.shape[2], causal)
    return _flash_vjp(q, k, v, bias, seed, scale, dropout, causal, interpret,
                      *_blocks(q.shape[2], causal, block_q, block_k, window),
                      window, kept)


@functools.partial(_jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash_vjp(q, k, v, bias, seed, scale, dropout, causal, interpret,
               block_q, block_k, window, kept):
    return _fwd_call(q, k, v, bias, seed, scale, dropout, causal, interpret,
                     block_q, block_k, window)


# Each kernel call sits behind a jit of its own. The layers of a model call
# it with the same shapes and static arguments: the first call traces the
# kernel body and lowers it to Mosaic, the others find that trace, and the
# lowered module holds one function a kernel, called once a layer. Without
# it every call is traced and lowered by itself: 4 s of set-up at 12 layers
# (compile.trace_lower_s 8.3 against 4.4 s, ledger, PR 26).
@functools.partial(_jax.jit, static_argnames=_STATIC)
def _fwd_call(q, k, v, bias, seed, scale, dropout, causal, interpret,
              block_q, block_k, window=None):
    """(out [B, H, S, D], lse [B, H, 1, S] float32: the kernel's own
    [B * H, 1, S] with the leading dim split, which costs XLA nothing; as
    [B, H, S] it is a relayout copy each way, a row of S lanes a head
    being tiled (1, 128) and a [H, S] matrix (8, 128))."""
    import jax
    import jax.numpy as jnp
    pl, _ = _pl()
    B, H, S, D = q.shape
    Dv = v.shape[3]
    args, in_specs, (_, ospec), _, lse_spec, n_q = _operands(
        q, k, v, bias, seed, block_q, block_k)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale, dropout, causal,
                          bias is not None, block_k, window=window),
        grid=(B * H, n_q),
        in_specs=in_specs,
        out_specs=[ospec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32)],
        scratch_shapes=_stages(1, S, block_q, block_k, window),
        interpret=interpret,
        **_compiler_params(interpret, fwd_vmem_limit_bytes(D)),
    )(*args)
    return out.reshape(B, H, S, Dv), lse.reshape(B, H, 1, S)


def _flash_fwd(q, k, v, bias, seed, scale, dropout, causal, interpret,
               block_q, block_k, window, kept):
    out, lse = _fwd_call(q, k, v, bias, seed, scale, dropout, causal,
                         interpret, block_q, block_k, window)
    # The inputs and the rows' statistic. Under a direct jax.vjp (the tests,
    # the tuner) nothing lowers a second forward. A Program's grad op does
    # not come here where its op declares Lse (fused_attention_grad calls
    # _bwd_call with the forward op's own); one that does (a desc older
    # than that output) lowers the forward kernel a second time for it,
    # which XLA merges with the forward op's own call: same kernel, same
    # operands (24 Mosaic calls for 12 layers either way; compiled for a
    # described v5e, tests/test_pallas_attention_mosaic.py). An op in a
    # sub-block that is differentiated as a whole (a scan op that keeps its
    # pullback, a remat_segment) has no grad op and comes here: it is told
    # (``kept``) that its backward reads the statistic kept here.
    if kept is not None:
        kept()
    return (out, lse), (q, k, v, bias, seed, lse)


@functools.partial(_jax.jit, static_argnames=_STATIC)
def _bwd_call(q, k, v, bias, seed, g, lse, scale, dropout, causal, interpret,
              block_q, block_k, window=None):
    """(dq, dk, dv) from the cotangent ``g`` of the output and the forward
    kernel's ``lse`` (same blocks, same seed: the mask is drawn again)."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl()
    B, H, S, D = q.shape
    kv, Dv = k.shape[1], v.shape[3]
    group = H // kv
    args, in_specs, (qspec, ospec), (kvspec, vspec), lse_spec, n_q = \
        _operands(q, k, v, bias, seed, block_q, block_k, by_kv_head=True)
    args += [g.reshape(B * H, S, Dv), lse.reshape(B * H, 1, S)]
    in_specs += [ospec, lse_spec]
    # dK^T, dV^T by K tile; with more tiles than one, the p and dP tiles a
    # Q block keeps between its passes
    scratch = ([pltpu.VMEM((S // block_k, width, block_k), jnp.float32)
                for width in (D, Dv)]
               + _stages(2, S, block_q, block_k, window))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale, dropout, causal,
                          bias is not None, group, block_k, window=window),
        grid=(B * kv, group * n_q),
        in_specs=in_specs,
        out_specs=[qspec, kvspec, vspec],
        out_shape=[jax.ShapeDtypeStruct((B * x.shape[1], S, x.shape[3]),
                                        x.dtype) for x in (q, k, v)],
        scratch_shapes=scratch,
        interpret=interpret,
        **_compiler_params(interpret, BWD_VMEM_LIMIT_BYTES),
    )(*args)
    return (dq.reshape(B, H, S, D), dk.reshape(B, kv, S, D),
            dv.reshape(B, kv, S, Dv))


def _flash_bwd(scale, dropout, causal, interpret, block_q, block_k, window,
               kept, res, g):
    import jax
    import jax.numpy as jnp
    import numpy as np
    q, k, v, bias, seed, lse = res
    dq, dk, dv = _bwd_call(q, k, v, bias, seed, g[0], lse, scale, dropout,
                           causal, interpret, block_q, block_k, window)
    return (dq, dk, dv, None if bias is None else jnp.zeros_like(bias),
            np.zeros(np.shape(seed), jax.dtypes.float0))


_flash_vjp.defvjp(_flash_fwd, _flash_bwd)


def supports_pallas(B, H, S, D, bias_shape, dropout, is_tpu):
    """Shape/placement gate for the Pallas lowering."""
    if S % _MIN_BLK_Q != 0 or S < _MIN_BLK_Q:
        return False
    if dropout and not is_tpu:
        return False  # in-kernel PRNG has no interpreter lowering
    if bias_shape is not None:
        # only [B,1,1,S]-broadcastable bias rows are supported fused
        if len(bias_shape) != 4 or bias_shape[1] != 1 or bias_shape[2] != 1:
            return False
    return True


# --------------------------------------------------------------------------------------
# registry op
# --------------------------------------------------------------------------------------

def _plan(ctx, q, k, v, bias):
    """Which lowering this op takes, from its attrs, its shapes and where it
    is lowered: ``(impl, scale, dropout, causal, blocks, window, shards)``
    with ``impl`` one of 'ulysses', 'ring', 'pallas', 'xla', ``blocks`` the
    kernels' (block_q, block_k), None without them, ``window`` the
    sliding window the lowering applies (``sliding_window``: None for none
    and for one no shorter than S) and ``shards`` the devices of the data
    axis over which the kernels run on each device's own batch rows
    (``ctx.island``; 1: no island). The forward op and its grad op both ask
    here, so the two cannot disagree; what cannot run raises."""
    B, H, S, D = q.shape
    kv_heads = k.shape[1]
    scale = float(ctx.attr("scale") or (1.0 / math.sqrt(D)))
    dropout = 0.0 if ctx.attr("is_test", False) else float(
        ctx.attr("dropout_prob", 0.0))
    causal = bool(ctx.attr("causal", False))
    window = sliding_window(ctx.attr("window", 0), S, causal)
    impl = ctx.attr("impl", "auto")
    from . import pallas_mode
    is_tpu = pallas_mode.on_tpu()

    gm = ctx.gspmd_mesh
    sp_n = gm.shape.get("sp", 1) if gm is not None else 1
    if kv_heads != H and (sp_n > 1 or impl in ("ring", "ulysses")):
        raise NotImplementedError(
            "fused_attention: grouped-query attention (fewer key/value than "
            "query heads) under sequence parallelism (ring / ulysses) is "
            "not built yet")
    if window is not None and (sp_n > 1 or impl in ("ring", "ulysses")):
        raise NotImplementedError(
            "fused_attention: a sliding window under sequence parallelism "
            "(ring / ulysses) is not built yet")
    ring_ok = sp_n > 1 and S % sp_n == 0 and (
        bias is None or (len(bias.shape) == 4 and bias.shape[1] == 1
                         and bias.shape[2] == 1))
    if impl == "ring" and not ring_ok:
        raise ValueError(
            f"fused_attention impl='ring' needs a GSPMD mesh with sp>1 "
            f"dividing S and a [B,1,1,S] bias; got sp={sp_n}, S={S}, "
            f"bias={None if bias is None else bias.shape}")
    if impl == "ulysses":
        mp_n = gm.shape.get("mp", 1) if gm is not None else 1
        h_local = H // mp_n if mp_n > 1 and H % mp_n == 0 else H
        if not (ring_ok and h_local % sp_n == 0):
            raise ValueError(
                f"fused_attention impl='ulysses' needs a GSPMD mesh with "
                f"sp>1 dividing S and the per-mp-shard head count, and a "
                f"[B,1,1,S] bias; got sp={sp_n}, S={S}, H={H} "
                f"({h_local} heads per mp shard), "
                f"bias={None if bias is None else bias.shape}")
        return "ulysses", scale, dropout, causal, None, None, 1
    if ring_ok and impl in ("auto", "ring"):
        return "ring", scale, dropout, causal, None, None, 1

    bias_shape = None if bias is None else bias.shape
    # under a mesh of several devices the kernels run in an island over the
    # data axis, each device on its own batch rows (ctx.island), where the
    # batch divides over it and the op has neither bias nor dropout (a
    # padding row's bias and the mask's seed would have to be cut with the
    # batch); else 'auto' takes the composed lowering at every S and
    # whatever a persisted decision says
    shards = 1 if bias is not None or dropout else ctx.data_shards(B)
    kernels = pallas_mode.lowers_kernels(
        ctx, impl, supports_pallas(B // shards, H, S, D, bias_shape, dropout,
                                   is_tpu),
        "fused_attention",
        f"requires S % {_MIN_BLK_Q} == 0, a [B,1,1,S] bias, and (for "
        f"dropout>0) a TPU; got S={S}, bias={bias_shape}, "
        f"dropout={dropout}, backend_tpu={is_tpu}. Use impl='auto' to let "
        f"the op choose the composed lowering.", shards=shards)
    # impl='auto' backend + block sizes are tunable choice points: a
    # persisted autotune decision (PADDLE_TPU_TUNE=cached/search) answers
    # where there is one, else the defaults measured on the v5e
    # (AUTO_PALLAS_MIN_S, default_block_q, default_block_k).
    from ..tuning import decide as _decide
    tune_params = {"b": B // shards, "h": H, "s": S, "d": D,
                   "dtype": str(q.dtype),
                   "has_bias": bias is not None, "dropout": dropout,
                   "causal": causal, "scale": scale}
    if window is not None:      # a bucket of its own, and its own defaults
        tune_params["window"] = window
    if kernels and (impl == "pallas" or _decide(
            "fused_attention.backend", tune_params) == "pallas"):
        return "pallas", scale, dropout, causal, tuple(int(b) for b in _decide(
            "fused_attention.block_sizes", tune_params)), window, shards
    return "xla", scale, dropout, causal, None, window, 1


def _kernel_seed(ctx, dropout):
    """The step's seed for the kernels' dropout mask, which they read for
    nothing else; the forward op and its grad op draw the same (one salt).
    A test-mode op draws none, like the dropout op under is_test: an
    inference program then holds no random op (0.5 s of set-up for a
    threefry lowering nothing reads). A training op without dropout draws
    it all the same: olmoe_1b_7b.pretrain_s4096, which has no other random
    op, runs 0.8% slower without it (XLA's schedule; chip runs, PR 27)."""
    import jax
    import jax.numpy as jnp
    if dropout or not ctx.attr("is_test", False):
        return jax.random.randint(ctx.rng(), (), 0, 2**31 - 1, jnp.int32)
    return jnp.int32(0)


@register("fused_attention", nondiff_inputs=("Bias",),
          nondiff_outputs=("Lse",))
def fused_attention(ctx, ins):
    """softmax(Q K^T * scale + Bias) V.

    Inputs: Q [B, heads, S, D], K [B, kv_heads, S, D], V [B, kv_heads, S,
    Dv] (Dv is D but for latent attention with values narrower than its
    keys; Out is then [B, heads, S, Dv]) with kv_heads
    dividing heads (grouped-query attention: query head i reads key/value
    head i // (heads / kv_heads), in place -- no lowering repeats K or V);
    optional Bias [B, 1, 1, S] additive (already -inf-masked). Attrs: scale (default 1/sqrt(D)), dropout_prob, causal,
    window (0: none; with causal, query i sees the keys i - window < j <= i:
    the kernels then visit the K tiles a Q block's window reaches and mask
    both edges, the composed lowering masks the scores; a window of S or
    more is plain causal attention and lowers as that),
    is_test, impl ('auto' | 'pallas' | 'ring' | 'ulysses' | 'composed').
    Outputs: Out [B, heads, S, D]; Lse [B, heads, 1, S] float32, the rows'
    softmax statistic (log of the sum of exp of a row's scores), for the
    op's own backward and no gradient's: the flash kernels write it and
    fused_attention_grad hands it to the backward kernel. The other
    lowerings have no use for it and leave zeros there that nothing reads
    (XLA drops them).

    Kernel choice (_plan): under a GSPMD jit whose mesh has an "sp" axis >1 (sequence
    parallelism), 'auto' opens the ring-attention shard_map island
    (parallel/ring_attention.py) so the sequence dim STAYS partitioned --
    GSPMD alone would all-gather K/V to every device; 'ulysses' instead does
    the all-to-all head-scatter schedule (parallel/ulysses.py, needs heads
    divisible by sp). Otherwise 'auto' is the Pallas flash kernel on
    TPU-supported shapes from S >= AUTO_PALLAS_MIN_S (at S=128 XLA's own
    fusion is measurably faster) where the jit spans one device, else the
    composed jnp path (a dp or mp mesh without sp: a Mosaic call cannot be
    partitioned by GSPMD). Which one an op took is counted at each compile
    (``attention_lowering_total``; observability/lowerings.py).
    """
    import jax
    import jax.numpy as jnp

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = ins.get("Bias", [None])[0]
    B, H, S, D = q.shape
    kv_heads = k.shape[1]
    if k.shape[3] != D:
        raise ValueError(f"fused_attention: q heads of {D} against k heads "
                         f"of {k.shape[3]}")
    if H % kv_heads or v.shape[1] != kv_heads:
        raise ValueError(
            f"fused_attention: {H} query heads over {kv_heads} key and "
            f"{v.shape[1]} value heads; the query heads must be a multiple "
            f"of the key/value heads")

    def no_stats():
        return jnp.zeros((B, H, 1, S), jnp.float32)

    if ctx.abstract:
        # eval_shape inference: mesh/backend are unknown here, and every impl
        # produces the same output shapes -- lower the composed path and
        # defer impl validation to the executor's real lowering
        scale = ctx.attr("scale") or (1.0 / math.sqrt(D))
        return {"Out": [composed_attention(
            q, k, v, bias, float(scale), 0.0, bool(ctx.attr("causal", False)),
            ctx.rng(), window=ctx.attr("window", 0))], "Lse": [no_stats()]}

    impl, scale, dropout, causal, blocks, window, shards = _plan(
        ctx, q, k, v, bias)
    block_q, block_k = blocks or (0, 0)
    ctx.report("attention_lowering_total", impl=impl, s=S, block_q=block_q,
               block_k=block_k, kv_heads=kv_heads, window=window or 0,
               heads=H, head_dim=D,
               value_dim=0 if v.shape[3] == D else v.shape[3],
               mesh="island" if shards > 1 else "none")
    if impl == "pallas":
        from . import pallas_mode
        for state, tiles in zip(("visited", "skipped"),
                                k_tiles(S, *blocks, causal, window)):
            ctx.report("attention_k_tiles_total", tiles, state=state,
                       window=window or 0)
        seed, interpret = _kernel_seed(ctx, dropout), pallas_mode.interpret()
        # (the forward a generic grad op lowers again reports for itself)
        kept = None if ctx.under_grad else functools.partial(
            ctx.report, "attention_backward_total", stats="saved")
        out, lse = ctx.island(
            lambda q, k, v: _flash_stats(q, k, v, bias, seed, scale, dropout,
                                         causal, interpret, *blocks, window,
                                         kept),
            (q, k, v), (True, True, True), shards)
        return {"Out": [out], "Lse": [lse]}
    if impl == "xla":
        out = composed_attention(q, k, v, bias, scale, dropout, causal,
                                 ctx.rng(), ctx.bernoulli_mask, window)
    else:
        gm = ctx.gspmd_mesh
        seed = jax.random.randint(ctx.rng(), (), 0, 2**31 - 1, jnp.int32)
        if impl == "ulysses":
            from ..parallel import ulysses as _uly
            out = _uly.ulysses_attention(q, k, v, bias, scale, dropout,
                                         causal, seed, gm)
        else:
            from ..parallel import ring_attention as _ring
            out = _ring.ring_attention(q, k, v, bias, scale, dropout, causal,
                                       seed, gm)
    return {"Out": [out], "Lse": [no_stats()]}


@register_grad("fused_attention")
def fused_attention_grad(ctx, ins, generic):
    """dQ, dK, dV. Where the forward op took the flash kernels (_plan, asked
    again with the forward's attrs, shapes and mesh) and declared ``Lse``,
    the backward kernel alone, on the forward's statistics, blocks and seed:
    no forward is lowered here, so none can survive beside it. Every other
    case is the generic grad (``jax.vjp`` over the forward's lowering): the
    composed lowering, a mesh, and a desc from before the op had ``Lse``
    (on the kernels that one lowers the forward kernel a second time for
    the statistics). Which it was is reported as
    ``attention_backward_total``."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = ins.get("Bias", [None])[0]
    lse, g = ins.get("Lse", [None])[0], ins.get("Out@GRAD", [None])[0]
    impl, scale, dropout, causal, blocks, window, shards = _plan(
        ctx, q, k, v, bias)
    if impl != "pallas" or lse is None or g is None:
        ctx.report("attention_backward_total",
                   stats="recomputed" if impl == "pallas" else "generic")
        return generic()
    from . import pallas_mode
    ctx.report("attention_backward_total", stats="saved")
    seed, interpret = _kernel_seed(ctx, dropout), pallas_mode.interpret()
    dq, dk, dv = ctx.island(
        lambda q, k, v, g, lse: _bwd_call(
            q, k, v, bias, seed, g, lse, scale, dropout, causal, interpret,
            *blocks, window),
        (q, k, v, g.astype(q.dtype), lse), (True,) * 5, shards)
    return {"Q@GRAD": [dq], "K@GRAD": [dk], "V@GRAD": [dv]}
