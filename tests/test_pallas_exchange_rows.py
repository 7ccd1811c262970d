"""The change of order of an exchange's received rows as the kernel of
``ops/pallas_exchange_rows.py`` (in the interpreter: tests/conftest.py)
against the composed gathers of ``ops/collective.py:RowExchange`` it
replaces on a TPU, bit for bit: plans under even and skewed routers with
ample, tight and overflowing budgets on both wires, both ways and there and
back; and the edges the slabs make, from segment tables written by hand:
every shift, empty and short segments, a segment on the buffer's last row,
a buffer all live and one with no live row."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_exchange_rows
from paddle_tpu.ops.collective import RowExchange

N, E = 4, 8


def bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def rows_of(rows, width, dtype, seed=0):
    """No row like another, none zero, negative zeros among them."""
    x = np.random.RandomState(seed).randn(rows, width).astype("float32")
    x[x == 0] = 1.0
    x[::7, ::5] = -0.0
    return jnp.asarray(x, dtype)


def by_hand(x, src, dst, length):
    out = np.zeros_like(np.asarray(x))
    for s, d, n in zip(src, dst, length):
        out[d:d + n] = np.asarray(x)[s:s + n]
    return out


def moved(x, src, dst, length):
    return pallas_exchange_rows.move_segments(
        x, *(jnp.asarray(v, jnp.int32) for v in (src, dst, length)),
        interpret=True)


def counts(routing, seed):
    """``cnt [N, E]``: under ``skewed`` device 0's two experts are
    preferred three times over (tests/test_moe_exchange.py's brute-force
    cases, ten times the rows)."""
    rng = np.random.RandomState(seed)
    lam = np.where(np.arange(E) < E // N, 90.0, 30.0) if (
        routing == "skewed") else np.full(E, 30.0)
    return rng.poisson(lam, (N, E)).astype(np.int32)


def plan_of(cnt, budget, impl, kernel=None, me=0):
    return RowExchange(jnp.asarray(cnt), "dp", N, budget, impl,
                       kernel=kernel, me=jnp.int32(me))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("impl", ["ragged", "padded"])
@pytest.mark.parametrize("budget", [1024, 512, 384, 128],
                         ids=["ample", "tight", "overflowing", "tiny"])
@pytest.mark.parametrize("routing", ["even", "skewed"])
def test_a_plan_both_ways_and_there_and_back(routing, budget, impl, dtype):
    cnt = counts(routing, budget)
    composed, kernel = plan_of(cnt, budget, impl), plan_of(cnt, budget, impl,
                                                           kernel=True)
    sm_start, em_start, mine = (np.asarray(v) for v in composed.segments)
    live, dropped = int(composed.live), int(composed.dropped)
    assert mine.sum() == live <= budget
    if budget <= 384 and routing == "skewed":
        assert dropped > 0      # the last sources' runs are cut
    got = rows_of(budget, 128, dtype)
    # out: source by source -> expert by expert; the live rows lead
    out = kernel.by_expert(got)
    want = np.asarray(got[composed.to_expert_major])
    assert out.dtype == got.dtype
    assert np.array_equal(bits(out)[:live], bits(want)[:live])
    assert not bits(out)[live:].any()
    assert np.array_equal(bits(out), bits(by_hand(
        got, sm_start.T.ravel(), em_start.T.ravel(), mine.T.ravel())))
    # back: the rows a source's segments cover (a padded wire's parts have
    # room behind them)
    y = rows_of(budget, 128, dtype, seed=1)
    covered = np.zeros(budget, bool)
    for s, n in zip(sm_start.ravel(), mine.ravel()):
        covered[s:s + n] = True
    back = kernel.by_source(y)
    want = np.asarray(y[composed.to_source_major])
    assert np.array_equal(bits(back)[covered], bits(want)[covered])
    assert not bits(back)[~covered].any()
    # there and back: every kept row returns to its place
    again = kernel.by_source(out)
    assert np.array_equal(bits(again)[covered], bits(got)[covered])
    assert not bits(again)[~covered].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_cells_width(dtype):
    cnt = counts("skewed", 7)
    composed, kernel = plan_of(cnt, 512, "ragged"), plan_of(
        cnt, 512, "ragged", kernel=True)
    live = int(composed.live)
    got = rows_of(512, 2304, dtype)
    out = kernel.by_expert(got)
    assert np.array_equal(bits(out)[:live],
                          bits(got[composed.to_expert_major])[:live])
    assert not bits(out)[live:].any()
    back = kernel.by_source(out)
    assert np.array_equal(bits(back)[:live], bits(got)[:live])


@pytest.mark.parametrize("shift", range(16))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_every_shift_between_source_and_destination(dtype, shift):
    # three segments in turn, the middle one ``shift`` rows off its place
    x = rows_of(256, 128, dtype)
    src, dst, length = [0, 40 + shift, 150], [0, 40, 100], [40, 60, 50]
    src[2] = 160 + shift
    assert np.array_equal(bits(moved(x, src, dst, length)),
                          bits(by_hand(x, src, dst, length)))


EDGES = {
    # (src, dst, length) over 256 rows
    "an_empty_segment": ([0, 90, 90, 7], [0, 60, 60, 100], [60, 0, 40, 83]),
    "segments_shorter_than_a_tile": (
        [200, 3, 77, 130, 18, 250], [0, 5, 6, 9, 16, 19], [5, 1, 3, 7, 3, 2]),
    "a_segment_ends_on_the_last_row": (
        [100, 0], [0, 156], [156, 100]),
    "a_segment_starts_from_the_last_rows": (
        [156, 0], [0, 100], [100, 156]),
    "live_is_the_budget": ([128, 0, 192, 64], [0, 64, 128, 192],
                           [64, 64, 64, 64]),
    "no_live_row": ([0, 0, 0], [0, 0, 0], [0, 0, 0]),
    "one_row": ([255], [0], [1]),
    "room_between_the_parts": ([0, 64, 128], [10, 100, 200], [30, 64, 56]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("edge", sorted(EDGES))
def test_the_edges_the_slabs_make(edge, dtype):
    x = rows_of(256, 128, dtype)
    src, dst, length = EDGES[edge]
    assert np.array_equal(bits(moved(x, src, dst, length)),
                          bits(by_hand(x, src, dst, length)))


def test_blocks_and_pieces_at_their_edges():
    """Several grid steps, a segment across a block's edge and across
    pieces, and many segments inside one piece."""
    rows = 3 * pallas_exchange_rows.BLOCK_ROWS
    rng = np.random.RandomState(3)
    length = np.concatenate([rng.randint(0, 9, 40), [1500, 700],
                             rng.randint(100, 200, 4)]).astype(np.int32)
    assert length.sum() < rows
    dst = np.cumsum(length) - length
    src = np.empty_like(dst)
    at = 5
    for i in rng.permutation(len(length)):
        src[i], at = at, at + length[i]
    x = rows_of(rows, 128, "bfloat16")
    assert np.array_equal(bits(moved(x, src, dst, length)),
                          bits(by_hand(x, src, dst, length)))


def test_what_the_kernel_takes():
    takes = pallas_exchange_rows.supports
    assert takes(81920, 2304, "bfloat16") and takes(81920, 128, "float32")
    # 8 MiB of rows a grid step at most
    assert pallas_exchange_rows.block_rows_of(81920, 2304, "bfloat16") == 1280
    assert pallas_exchange_rows.block_rows_of(81920, 128, "float32") == 2048
    assert pallas_exchange_rows.block_rows_of(1024, 8192, "float32") == 256
    assert not takes(81920, 2304, "int8")       # 1 byte an element
    assert not takes(81920, 64, "bfloat16")     # no whole vreg of lanes
    assert not takes(96, 128, "bfloat16")       # no whole piece of rows
