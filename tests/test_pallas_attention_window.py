"""Sliding-window attention: the flash kernels (Pallas interpreter on the
CPU: the body that compiles on the TPU) and the composed lowering against a
mask written out -- key j visible to query i iff i - window < j <= i --,
outputs, the rows' statistic and every gradient; the tiles a Q block visits
against a count by hand; the op and its grad lowering through a Program; the
counters' labels."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.ops import pallas_attention as pa

SCALE = 0.125


def _qkv(H, kv, S, D=32, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(1, h, S, D), jnp.float32)
            for h in (H, kv, kv, H)]


def explicit(q, k, v, window):
    """(out, lse) with the [S, S] mask written out, K and V repeated."""
    H, S = q.shape[1], q.shape[2]
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * SCALE
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    s = jnp.where((j <= i) & (j > i - window), s, -jnp.inf)
    return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v),
            jax.nn.logsumexp(s, -1))


def both(f, q, k, v, g):
    (out, lse), vjp = jax.vjp(f, q, k, v)
    return [out, lse] + list(vjp((g, jnp.zeros_like(lse))))


# S of one tile and of several; the window under, equal to and over a K
# tile, and one key wide; groups of 1, 6 and 9 query heads a key/value head
CASES = [
    # S, block_q, block_k, window, heads, kv heads
    (128, 128, 128, 48, 2, 2),        # one tile is the row
    (256, None, None, 64, 2, 1),      # the defaults below the tiled lengths
    (512, 128, 128, 100, 6, 1),       # window under a tile, group 6
    (512, 128, 128, 128, 9, 1),       # equal to a tile, group 9
    (512, 128, 128, 300, 2, 2),       # over two tiles: clear tiles between
    (512, 256, 128, 200, 6, 1),       # a Q block of two tiles
    (512, 128, 256, 200, 2, 2),       # a K tile of two Q blocks
    (512, 128, 128, 1, 2, 1),         # each query sees itself alone
    (384, 128, 128, 129, 3, 3),       # one key over a tile
]


@pytest.mark.parametrize("S,block_q,block_k,window,H,kv", CASES)
def test_window_kernels_against_the_mask_written_out(S, block_q, block_k,
                                                     window, H, kv):
    q, k, v, g = _qkv(H, kv, S)
    with jax.default_matmul_precision("highest"):
        want = both(lambda q, k, v: explicit(q, k, v, window), q, k, v, g)
        got = both(lambda q, k, v: tuple(
            x if i == 0 else x[:, :, 0] for i, x in enumerate(pa._flash_stats(
                q, k, v, None, jnp.int32(7), SCALE, 0.0, True, True, block_q,
                block_k, window))), q, k, v, g)
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=5e-6, err_msg=name)


@pytest.mark.parametrize("window,H,kv", [(100, 6, 1), (128, 9, 1),
                                          (300, 2, 2), (1, 2, 1)])
def test_composed_lowering_against_the_mask_written_out(window, H, kv):
    q, k, v, g = _qkv(H, kv, 256)
    with jax.default_matmul_precision("highest"):
        want_out, _ = explicit(q, k, v, window)
        want = jax.vjp(lambda q, k, v: explicit(q, k, v, window)[0],
                       q, k, v)[1](g)
        out, vjp = jax.vjp(lambda q, k, v: pa.composed_attention(
            q, k, v, None, SCALE, 0.0, True, None, window=window), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=0, atol=5e-6)
    for a, b in zip(vjp(g), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=5e-6)


@pytest.mark.parametrize("window", [256, 257, 1 << 30])
def test_a_window_of_the_sequence_or_more_is_plain_causal_bit_for_bit(window):
    q, k, v, g = _qkv(2, 1, 256)

    def run(window):
        return both(lambda q, k, v: pa._flash_stats(
            q, k, v, None, jnp.int32(7), SCALE, 0.0, True, True, 128, 128,
            window), q, k, v, g)
    for a, b in zip(run(window), run(None)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert pa.sliding_window(window, 256, True) is None
    assert pa.sliding_window(255, 256, True) == 255


def test_a_window_needs_causal():
    q, k, v, _ = _qkv(2, 2, 128)
    with pytest.raises(ValueError, match="causal"):
        pa._flash(q, k, v, None, jnp.int32(7), SCALE, 0.0, False, True,
                  None, None, 64)


def _tiles_by_hand(S, block_q, block_k, window):
    """[Q blocks, K tiles]: whether the tile holds a visible (query, key)
    pair, counted pair by pair."""
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    seen = (j <= i) & (j > i - window)
    return seen.reshape(S // block_q, block_q, S // block_k, block_k).any(
        axis=(1, 3))


@pytest.mark.parametrize("S,block_q,block_k,window,want", [
    (4096, 512, 512, 512, 15), (4096, 512, 1024, 512, 11),
    (4096, 256, 256, 512, 45), (512, 128, 128, 300, 10),
    (512, 128, 128, 1, 4)])
def test_tiles_visited_are_those_that_hold_a_visible_pair(S, block_q, block_k,
                                                          window, want):
    by_hand = _tiles_by_hand(S, block_q, block_k, window)
    visited, skipped = pa.k_tiles(S, block_q, block_k, True, window)
    assert visited == want == by_hand.sum()
    assert visited + skipped == by_hand.size
    # a stage holds the most one Q block visits, not a tile a K tile
    assert pa._tiles_by_block(S, block_q, block_k, window) == \
        by_hand.sum(axis=1).tolist()


def test_default_blocks_follow_the_window():
    assert (pa.default_block_q(4096, True, 512),
            pa.default_block_k(4096, True, 512)) == pa.WINDOW_BLOCKS
    # no window, or one of the sequence: the causal tiles as before
    assert (pa.default_block_q(4096, True),
            pa.default_block_k(4096, True)) == pa.CAUSAL_BLOCKS
    assert pa.default_block_k(512, True, 128) == 512       # one tile a row
    assert pa.default_block_q(512, True, 128) == 512


def _program(window, impl, H=6, kv=1, S=256, D=32):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        q = fluid.data("q", [1, H, S, D], "float32", **A)
        k = fluid.data("k", [1, kv, S, D], "float32", **A)
        v = fluid.data("v", [1, kv, S, D], "float32", **A)
        for x in (q, k, v):
            x.stop_gradient = False
        out = fluid.layers.fused_attention(q, k, v, causal=True, scale=SCALE,
                                           impl=impl, window=window)
        loss = fluid.layers.reduce_sum(fluid.layers.square(out))
        fluid.append_backward(loss)
    return main, startup, out


@pytest.mark.parametrize("impl", ["pallas", "composed"])
def test_the_op_and_its_grad_lowering_carry_the_window(impl):
    """Through a Program: the layer's ``window`` reaches the forward kernel,
    the grad lowering's backward kernel (on the forward's ``Lse``) and the
    composed lowering, and the three agree with the mask written out."""
    window = 100
    q, k, v, _ = _qkv(6, 1, 256)
    main, startup, out = _program(window, impl)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    got = exe.run(main, feed={"q": np.asarray(q), "k": np.asarray(k),
                              "v": np.asarray(v)},
                  fetch_list=[out.name, "q@GRAD", "k@GRAD", "v@GRAD"],
                  scope=scope)
    with jax.default_matmul_precision("highest"):
        want_out, vjp = jax.vjp(
            lambda q, k, v: explicit(q, k, v, window)[0], q, k, v)
        want = [want_out] + list(vjp(2 * want_out))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=2e-5)
    exe.close()


def test_counters_carry_window_heads_and_head_size():
    """Three ops as ``fused_attention`` reports them at the Laguna cell's
    shapes (two window layers, one full), through the pass the executor
    runs."""
    import lowering_reports
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops.pallas_attention import k_tiles
    main = fluid.Program()
    for salt, block_k, window, heads in ((1, 512, 512, 72), (2, 512, 512, 72),
                                         (3, 1024, 0, 48)):
        ctx = LowerCtx({}, salt=salt, program=main)
        ctx.report("attention_lowering_total", impl="pallas", s=4096,
                   block_q=512, block_k=block_k, kv_heads=8, window=window,
                   heads=heads, head_dim=128)
        for state, tiles in zip(("visited", "skipped"), k_tiles(
                4096, 512, block_k, True, window or None)):
            ctx.report("attention_k_tiles_total", tiles, state=state,
                       window=window)
    registry = lowering_reports.publish(main, "step")
    assert lowering_reports.read(
        registry, "attention_lowering_total", "window", "heads",
        "head_dim") == {("512", "72", "128"): 2, ("0", "48", "128"): 1}
    assert lowering_reports.read(
        registry, "attention_k_tiles_total", "state", "window") == {
            ("visited", "512"): 30, ("skipped", "512"): 98,
            ("visited", "0"): 20, ("skipped", "0"): 12}


def test_the_layer_refuses_a_window_without_causal():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        q, k, v = (fluid.data(n, [1, 2, 128, 32], "float32",
                              append_batch_size=False) for n in "qkv")
        with pytest.raises(ValueError, match="causal"):
            fluid.layers.fused_attention(q, k, v, window=8)
