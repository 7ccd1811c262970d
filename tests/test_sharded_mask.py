"""A dropout mask under a GSPMD mesh is drawn shard by shard, on the device
that uses the shard (``LowerCtx.bernoulli_mask``): XLA's SPMD partitioner has
no rule for ``RngBitGenerator`` and would draw the global mask on every
device. The suite's 8 host devices carry the meshes."""
import re

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core.registry import LowerCtx

P_DROP = 0.25
B, WIDTH = 64, 256


def _strategy(mesh_shape, **kw):
    return fluid.DistributedStrategy(mesh_shape=mesh_shape, **kw)


def _dropout_program(p=P_DROP, width=WIDTH, seed=11, n_dropout=1):
    """``sum(dropout(x) * c)`` with x's gradient: fetches (Out, Mask of the
    first dropout, dX)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [width], "float32")
        x.stop_gradient = False
        c = fluid.data("c", [width], "float32")
        h = x
        for _ in range(n_dropout):
            h = fluid.layers.dropout(
                h, p, dropout_implementation="upscale_in_train")
        loss = fluid.layers.reduce_sum(h * c)
        dx, = fluid.gradients(loss, x)
    mask = next(op for op in main.global_block().ops
                if op.type == "dropout").outputs["Mask"][0]
    return main, [h.name, mask, dx.name]


def _attention_program(dropout=0.5, S=16, seed=13):
    """Composed ``fused_attention`` whose output and dV show its mask: with
    q = k = 0 the probabilities are 1/S, so with v = c = I the output is
    ``mask / (S (1 - p))`` and dV its transpose -- through the mask the grad
    op's re-lowered forward drew."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        q = fluid.data("q", [2, S, S], "float32")
        k = fluid.data("k", [2, S, S], "float32")
        v = fluid.data("v", [2, S, S], "float32")
        v.stop_gradient = False
        c = fluid.data("c", [2, S, S], "float32")
        out = fluid.layers.fused_attention(q, k, v, dropout_prob=dropout,
                                           impl="composed")
        dv, = fluid.gradients(fluid.layers.reduce_sum(out * c), v)
    return main, [out.name, dv.name]


def _attention_feed(batch, S=16):
    eye = np.broadcast_to(np.eye(S, dtype=np.float32), (batch, 2, S, S))
    zeros = np.zeros((batch, 2, S, S), np.float32)
    return {"q": zeros, "k": zeros, "v": eye.copy(), "c": eye.copy()}


def _dropout_feed(batch=B, width=WIDTH):
    rng = np.random.RandomState(0)
    return {"x": rng.rand(batch, width).astype("float32") + 0.5,
            "c": rng.rand(batch, width).astype("float32") + 0.5}


def _run(main, fetch, feed, strategy=None, runs=1, lowered=None,
         startup=None):
    """``runs`` runs of ``main`` from run counter 0 in a fresh scope (after
    ``startup`` if given), under ``strategy`` if given; with ``lowered`` a
    list, the lowered module's text (before partitioning) of every compile
    of ``main`` lands in it."""
    main._rng_run_counter = 0
    target = main if strategy is None else \
        fluid.CompiledProgram(main).with_strategy(strategy)
    real = executor_mod.Executor._aot_compile

    def spying(self, key, compiled, args):
        lowered.append(compiled.fn.lower(*args).as_text())
        return real(self, key, compiled, args)

    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        if startup is not None:
            exe.run(startup)
        if lowered is not None:
            executor_mod.Executor._aot_compile = spying
        try:
            return [[np.asarray(o) for o in
                     exe.run(target, feed=feed, fetch_list=fetch)]
                    for _ in range(runs)]
        finally:
            executor_mod.Executor._aot_compile = real


def _rng_leading_dims(text):
    """Leading dimension of every ``rng_bit_generator`` result of a lowered
    module."""
    return [int(m) for m in re.findall(
        r"stablehlo\.rng_bit_generator.*->\s*\(tensor<2xui64>, "
        r"tensor<(\d+)x[^>]*xui32>\)", text)]


def _draw_counts():
    from paddle_tpu.observability.metrics import REGISTRY
    from collections import Counter
    fam = REGISTRY.get("mask_draw_total")
    total = Counter()           # over the programs' labels
    for k, c in (fam.items() if fam is not None else ()):
        total[dict(k)["draw"], dict(k)["shards"]] += c.value
    return total


def _grown(before):
    return {k: v - before.get(k, 0) for k, v in _draw_counts().items()
            if v != before.get(k, 0)}


# ------------------------------------------------------------- (a) the draw --

def test_dp4_mask_has_the_rate_and_independent_shards():
    main, fetch = _dropout_program()
    (_, mask, _), = _run(main, fetch, _dropout_feed(), _strategy({"dp": 4}))
    keep = 1.0 - P_DROP
    assert mask.shape == (B, WIDTH)
    assert set(np.unique(mask)) == {0.0, 1.0}
    sigma = np.sqrt(keep * P_DROP / mask.size)
    assert abs(mask.mean() - keep) < 4 * sigma
    shards = np.split(mask, 4)
    agree = keep ** 2 + P_DROP ** 2
    sigma = np.sqrt(agree * (1 - agree) / shards[0].size)
    for i in range(4):
        for j in range(i + 1, 4):
            assert abs((shards[i] == shards[j]).mean() - agree) < 4 * sigma, \
                (i, j)


def test_dp4_mask_differs_run_to_run_and_repeats_from_the_same_counter():
    main, fetch = _dropout_program()
    ds = _strategy({"dp": 4})
    first, second = _run(main, fetch, _dropout_feed(), ds, runs=2)
    assert (first[1] != second[1]).mean() > 0.2
    again, = _run(main, fetch, _dropout_feed(), ds)
    assert again[1].tobytes() == first[1].tobytes()
    assert again[0].tobytes() == first[0].tobytes()


# ------------------------------------------- (b) forward and backward agree --

def test_dp4_dropout_grad_uses_the_forwards_mask():
    main, fetch = _dropout_program(p=0.5)       # 1 / (1 - p) is exact
    feed = _dropout_feed()
    (out, mask, dx), = _run(main, fetch, feed, _strategy({"dp": 4}))
    assert 0.45 < mask.mean() < 0.55
    np.testing.assert_array_equal(out, feed["x"] * mask / np.float32(0.5))
    np.testing.assert_array_equal(dx, mask * feed["c"] / np.float32(0.5))


def test_dp4_attention_relowered_forward_draws_the_forwards_mask():
    main, fetch = _attention_program()
    before = _draw_counts()
    (out, dv), = _run(main, fetch, _attention_feed(8), _strategy({"dp": 4}))
    # forward op and the forward its grad op lowers again: one note, one mask
    assert _grown(before) == {("shard", "4"): 1}
    kept = out != 0
    assert 0.4 < kept.mean() < 0.6
    np.testing.assert_array_equal(out[kept], np.float32(1 / (16 * 0.5)))
    assert dv.tobytes() == np.swapaxes(out, -1, -2).copy().tobytes()
    shards = np.split(kept, 4)
    assert 0.4 < (shards[0] == shards[1]).mean() < 0.6


def test_relowered_forward_equals_the_forward_bit_for_bit():
    """What ``registry``'s generic grad op does, by hand: the forward lowered
    again under ``jax.vjp`` with the forward's key and salt reaches the same
    island; its primal is the forward op's output to the bit."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.core import registry
    mesh = _strategy({"dp": 4}).build_mesh()
    attrs = {"dropout_prob": 0.5, "impl": "composed"}
    fwd = registry.get("fused_attention").lower
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(8, 2, 16, 8), jnp.float32)
               for _ in range(3))

    def ctx(key):
        return LowerCtx(attrs, key, 77, gspmd_mesh=mesh, data_axis="dp")

    def both(q, k, v, key):
        out = fwd(ctx(key), {"Q": [q], "K": [k], "V": [v]})["Out"][0]
        primal, vjp = jax.vjp(
            lambda q, k, v: fwd(ctx(key), {"Q": [q], "K": [k],
                                           "V": [v]})["Out"][0], q, k, v)
        return out, primal, vjp(jnp.ones_like(primal))[2]

    sh = NamedSharding(mesh, P("dp"))
    out, primal, dv = jax.jit(both, in_shardings=(
        sh, sh, sh, NamedSharding(mesh, P())))(q, k, v, jax.random.PRNGKey(3))
    assert np.asarray(out).tobytes() == np.asarray(primal).tobytes()
    assert np.isfinite(np.asarray(dv)).all()


# ------------------------------------------------- (c) the lowered modules --

def _program_of(op):
    if op == "dropout":
        main, fetch = _dropout_program()
        return main, fetch, _dropout_feed, B
    main, fetch = _attention_program()
    return main, fetch, _attention_feed, 8


@pytest.mark.parametrize("op", ["dropout", "fused_attention"])
def test_dp4_module_draws_no_mask_at_the_global_batch(op):
    main, fetch, feed_of, batch = _program_of(op)
    texts = []
    _run(main, fetch, feed_of(batch), _strategy({"dp": 4}), lowered=texts)
    dims = _rng_leading_dims(texts[0])
    assert dims and set(dims) == {batch // 4}, dims


@pytest.mark.parametrize("op", ["dropout", "fused_attention"])
def test_one_device_module_is_what_plain_bernoulli_lowers(op, monkeypatch):
    import jax
    main, fetch, feed_of, batch = _program_of(op)
    texts = []
    _run(main, fetch, feed_of(batch), lowered=texts)
    monkeypatch.setattr(
        LowerCtx, "bernoulli_mask",
        lambda self, key, keep, shape: jax.random.bernoulli(key, keep, shape))
    main._version += 1          # the same Program, compiled again
    _run(main, fetch, feed_of(batch), lowered=texts)
    assert len(texts) == 2 and texts[0] == texts[1]
    assert set(_rng_leading_dims(texts[0])) == {batch}


# ------------------------------------------------------- (d) the fall-backs --

def test_fallback_leading_dimension_not_divisible():
    main, fetch = _dropout_program()
    before, texts = _draw_counts(), []
    (_, mask, _), = _run(main, fetch, _dropout_feed(batch=6),
                         _strategy({"dp": 4}, data_rules=[(".", ())]),
                         lowered=texts)
    assert mask.shape == (6, WIDTH)
    assert _grown(before) == {("global", "1"): 1}
    assert set(_rng_leading_dims(texts[0])) == {6}


def test_dp2_mp2_island_spans_the_mesh_and_shards_over_dp_alone():
    main, fetch = _dropout_program(p=0.5)
    before, texts = _draw_counts(), []
    feed = _dropout_feed()
    (out, mask, dx), = _run(main, fetch, feed, _strategy({"dp": 2, "mp": 2}),
                            lowered=texts)
    assert _grown(before) == {("shard", "2"): 1}
    assert set(_rng_leading_dims(texts[0])) == {B // 2}
    assert 'out_shardings=[<@mesh, [{"dp"}, {}]>] manual_axes={"dp", "mp"}' \
        in texts[0]
    assert mask.shape == (B, WIDTH)
    assert abs(mask.mean() - 0.5) < 0.02
    halves = np.split(mask, 2)
    assert abs((halves[0] == halves[1]).mean() - 0.5) < 0.03
    np.testing.assert_array_equal(dx, mask * feed["c"] / np.float32(0.5))


def test_fallback_inside_the_explicit_dp_shard_map():
    """``_explicit_dp`` lowers every op inside its own ``shard_map``
    (``ctx.mesh``): the op draws its local batch with the step's per-shard
    key, as before."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [32], "float32")
        label = fluid.data("label", [1], "int64")
        h = fluid.layers.dropout(fluid.layers.fc(x, 64, act="relu"), 0.5)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(h, 10), label))
        fluid.optimizer.SGD(0.05).minimize(loss)
    ds = _strategy({"dp": 2})
    ds.comm_compression = "int8"
    ds.comm_compress_min_bytes = 0
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(16, 32).astype("float32"),
            "label": rng.randint(0, 10, (16, 1)).astype("int64")}
    mask = next(op for op in main.global_block().ops
                if op.type == "dropout").outputs["Mask"][0]
    before, texts = _draw_counts(), []
    (m,), = _run(main, [mask], feed, ds, lowered=texts, startup=startup)
    assert m.shape == (16, 64)
    assert _grown(before) == {("global", "1"): 1}
    assert set(_rng_leading_dims(texts[0])) == {8}


def test_fallback_inside_another_ops_island():
    """An op lowered inside another op's ``shard_map`` over the mesh (the
    pipeline's stages) is handed ``gspmd_mesh`` too: it opens no island
    inside the island."""
    import jax
    from jax.sharding import PartitionSpec as P
    mesh = _strategy({"dp": 4}).build_mesh()
    ctx = LowerCtx({}, jax.random.PRNGKey(0), 5, gspmd_mesh=mesh,
                   data_axis="dp")

    def stage(key):
        return ctx.bernoulli_mask(key, 0.5, (8, 4))

    out = jax.jit(jax.shard_map(stage, mesh=mesh, in_specs=P(),
                                out_specs=P("dp")))(jax.random.PRNGKey(1))
    assert out.shape == (32, 4)
    want = jax.random.bernoulli(jax.random.PRNGKey(1), 0.5, (8, 4))
    np.testing.assert_array_equal(np.asarray(out)[:8], np.asarray(want))


def test_fallback_shape_inference_draws_the_declared_shape():
    import jax
    key = jax.random.PRNGKey(0)
    got = LowerCtx({}, abstract=True).bernoulli_mask(key, 0.5, (6, 3))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jax.random.bernoulli(key, 0.5, (6, 3))))
    main, _ = _dropout_program()
    out = next(op for op in main.global_block().ops if op.type == "dropout")
    var = main.global_block().find_var_recursive(out.outputs["Mask"][0])
    assert tuple(var.shape) == (-1, WIDTH)


# ------------------------------------------------------------ (e) the counter --

@pytest.mark.parametrize("dp,want", [(4, {("shard", "4"): 2}),
                                     (1, {("global", "1"): 2})])
def test_mask_draw_total_counts_each_dropout_op_once_a_compile(dp, want):
    main, fetch = _dropout_program(n_dropout=2)
    before = _draw_counts()
    _run(main, fetch, _dropout_feed(),
         _strategy({"dp": dp}) if dp > 1 else None, runs=2)
    assert _grown(before) == want
