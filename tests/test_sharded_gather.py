"""Rows gathered along axis 0 under a GSPMD mesh leave ``gather`` laid over
the strategy's data axis (the op's island, ``ops/tensor_ops.py``): from a
data-sharded operand and replicated indices (BERT's flat ``mask_pos``) XLA's
SPMD partitioner would leave them replicated, and the whole masked-LM head
with them, forward and backward, on every device. The suite's 8 host devices
carry the meshes."""
import contextlib
import re
from collections import Counter

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core.registry import LowerCtx
from paddle_tpu.models import bert

B, S, H, V = 8, 16, 32, 96
M = 40                      # masked positions: no other size of the model
# the cell's layout (benchmark/workloads/bert_base.pretrain_s128_dp4.json):
# the flat indices belong to no shard, so the user lays them replicated
REPLICATED = [("mask_pos|mask_label", ())]


def _strategy(mesh_shape, **kw):
    return fluid.DistributedStrategy(mesh_shape=mesh_shape, **kw)


def _bert(n_mask=M):
    """A tiny float32 BERT pre-train Program without dropout: (train
    program, startup, test clone, [loss, every parameter's gradient])."""
    cfg = bert.BertConfig(vocab_size=V, hidden=H, n_layers=2, n_heads=2,
                          ffn_hidden=64, max_seq_len=S, dropout=0.0,
                          dtype="float32")
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        feeds = [fluid.data(n, [B, S], "int64", **A)
                 for n in ("src_ids", "pos_ids", "sent_ids")]
        feeds.append(fluid.data("input_mask", [B, S], "float32", **A))
        feeds += [fluid.data(n, [n_mask, 1], "int64", **A)
                  for n in ("mask_pos", "mask_label")]
        feeds.append(fluid.data("nsp_label", [B, 1], "int64", **A))
        loss, _, _ = bert.pretrain(*feeds, cfg)
        test = main.clone(for_test=True)
        grads = [g.name for _, g in fluid.append_backward(loss)]
    return main, startup, test, [loss.name] + grads


def _feed(n_mask=M):
    rng = np.random.RandomState(0)
    return {"src_ids": rng.randint(0, V, (B, S)).astype("int64"),
            "pos_ids": np.tile(np.arange(S), (B, 1)).astype("int64"),
            "sent_ids": rng.randint(0, 2, (B, S)).astype("int64"),
            "input_mask": np.ones((B, S), "float32"),
            "mask_pos": rng.permutation(B * S)[:n_mask].reshape(-1, 1)
            .astype("int64"),
            "mask_label": rng.randint(0, V, (n_mask, 1)).astype("int64"),
            "nsp_label": rng.randint(0, 2, (B, 1)).astype("int64")}


@contextlib.contextmanager
def _compiles(texts, lowered=False):
    """Every executor compile inside the block adds its module's text to
    ``texts``: the optimized (partitioned) HLO, or with ``lowered`` the
    module as traced, before partitioning."""
    real = executor_mod.Executor._aot_compile

    def spying(self, key, step, args):
        real(self, key, step, args)
        texts.append(step.fn.lower(*args).as_text() if lowered
                     else step.executable.as_text())

    executor_mod.Executor._aot_compile = spying
    try:
        yield
    finally:
        executor_mod.Executor._aot_compile = real


def _run(strategy=None, n_mask=M, compiled=None):
    """One step of the train program (loss and gradients) and one run of its
    test clone, from the same seeded weights; with ``compiled`` a list, the
    optimized HLO of both compiles lands in it."""
    main, startup, test, fetch = _bert(n_mask)

    def target(program):
        return program if strategy is None else \
            fluid.CompiledProgram(program).with_strategy(strategy)

    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with _compiles([] if compiled is None else compiled):
            train = exe.run(target(main), feed=_feed(n_mask),
                            fetch_list=fetch)
            test_loss, = exe.run(target(test), feed=_feed(n_mask),
                                 fetch_list=fetch[:1])
    return [np.asarray(o) for o in train], np.asarray(test_loss)


@pytest.fixture(scope="module")
def one_device():
    return _run()


def _dot_dims(text):
    """Every dimension of every product's result and operands in an HLO
    module's text."""
    dims = set()
    for line in re.findall(r"^.* dot\(.*$", text, re.M):
        shapes = re.findall(r"\w+\[([\d,]*)\]", line.split(", lhs_")[0])
        assert shapes, line
        for shape in shapes:
            dims.update(int(d) for d in shape.split(",") if d)
    return dims


def _layout_counts():
    from paddle_tpu.observability.metrics import REGISTRY
    fam = REGISTRY.get("gather_layout_total")
    total = Counter()           # over the programs' labels
    for k, c in (fam.items() if fam is not None else ()):
        total[dict(k)["layout"], dict(k)["shards"]] += c.value
    return total


def _grown(before):
    return {k: v - before.get(k, 0) for k, v in _layout_counts().items()
            if v != before.get(k, 0)}


LAYOUTS = {"replicated_indices": dict(data_rules=REPLICATED),
           "default_layout": {}}


# ------------------------------------- (a) same loss, same gradients as one --

@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_dp4_loss_and_every_gradient_equal_the_one_device_run(layout,
                                                               one_device):
    (loss, *grads), test_loss = _run(_strategy({"dp": 4}, **LAYOUTS[layout]))
    (want, *want_grads), want_test = one_device
    np.testing.assert_allclose(test_loss, want_test, rtol=2e-6)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    assert len(grads) == len(want_grads) > 20
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, rtol=0,
                                   atol=2e-5 * np.abs(ref).max())


# ------------------------------------------------- (b) the compiled modules --

@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_dp4_step_has_no_product_at_all_masked_rows(layout):
    texts = []
    _run(_strategy({"dp": 4}, **LAYOUTS[layout]), compiled=texts)
    train, test = (_dot_dims(t) for t in texts)
    # forward [M/4, H] x [H, V] and [M/4, H] x [H, H]; backward the same
    # rows against dLogits, and the weights' gradients contract over them
    assert M // 4 in train and M // 4 in test
    assert M not in train and M not in test
    rows = [line for line in texts[0].splitlines() if " dot(" in line
            and re.search(rf"\[{M // 4},(?:{H}|{V})\]", line)]
    assert any("_grad#" in line for line in rows), rows
    assert any("_grad#" not in line for line in rows), rows
    # the island's collectives are all-reduces, whichever way the indices
    # are laid out: an all-gather runs across the neighbouring fusions on a
    # TPU and slows them (PERF.md section 6, PR 36)
    assert not any(" all-gather(" in t or " all-gather-start(" in t
                   for t in texts)


def test_index_count_the_data_axis_does_not_divide_stays_global():
    """The parent's shape of the fault, pinned: without the layout every
    device runs the head on all the masked rows."""
    texts, before = [], _layout_counts()
    (loss, *_), _ = _run(_strategy({"dp": 4}, data_rules=REPLICATED),
                         n_mask=42, compiled=texts)
    assert _grown(before) == {("global", "1"): 2}
    assert 42 in _dot_dims(texts[0])
    (want, *_), _ = _run(n_mask=42)
    np.testing.assert_allclose(loss, want, rtol=2e-6)


def test_one_device_module_is_what_plain_take_lowers(monkeypatch):
    import jax.numpy as jnp
    from paddle_tpu.core import registry
    main, startup, _, fetch = _bert()
    lowered = []
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()), _compiles(lowered, lowered=True):
        exe.run(startup)
        lowered.clear()
        exe.run(main, feed=_feed(), fetch_list=fetch[:1])
        monkeypatch.setattr(
            registry.get("gather"), "lower",
            lambda ctx, ins: {"Out": [jnp.take(
                ins["X"][0], ins["Index"][0].astype("int32"),
                axis=ctx.attr("axis", 0))]})
        main._version += 1          # the same Program, compiled again
        exe.run(main, feed=_feed(), fetch_list=fetch[:1])
    assert len(lowered) == 2 and lowered[0] == lowered[1]
    assert "manual" not in lowered[0]


# ------------------------------------------------------------ (c) the counter --

@pytest.mark.parametrize("mesh,want", [
    ({"dp": 4}, {("shard", "4"): 2}),           # train step and test clone
    ({"dp": 2, "mp": 2}, {("shard", "2"): 2}),
    (None, {("global", "1"): 2})])
def test_gather_layout_total_counts_the_op_once_a_compile(mesh, want):
    before = _layout_counts()
    _run(_strategy(mesh, data_rules=REPLICATED) if mesh else None)
    assert _grown(before) == want


def _islands(fn, *args):
    import jax
    return str(jax.make_jaxpr(fn)(*args)).count("shard_map")


def _layouts(program):
    """{the salt of each op that reported ``gather_layout_total``: its
    (layout, shards)}, of the trace just made."""
    return {salt: (dict(labels)["layout"], dict(labels)["shards"])
            for family, salt, labels in program._lowering_notes
            if family == "gather_layout_total"}


def _gather(ctx, x, idx):
    from paddle_tpu.core import registry
    return registry.get("gather").lower(ctx, {"X": [x], "Index": [idx]})[
        "Out"][0]


def test_axis_0_under_the_mesh_opens_the_island_and_axis_1_does_not():
    import jax.numpy as jnp
    mesh = _strategy({"dp": 4}).build_mesh()
    x, idx = jnp.ones((16, 8)), jnp.arange(8)
    program = fluid.Program()

    def ctx(axis, **kw):
        kw = {"gspmd_mesh": mesh, "data_axis": "dp", **kw}
        return LowerCtx({"axis": axis}, None, 9, program=program, **kw)

    def islands(ctx, x=x, idx=idx):
        program._lowering_notes.clear()
        return _islands(lambda x, i: _gather(ctx, x, i), x, idx)

    assert islands(ctx(0)) == 1
    assert _layouts(program) == {9: ("shard", 4)}
    assert islands(ctx(1)) == 0
    assert program._lowering_notes == {}        # outside the rule: no note
    for plain in (ctx(0, data_axis="mp"),       # an axis the mesh lacks
                  ctx(0, gspmd_mesh=None)):
        assert islands(plain) == 0
        assert _layouts(program) == {9: ("global", 1)}
    # rows or indices the data axis does not divide; rows that do not add
    assert islands(ctx(0), x=jnp.ones((18, 8))) == 0
    assert islands(ctx(0), idx=jnp.arange(6)) == 0
    assert islands(ctx(0), x=jnp.ones((16, 8), bool)) == 0


@pytest.mark.parametrize("index_spec", [(), ("dp",)])
def test_island_equals_take_for_indices_laid_either_way(index_spec):
    """Indices from the end count as ``take``'s do; the rows' gradient is
    the scatter-add of ``take``'s."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _strategy({"dp": 4}).build_mesh()
    ctx = LowerCtx({"axis": 0}, None, 9, gspmd_mesh=mesh, data_axis="dp")
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(32, 3, 5), jnp.float32)
    idx = jnp.asarray(rng.randint(-32, 32, (16, 1)), jnp.int32)
    c = jnp.asarray(rng.randn(16, 1, 3, 5), jnp.float32)

    def value_and_grad(take):
        return lambda x, i: jax.value_and_grad(
            lambda x: jnp.sum(take(x, i) * c))(x)

    got, dgot = jax.jit(
        value_and_grad(lambda x, i: _gather(ctx, x, i)),
        in_shardings=(NamedSharding(mesh, P("dp")),
                      NamedSharding(mesh, P(*index_spec))))(x, idx)
    want, dwant = value_and_grad(lambda x, i: jnp.take(x, i, axis=0))(x, idx)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(dgot, dwant, rtol=1e-5, atol=1e-6)
    rows = jax.jit(lambda x, i: _gather(ctx, x, i))(x, idx)
    np.testing.assert_array_equal(rows, jnp.take(x, idx, axis=0))
    assert rows.sharding.spec[0] == "dp"


def test_no_island_inside_another_ops_island():
    """An op lowered inside a ``shard_map`` over the mesh (a pipeline's
    stages, which are handed ``gspmd_mesh`` too) is today's ``take``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    mesh = _strategy({"dp": 4}).build_mesh()
    program = fluid.Program()
    ctx = LowerCtx({"axis": 0}, None, 9, program=program, gspmd_mesh=mesh,
                   data_axis="dp")
    x, idx = jnp.arange(64.0).reshape(16, 4), jnp.arange(8)

    def stage(x, i):
        return _gather(ctx, x, i)

    island = jax.shard_map(stage, mesh=mesh, in_specs=(P(), P()),
                           out_specs=P("dp"))
    assert _islands(island, x, idx) == 1        # the stage's own
    assert _layouts(program) == {9: ("global", 1)}
    np.testing.assert_array_equal(np.asarray(jax.jit(island)(x, idx))[:8],
                                  np.asarray(x)[:8])


def _head_program():
    """A gather of rows ahead of a small classifier, with an optimizer: what
    the explicit-dp builder is handed."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [32], "float32")
        pos = fluid.data("pos", [8, 1], "int64", append_batch_size=False)
        label = fluid.data("label", [8, 1], "int64", append_batch_size=False)
        rows = fluid.layers.reshape(
            fluid.layers.gather(fluid.layers.fc(x, 64, act="relu"), pos),
            [-1, 64])
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(rows, 10), label))
        fluid.optimizer.SGD(0.05).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(16, 32).astype("float32"),
            "pos": np.arange(8).reshape(8, 1).astype("int64"),
            "label": rng.randint(0, 10, (8, 1)).astype("int64")}
    return main, startup, loss, feed


def test_no_island_inside_the_explicit_dp_shard_map():
    """``_explicit_dp`` lowers every op inside its own ``shard_map``
    (``ctx.mesh``, no ``gspmd_mesh``): each shard takes from its own rows,
    as before."""
    main, startup, loss, feed = _head_program()
    ds = _strategy({"dp": 2}, data_rules=[("pos|label", ())])
    ds.comm_compression = "int8"
    ds.comm_compress_min_bytes = 0
    lowered, before = [], _layout_counts()
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with _compiles(lowered, lowered=True):
            out, = exe.run(fluid.CompiledProgram(main).with_strategy(ds),
                           feed=feed, fetch_list=[loss])
    assert np.isfinite(np.asarray(out)).all()
    assert _grown(before) == {("global", "1"): 1}
    assert lowered[0].count("manual_axes") == 1     # the builder's shard_map


# ------------------------------------------------- (d) a mesh of two axes --

def test_dp2_mp2_keeps_the_one_device_loss_and_gradients(one_device):
    texts = []
    (loss, *grads), test_loss = _run(
        _strategy({"dp": 2, "mp": 2}, param_rules=bert.tp_param_rules(),
                  data_rules=REPLICATED), compiled=texts)
    (want, *want_grads), want_test = one_device
    np.testing.assert_allclose(test_loss, want_test, rtol=2e-6)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, rtol=0,
                                   atol=2e-5 * np.abs(ref).max())
    dims = _dot_dims(texts[0])
    assert M // 2 in dims and M not in dims     # the data axis alone
