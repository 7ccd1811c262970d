"""An expert layer that meets its exchange (``layers.moe_ffn(expert_axis=)``,
ops/decoder_ops.py, ops/collective.py:RowExchange) on four CPU devices, at
small sizes: the whole Mellum2 program on ``{"dp": 4}`` against its plain
reference (loss, every position, every parameter's gradient; uneven routing
and an overflowing budget included), the share test of the model-configs
guide's section 4 (the four ``experts_held`` shares add up to the uncut layer
and to the exchanged one), and one device with ``expert_axis`` set against
the layer without it, bit for bit. XLA's CPU backend has no
ragged-all-to-all: the wire here is the padded one."""
import json
import os

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.initializer import Normal
from paddle_tpu.layer_helper import ParamAttr

from benchmark.programs import mellum2_pretrain as program
from benchmark.references import mellum2_pretrain as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_model(**over):
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "mellum2_12b_a2_5b.json")))
    model = {k: v for k, v in cfg.items() if k != "rehearsal"}
    model.update(cfg["rehearsal"])
    model.update(dtype="float32", num_hidden_layers=2,
                 layer_types=["sliding_attention", "full_attention"],
                 mlp_layer_types=["sparse", "sparse"])
    model.update(over)
    return model


def mesh4(prog, rules=(("ids|labels", ("dp",)),)):
    return fluid.CompiledProgram(prog).with_strategy(
        fluid.DistributedStrategy(mesh_shape={"dp": 4},
                                  data_rules=list(rules)))


def run_model(model, params, seed, sharpen=None):
    """The train program's first step on dp4: what it fetched, by name, and
    the weights it started from."""
    built = program.build(model, params)
    exe, scope = fluid.Executor(), fluid.Scope()
    built["startup"].random_seed = built["main"].random_seed = seed
    exe.run(built["startup"], scope=scope)
    if sharpen:
        for name in built["params"]:
            if name.endswith("_router_w"):
                w = np.array(scope.find_var(name))
                w[:, :2] *= sharpen         # two experts, both on chip 0
                scope.set_var(name, jax.numpy.asarray(w))
    weights = [np.asarray(scope.find_var(n), np.float32)
               for n in built["params"]]
    batch = program.batch(model, params, np.random.RandomState(seed))
    names = (built["check"]["loss"] + [built["positions"]]
             + built["expert_index"] + built["expert_dropped"]
             + [n + "@GRAD" for n in built["params"]])
    got = exe.run(mesh4(built["main"]), feed=batch, fetch_list=names,
                  scope=scope)
    return built, dict(zip(names, map(np.asarray, got))), weights, batch


PARAMS = {"batch": 4, "seq": 32}


@pytest.mark.parametrize("sharpen", [None, 40.0], ids=["seeded", "uneven"])
def test_program_on_dp4_equals_the_reference(sharpen):
    model = small_model(moe_row_budget=4 * 32 * 2 * 4)   # every row fits
    built, got, weights, batch = run_model(model, PARAMS, 3, sharpen)
    index = np.stack([got[n] for n in built["expert_index"]])
    if sharpen:     # chip 0's two experts draw over twice an even share
        to_chip0 = (index[0] < 2).sum() / index[0].size
        assert to_chip0 > 2 * 0.25, to_chip0
    for name in built["expert_dropped"]:
        assert int(got[name][0]) == 0

    def f(weights):
        return reference.forward(weights, batch, model, chosen=index)
    with jax.default_matmul_precision("highest"):
        want = f(weights)
        grads = jax.grad(lambda w: f(w)["loss"])(weights)
    loss = float(got[built["check"]["loss"][0]].reshape(-1)[0])
    assert abs(loss - float(want["loss"])) < 2e-5 * float(want["loss"])
    np.testing.assert_allclose(got[built["positions"]].reshape(-1),
                               np.asarray(want["positions"]), rtol=2e-4,
                               atol=2e-5)
    for name, g in zip(built["params"], grads):
        g, mine = np.asarray(g), got[name + "@GRAD"]
        assert mine.shape == g.shape, name
        assert np.abs(mine - g).max() <= 2e-4 * np.abs(g).max() + 1e-8, name


def test_an_overflowing_budget_counts_exactly_the_rows_it_drops():
    """The padded wire gives a pair of chips budget / 4 rows: with two
    sharpened experts on chip 0 every chip's run to it is cut, and the
    count is what the experts chosen say it must be."""
    budget = 96                                  # 24 rows a pair of chips
    model = small_model(moe_row_budget=budget)
    built, got, _, _ = run_model(model, PARAMS, 5, sharpen=40.0)
    tokens, k, E = 4 * 32, model["num_experts_per_tok"], model["num_experts"]
    for name_i, name_d in zip(built["expert_index"], built["expert_dropped"]):
        index = got[name_i].reshape(4, tokens // 4, k)      # by source chip
        owner = index // (E // 4)
        sent = np.stack([(owner[c] == o).sum() for c in range(4)
                         for o in range(4)]).reshape(4, 4)
        want = np.maximum(sent - budget // 4, 0).sum()
        assert want > 0
        assert int(got[name_d][0]) == want


def moe_program(T=64, H=32, E=8, k=2, W=16, **kw):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [T, H], "float32", append_batch_size=False)
        x.stop_gradient = False
        out, aux = layers.moe_ffn(
            x, E, k, W, param_attr=ParamAttr(initializer=Normal(0.0, 0.5)),
            name="moe", norm_topk=True, **kw)
        loss = layers.mean(layers.square(out))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, out, loss


def run_layer(x, weights=None, mesh=False, fetch=("x@GRAD",
                                                  "moe_router_w@GRAD"), **kw):
    main, startup, out, loss = moe_program(**kw)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    for name, value in (weights or {}).items():
        scope.set_var(name, jax.numpy.asarray(value))
    held = {n: np.asarray(scope.find_var(n)) for n in scope.var_names()}
    prog = mesh4(main, [("x", ("dp",))]) if mesh else main
    got = exe.run(prog, feed={"x": x},
                  fetch_list=[out.name, loss.name, *fetch], scope=scope)
    return [np.asarray(g) for g in got], held


def test_one_device_with_expert_axis_is_the_layer_without_it_bit_for_bit():
    x = np.random.RandomState(0).randn(64, 32).astype(np.float32)
    fetch = ("x@GRAD", "moe_router_w@GRAD", "moe_gate_w@GRAD",
             "moe_down_w@GRAD")
    plain, held = run_layer(x, fetch=fetch)
    weights = {n: v for n, v in held.items() if n.startswith("moe_")
               and not n.endswith("dropped_rows")}
    axis, _ = run_layer(x, weights, fetch=fetch, expert_axis="dp")
    for a, b in zip(plain, axis):
        assert np.array_equal(a, b)


def test_the_four_shares_add_up_to_the_uncut_layer_and_to_the_exchange():
    """The model-configs guide's share test: each of four chips holds two
    of the eight experts (``experts_held``) and computes its part for every
    token; the parts add up, a token, to the layer that holds all eight and
    to the layer that splits them over the mesh and exchanges the rows."""
    x = np.random.RandomState(1).randn(64, 32).astype(np.float32)
    (whole, *_), held = run_layer(x, fetch=())
    stacked = {n: held[n] for n in ("moe_gate_w", "moe_up_w", "moe_down_w")}
    parts = np.zeros_like(whole)
    for c in range(4):
        share = {n: v[2 * c:2 * c + 2] for n, v in stacked.items()}
        share["moe_router_w"] = held["moe_router_w"]
        (part, *_), _ = run_layer(x, share, fetch=(),
                                  experts_held=(2 * c, 2))
        parts += part
    weights = dict(stacked, moe_router_w=held["moe_router_w"])
    (crossed, *_), _ = run_layer(x, weights, mesh=True, fetch=(),
                                 expert_axis="dp")
    scale = np.abs(whole).max()
    assert np.abs(parts - whole).max() <= 1e-5 * scale
    assert np.abs(crossed - whole).max() <= 1e-5 * scale
    assert np.abs(crossed - parts).max() <= 1e-5 * scale


def test_the_exchange_is_counted_and_scoped():
    from paddle_tpu.observability import lowerings
    from paddle_tpu.observability.metrics import MetricsRegistry
    x = np.random.RandomState(2).randn(64, 32).astype(np.float32)
    main, startup, out, loss = moe_program(expert_axis="dp", row_budget=64)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    seen = {}
    real = lowerings.publish

    def publish(notes, program, registry=None, role=""):
        fresh = MetricsRegistry()
        real(dict(notes), program, fresh, role)
        seen[role] = fresh
        return real(notes, program, registry, role)
    lowerings.publish = publish
    try:
        exe.run(mesh4(main, [("x", ("dp",))]), feed={"x": x},
                fetch_list=[loss.name], scope=scope)
    finally:
        lowerings.publish = real
    registry = seen["train"]

    def total(family, **want):
        return sum(child.value for labels, child in
                   registry.get(family).items()
                   if set(want.items()) <= set(labels))
    # out and back, forward and backward: four crossings a layer and step
    for crossing in ("dispatch", "combine", "dispatch_grad", "combine_grad"):
        assert total("moe_exchange_lowering_total", axis="dp", impl="padded",
                     crossing=crossing) == 1, crossing
    away = 64 // 4 * 2 * 3 // 4         # a chip's assignments x 3/4
    for way in ("out", "back"):
        assert total("moe_exchange_even_rows", direction=way) == 2 * away
    # off a TPU the grouped products open their island and run ragged_dot
    # in it: an island, but no kernel op
    assert total("moe_expert_matmul_lowering_total", impl="composed",
                 mesh="island") == 3
    assert total("moe_expert_matmul_lowering_total", impl="pallas") == 0


# --------------------------------------------------------------------------
# The plan of a crossing (collective.RowExchange), both wires, against a
# brute-force walk of every row: no device, no wire -- the wire is emulated
# from the very offsets and sizes the plan hands jax.lax.ragged_all_to_all /
# all_to_all, so the cut logic the chip runs (ragged) is checked here too.
# --------------------------------------------------------------------------

def _plans(cnt, n, budget, impl):
    """Every device's plan fields, stacked on a leading device axis."""
    from paddle_tpu.ops.collective import RowExchange
    fields = ("send_off", "kept", "in_off_there", "taken", "in_off",
              "send_off_there", "group", "dropped", "live",
              "to_expert_major", "to_source_major")

    def one(_):
        plan = RowExchange(jax.numpy.asarray(cnt), "dp", n, budget, impl)
        return tuple(getattr(plan, f) for f in fields)
    got = jax.vmap(one, axis_name="dp")(jax.numpy.arange(n))
    return {f: np.asarray(g) for f, g in zip(fields, got)}


def _brute_force(cnt, n, budget, impl):
    """What each device must receive: ``pool[c]`` the (source, expert, rank
    within the source's rows of that expert) of every row that arrives at
    device c, source by source, and the rows dropped there."""
    E = cnt.shape[1]
    per = E // n
    pools, dropped = [], []
    for c in range(n):
        pool, lost, room = [], 0, budget
        for j in range(n):
            run = [(j, e, r) for e in range(c * per, (c + 1) * per)
                   for r in range(cnt[j, e])]
            fits = min(len(run), room if impl == "ragged" else budget // n)
            pool.append(run[:fits])
            lost += len(run) - fits
            room -= fits
        pools.append(pool)
        dropped.append(lost)
    return pools, dropped


@pytest.mark.parametrize("impl", ["ragged", "padded"])
@pytest.mark.parametrize("budget", [16, 40, 64, 256],
                         ids=["tiny", "overflowing", "tight", "ample"])
@pytest.mark.parametrize("seed", [0, 1])
def test_the_plan_of_a_crossing_against_brute_force(impl, budget, seed):
    n, E, per = 4, 8, 2
    rng = np.random.RandomState(seed)
    # uneven: device 0's experts are preferred three times over
    cnt = rng.poisson(np.where(np.arange(E) < per, 9.0, 3.0),
                      (n, E)).astype(np.int32)
    sorted_rows = int(cnt.sum(1).max())
    plan = _plans(cnt, n, budget, impl)
    pools, dropped = _brute_force(cnt, n, budget, impl)
    assert sum(dropped) > 0 or budget >= 64
    # every sender's sorted buffer, a row its (source, expert, rank)
    bufs = [[(j, e, r) for e in range(E) for r in range(cnt[j, e])]
            for j in range(n)]
    part = budget // n
    for c in range(n):
        assert int(plan["dropped"][c]) == dropped[c]
        assert [int(v) for v in plan["taken"][c]] == [
            len(run) for run in pools[c]]
        want_group = [sum(1 for run in pools[c] for (_, e, _) in run
                          if e == c * per + g) for g in range(per)]
        assert [int(v) for v in plan["group"][c]] == want_group
        assert int(plan["live"][c]) == sum(want_group)
    # the wire out, from the plan's own offsets and sizes
    recv = [[None] * budget for _ in range(n)]
    for j in range(n):
        for c in range(n):
            assert int(plan["kept"][j][c]) == int(plan["taken"][c][j])
            start = int(plan["send_off"][j][c])
            at = (int(plan["in_off_there"][j][c]) if impl == "ragged"
                  else j * part)
            assert at == int(plan["in_off"][c][j])
            for i in range(int(plan["kept"][j][c])):
                assert recv[c][at + i] is None      # no run overwrites one
                recv[c][at + i] = bufs[j][start + i]
    for c in range(n):
        arrived = [row for run in pools[c] for row in run]
        assert sorted(r for r in recv[c] if r is not None) == sorted(arrived)
        # expert by expert, within an expert source by source, in order
        live = int(plan["live"][c])
        laid = [recv[c][int(i)] for i in plan["to_expert_major"][c][:live]]
        assert laid == sorted(arrived, key=lambda row: (row[1], row[0],
                                                        row[2]))
        # and back: the transpose lays them source by source again
        again = [None] * budget
        for i in range(budget):
            at = int(plan["to_source_major"][c][i])
            if recv[c][i] is not None:
                again[i] = laid[at]
        assert again == recv[c]
    # the wire back: every kept row returns to its place in its sender's
    # sorted buffer, a dropped row's place stays empty
    for j in range(n):
        home = [None] * sorted_rows
        for c in range(n):
            assert int(plan["send_off_there"][c][j]) == int(
                plan["send_off"][j][c])
            for i in range(int(plan["taken"][c][j])):
                home[int(plan["send_off_there"][c][j]) + i] = recv[c][
                    int(plan["in_off"][c][j]) + i]
        kept = {row for c in range(n) for row in pools[c][j]}
        assert [row for row in home if row is not None] == [
            row for row in bufs[j] if row in kept]
        assert all(home[i] in (None, bufs[j][i])
                   for i in range(len(bufs[j])))


def _crossings_compiled(H, **kw):
    """``moe_exchange_rows_lowering_total`` of a layer's train step on dp4
    at width ``H``, by (impl, mesh, way), and what the step fetched."""
    from paddle_tpu.observability import lowerings
    from paddle_tpu.observability.metrics import MetricsRegistry
    x = np.random.RandomState(4).randn(64, H).astype(np.float32)
    seen, real = {}, lowerings.publish

    def publish(notes, program, registry=None, role=""):
        fresh = MetricsRegistry()
        real(dict(notes), program, fresh, role)
        seen[role] = fresh
        return real(notes, program, registry, role)
    lowerings.publish = publish
    try:
        got, held = run_layer(x, mesh=True, H=H, expert_axis="dp", **kw)
    finally:
        lowerings.publish = real
    family = seen["train"].get("moe_exchange_rows_lowering_total")
    count = {}
    for labels, child in family.items():
        labels = dict(labels)
        key = labels["impl"], labels["mesh"], labels["way"]
        count[key] = count.get(key, 0) + child.value
    return count, got, held


def test_the_received_rows_change_order_composed_off_a_tpu():
    """At the CPU tests' widths (no whole vreg of lanes) the composed
    gather stays, and says so: four crossings a layer and step."""
    count, _, _ = _crossings_compiled(32, row_budget=64)
    assert count == {("composed", "none", "out"): 2,
                     ("composed", "none", "back"): 2}


def test_the_received_rows_change_order_by_the_kernel_in_the_island():
    """What the chip reports (``impl=pallas, mesh=island``: the labels
    ``kernels.mesh_island_ops.mellum2`` matches), here in the interpreter
    at a width the kernel takes: the same step as the composed gather's,
    bit for bit -- the kernel performs the gather's permutation."""
    from paddle_tpu.ops import pallas_mode
    count, got, held = _crossings_compiled(128, row_budget=128)
    assert count == {("pallas", "island", "out"): 2,
                     ("pallas", "island", "back"): 2}
    pallas_mode.TEST_INTERPRET = False      # no kernel can run: composed
    try:
        weights = {n: v for n, v in held.items() if n.startswith("moe_")
                   and not n.endswith("dropped_rows")}
        x = np.random.RandomState(4).randn(64, 128).astype(np.float32)
        want, _ = run_layer(x, weights, mesh=True, H=128, expert_axis="dp",
                            row_budget=128)
    finally:
        pallas_mode.TEST_INTERPRET = True
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
