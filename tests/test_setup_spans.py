"""Set-up's span tree (PR 53): building the Program (category ``build``), a
compile miss with JAX's own events as its children (category ``jax``), the
trace's seconds by op type, the program's role, ``process_uptime_seconds``
-- and the guard that a warm ``Executor.run`` records what it did before."""
import importlib
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import registry
from paddle_tpu.observability import timeline
from paddle_tpu.observability.metrics import REGISTRY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A session like the benchmark's: startup program, the test clone, the train
# step; printed as one JSON line. Run twice against one temporary cache
# directory: every compile of the first run misses it, of the second hits.
_SESSION = r"""
import json, sys
import jax
import numpy as np
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
raw = {"trace": 0}
def count(event, secs, **_):
    raw["trace"] += event.endswith("jaxpr_trace_duration")
jax.monitoring.register_event_duration_secs_listener(count)
import paddle_tpu as fluid
from paddle_tpu.observability import timeline
from paddle_tpu.observability.metrics import REGISTRY
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.data("x", [16], "float32")
    label = fluid.data("label", [1], "int64")
    h = fluid.layers.fc(x, 16, act="relu")
    loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
        fluid.layers.fc(h, 4), label))
    test = main.clone(for_test=True)
    fluid.optimizer.Adam(0.01).minimize(loss)
exe = fluid.Executor()
exe.run(startup)
feed = {"x": np.ones((4, 16), "float32"), "label": np.zeros((4, 1), "int64")}
exe.run(test, feed=feed, fetch_list=[loss.name])
exe.run(main, feed=feed, fetch_list=[loss])
jax.jit(lambda v: v * 3 + 1)(np.ones(3, "float32"))   # not the program's
gauges = {}
for name in ("program_role", "program_compile_seconds",
             "process_uptime_seconds", "lowering_seconds_total"):
    gauges[name] = [[dict(k), c.value] for k, c in REGISTRY.get(name).items()]
print(json.dumps({
    "spans": [[s.name, s.cat, s.t0, s.dur, s.args, s.id, s.parent]
              for s in timeline.spans()],
    "gauges": gauges, "raw_trace_events": raw["trace"],
    "labels": {"startup": f"{id(startup)}:v{startup._version}",
               "eval": f"{id(test)}:v{test._version}",
               "train": f"{id(main)}:v{main._version}"}}))
"""


class S:
    """One printed span."""

    def __init__(self, row):
        (self.name, self.cat, self.t0, self.dur, self.args, self.id,
         self.parent) = row
        self.args = self.args or {}
        self.end = self.t0 + self.dur


@pytest.fixture(scope="module")
def sessions(tmp_path_factory):
    """{"miss" | "hit": the session's printed line}."""
    cache = tmp_path_factory.mktemp("jax_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(cache))
    out = {}
    for state in ("miss", "hit"):
        r = subprocess.run([sys.executable, "-c", _SESSION], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-3000:]
        out[state] = json.loads(r.stdout.strip().splitlines()[-1])
        out[state]["spans"] = [S(row) for row in out[state]["spans"]]
    return out


def _kids(spans, parent):
    return sorted((s for s in spans if s.parent == parent.id),
                  key=lambda s: s.t0)


@pytest.mark.parametrize("state", ["miss", "hit"])
def test_compile_miss_leaves_one_tree(sessions, state):
    """``run`` > ``compile`` > ``trace_lower`` > {``jaxpr_trace``,
    ``mlir_lower``}, ``compile`` > ``backend_compile`` [> ``cache_load`` on a
    hit of the persistent cache, none on a miss], ``run`` >
    ``post_compile``: every child inside its parent, siblings' sum no more
    than the parent, one tree a compiled program."""
    spans = sessions[state]["spans"]
    runs = [s for s in spans if s.name == "run"]
    assert [r.args["role"] for r in runs] == ["startup", "eval", "train"]
    for run in runs:
        kids = _kids(spans, run)
        assert [k.name for k in kids] == [
            "feed_prep", "compile", "post_compile", "dispatch"]
        comp, post = kids[1], kids[2]
        assert comp.args["role"] == post.args["role"] == run.args["role"]
        tl, backend = _kids(spans, comp)
        assert (tl.name, tl.cat) == ("trace_lower", "executor")
        assert (backend.name, backend.cat) == ("backend_compile", "jax")
        assert [(k.name, k.cat) for k in _kids(spans, tl)] == [
            ("jaxpr_trace", "jax"), ("mlir_lower", "jax")]
        loads = _kids(spans, backend)
        assert [k.name for k in loads] == (
            ["cache_load"] if state == "hit" else [])
    for s in spans:
        kids = _kids(spans, s)
        assert all(s.t0 <= k.t0 and k.end <= s.end + 1e-9 for k in kids), s.name
        assert sum(k.dur for k in kids) <= s.dur + 1e-9, s.name
        assert all(a.end <= b.t0 + 1e-9 for a, b in zip(kids, kids[1:]))


def test_one_jaxpr_trace_however_many_inner_jits_are_traced(sessions):
    """JAX fires the trace event for every inner ``jit`` it traces; each
    ``trace_lower`` keeps one child of that name, and what fired outside an
    executor compile (the session's own ``jax.jit`` at its end) none."""
    spans = sessions["miss"]["spans"]
    kept = [s for s in spans if s.name == "jaxpr_trace"]
    assert sessions["miss"]["raw_trace_events"] > len(kept) == 3
    assert {s.parent for s in spans if s.cat == "jax"} <= {
        s.id for s in spans if s.cat in ("executor", "jax")}
    assert len([s for s in spans if s.name == "backend_compile"]) == 3


@pytest.mark.parametrize("role", ["startup", "eval", "train"])
def test_role_and_parts_of_each_program_of_a_session(sessions, role):
    """``program_role`` names the three programs of a session, and
    ``program_compile_seconds`` holds each one's parts as its spans do."""
    s = sessions["hit"]
    label = s["labels"][role]
    assert [k["role"] for k, v in s["gauges"]["program_role"]
            if k["program"] == label] == [role]
    parts = {k["part"]: v for k, v in s["gauges"]["program_compile_seconds"]
             if k["program"] == label and k["role"] == role}
    assert set(parts) == {"trace", "lower", "cache_load", "backend",
                          "post_compile", "total"}
    comp = next(x for x in s["spans"] if x.name == "compile"
                and x.args["program"] == label)
    post = next(x for x in s["spans"] if x.name == "post_compile"
                and x.args["program"] == label)
    assert parts["total"] == comp.dur and parts["post_compile"] == post.dur
    tl, backend = _kids(s["spans"], comp)
    assert [parts["trace"], parts["lower"]] == [
        k.dur for k in _kids(s["spans"], tl)]
    assert parts["backend"] == backend.dur
    assert 0 < parts["cache_load"] <= parts["backend"]
    assert parts["trace"] + parts["lower"] + parts["backend"] <= parts["total"]
    # a compile that missed the cache says 0, not nothing (a reader of a
    # cold run must find the part)
    assert [v for k, v in
            sessions["miss"]["gauges"]["program_compile_seconds"]
            if k["role"] == role and k["part"] == "cache_load"] == [0.0]


@pytest.mark.parametrize("role", ["startup", "eval", "train"])
def test_lowering_seconds_lie_within_the_programs_trace(sessions, role):
    """``lowering_seconds_total`` summed over a program's op types is part
    of its ``jaxpr_trace`` span: the lowerings run inside the trace."""
    s = sessions["miss"]
    label = s["labels"][role]
    by_op = {k["op_type"]: v for k, v in s["gauges"]["lowering_seconds_total"]
             if k["program"] == label}
    assert by_op and all(
        k["role"] == role and k["family"] == "xla"
        for k, _ in s["gauges"]["lowering_seconds_total"]
        if k["program"] == label)
    comp = next(x for x in s["spans"] if x.name == "compile"
                and x.args["program"] == label)
    trace = _kids(s["spans"], _kids(s["spans"], comp)[0])[0]
    assert 0 < sum(by_op.values()) <= trace.dur
    if role == "train":
        assert {"adam", "mul", "mul_grad"} <= set(by_op)


def test_process_uptime_is_ordered(sessions):
    at = {k["at"]: v for k, v in
          sessions["miss"]["gauges"]["process_uptime_seconds"]}
    assert 0 < at["import_start"] <= at["import_end"] <= at["first_executor"]
    # the first Executor is marked once: a second one leaves it
    assert timeline._PROCESS_START is not None
    fluid.Executor()
    v0 = REGISTRY.gauge("process_uptime_seconds", at="first_executor").value
    fluid.Executor()
    assert REGISTRY.gauge("process_uptime_seconds",
                          at="first_executor").value == v0


def test_build_spans_open_only_at_the_outermost_call():
    """``minimize`` holds no ``append_backward`` child (``phase_seconds`` of
    category ``build`` is a sum of disjoint times); ``append_backward`` by
    itself is a span, ``clone`` only as ``for_test``."""
    main, startup = fluid.Program(), fluid.Program()
    timeline.clear()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [8], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 4))
        main.clone()
        main.clone(for_test=True)
        fluid.append_backward(loss)
        fluid.optimizer.SGD(0.1).minimize(loss)
    fluid.CompiledProgram(main).with_strategy(
        fluid.DistributedStrategy(mesh_shape={"dp": 1}))
    built = [(s.name, s.cat, s.parent) for s in timeline.spans()]
    assert built == [("clone", "build", 0), ("append_backward", "build", 0),
                     ("minimize", "build", 0), ("with_strategy", "build", 0)]


def _small(dim):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [dim], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 4))
    return main, startup, loss


def test_a_warm_run_records_the_spans_it_recorded_before():
    """The guard that the hot path did not grow: names, order, categories
    and argument keys of a warm ``Executor.run``."""
    main, startup, loss = _small(19)
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 19), "float32")}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        timeline.clear()
        exe.run(main, feed=feed, fetch_list=[loss])
    assert [(s.name, s.cat, sorted(s.args or {})) for s in timeline.spans()
            ] == [("state_lookup", "executor", []),
                  ("h2d", "executor", ["bytes", "n"]),
                  ("feed_prep", "executor", ["program", "step"]),
                  ("dispatch", "executor", ["program", "step"]),
                  ("run", "executor", ["program", "step"])]


def test_an_event_outside_a_compile_span_leaves_nothing():
    import jax
    timeline.clear()
    jax.jit(lambda v: v * 5 - 2)(np.ones(7, "float32"))
    with timeline.phase("compile", cat="test"):      # not the executor's
        jax.jit(lambda v: v * 7 - 2)(np.ones(7, "float32"))
        assert timeline.settle_jax_events() == {}
    assert [s.name for s in timeline.spans()] == ["compile"]


@pytest.mark.parametrize("where", ["trace", "backend"])
def test_a_compile_that_raises_leaves_no_span_and_no_counter(monkeypatch,
                                                             where):
    """Neither ``compile`` nor a span of JAX's events, no role, no parts and
    no lowering seconds; the compile that then succeeds leaves one tree."""
    import jax
    main, startup, loss = _small(23 if where == "trace" else 29)
    label = f"{id(main)}:v{main._version}"
    exe = fluid.Executor()
    feed = {"x": np.ones((2, main.global_block().var("x").shape[-1]),
                         "float32")}

    def refuse(*a, **kw):
        raise RuntimeError("Mosaic says no")

    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        timeline.clear()
        with monkeypatch.context() as m:
            if where == "trace":
                m.setattr(registry.get("mean"), "lower", refuse)
            else:
                m.setattr(jax.stages.Lowered, "compile", refuse)
            with pytest.raises(RuntimeError, match="Mosaic says no"):
                exe.run(main, feed=feed, fetch_list=[loss])
        names = [s.name for s in timeline.spans()]
        assert not {"compile", "post_compile", "jaxpr_trace", "mlir_lower",
                    "backend_compile", "cache_load"} & set(names)
        if where == "trace":
            assert "trace_lower" not in names
        for family in ("program_role", "program_compile_seconds",
                       "lowering_seconds_total"):
            fam = REGISTRY.get(family)
            assert not [k for k, _ in (fam.items() if fam else ())
                        if ("program", label) in k]
        timeline.clear()
        exe.run(main, feed=feed, fetch_list=[loss])
    names = [s.name for s in timeline.spans()]
    assert all(names.count(n) == 1 for n in (
        "compile", "trace_lower", "jaxpr_trace", "mlir_lower",
        "backend_compile"))


def test_the_listener_registers_once():
    from jax._src import monitoring
    REGISTRY_generation = REGISTRY.generation
    for _ in range(3):
        importlib.import_module("paddle_tpu.observability.timeline")
        timeline._listen_to_jax()
    REGISTRY.remove_labeled("phase_seconds", phase="nothing", cat="test")
    assert REGISTRY.generation > REGISTRY_generation
    timeline._listen_to_jax()
    assert [f.__name__ for f in monitoring.get_event_duration_listeners()
            ].count("_on_jax_event") == 1


def test_a_control_flow_ops_sub_block_is_not_counted_twice(monkeypatch):
    """Self time: the seconds of the ops in a ``while`` op's sub-block are
    theirs, not also the ``while`` op's."""
    from paddle_tpu import layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        i = layers.fill_constant([1], "float32", 0)
        limit = layers.fill_constant([1], "float32", 3)
        acc = layers.fill_constant([1], "float32", 1)
        cond = layers.less_than(i, limit)
        w = layers.While(cond, max_iters=3)
        with w.block():
            layers.assign(layers.scale(acc, 2.0), acc)
            layers.increment(i, in_place=True)
            layers.less_than(i, limit, cond=cond)
    scale = registry.get("scale")
    real = scale.lower

    def slow(ctx, ins):
        time.sleep(0.05)
        return real(ctx, ins)
    monkeypatch.setattr(scale, "lower", slow)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        out, = exe.run(main, fetch_list=[acc])
    assert float(out[0]) == 8.0
    label = f"{id(main)}:v{main._version}"
    by_op = {dict(k)["op_type"]: c.value
             for k, c in REGISTRY.get("lowering_seconds_total").items()
             if ("program", label) in k}
    assert by_op["scale"] >= 0.05
    assert by_op["while"] < 0.05 <= by_op["scale"] + by_op["while"]
    trace = [s for s in timeline.spans("jaxpr_trace")][-1]
    assert sum(by_op.values()) <= trace.dur


def test_kernel_family_is_the_ops_that_asked_the_kernel_rule():
    """``family`` is ``kernel`` where the lowering asked
    ``pallas_mode.lowers_kernels`` (a gated ``rms_norm``, whatever the
    answer; through the forward a generic grad op lowers again too), ``xla``
    for the rest (an ``rms_norm`` without a gate)."""
    from paddle_tpu import layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [4, 128], "float32")
        q = layers.rotary_embedding(layers.reshape(x, [-1, 1, 4, 128]))
        y = layers.rms_norm(layers.reshape(q, [-1, 4, 128]))
        loss = layers.mean(y)
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 4, 128), "float32")},
                fetch_list=[loss])
    label = f"{id(main)}:v{main._version}"
    family = {dict(k)["op_type"]: dict(k)["family"]
              for k, _ in REGISTRY.get("lowering_seconds_total").items()
              if ("program", label) in k}
    assert family["rotary_embedding"] == "kernel"
    assert family["rotary_embedding_grad"] == "kernel"
    assert family["rms_norm"] == family["rms_norm_grad"] == "xla"
    assert family["mean"] == "xla"


def test_state_is_placed_over_a_mesh_at_the_miss_only():
    """Under a strategy the first run of a compiled program lays the state
    over the mesh in a ``place_state`` span under ``state_lookup``; the next
    run finds it there and opens none."""
    import jax
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [8], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    compiled = fluid.CompiledProgram(main).with_strategy(
        fluid.DistributedStrategy(mesh_shape={"dp": 2}))
    exe = fluid.Executor()
    feed = {"x": np.ones((4, 8), "float32")}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        timeline.clear()
        first, = exe.run(compiled, feed=feed, fetch_list=[loss])
        miss = timeline.spans()
        timeline.clear()
        exe.run(compiled, feed=feed, fetch_list=[loss])
        hit = timeline.spans()
    place, = [s for s in miss if s.name == "place_state"]
    lookup, = [s for s in miss if s.name == "state_lookup"]
    assert place.cat == "build" and place.parent == lookup.id
    assert place.args["n"] >= 2 and place.args["bytes"] > 0
    assert not [s for s in hit if s.name == "place_state"]
    assert np.isfinite(first).all()


def test_chip_smoke_reads_the_programs_compile_spans():
    """``chip_smoke.py`` has no listener of its own: seconds and
    persistent-cache hits / misses come from the ``backend_compile`` /
    ``cache_load`` spans' histograms."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert not hasattr(smoke, "CompileWatch")
    s0, h0, m0 = smoke.compile_spans()
    timeline.record_span("backend_compile", 1.0, 0.5, cat="jax")
    timeline.record_span("backend_compile", 2.0, 0.25, cat="jax")
    timeline.record_span("cache_load", 2.0, 0.125, cat="jax")
    s1, h1, m1 = smoke.compile_spans()
    assert (s1 - s0, h1 - h0, m1 - m0) == (0.75, 1, 1)
