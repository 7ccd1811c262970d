"""Flight recorder: phase timeline + Chrome-trace export, tensor-health
watchdog, device-memory telemetry, step-time anomaly detection, and the
no-hot-path-I/O guard (PR 2 acceptance pins)."""
import builtins
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.observability import (anomaly, health, journal, memory,
                                      timeline)
from paddle_tpu.observability.metrics import REGISTRY, MetricsRegistry


def _counter_val(name, **labels):
    fam = REGISTRY.get(name)
    if fam is None:
        return 0.0
    key = tuple(sorted((k, str(v)) for k, v in labels.items()))
    child = fam.children.get(key)
    return child.value if child is not None else 0.0


def _loss_program(dim=4):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [dim], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 4))
    return main, startup, loss


# ---------------------------------------------------------------- timeline --

@pytest.mark.smoke
def test_executor_phase_spans_and_trace_export(tmp_path, monkeypatch):
    """Acceptance pin: a 3-step run under PADDLE_TPU_OBS=1 yields a valid
    Chrome trace containing executor phase spans (feed_prep/dispatch/
    fetch_sync), record_event host spans, and >=1 memory counter track."""
    monkeypatch.setenv("PADDLE_TPU_OBS", "1")
    monkeypatch.setenv("PADDLE_TPU_OBS_JOURNAL", str(tmp_path / "j.jsonl"))
    timeline.clear()
    main, startup, loss = _loss_program(dim=13)
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 13), "float32")}
    from paddle_tpu import profiler
    profiler.start_profiler()
    fluid.set_flags({"FLAGS_profile_executor": True})
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        fluid.set_flags({"FLAGS_profile_executor": False})
        profiler.stop_profiler(profile_path=os.devnull)

    names = {s[0] for s in timeline.spans()}
    assert {"feed_prep", "dispatch", "fetch_sync", "compile",
            "journal"} <= names
    # spans carry the per-program step index
    steps = [s[4]["step"] for s in timeline.spans("dispatch")
             if s[4] and s[4].get("program", "").startswith(str(id(main)))]
    assert steps == [0, 1, 2]

    out = timeline.export_chrome_trace(str(tmp_path / "trace.json"))
    events = timeline.validate_trace(out)       # valid + monotone ts
    span_names = {e["name"] for e in events if e.get("ph") == "X"}
    assert {"feed_prep", "dispatch", "fetch_sync"} <= span_names
    assert any(e["name"].startswith("executor_run_v") for e in events
               if e.get("ph") == "X")           # record_event host span
    counter_tracks = {e["name"] for e in events if e.get("ph") == "C"}
    assert "device_memory_bytes" in counter_tracks
    profiler.reset_profiler()


def test_phase_seconds_histogram_mirrors_spans():
    timeline.clear()
    h = REGISTRY.histogram("phase_seconds", phase="unit_phase", cat="test")
    n0 = h.count
    with timeline.phase("unit_phase", cat="test", step=7):
        pass
    timeline.record_span("unit_phase", 1.0, 0.001, cat="test", step=8)
    assert h.count == n0 + 2
    assert len(timeline.spans("unit_phase")) == 2
    # same phase name, different category: its own series (executor vs
    # Predictor dispatch times must not share a histogram)
    other = REGISTRY.histogram("phase_seconds", phase="unit_phase",
                               cat="other")
    m0 = other.count
    timeline.record_span("unit_phase", 2.0, 0.001, cat="other")
    assert h.count == n0 + 2 and other.count == m0 + 1


def test_validate_trace_rejects_malformed(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "a", "ts": -5.0, "dur": 1.0, "pid": 1}]}))
    with pytest.raises(ValueError, match="negative"):
        timeline.validate_trace(str(bad))
    unsorted = tmp_path / "unsorted.json"
    unsorted.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "a", "ts": 9.0, "dur": 1.0, "pid": 1},
        {"ph": "X", "name": "b", "ts": 1.0, "dur": 1.0, "pid": 1}]}))
    with pytest.raises(ValueError, match="sorted"):
        timeline.validate_trace(str(unsorted))


def test_train_from_dataset_records_feed_wait_spans(tmp_path):
    data_file = tmp_path / "d.txt"
    data_file.write_text("".join(
        "%d;%d\n" % (i % 5, i % 3) for i in range(12)))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        a = fluid.data("a", [1], "int64")
        b = fluid.data("b", [1], "int64")
        s = fluid.layers.cast(a + b, "float32")
        loss = fluid.layers.mean(fluid.layers.fc(s, 2))
    ds = fluid.DatasetFactory().create_dataset("QueueDataset")
    ds.set_batch_size(4)
    ds.set_use_var([a, b])
    ds.set_filelist([str(data_file)])
    timeline.clear()
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.train_from_dataset(main, dataset=ds, fetch_list=[loss])
    assert timeline.spans("feed_wait"), "prefetch consumer recorded no waits"


# --------------------------------------------------------------- span tree --

def _children(spans, parent):
    return [s for s in spans if s.parent == parent.id]


def _only(spans, name, cat="executor"):
    got = [s for s in spans if s.name == name and s.cat == cat]
    assert len(got) == 1, (name, [s.name for s in spans])
    return got[0]


def _self_time(spans, span):
    return span.dur - sum(c.dur for c in _children(spans, span))


def test_span_tuple_keeps_its_first_six_fields():
    """Readers index the ring's tuples (``benchmark/probe.py`` [0] [2] [3];
    goodput, blackbox and the exporter the first six): the tree's fields
    come after them."""
    assert timeline.Span._fields == ("name", "cat", "t0", "dur", "args",
                                     "tid", "id", "parent")
    timeline.clear()
    import threading
    with timeline.phase("outer", cat="test", step=3):
        timeline.record_span("timed_by_hand", 5.0, 0.25, cat="test")
    by_hand, outer = timeline.spans()
    name, cat, t0, dur, args, tid = by_hand[:6]
    assert (name, cat, t0, dur, args, tid) == (
        "timed_by_hand", "test", 5.0, 0.25, None, threading.get_ident())
    assert outer[:2] == ("outer", "test") and outer[4] == {"step": 3}
    # an explicit t0 takes the stack's top as its parent; ids are unique
    assert by_hand.parent == outer.id and outer.parent == 0
    assert by_hand.id != outer.id


def test_span_tree_over_one_executor_run():
    """One miss and one hit of ``Executor.run``: every span hangs off its
    ``run``, the compile path shows on the miss only, and ``run``'s self
    time is what the children leave."""
    main, startup, loss = _loss_program(dim=11)
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 11), "float32")}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        timeline.clear()
        exe.run(main, feed=feed, fetch_list=[loss])
        miss = timeline.spans()
        timeline.clear()
        exe.run(main, feed=feed, fetch_list=[loss])
        hit = timeline.spans()

    for spans, was_miss in ((miss, True), (hit, False)):
        run = _only(spans, "run")
        assert run.parent == 0                      # a root
        assert run.args["program"].startswith(str(id(main)))
        kids = {s.name for s in _children(spans, run)}
        assert kids == ({"feed_prep", "dispatch"}
                        | ({"compile", "post_compile"} if was_miss
                           else set()))
        prep = _only(spans, "feed_prep")
        assert [s.name for s in _children(spans, prep)] == [
            "state_lookup", "h2d"]
        assert prep.args["step"] == run.args["step"]
        names = {s.name for s in spans}
        if was_miss:
            comp = _only(spans, "compile")
            # the Python trace + lower, then the backend: JAX's own events
            # (tests/test_setup_spans.py holds the tree below them)
            assert [s.name for s in _children(spans, comp)] == [
                "trace_lower", "backend_compile"]
            assert _self_time(spans, comp) >= 0
        else:
            assert not names & {"compile", "trace_lower", "post_compile"}
        # children lie inside their parent, so self time is well defined
        for s in spans:
            for c in _children(spans, s):
                assert s.t0 <= c.t0 and c.t0 + c.dur <= s.t0 + s.dur
        assert 0 <= _self_time(spans, run) < run.dur
        assert {s.tid for s in spans} == {run.tid}


@pytest.mark.parametrize("kind", ["numpy", "device", "list"])
def test_h2d_span_counts_host_feed_bytes(kind):
    """``h2d``'s ``bytes`` is the sum of ``nbytes`` of the feeds that were
    host arrays; a feed that already lives on the device counts 0, and a
    list, which has no ``nbytes`` and is converted once, what arrived."""
    import jax.numpy as jnp
    main, startup, loss = _loss_program(dim=9)
    exe = fluid.Executor()
    x = np.ones((4, 9), "float32")
    feed = {"numpy": x, "device": jnp.asarray(x), "list": x.tolist()}[kind]
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        timeline.clear()
        exe.run(main, feed={"x": feed}, fetch_list=[loss])
    h2d = _only(timeline.spans(), "h2d")
    assert h2d.args == {"bytes": 0 if kind == "device" else x.nbytes, "n": 1}


def _two_slot_dataset(tmp_path, rows=12, batch=4):
    data_file = tmp_path / "d.txt"
    data_file.write_text("".join(
        "%d;%d\n" % (i % 5, i % 3) for i in range(rows)))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        a = fluid.data("a", [1], "int64")
        b = fluid.data("b", [1], "int64")
        s = fluid.layers.cast(a + b, "float32")
        loss = fluid.layers.mean(fluid.layers.fc(s, 2))
    ds = fluid.DatasetFactory().create_dataset("QueueDataset")
    ds.set_batch_size(batch)
    ds.set_use_var([a, b])
    ds.set_filelist([str(data_file)])
    return main, startup, loss, ds, data_file


def test_span_tree_over_one_dataset_epoch(tmp_path):
    """One ``train_from_dataset`` epoch: the calling thread holds
    ``train_from_dataset`` > ``run`` / ``feed_wait`` / ``fetch_sync``; the
    prefetch worker's spans are roots of its own thread, with the file
    read under the ``produce`` that reached it."""
    main, startup, loss, ds, data_file = _two_slot_dataset(tmp_path)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        timeline.clear()
        exe.train_from_dataset(main, dataset=ds, fetch_list=[loss])
    spans = timeline.spans()
    epoch = _only(spans, "train_from_dataset", cat="dataset")
    assert epoch.parent == 0
    runs = [s for s in spans if s.name == "run"]
    assert len(runs) == 3 and all(r.parent == epoch.id for r in runs)
    assert [r.args["step"] for r in runs] == [0, 1, 2]
    sync = _only(spans, "fetch_sync", cat="dataset")
    assert sync.parent == epoch.id
    assert all(s.parent == epoch.id for s in spans if s.name == "feed_wait")
    assert 0 <= _self_time(spans, epoch) < epoch.dur

    worker = [s for s in spans if s.tid != epoch.tid]
    assert {s.name for s in worker} <= {"produce", "parse_file", "put_wait"}
    produce = [s for s in worker if s.name == "produce"]
    # one per next(batches): three batches and the exhausted call
    assert len(produce) == 4
    assert all(s.parent == 0 and s.cat == "dataset" for s in produce)
    parse = _only(spans, "parse_file", cat="dataset")
    assert parse.parent == produce[0].id and parse.tid == produce[0].tid
    assert parse.args["bytes"] == data_file.stat().st_size
    assert parse.args["rows"] == 12
    assert isinstance(parse.args["native"], bool)
    assert produce[0].dur >= parse.dur


@pytest.mark.parametrize("slow", ["producer", "consumer"])
def test_queue_waits_recorded_only_when_dry_or_full(slow):
    """``feed_wait`` only when the consumer finds the queue empty,
    ``put_wait`` only when the worker finds it full."""
    import time

    def batches():
        for i in range(6):
            if slow == "producer":
                time.sleep(0.03)
            yield {"x": np.full((1,), i, "float32")}

    timeline.clear()
    got = []
    # depth 2 under the slow producer: the end sentinel, put right behind
    # the last batch, then finds room too
    depth = 2 if slow == "producer" else 1
    for item in fluid.Executor._prefetch_batches(batches(), depth=depth):
        got.append(int(item["x"][0]))
        if slow == "consumer":
            time.sleep(0.03)
    assert got == list(range(6))
    waits = {n: len(timeline.spans(n)) for n in ("feed_wait", "put_wait")}
    if slow == "producer":
        # every get found the queue dry; the worker never found it full
        assert waits["feed_wait"] >= 6 and waits["put_wait"] == 0
    else:
        # only the very first get (the worker had not started) can be dry
        assert waits["feed_wait"] <= 1 and waits["put_wait"] >= 3
    assert all(s.parent == 0 for s in timeline.spans("put_wait"))


def test_abandoned_epoch_stops_the_worker_at_once():
    """The consumer left (``Executor.run`` raised mid-epoch): the worker
    hands on nothing more, however much room the queue has -- it does not
    produce and parse ``depth`` further batches first."""
    import threading
    import time
    produced = []

    def batches():
        for i in range(50):
            time.sleep(0.02)
            produced.append(i)
            yield {"x": np.full((1,), i, "float32")}

    epoch = fluid.Executor._prefetch_batches(batches(), depth=8)
    assert int(next(epoch)["x"][0]) == 0
    epoch.close()                       # while the worker makes batch 1
    for t in threading.enumerate():
        if t.name == "dataset-prefetch":
            t.join(5.0)
            assert not t.is_alive()
    assert produced == [0, 1]


@pytest.mark.parametrize("outcome", ["compiled", "raised"])
def test_compile_span_only_for_a_compile_that_succeeded(monkeypatch, outcome):
    """A compile that raises leaves no ``compile`` span (readers sum the
    phase as compile time of programs that ran); ``run`` spans an
    exception like any return."""
    main, startup, loss = _loss_program(dim=17)
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 17), "float32")}

    def refuse(self, key, compiled, args):
        self._cache.pop(key, None)
        raise RuntimeError("Mosaic says no")

    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        timeline.clear()
        if outcome == "raised":
            monkeypatch.setattr(fluid.Executor, "_aot_compile", refuse)
            with pytest.raises(RuntimeError, match="Mosaic says no"):
                exe.run(main, feed=feed, fetch_list=[loss])
        else:
            exe.run(main, feed=feed, fetch_list=[loss])
    spans = timeline.spans()
    assert len(timeline.spans("compile")) == (outcome == "compiled")
    run = _only(spans, "run")
    assert _only(spans, "feed_prep").parent == run.id


def test_goodput_window_keeps_to_the_phases_the_ledger_sums(tmp_path):
    """``span_window()`` is the wall ``goodput.compute_live`` divides by:
    a file load before training, ``run``'s bookkeeping before its first
    ``feed_prep`` and the worker's spans do not stretch it."""
    from paddle_tpu.observability import goodput
    assert timeline.WINDOW_PHASES == set(goodput._PHASE_CAUSE)
    main, startup, loss, _, data_file = _two_slot_dataset(tmp_path)
    ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    ds.set_batch_size(4)
    ds.set_use_var([main.global_block().var("a"),
                    main.global_block().var("b")])
    ds.set_filelist([str(data_file)])
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        timeline.clear()
        ds.load_into_memory()
        assert timeline.spans("parse_file")
        assert timeline.span_window() == (None, None)
        exe.train_from_dataset(main, dataset=ds, fetch_list=[loss])
    spans = timeline.spans()
    summed = [s for s in spans if (s.name, s.cat) in timeline.WINDOW_PHASES]
    t0, t1 = timeline.span_window()
    assert t0 == min(s.t0 for s in summed)
    assert t1 == max(s.t0 + s.dur for s in summed)
    # the containers reach further on both sides, and do not count
    epoch = _only(spans, "train_from_dataset", cat="dataset")
    assert epoch.t0 < t0 and t1 < epoch.t0 + epoch.dur


def test_record_span_keeps_its_histogram_handle(monkeypatch):
    """No import and no label lookup per span: the ``phase_seconds`` child
    is looked up once per (name, category) -- and again after the registry
    dropped it, or the span would feed a histogram nobody can read."""
    timeline.record_span("cached_phase", 1.0, 0.001, cat="test")
    lookups = []
    real = REGISTRY.histogram
    monkeypatch.setattr(
        REGISTRY, "histogram",
        lambda *a, **kw: lookups.append(kw) or real(*a, **kw))
    with timeline.phase("cached_phase", cat="test"):
        pass
    timeline.record_span("cached_phase", 2.0, 0.001, cat="test")
    assert lookups == []
    monkeypatch.undo()
    h = REGISTRY.histogram("phase_seconds", phase="cached_phase", cat="test")
    n0 = h.count
    assert REGISTRY.remove_labeled("phase_seconds", phase="cached_phase",
                                   cat="test")
    timeline.record_span("cached_phase", 3.0, 0.001, cat="test")
    fresh = REGISTRY.histogram("phase_seconds", phase="cached_phase",
                               cat="test")
    assert fresh is not h and fresh.count == 1 and h.count == n0
    # reset() drops every family: a private registry shows the generation
    reg = MetricsRegistry()
    g0 = reg.generation
    reg.reset()
    assert reg.generation == g0 + 1


def test_phases_ride_a_plain_jax_capture(tmp_path, monkeypatch):
    """A capture started with plain ``jax.profiler.start_trace`` -- not
    through ``paddle_tpu.profiler`` -- holds the program's phases on the
    host plane, nested as in the ring, the compile-miss path's included;
    the export from that capture holds each phase once, with the args and
    the parent links the ring has for it."""
    import glob
    import jax
    monkeypatch.setenv("PADDLE_TPU_VALIDATE", "warn")
    main, startup, loss = _loss_program(dim=7)
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 7), "float32")}
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        timeline.clear()
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        for _ in range(3):              # a miss, then two hits
            exe.run(main, feed=feed, fetch_list=[loss])
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in data.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events
            if e.name.startswith("paddle_tpu.")]
    by = {}
    for n, a, b in host:
        by.setdefault(n, []).append((a, b))
    assert len(by["paddle_tpu.executor.dispatch"]) == 3
    assert len(by["paddle_tpu.executor.h2d"]) == 3
    # the miss: every phase of its path is an annotation, none a span
    # recorded after the fact
    (va, vb), = by["paddle_tpu.executor.verify"]
    (ca, cb), = by["paddle_tpu.executor.compile"]
    (ta, tb), = by["paddle_tpu.executor.trace_lower"]
    ra, rb = by["paddle_tpu.executor.run"][0]
    assert ra <= va <= vb <= ca <= ta <= tb <= cb <= rb
    for (ra, rb), (da, db), (ha, hb), (fa, fb) in zip(
            by["paddle_tpu.executor.run"],
            by["paddle_tpu.executor.dispatch"],
            by["paddle_tpu.executor.h2d"],
            by["paddle_tpu.executor.feed_prep"]):
        assert ra <= fa <= ha and hb <= fb <= da and db <= rb
    # one clock: annotation minus ring start is the same offset for every
    # span, to within what two clock reads a few lines apart can differ
    ring = timeline.spans("dispatch")
    offsets = [a - s.t0 * 1e9 for (a, _), s in
               zip(by["paddle_tpu.executor.dispatch"], ring)]
    assert max(offsets) - min(offsets) < 50e3, offsets

    out = timeline.export_chrome_trace(str(tmp_path / "t.json"),
                                       trace_dir=str(tmp_path))
    events = [e for e in timeline.validate_trace(out) if e.get("ph") == "X"]
    xs = [e["name"] for e in events]
    for phase_name in ("run", "feed_prep", "h2d", "dispatch"):
        assert xs.count(f"paddle_tpu.executor.{phase_name}") == 3
        assert xs.count(phase_name) == 0
    # what the annotation cannot carry comes from the ring, found by the
    # span's id: args known only at the end, and the tree
    ring = {s.id: s for s in timeline.spans()}
    ours = [e for e in events if e["name"].startswith("paddle_tpu.")]
    assert ours and all(e["args"] == (ring[e["span_id"]].args or {})
                        and e["parent_id"] == ring[e["span_id"]].parent
                        for e in ours)
    runs = [e for e in ours if e["name"] == "paddle_tpu.executor.run"]
    assert [e["args"]["step"] for e in runs] == [0, 1, 2]
    h2d = [e for e in ours if e["name"] == "paddle_tpu.executor.h2d"]
    assert all(e["args"] == {"bytes": feed["x"].nbytes, "n": 1} for e in h2d)
    # so obs_report's self time works on a capture's export too
    from tools import obs_report
    table = obs_report.render_timeline(timeline.validate_trace(out))
    line, = [ln for ln in table.splitlines()
             if ln.strip().startswith("paddle_tpu.executor.run:")]
    assert " self=" in line


# ------------------------------------------------------------------ health --

def test_health_raise_names_offending_fetch(monkeypatch, tmp_path):
    """Acceptance pin: NaN in a fetched loss under HEALTH=raise raises with
    the variable name and journals a tensor_nonfinite event."""
    monkeypatch.setenv("PADDLE_TPU_OBS_HEALTH", "raise")
    journal.clear()
    main, startup, loss = _loss_program(dim=3)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with pytest.raises(FloatingPointError) as ei:
            exe.run(main, feed={"x": np.full((2, 3), np.inf, "float32")},
                    fetch_list=[loss])
    assert loss.name in str(ei.value)
    evs = journal.recent(event="tensor_nonfinite")
    assert evs and evs[-1]["var"] == loss.name
    assert evs[-1]["where"] == "executor"
    assert _counter_val("tensor_nonfinite_total", where="executor") >= 1


def test_health_warn_mode_continues(monkeypatch, recwarn):
    monkeypatch.setenv("PADDLE_TPU_OBS_HEALTH", "warn")
    main, startup, loss = _loss_program(dim=5)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        out = exe.run(main, feed={"x": np.full((2, 5), np.nan, "float32")},
                      fetch_list=[loss])
    assert math.isnan(float(np.asarray(out[0])))   # run completed
    assert any("NaN/Inf" in str(w.message) for w in recwarn.list)


def test_health_off_never_scans(monkeypatch):
    """Acceptance pin: with the mode off the watchdog adds no device work --
    the scan entry point must not even be reached."""
    monkeypatch.delenv("PADDLE_TPU_OBS_HEALTH", raising=False)

    def boom(*a, **k):
        raise AssertionError("health scan ran with PADDLE_TPU_OBS_HEALTH off")

    monkeypatch.setattr(health, "nonfinite_names", boom)
    main, startup, loss = _loss_program(dim=6)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": np.full((2, 6), np.nan, "float32")},
                fetch_list=[loss])   # NaN, but nobody looks


def test_health_healthy_run_is_silent(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_OBS_HEALTH", "raise")
    journal.clear()
    main, startup, loss = _loss_program(dim=7)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 7), "float32")},
                fetch_list=[loss])
    assert journal.recent(event="tensor_nonfinite") == []


def test_health_skips_integer_tensors():
    assert health.nonfinite_names(
        [("ids", np.arange(4)), ("mask", np.ones(3, bool))]) == []


def test_health_state_scan(monkeypatch):
    """PADDLE_TPU_OBS_HEALTH_STATE=1 extends the scan to written state: a
    NaN feed poisons the fc weight through the optimizer update."""
    monkeypatch.setenv("PADDLE_TPU_OBS_HEALTH", "raise")
    monkeypatch.setenv("PADDLE_TPU_OBS_HEALTH_STATE", "1")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [4], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with pytest.raises(FloatingPointError):
            # no fetch_list: only the state scan can catch it
            exe.run(main, feed={"x": np.full((2, 4), np.nan, "float32")},
                    fetch_list=[])


def test_health_bad_mode_rejected(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_OBS_HEALTH", "sometimes")
    with pytest.raises(ValueError, match="PADDLE_TPU_OBS_HEALTH"):
        health.mode()


def test_health_mode_toggle_aliases(monkeypatch):
    """The 0/1 spelling every sibling env var uses must work, not crash the
    first Executor.run: truthy -> warn, falsy -> off."""
    for raw, want in (("1", "warn"), ("true", "warn"), ("on", "warn"),
                      ("0", "off"), ("false", "off"), ("", "off"),
                      ("RAISE", "raise")):
        monkeypatch.setenv("PADDLE_TPU_OBS_HEALTH", raw)
        assert health.mode() == want, raw


# ------------------------------------------------------------------ memory --

def test_memory_sample_sets_gauges_and_counter_track():
    timeline.clear()
    reg = MetricsRegistry()
    snap = memory.sample_device_memory("test", registry=reg)
    assert snap, "no devices sampled"
    for dev, vals in snap.items():
        assert vals["bytes_in_use"] >= 0
        assert vals["peak_bytes"] >= vals["bytes_in_use"] or \
            vals["peak_bytes"] >= 0
        assert reg.gauge("device_memory_bytes_in_use",
                         device=dev).value == vals["bytes_in_use"]
    assert reg.counter("memory_samples_total", reason="test").value == 1
    assert timeline.counters("device_memory_bytes")


def test_program_memory_gauges_after_compile():
    main, startup, loss = _loss_program(dim=9)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 9), "float32")},
                fetch_list=[loss])
    label = f"{id(main)}:v{main._version}"
    fam = REGISTRY.get("program_peak_bytes")
    assert fam is not None
    key = (("program", label),)
    assert key in fam.children and fam.children[key].value > 0
    # compile-time occupancy samples happened
    assert _counter_val("memory_samples_total", reason="compile") >= 1


def test_memory_interval_sampling(monkeypatch, tmp_path):
    monkeypatch.setenv("PADDLE_TPU_OBS", "1")
    monkeypatch.setenv("PADDLE_TPU_OBS_JOURNAL", str(tmp_path / "j.jsonl"))
    monkeypatch.setenv("PADDLE_TPU_OBS_MEM_INTERVAL", "2")
    assert memory.sample_interval() == 2
    c0 = _counter_val("memory_samples_total", reason="interval")
    main, startup, loss = _loss_program(dim=10)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(4):
            exe.run(main, feed={"x": np.ones((2, 10), "float32")},
                    fetch_list=[loss])
    # 5 journaled runs (startup + 4) at interval 2 -> 2 interval samples
    assert _counter_val("memory_samples_total", reason="interval") == c0 + 2


def test_memory_interval_env_fallback(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_OBS_MEM_INTERVAL", "not-a-number")
    assert memory.sample_interval() == memory.DEFAULT_INTERVAL
    monkeypatch.setenv("PADDLE_TPU_OBS_MEM_INTERVAL", "0")
    assert memory.sample_interval() == 1


# ----------------------------------------------------------------- anomaly --

def test_anomaly_detector_flags_spike_and_journals():
    journal.clear()
    reg = MetricsRegistry()
    det = anomaly.StepTimeAnomalyDetector(registry=reg)
    for _ in range(16):
        assert det.observe("p:v0", 0.010) is None   # steady state: quiet
    rec = det.observe("p:v0", 0.200)                # 20x spike
    assert rec is not None and rec["event"] == "step_time_anomaly"
    assert rec["step_ms"] == 200.0 and rec["program"] == "p:v0"
    assert reg.counter("anomaly_total", kind="step_time").value == 1
    evs = journal.recent(event="step_time_anomaly")
    assert evs and evs[-1]["step_ms"] == 200.0


def test_anomaly_detector_warmup_and_jitter_tolerance():
    det = anomaly.StepTimeAnomalyDetector(registry=MetricsRegistry())
    # fewer than min_samples: never flags, even for a huge value
    for _ in range(det.min_samples - 1):
        assert det.observe("p", 0.01) is None
    assert det.observe("p", 10.0) is None   # window still warming up
    det2 = anomaly.StepTimeAnomalyDetector(registry=MetricsRegistry())
    # +/-8% noise around 10ms stays under the relative floor
    vals = [0.010 + 0.0008 * ((i % 5) - 2) for i in range(40)]
    assert all(det2.observe("q", v) is None for v in vals)


def test_anomaly_windows_keyed_per_cache_entry():
    """Two feed signatures of one program may legitimately differ by large
    factors; they must not share a median (the executor passes its compile
    cache key as the window key), and eviction retires exactly one window."""
    det = anomaly.StepTimeAnomalyDetector(registry=MetricsRegistry())
    for _ in range(16):
        det.observe("p:v0", 0.010, key=("p", "small"))
    # slower shape, same label, own window: still warming up, not anomalous
    assert det.observe("p:v0", 0.500, key=("p", "big")) is None
    # same window would have flagged: prove it by feeding the small key
    assert det.observe("p:v0", 0.500, key=("p", "small")) is not None
    det.retire(("p", "small"))
    assert det.observe("p:v0", 0.500, key=("p", "small")) is None  # fresh


def test_anomaly_executor_feeds_warm_steps_only(monkeypatch, tmp_path):
    observed = []
    monkeypatch.setattr(
        anomaly.DETECTOR, "observe",
        lambda label, s, key=None: observed.append((label, s, key)))
    monkeypatch.delenv("PADDLE_TPU_OBS", raising=False)
    main, startup, loss = _loss_program(dim=11)
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 11), "float32")}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])  # compile: not observed
        exe.run(main, feed=feed, fetch_list=[loss])  # warm but obs off: the
        # un-synced run_s is bare dispatch time -- must not feed the window
        assert observed == []
        monkeypatch.setenv("PADDLE_TPU_OBS", "1")
        monkeypatch.setenv("PADDLE_TPU_OBS_JOURNAL", str(tmp_path / "j.jsonl"))
        exe.run(main, feed=feed, fetch_list=[loss])  # warm + synced: observed
    main_label = f"{id(main)}:v{main._version}"
    assert [o for o in observed if o[0] == main_label] and \
        all(o[0] != main_label or o[1] > 0 for o in observed)
    # exactly one warm synced main-program step
    assert sum(1 for o in observed if o[0] == main_label) == 1


# ------------------------------------------------------------ no-I/O guard --

@pytest.mark.smoke
def test_no_journal_or_trace_io_when_obs_unset(tmp_path, monkeypatch):
    """Tier-1 guard: a 3-step Executor.run with every observability env var
    unset performs ZERO open() calls on the journal/trace paths."""
    for var in ("PADDLE_TPU_OBS", "PADDLE_TPU_OBS_HEALTH",
                "PADDLE_TPU_OBS_HEALTH_STATE", "PADDLE_TPU_OBS_MEM_INTERVAL"):
        monkeypatch.delenv(var, raising=False)
    jpath = str(tmp_path / "guard_journal.jsonl")
    monkeypatch.setenv("PADDLE_TPU_OBS_JOURNAL", jpath)
    monkeypatch.chdir(tmp_path)

    main, startup, loss = _loss_program(dim=8)
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 8), "float32")}
    opened = []
    real_open = builtins.open

    def spy_open(file, *a, **k):
        opened.append(str(file))
        return real_open(file, *a, **k)

    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])   # compile outside spy
        monkeypatch.setattr(builtins, "open", spy_open)
        try:
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss])
        finally:
            monkeypatch.setattr(builtins, "open", real_open)
    watched = [p for p in opened
               if "journal" in p or "trace" in p or "timeline" in p
               or p.endswith(".jsonl") or "paddle_tpu_obs" in p]
    assert watched == [], f"hot path opened observability files: {watched}"
    assert not os.path.exists(jpath)
    assert list(tmp_path.iterdir()) == []


# --------------------------------------------------- profiler trace export --

def test_export_chrome_tracing_unifies_host_and_phase_spans(tmp_path):
    """Satellite pin: RecordEvent host spans and executor phase spans land
    in ONE valid trace file; ts/dur are non-negative and sorted."""
    from paddle_tpu import profiler
    timeline.clear()
    profiler.reset_profiler()
    profiler.start_profiler()
    with profiler.record_event("unify_host_span"):
        with timeline.phase("unify_exec_phase", step=0):
            pass
    profiler.stop_profiler(profile_path=os.devnull)
    out = profiler.export_chrome_tracing(None, str(tmp_path / "t.json"))
    events = timeline.validate_trace(out)
    names = {e["name"] for e in events if e.get("ph") == "X"}
    assert {"unify_host_span", "unify_exec_phase"} <= names
    for e in events:
        if e.get("ph") == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    profiler.reset_profiler()


def test_merge_chrome_traces_missing_and_empty_inputs(tmp_path):
    from paddle_tpu import profiler
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "a", "ts": 1.0, "dur": 1.0, "pid": 1}]}))
    with pytest.raises(FileNotFoundError, match="cannot be opened"):
        profiler.merge_chrome_traces(
            [str(ok), str(tmp_path / "nope.json")],
            str(tmp_path / "m.json"))
    empty = tmp_path / "empty.json"
    empty.write_text("")
    with pytest.raises(ValueError, match="not valid trace JSON"):
        profiler.merge_chrome_traces([str(ok), str(empty)],
                                     str(tmp_path / "m2.json"))
    # valid inputs still merge
    merged = profiler.merge_chrome_traces([str(ok), str(ok)],
                                          str(tmp_path / "m3.json"))
    with open(merged) as f:
        evs = json.load(f)["traceEvents"]
    assert len(evs) == 2 and len({e["pid"] for e in evs}) == 2


def test_export_with_xplane_capture_skips_host_span_synthesis(tmp_path):
    """With an xplane capture the RecordEvent spans AND the flight-recorder
    phases already ride it via TraceAnnotation -- synthesizing them again
    would double-count every span in obs_report's timeline section."""
    import gzip
    from paddle_tpu import profiler
    timeline.clear()
    profiler.reset_profiler()
    profiler.start_profiler()
    with profiler.record_event("dup_host_span"):
        with timeline.phase("exec_phase_x", step=0):
            pass
    profiler.stop_profiler(profile_path=os.devnull)
    timeline.counter_sample("device_memory_bytes", {"cpu:0": 1e6})
    (tmp_path / "cap").mkdir()
    (tmp_path / "cap" / "x.trace.json.gz").write_bytes(gzip.compress(
        json.dumps({"traceEvents": [
            {"ph": "X", "name": "dup_host_span", "ts": 10.0, "dur": 2.0,
             "pid": 1},
            {"ph": "X", "name": "paddle_tpu.executor.exec_phase_x",
             "ts": 10.5, "dur": 1.0, "pid": 1, "args": {"span_id": str(
                 timeline.spans("exec_phase_x")[0].id)}}]}).encode()))
    out = timeline.export_chrome_trace(str(tmp_path / "t.json"),
                                       trace_dir=str(tmp_path))
    events = timeline.validate_trace(out)
    xs = [e["name"] for e in events if e.get("ph") == "X"]
    # each span once, under the name it has in the capture; the ring's copy
    # of the phase is not spliced in beside it
    assert sorted(xs) == ["dup_host_span",
                          "paddle_tpu.executor.exec_phase_x"]
    # flight-recorder phases still ride along, args and all
    assert any(e.get("ph") == "X"
               and e["name"] == "paddle_tpu.executor.exec_phase_x"
               and e["args"] == {"step": 0} for e in events)
    # the counter tracks are what the capture lacks: they still ride along
    assert any(e.get("ph") == "C" and e["name"] == "device_memory_bytes"
               for e in events)
    # a trace_dir with no capture is a caller error, not a silent host-only
    # file masquerading as the device timeline
    (tmp_path / "empty").mkdir()
    with pytest.raises(FileNotFoundError, match="xplane"):
        timeline.export_chrome_trace(str(tmp_path / "t2.json"),
                                     trace_dir=str(tmp_path / "empty"))
    profiler.reset_profiler()


def test_merge_chrome_traces_resorts_overlapping_inputs(tmp_path):
    """Per-process captures of one run overlap in ts; the merged file must
    still be monotone or obs_report --trace rejects it."""
    from paddle_tpu import profiler
    a = tmp_path / "a.json"
    a.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "a0", "ts": 1.0, "dur": 1.0, "pid": 1},
        {"ph": "X", "name": "a1", "ts": 9.0, "dur": 1.0, "pid": 1}]}))
    b = tmp_path / "b.json"
    b.write_text(json.dumps({"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 2, "args": {"name": "x"}},
        {"ph": "X", "name": "b0", "ts": 2.0, "dur": 1.0, "pid": 2}]}))
    merged = profiler.merge_chrome_traces([str(a), str(b)],
                                          str(tmp_path / "m.json"))
    events = timeline.validate_trace(merged)   # raises if not sorted
    xs = [e["name"] for e in events if e.get("ph") == "X"]
    assert xs == ["a0", "b0", "a1"]


def test_shift_onto_xplane_aligns_clock_domains(monkeypatch):
    """perf_counter-domain spans must be re-anchored onto the xplane
    capture's own ts epoch, not merged hours away from the device events."""
    from paddle_tpu import profiler
    xplane = [{"ph": "M", "pid": 1, "name": "process_name", "args": {}},
              {"ph": "X", "name": "dev_op", "ts": 500.0, "dur": 5.0,
               "pid": 1}]
    # capture in dir "d" started at perf_counter == 2.0 s; span 100 us later
    monkeypatch.setattr(profiler._agg, "trace_anchor", ("d", 2e6),
                        raising=False)
    perf = [{"ph": "X", "name": "phase", "ts": 2e6 + 100.0, "dur": 3.0,
             "pid": 90001}]
    out = timeline._shift_onto_xplane(perf, xplane, "d")
    assert out[0]["ts"] == pytest.approx(600.0)   # 500 + 100
    # anchor from a DIFFERENT capture dir must not apply: min-align instead
    out2 = timeline._shift_onto_xplane(perf, xplane, "other_dir")
    assert out2[0]["ts"] == pytest.approx(500.0)
    # no anchor at all: the two minima align (best effort)
    monkeypatch.setattr(profiler._agg, "trace_anchor", None, raising=False)
    out3 = timeline._shift_onto_xplane(perf, xplane, "d")
    assert out3[0]["ts"] == pytest.approx(500.0)
    # spans that began before the capture clamp to 0, keeping the file valid
    monkeypatch.setattr(profiler._agg, "trace_anchor", ("d", 3e6),
                        raising=False)
    out4 = timeline._shift_onto_xplane(perf, xplane, "d")
    assert out4[0]["ts"] == 0.0


def test_profiler_summary_empty_is_well_formed():
    from paddle_tpu import profiler
    profiler.reset_profiler()
    table = profiler.summary()
    assert "Event" in table and "Calls" in table
    assert "(no events recorded)" in table
    # stop on a never-enabled aggregate: same well-formed empty table, and
    # no defaultdict side-effect rows appear afterwards
    table2 = profiler.stop_profiler(profile_path=os.devnull)
    assert "(no events recorded)" in table2
    assert profiler._agg.times == {}


def test_start_profiler_clears_previous_sessions_spans():
    """A second profiling session must not export the first one's
    RecordEvent spans (pre-capture spans would clamp to ts 0 in a spliced
    xplane timeline)."""
    from paddle_tpu import profiler
    profiler.start_profiler()
    with profiler.record_event("session_a_span"):
        pass
    profiler.stop_profiler(profile_path=os.devnull)
    profiler.start_profiler()
    with profiler.record_event("session_b_span"):
        pass
    profiler.stop_profiler(profile_path=os.devnull)
    names = [s[0] for s in profiler._agg.spans]
    assert "session_b_span" in names and "session_a_span" not in names
    profiler.reset_profiler()


def test_executor_close_retires_telemetry(monkeypatch, tmp_path):
    """close() drops the compile cache, so it must retire the per-program
    gauges and anomaly windows with it -- same invariant as eviction."""
    monkeypatch.setenv("PADDLE_TPU_OBS", "1")
    monkeypatch.setenv("PADDLE_TPU_OBS_JOURNAL", str(tmp_path / "j.jsonl"))
    main, startup, loss = _loss_program(dim=9)
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 9), "float32")}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        exe.run(main, feed=feed, fetch_list=[loss])   # warm: feeds a window
    label = f"{id(main)}:v{main._version}"

    def has_gauge():
        fam = REGISTRY.get("program_flops")
        return bool(fam) and any(dict(k).get("program") == label
                                 for k in fam.children)

    def has_window():
        return any(isinstance(k, tuple) and k and k[0] == id(main)
                   for k in anomaly.DETECTOR._windows)

    assert has_gauge() and has_window()
    exe.close()
    assert not has_gauge() and not has_window()


def test_executor_close_keeps_sibling_telemetry(monkeypatch, tmp_path):
    """Gauges are process-global: closing one executor must not delete a
    label a still-live sibling executor caches."""
    monkeypatch.setenv("PADDLE_TPU_OBS", "1")
    monkeypatch.setenv("PADDLE_TPU_OBS_JOURNAL", str(tmp_path / "j.jsonl"))
    main, startup, loss = _loss_program(dim=10)
    feed = {"x": np.ones((2, 10), "float32")}
    exe_a, exe_b = fluid.Executor(), fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe_a.run(startup)
        exe_a.run(main, feed=feed, fetch_list=[loss])
        exe_b.run(main, feed=feed, fetch_list=[loss])
    label = f"{id(main)}:v{main._version}"

    def has_gauge():
        fam = REGISTRY.get("program_flops")
        return bool(fam) and any(dict(k).get("program") == label
                                 for k in fam.children)

    assert has_gauge()
    exe_b.close()
    assert has_gauge()       # exe_a still caches the label
    exe_a.close()
    assert not has_gauge()   # last live entry anywhere: now retired


def test_reset_profiler_clears_spans():
    from paddle_tpu import profiler
    profiler.start_profiler()
    with profiler.record_event("span_to_clear"):
        pass
    profiler.stop_profiler(profile_path=os.devnull)
    assert profiler._agg.spans
    profiler.reset_profiler()
    assert profiler._agg.spans == [] and profiler._agg.times == {}


# --------------------------------------------------------------- predictor --

def test_predictor_phases_and_health(tmp_path, monkeypatch):
    import paddle_tpu.io as io
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [4], "float32")
        y = fluid.layers.fc(x, 2)
    exe = fluid.Executor()
    scope = fluid.Scope()
    model_dir = str(tmp_path / "model")
    with fluid.scope_guard(scope):
        exe.run(startup)
        io.save_inference_model(model_dir, ["x"], [y], exe,
                                main_program=main)
    from paddle_tpu.inference import Predictor
    timeline.clear()
    pred = Predictor(model_dir)
    out = pred.run({"x": np.ones((2, 4), "float32")})
    assert out[0].shape == (2, 2)
    cats = {s[1] for s in timeline.spans()}
    assert "predictor" in cats
    names = {s[0] for s in timeline.spans() if s[1] == "predictor"}
    assert {"feed_prep", "dispatch", "fetch_sync"} <= names
    monkeypatch.setenv("PADDLE_TPU_OBS_HEALTH", "raise")
    with pytest.raises(FloatingPointError):
        pred.run({"x": np.full((2, 4), np.nan, "float32")})


# -------------------------------------------------------------- obs_report --

def test_obs_report_trace_cli(tmp_path):
    timeline.clear()
    # run [0.9995, 1.0065] holds feed_prep and dispatch: 7 ms, 2 its own
    with timeline._lock:
        for span in (("feed_prep", "executor", 1.0, 0.001, {"step": 0},
                      0, 2, 1),
                     ("dispatch", "executor", 1.001, 0.004, {"step": 0},
                      0, 3, 1),
                     ("run", "executor", 0.9995, 0.007, {"step": 0},
                      0, 1, 0)):
            timeline._spans.append(timeline.Span(*span))
    timeline.counter_sample("device_memory_bytes", {"cpu:0": 1e6}, t=1.005)
    tpath = timeline.export_chrome_trace(str(tmp_path / "t.json"))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-m", "tools.obs_report",
                        "--trace", tpath], cwd=repo, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "== Timeline ==" in r.stdout
    assert "feed_prep" in r.stdout and "dispatch" in r.stdout
    assert "device_memory_bytes" in r.stdout
    # the phase table prints self time beside total, from the parent links
    table = {ln.split(":")[0].strip(): ln for ln in r.stdout.splitlines()}
    assert "total=7.000 self=2.000" in table["run"]
    assert "total=4.000 self=4.000" in table["dispatch"]
    timeline.clear()


def test_obs_report_health_memory_sections():
    from tools.obs_report import render_health, render_memory
    events = [
        {"event": "tensor_nonfinite", "program": "9:v1", "where": "executor",
         "var": "loss", "vars": ["loss"]},
        {"event": "step_time_anomaly", "program": "9:v1", "step_ms": 80.0,
         "median_ms": 8.0, "mad_ms": 0.4, "limit_ms": 11.2, "n_window": 64},
    ]
    h = render_health(events)
    assert "NONFINITE" in h and "'loss'" in h and "80.0ms" in h
    snapshot = {"families": [
        {"name": "device_memory_bytes_in_use", "type": "gauge", "help": "",
         "samples": [{"labels": {"device": "tpu:0"}, "value": 2.5e9}]},
        {"name": "program_peak_bytes", "type": "gauge", "help": "",
         "samples": [{"labels": {"program": "9:v1"}, "value": 4e9}]},
    ]}
    m = render_memory(snapshot)
    assert "tpu:0" in m and "2.500 GB" in m and "peak 4.000 GB" in m
    # a Prometheus text dump parses to one single-sample family PER series
    # (duplicate names): every device must still render, not just the last
    prom_shape = {"families": [
        {"name": "device_memory_bytes_in_use", "type": "gauge", "help": "",
         "samples": [{"labels": {"device": f"tpu:{i}"}, "value": 1e9 * (i + 1)}]}
        for i in range(3)]}
    m2 = render_memory(prom_shape)
    assert "tpu:0" in m2 and "tpu:1" in m2 and "tpu:2" in m2


def test_pipeline_schedule_span():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from paddle_tpu.parallel import pipeline_spmd

    timeline.clear()
    S, M, MB, D = 2, 3, 2, 4
    mesh = Mesh(np.array(jax.devices()[:S]).reshape(S), ("pp",))
    W = np.tile(np.eye(D, dtype="float32")[None], (S, 1, 1))
    x = np.ones((M, MB, D), "float32")
    pipeline_spmd(lambda p, h: h @ p, jnp.asarray(W), jnp.asarray(x), mesh,
                  axis="pp")
    spans = timeline.spans("pipeline_schedule")
    assert spans and spans[-1][4]["stages"] == S
    assert spans[-1][4]["ticks"] == M + S - 1
