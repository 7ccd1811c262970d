"""Fleet telemetry tests (ISSUE 10): the goodput ledger, the live metrics
endpoint, cross-rank aggregation + straggler detection, and the
zero-overhead guard that keeps all of it free when disarmed.

Acceptance pins:
- on an MLP run with checkpointing + an injected transient fault, the
  goodput ledger's cause breakdown sums to wall-clock within 5% and
  ``goodput_fraction`` is exported;
- a live ``/metrics`` scrape parses via ``parse_prometheus`` and repeated
  quiescent scrapes are byte-stable;
- ``/healthz`` reflects the watchdog state; a taken port degrades with one
  warning, never an exception;
- the 2-rank ``dist_fleet_runner.py`` flags exactly the slowed rank
  (scrape transport runs anywhere; the collective-gather variant is
  skipif-gated on a multiprocess backend).
"""
import builtins
import json
import os
import socket
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.observability import (export as obs_export, fleet, goodput,
                                      health, journal, server)
from paddle_tpu.observability.metrics import REGISTRY, MetricsRegistry

_RUNNER = os.path.join(os.path.dirname(__file__), "dist_fleet_runner.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _train_program(dim=32, seed=0):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [dim], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(
            fluid.layers.fc(x, dim, act="relu"), dim))
        fluid.optimizer.Momentum(0.01, 0.9).minimize(loss)
    return main, startup, loss


def _feed(dim=32, seed=0):
    return {"x": np.random.RandomState(seed).rand(8, dim).astype("float32")}


@pytest.fixture
def obs_env(tmp_path, monkeypatch):
    """Journaling on, journal path isolated, server/fleet torn down."""
    monkeypatch.setenv("PADDLE_TPU_OBS", "1")
    monkeypatch.setenv("PADDLE_TPU_OBS_JOURNAL",
                       str(tmp_path / "journal.jsonl"))
    yield tmp_path
    server.stop()
    fleet.disarm()
    journal.clear()


# ---------------------------------------------------------------- goodput --

def test_goodput_from_synthetic_sources():
    """Cause mapping: phase sums + journal events land in the documented
    buckets and the breakdown sums to the wall exactly (other = rest)."""
    reg = MetricsRegistry()
    for phase, cat, secs in (("dispatch", "executor", 4.0),
                             ("fetch_sync", "executor", 2.0),
                             ("feed_prep", "executor", 0.3),
                             ("journal", "executor", 0.1),
                             ("compile", "executor", 0.8),
                             ("verify", "executor", 0.05),
                             ("feed_wait", "dataset", 0.5),
                             ("run", "executor", 6.0)):    # container
        reg.histogram("phase_seconds", phase=phase, cat=cat).observe(secs)
    reg.histogram("autotune_search_seconds").observe(0.25)
    events = [
        {"event": "run", "cache": "hit", "run_ms": 100.0, "ts": 1.0},
        {"event": "run", "cache": "hit", "run_ms": 100.0, "ts": 2.0},
        {"event": "ckpt_save", "blocked_ms": 400.0, "ts": 3.0},
        {"event": "retry", "backoff_ms": 150.0, "ts": 4.0},
        {"event": "skip", "step": 5, "ts": 5.0},
        {"event": "rollback", "step": 9, "to_step": 7, "ts": 6.0},
        {"event": "elastic_restart_downtime", "downtime_s": 1.5, "ts": 7.0},
    ]
    rep = goodput.compute(events=events, snapshot=obs_export.to_dict(reg),
                          wall_seconds=12.0)
    b = rep.breakdown
    # skip = 1 median step (0.1s), rollback = 2 x median: RE-classified
    # out of the productive dispatch bucket (the executor had already
    # recorded the discarded steps as ordinary execution), never added
    # on top -- the discarded work must LOWER goodput, not inflate loss
    assert b["dispatch"] == pytest.approx(4.0 - 0.3)
    assert b["fetch_sync"] == pytest.approx(2.0)
    assert b["skipped_steps"] == pytest.approx(0.1)
    assert b["rollback"] == pytest.approx(0.2)
    assert b["compile"] == pytest.approx(0.8)
    assert b["verify"] == pytest.approx(0.05)
    assert b["feed_wait"] == pytest.approx(0.5)
    assert b["telemetry"] == pytest.approx(0.1)
    assert b["autotune"] == pytest.approx(0.25)
    assert b["checkpoint"] == pytest.approx(0.4)
    assert b["retry_backoff"] == pytest.approx(0.15)
    assert b["elastic_restart"] == pytest.approx(1.5)
    # the run container must NOT be double-counted
    assert sum(b.values()) == pytest.approx(12.0)
    assert rep.productive_seconds == pytest.approx(5.7)
    assert rep.goodput_fraction == pytest.approx(5.7 / 12.0)
    assert rep.median_step_ms == pytest.approx(100.0)
    # strict async reading: fetch_sync counts lost
    strict = goodput.compute(events=events,
                             snapshot=obs_export.to_dict(reg),
                             wall_seconds=12.0,
                             count_sync_as_productive=False)
    assert strict.goodput_fraction == pytest.approx(3.7 / 12.0)
    assert "fetch_sync" in strict.lost
    summary = rep.summary()
    assert "goodput 47.5%" in summary and "lost compile" in summary


def test_goodput_journal_only_degrades():
    """No metrics snapshot (journal-only obs_report): step/compile time
    falls back to the journaled run_ms/compile_ms."""
    events = [
        {"event": "run", "cache": "miss", "run_ms": 50.0,
         "compile_ms": 900.0, "ts": 10.0},
        {"event": "run", "cache": "hit", "run_ms": 50.0, "ts": 11.0},
        {"event": "run", "cache": "hit", "run_ms": 120.0, "ts": 12.0},
    ]
    rep = goodput.compute(events=events)
    assert rep.n_steps == 3
    assert rep.breakdown["dispatch"] == pytest.approx(0.22)
    assert rep.breakdown["compile"] == pytest.approx(0.9)
    # wall from the journal ts window + the first event's own duration
    assert rep.wall_seconds == pytest.approx(2.0 + 0.95)
    assert "journal_window" in rep.sources
    # empty everything degrades to a zero report, never raises
    empty = goodput.compute()
    assert empty.wall_seconds == 0 and empty.goodput_fraction == 0.0
    assert "no goodput window" in empty.summary()


def test_goodput_wall_window_survives_span_ring_wrap():
    """A long run wraps the bounded span ring; the live wall window must
    come from the persistent anchors, or cumulative phase sums would
    overflow a shrunken window and clamp goodput to 1.0."""
    from paddle_tpu.observability import timeline
    saved = (timeline.spans(), timeline.counters(), timeline.span_window())
    timeline.clear()
    try:
        timeline.record_span("dispatch", 0.0, 1e-9)
        timeline.record_span("dispatch", 500.0, 1e-9)
        with timeline._lock:   # flood the ring, evicting both real spans
            for _ in range(timeline._SPAN_CAP):
                timeline._spans.append(("x", "executor", 100.0, 0.0,
                                        None, 0))
        assert all(s[2] == 100.0 for s in timeline.spans())
        t0, t1 = timeline.span_window()
        assert t0 == 0.0 and t1 == pytest.approx(500.0)
        # ring-derived window would be 0 wide; the live ledger's is not
        assert goodput.compute_live().wall_seconds == pytest.approx(500.0)
    finally:
        with timeline._lock:
            timeline._spans.clear()
            timeline._spans.extend(saved[0])
            timeline._counters.clear()
            timeline._counters.extend(saved[1])
            timeline._window[0], timeline._window[1] = saved[2]


def test_goodput_prefers_cumulative_families_over_aged_journal():
    """Once ckpt_save/skip events age out of the journal ring, the
    cumulative checkpoint_blocked_seconds histogram / steps_skipped_total
    counter keep the causes honest."""
    reg = MetricsRegistry()
    reg.histogram("phase_seconds", phase="dispatch",
                  cat="executor").observe(5.0)
    reg.histogram("checkpoint_blocked_seconds", mode="sync").observe(0.9)
    reg.counter("steps_skipped_total").inc(3)
    events = [{"event": "run", "cache": "hit", "run_ms": 100.0, "ts": 1.0},
              {"event": "ckpt_save", "blocked_ms": 50.0, "ts": 2.0}]
    rep = goodput.compute(events=events, snapshot=obs_export.to_dict(reg),
                          wall_seconds=10.0)
    assert rep.breakdown["checkpoint"] == pytest.approx(0.9)   # not 0.05
    # 3 skips x 100ms median, reclassified out of dispatch
    assert rep.breakdown["skipped_steps"] == pytest.approx(0.3)
    assert rep.breakdown["dispatch"] == pytest.approx(4.7)


def test_goodput_metrics_only_snapshot_uses_exported_window():
    """obs_report --metrics dump.json --goodput (no journal): the wall
    comes from the goodput_wall_seconds gauge the export wrote."""
    reg = MetricsRegistry()
    reg.histogram("phase_seconds", phase="dispatch",
                  cat="executor").observe(3.0)
    reg.gauge("goodput_wall_seconds").set(8.0)
    rep = goodput.compute(snapshot=obs_export.to_dict(reg))
    assert rep.wall_seconds == pytest.approx(8.0)
    assert "exported_window" in rep.sources
    assert rep.goodput_fraction == pytest.approx(3.0 / 8.0)


def test_goodput_export_counters_are_monotone_deltas():
    reg = MetricsRegistry()
    rep1 = goodput.GoodputReport(10.0, {"dispatch": 5.0, "compile": 2.0,
                                        "other": 3.0})
    goodput.export(rep1, reg)
    assert reg.get("goodput_fraction") is not None
    c = reg.counter("lost_seconds_total", cause="compile")
    assert c.value == pytest.approx(2.0)
    # same report re-exported: counters must not double
    goodput.export(rep1, reg)
    assert c.value == pytest.approx(2.0)
    # progressed ledger: only the delta lands
    rep2 = goodput.GoodputReport(20.0, {"dispatch": 11.0, "compile": 2.5,
                                        "other": 6.5})
    goodput.export(rep2, reg)
    assert c.value == pytest.approx(2.5)
    assert reg.gauge("goodput_fraction").value == pytest.approx(11.0 / 20.0)


def test_goodput_acceptance_checkpoint_and_fault(obs_env, monkeypatch):
    """ISSUE 10 acceptance: MLP + checkpointing + one injected transient
    fault -> the ledger's cause breakdown sums to wall-clock within 5%,
    checkpoint/retry/compile causes are attributed, goodput_fraction is
    exported, and obs_report renders the section."""
    from paddle_tpu.resilience import faults
    from paddle_tpu.resilience.recovery import StepGuardian
    from paddle_tpu.utils.checkpointer import Checkpointer

    main, startup, loss = _train_program()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, str(obs_env / "ck"), max_to_keep=2)
        g = StepGuardian(exe, main, checkpointer=ck, retry_backoff=0.05)
        faults.install("exc@dispatch:step=5")
        try:
            with goodput.run_ledger() as led:
                for i in range(12):
                    g.run(feed=_feed(), fetch_list=[loss])
                    if i % 4 == 3:
                        ck.save(i)
        finally:
            faults.clear()
            g.close()
        ck.close()
    rep = led.report()
    b = rep.breakdown
    assert rep.wall_seconds > 0 and rep.n_steps >= 12
    # named causes from this exact scenario
    assert b["compile"] > 0, b
    assert b["checkpoint"] > 0, b
    assert b["retry_backoff"] > 0, b
    assert rep.productive_seconds > 0
    # breakdown sums to wall within 5% (other absorbs unattributed host
    # time; overlap between sources must stay under the tolerance)
    assert abs(sum(b.values()) - rep.wall_seconds) <= 0.05 * rep.wall_seconds
    assert rep.overaccounted_seconds <= 0.05 * rep.wall_seconds
    assert 0.0 < rep.goodput_fraction <= 1.0
    # exported surface
    reg = MetricsRegistry()
    goodput.export(rep, reg)
    assert reg.gauge("goodput_fraction").value == \
        pytest.approx(rep.goodput_fraction)
    assert reg.counter("lost_seconds_total",
                       cause="checkpoint").value > 0
    # obs_report renders it from the journal file + a metrics dump
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools import obs_report
    mpath = obs_env / "metrics.json"
    obs_export.dump_json(str(mpath))
    out = obs_report.render_report(
        journal.read_journal(str(obs_env / "journal.jsonl")),
        obs_report.load_metrics(str(mpath)), goodput=True, fleet=True)
    assert "== Goodput ==" in out and "-> goodput" in out
    assert "lost checkpoint" in out
    assert "== Fleet ==" in out


# ----------------------------------------------------------------- server --

def test_metrics_endpoint_roundtrip_and_stability(obs_env, monkeypatch):
    """Scrape /metrics during a live run: parse_prometheus round-trips it,
    quiescent re-scrapes are byte-stable, /goodput + /journal serve."""
    monkeypatch.setenv("PADDLE_TPU_OBS_PORT", "0")   # ephemeral port
    main, startup, loss = _train_program()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor()
        srv = server.current()
        assert srv is not None, "PADDLE_TPU_OBS_PORT did not arm the server"
        exe.run(startup)
        for _ in range(6):
            exe.run(main, feed=_feed(), fetch_list=[loss])
        mid = urllib.request.urlopen(srv.url + "/metrics").read().decode()
        assert ("executor_run_seconds_count", ()) in \
            obs_export.parse_prometheus(mid)
        for _ in range(6):
            exe.run(main, feed=_feed(), fetch_list=[loss])
        t1 = urllib.request.urlopen(srv.url + "/metrics").read().decode()
        t2 = urllib.request.urlopen(srv.url + "/metrics").read().decode()
        assert t1 == t2, "quiescent scrapes must be byte-stable"
        parsed = obs_export.parse_prometheus(t1)
        # the scrape mirrors the live registry exactly (REGISTRY is
        # process-global, so compare against it rather than a constant)
        assert parsed[("executor_runs_total", ())] == \
            REGISTRY.counter("executor_runs_total").value
        assert ("goodput_fraction", ()) in parsed
        assert any(name == "lost_seconds_total"
                   for name, _labels in parsed)
        # /goodput serves the same ledger as JSON
        g = json.load(urllib.request.urlopen(srv.url + "/goodput"))
        assert g["goodput_fraction"] == \
            pytest.approx(parsed[("goodput_fraction", ())], abs=1e-6)
        assert g["wall_seconds"] > 0
        # /journal tail is bounded and JSONL
        lines = urllib.request.urlopen(
            srv.url + "/journal?n=5").read().decode().strip().splitlines()
        assert 0 < len(lines) <= 5
        assert json.loads(lines[-1])["event"] == "run"
        # unknown route -> 404, never a crash
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/nope")
        assert ei.value.code == 404


def test_healthz_reflects_watchdog_state(obs_env, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_OBS_PORT", "0")
    monkeypatch.setenv("PADDLE_TPU_OBS_HEALTH", "warn")
    # the verdict reads a process-wide counter: forget what earlier tests
    # of this worker tripped, or the outcome depends on the schedule
    fam = REGISTRY.get("tensor_nonfinite_total")
    for labels, _ in (fam.items() if fam is not None else ()):
        REGISTRY.remove_labeled("tensor_nonfinite_total", **dict(labels))
    srv = server.start()
    assert srv is not None
    doc = json.load(urllib.request.urlopen(srv.url + "/healthz"))
    assert doc["status"] == "ok" and doc["health_mode"] == "warn"
    base_nonfinite = doc["nonfinite_total"]
    # drive the watchdog: one non-finite tensor through the real scan
    with pytest.warns(UserWarning):
        health.check([("loss", np.array([np.inf], np.float32))],
                     "prog:v0", where="executor", health_mode="warn")
    health.take_verdict("prog:v0")   # don't leak a stashed verdict
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(srv.url + "/healthz")
    assert ei.value.code == 503
    doc = json.loads(ei.value.read())
    assert doc["status"] == "unhealthy"
    assert doc["nonfinite_total"] == base_nonfinite + 1
    assert doc["last_nonfinite"]["var"] == "loss"


def test_port_in_use_degrades_warn_once(obs_env, recwarn):
    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    port = blocker.getsockname()[1]
    try:
        assert server.start(port=port) is None
        w1 = [w for w in recwarn.list
              if "cannot bind" in str(w.message)]
        assert len(w1) == 1, "bind failure must warn"
        server.stop()
        assert server.start(port=port) is None
        w2 = [w for w in recwarn.list
              if "cannot bind" in str(w.message)]
        assert len(w2) == 1, "second failure on the same port: warn ONCE"
    finally:
        blocker.close()


def test_port_offset_by_rank(monkeypatch):
    monkeypatch.setenv("NUM_PROCESSES", "4")
    monkeypatch.setenv("PROCESS_ID", "2")
    monkeypatch.setenv(server.PORT_ENV, "9500")
    assert server.port_from_env() == 9502
    monkeypatch.setenv("NUM_PROCESSES", "1")
    monkeypatch.setenv("PROCESS_ID", "0")
    assert server.port_from_env() == 9500


# ------------------------------------------------------------------ guard --

@pytest.mark.smoke
def test_zero_overhead_when_disarmed(tmp_path, monkeypatch):
    """ISSUE 10 guard: with PADDLE_TPU_OBS_PORT / PADDLE_TPU_FLEET unset a
    training run opens no sockets, spawns no threads, arms no monitor and
    performs no file I/O."""
    for var in ("PADDLE_TPU_OBS_PORT", "PADDLE_TPU_FLEET",
                "PADDLE_TPU_OBS", "PADDLE_TPU_OBS_JOURNAL"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.chdir(tmp_path)
    server.stop()
    fleet.disarm()
    sockets, opened = [], []
    real_socket = socket.socket
    real_open = builtins.open

    class SpySocket(socket.socket):
        def __init__(self, *a, **k):
            sockets.append(1)
            super().__init__(*a, **k)

    def spy_open(file, *a, **k):
        opened.append(str(file))
        return real_open(file, *a, **k)

    main, startup, loss = _train_program()
    with fluid.scope_guard(fluid.Scope()):
        threads_before = set(threading.enumerate())
        monkeypatch.setattr(socket, "socket", SpySocket)
        exe = fluid.Executor()          # the arming points read env only
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[loss])  # compile pre-spy
        monkeypatch.setattr(builtins, "open", spy_open)
        try:
            for _ in range(3):
                exe.run(main, feed=_feed(), fetch_list=[loss])
        finally:
            monkeypatch.setattr(builtins, "open", real_open)
            monkeypatch.setattr(socket, "socket", real_socket)
    assert sockets == [], "disarmed run created sockets"
    assert fleet.MONITOR is None
    assert server.current() is None
    watched = [p for p in opened if ".jsonl" in p or "paddle_tpu" in p]
    assert watched == [], f"disarmed hot path opened files: {watched}"
    new_threads = {t for t in set(threading.enumerate()) - threads_before
                   if t.name.startswith("paddle-tpu-")}
    assert new_threads == set()


# ------------------------------------------------------------------ fleet --

def test_detect_stragglers_leave_one_out():
    rows = [{"rank": r, "step_ms": 4.0 + 0.1 * r, "n": 16}
            for r in range(5)]
    assert fleet.detect_stragglers(rows) == []
    rows[3]["step_ms"] = 40.0
    flagged = fleet.detect_stragglers(rows)
    assert [f["rank"] for f in flagged] == [3]
    assert flagged[0]["limit_ms"] < 40.0
    # 2-rank fleet: the straggler must not hide inside its own reference
    two = [{"rank": 0, "step_ms": 2.0, "n": 16},
           {"rank": 1, "step_ms": 20.0, "n": 16}]
    assert [f["rank"] for f in fleet.detect_stragglers(two)] == [1]
    # insufficient samples are ineligible (warmup must not false-flag)
    two[1]["n"] = 2
    assert fleet.detect_stragglers(two) == []
    # a quiet fleet's tiny MAD must not flag microseconds of skew
    quiet = [{"rank": r, "step_ms": 1.0 + 1e-4 * r, "n": 16}
             for r in range(4)]
    assert fleet.detect_stragglers(quiet) == []


def test_goodput_reclassification_never_invents_seconds():
    """When the discard estimate exceeds the recorded productive time, only
    what was actually moved counts as loss -- the breakdown still sums."""
    reg = MetricsRegistry()
    reg.histogram("phase_seconds", phase="dispatch",
                  cat="executor").observe(0.03)
    reg.histogram("phase_seconds", phase="fetch_sync",
                  cat="executor").observe(0.02)
    events = [
        {"event": "run", "cache": "hit", "run_ms": 100.0, "ts": 1.0},
        {"event": "skip", "step": 2, "ts": 2.0},
        {"event": "skip", "step": 3, "ts": 3.0},
    ]
    rep = goodput.compute(events=events, snapshot=obs_export.to_dict(reg),
                          wall_seconds=1.0)
    b = rep.breakdown
    assert b["skipped_steps"] == pytest.approx(0.05)   # capped, not 0.2
    assert b["dispatch"] == 0.0 and b["fetch_sync"] == 0.0
    assert sum(b.values()) == pytest.approx(1.0)
    assert rep.overaccounted_seconds == 0.0


def test_gather_cadence_is_step_keyed_and_fires_once(monkeypatch):
    """A retried/rewound step (same program step index) must not issue a
    second lone collection -- the collective stays rank-aligned."""
    mon = fleet.FleetMonitor("gather", interval=4, period=60.0)
    calls = []
    monkeypatch.setattr(mon, "collect", lambda *a, **k: calls.append(1))
    for i in range(4):
        mon.on_step(step=i)
    assert len(calls) == 1            # boundary at committed step 4
    mon.on_step(step=3)               # guardian rewound + re-ran step 3
    assert len(calls) == 1, "re-run of a collected step must not re-fire"
    for i in range(4, 8):
        mon.on_step(step=i)
    assert len(calls) == 2
    mon.close()


def test_scrape_without_peers_warns(monkeypatch, recwarn):
    monkeypatch.setenv("NUM_PROCESSES", "2")
    monkeypatch.setenv("PROCESS_ID", "0")
    monkeypatch.delenv("PADDLE_TPU_OBS_PORT", raising=False)
    monkeypatch.delenv("PADDLE_TPU_FLEET_PEERS", raising=False)
    mon = fleet.FleetMonitor("scrape", period=60.0)
    try:
        assert any("no peer endpoints" in str(w.message)
                   for w in recwarn.list)
    finally:
        mon.close()


def test_fleet_monitor_local_collection(obs_env, monkeypatch):
    """Single-process gather mode: cadence fires, gauges export with
    rank/host labels, fleet events journal, no straggler verdicts."""
    monkeypatch.setenv("PADDLE_TPU_FLEET", "gather")
    monkeypatch.setenv("PADDLE_TPU_FLEET_INTERVAL", "4")
    main, startup, loss = _train_program()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor()
        assert fleet.MONITOR is not None
        exe.run(startup)
        for _ in range(9):
            exe.run(main, feed=_feed(), fetch_list=[loss])
    events = journal.recent(event="fleet")
    assert len(events) >= 2
    last = events[-1]
    assert last["transport"] == "local" and last["n_ranks"] == 1
    row = last["ranks"][0]
    assert row["rank"] == 0 and row["steps"] >= 8
    assert row["step_ms"] is not None and row["n"] >= 4
    assert journal.recent(event="straggler") == []
    fam = REGISTRY.get("fleet_step_time_ms")
    assert fam is not None
    labels = [dict(k) for k, _c in fam.items()]
    assert any(l.get("rank") == "0" and l.get("host") for l in labels)


def test_fleet_rows_roundtrip_through_prometheus():
    """The scrape transport's wire format: export_local gauges ->
    to_prometheus -> parse_prometheus -> the same row."""
    reg = MetricsRegistry()
    labels = {"rank": "3", "host": "h3"}
    reg.gauge("fleet_step_time_ms", **labels).set(12.5)
    reg.gauge("fleet_step_time_mad_ms", **labels).set(0.5)
    reg.gauge("fleet_warm_samples", **labels).set(16)
    reg.gauge("fleet_steps", **labels).set(640)
    reg.gauge("fleet_restarts", **labels).set(1)
    rows = fleet._rows_from_samples(
        obs_export.parse_prometheus(obs_export.to_prometheus(reg)))
    assert rows == [{"rank": 3, "host": "h3", "step_ms": 12.5,
                     "mad_ms": 0.5, "n": 16, "steps": 640, "restarts": 1}]


def _launch_fleet(mode, slow_ms=30.0):
    env = dict(os.environ)
    for var in ("XLA_FLAGS", "JAX_PLATFORMS", "PADDLE_TPU_OBS",
                "PADDLE_TPU_OBS_JOURNAL", "PADDLE_TPU_FLEET",
                "PADDLE_TPU_OBS_PORT", "PADDLE_TPU_FAULTS"):
        env.pop(var, None)
    port, obs_base = _free_port(), _free_port()
    procs = [subprocess.Popen(
        [sys.executable, _RUNNER, str(r), "2", str(port), mode,
         str(obs_base), str(slow_ms)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env)
        for r in range(2)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, (
            f"fleet rank failed rc={p.returncode}:\n{err.decode()[-2000:]}")
        outs.append(out.decode())
    return outs


def _tagged(out, tag):
    for line in out.splitlines():
        if line.startswith(tag + ":"):
            return json.loads(line[len(tag) + 1:])
    raise AssertionError(f"no {tag} line in output: {out[-500:]}")


def test_two_rank_straggler_detection_scrape():
    """ISSUE 10 acceptance: rank 1 runs with an injected per-step hang;
    rank 0, scraping peer /metrics endpoints, flags EXACTLY rank 1."""
    outs = _launch_fleet("scrape")
    assert _tagged(outs[0], "STRAGGLERS") == [1]
    table = _tagged(outs[0], "FLEET")
    assert table["n_ranks"] == 2 and table["transport"] == "scrape"
    by_rank = {r["rank"]: r for r in table["ranks"]}
    assert by_rank[1]["step_ms"] > by_rank[0]["step_ms"]


# lazily evaluated skip condition shared with test_multihost.py: plain
# collection must not pay the jax-import subprocess probe.  The probe
# function must land in THIS module's namespace -- pytest evaluates the
# string condition against the test's own globals.
from test_multihost import (_ranks_would_run_cpu,  # noqa: E402,F401
                            requires_multiprocess_backend)


@requires_multiprocess_backend
def test_two_rank_straggler_detection_gather():
    outs = _launch_fleet("gather")
    assert _tagged(outs[0], "STRAGGLERS") == [1]
    table = _tagged(outs[0], "FLEET")
    assert table["n_ranks"] == 2 and table["transport"] == "gather"


# ------------------------------------------------------------- satellites --

def test_journal_rank_field(monkeypatch):
    journal.clear()
    monkeypatch.setenv("NUM_PROCESSES", "2")
    monkeypatch.setenv("PROCESS_ID", "1")
    try:
        ev = journal.emit({"event": "probe"})
        assert ev["rank"] == 1 and journal.current_rank() == 1
    finally:
        journal.clear()
    monkeypatch.setenv("NUM_PROCESSES", "1")
    monkeypatch.setenv("PROCESS_ID", "0")
    ev = journal.emit({"event": "probe"})
    assert "rank" not in ev and journal.current_rank() is None
    journal.clear()


def test_merged_traces_keep_rank_tracks(tmp_path, monkeypatch):
    """merge_chrome_traces over per-rank exports keeps distinct,
    rank-tagged process track names."""
    from paddle_tpu import profiler
    from paddle_tpu.observability import timeline
    paths = []
    for rank in ("0", "1"):
        journal.clear()
        monkeypatch.setenv("NUM_PROCESSES", "2")
        monkeypatch.setenv("PROCESS_ID", rank)
        saved = (timeline.spans(), timeline.counters())
        timeline.clear()
        try:
            with timeline._lock:
                timeline._spans.append(
                    ("dispatch", "executor", 1.0, 0.01, {"step": 0}))
            p = str(tmp_path / f"rank{rank}.json")
            timeline.export_chrome_trace(p, include_profiler=False)
            paths.append(p)
        finally:
            with timeline._lock:
                timeline._spans.clear()
                timeline._spans.extend(saved[0])
                timeline._counters.clear()
                timeline._counters.extend(saved[1])
    journal.clear()
    merged = profiler.merge_chrome_traces(paths,
                                          str(tmp_path / "merged.json"))
    with open(merged) as f:
        events = json.load(f)["traceEvents"]
    names = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert any("[rank 0]" in n and "flight recorder" in n for n in names)
    assert any("[rank 1]" in n and "flight recorder" in n for n in names)
    pids = {e["pid"]: e["args"]["name"] for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert len(set(pids.values())) == len(pids), "track names must differ"


def test_launch_restart_downtime_measured(tmp_path, monkeypatch):
    """The elastic-restart satellite: kill -> respawn downtime is measured
    and fed to the ledger as lost_seconds_total{cause=elastic_restart}."""
    from paddle_tpu.parallel import launch
    journal.clear()
    monkeypatch.chdir(tmp_path)
    script = tmp_path / "flaky.py"
    script.write_text(
        "import os, sys\n"
        "sys.exit(1 if os.environ.get('PADDLE_RESTART_ATTEMPT') == '0' "
        "else 0)\n")
    before = REGISTRY.counter("lost_seconds_total",
                              cause="elastic_restart").value
    codes = launch.launch(1, [str(script)], max_restarts=1,
                          restart_backoff=0.05,
                          log_dir=str(tmp_path / "logs"))
    assert codes == [0]
    evs = journal.recent(event="elastic_restart_downtime")
    assert len(evs) == 1
    assert evs[0]["attempt"] == 1 and evs[0]["downtime_s"] > 0
    after = REGISTRY.counter("lost_seconds_total",
                             cause="elastic_restart").value
    assert after - before == pytest.approx(evs[0]["downtime_s"], abs=0.05)
    # the goodput ledger picks the downtime up from the journal
    rep = goodput.compute(events=journal.recent())
    assert rep.breakdown["elastic_restart"] == \
        pytest.approx(evs[0]["downtime_s"], abs=1e-6)
    journal.clear()


def test_obs_report_goodput_fleet_cli(tmp_path):
    """CLI surface: --goodput/--fleet flags render their sections from a
    journal file (no metrics dump needed)."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools import obs_report
    jpath = tmp_path / "j.jsonl"
    with open(jpath, "w") as f:
        for e in (
            {"event": "run", "cache": "hit", "run_ms": 5.0, "ts": 1.0},
            {"event": "run", "cache": "hit", "run_ms": 5.0, "ts": 2.0},
            {"event": "fleet", "transport": "scrape", "n_ranks": 2,
             "median_ms": 5.0, "skew": 4.0, "stragglers": [1],
             "ranks": [{"rank": 0, "host": "a", "step_ms": 5.0,
                        "mad_ms": 0.1, "n": 8, "steps": 32, "restarts": 0},
                       {"rank": 1, "host": "b", "step_ms": 20.0,
                        "mad_ms": 0.2, "n": 8, "steps": 32,
                        "restarts": 0}], "ts": 3.0},
            {"event": "straggler", "rank": 1, "host": "b", "step_ms": 20.0,
             "median_ms": 5.0, "mad_ms": 0.1, "limit_ms": 7.0,
             "n_ranks": 2, "ts": 4.0},
        ):
            f.write(json.dumps(e) + "\n")
    import contextlib
    import io
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = obs_report.main(["--journal", str(jpath), "--goodput",
                              "--fleet"])
    out = buf.getvalue()
    assert rc == 0
    assert "== Goodput ==" in out and "-> goodput" in out
    assert "== Fleet ==" in out and "STRAGGLER rank 1" in out
