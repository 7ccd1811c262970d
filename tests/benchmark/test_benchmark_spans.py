"""The readers that put the program's span tree against the device trace,
checked without a device: ``span_idle_overlap`` on a hand-made ``Trace`` and
hand-made spans (so the offset and every exposed nanosecond can be worked
out by eye), ``phase_total`` on the program's registry, and both accessors
of ``probe_spans`` against the program's flight recorder as it is."""
import json
import os
import threading

import pytest

from benchmark import probe_spans
from benchmark import trace as tr
from benchmark.probe_spans import Node
from benchmark.reducers import phase_total, span_idle_overlap

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OFFSET = 123_456_789.0           # trace ns = perf_counter ns + OFFSET
T = 5.0                          # perf_counter seconds where the spans begin


def spec_of(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


class Ev:
    """The part of run.py's Evidence the span readers use."""

    def __init__(self, trace, steps=2):
        self.trace, self.traced_steps, self.said = trace, steps, []
        self.say = self.said.append


def at(us):
    """perf_counter seconds of a point ``us`` microseconds into the toy."""
    return T + us * 1e-6


def ns(us):
    """The same point on the trace's axis."""
    return T * 1e9 + us * 1e3 + OFFSET


def toy(lead_us=(0.0, 0.0), me=None):
    """Two steps in a window of 1000 us. The device runs ops during
    [100, 400] and [500, 900] us (and a 10 us pause at 250 that is no host
    gap), so it idles [0, 100], [400, 500] and [900, 1000]. On the calling
    thread: run A [20, 180] with feed_prep [30, 90] > h2d [40, 80] and
    dispatch [100, 170]; run B [420, 560] with feed_prep [430, 470] >
    h2d [440, 460] and dispatch [480, 550]; a worker thread's produce over
    the whole window. ``bench.exe_run`` wraps each run, beginning
    ``lead_us`` before it."""
    me = me or threading.get_ident()
    spans = [
        Node("h2d", at(40), 40e-6, me, 3, 2),
        Node("feed_prep", at(30), 60e-6, me, 2, 1),
        Node("dispatch", at(100), 70e-6, me, 4, 1),
        Node("produce", at(0), 1000e-6, me + 1, 9, 0),
        Node("run", at(20), 160e-6, me, 1, 0),
        Node("h2d", at(440), 20e-6, me, 7, 6),
        Node("feed_prep", at(430), 40e-6, me, 6, 5),
        Node("dispatch", at(480), 70e-6, me, 8, 5),
        Node("run", at(420), 140e-6, me, 5, 0),
    ]
    ops = [("%fusion.1 = f32[8] fusion(%p)", ns(100), ns(250)),
           ("%fusion.2 = f32[8] fusion(%p)", ns(260), ns(400)),
           ("%fusion.1 = f32[8] fusion(%p)", ns(500), ns(900))]
    host = [("bench.next_batch", ns(10), ns(12)),
            ("bench.exe_run", ns(20 - lead_us[0]), ns(185)),
            ("bench.exe_run", ns(420 - lead_us[1]), ns(565)),
            ("bench.final_sync", ns(600), ns(1000))]
    trace = tr.Trace({"/device:TPU:0": {tr.OPS_LINE: ops}}, host,
                     (ns(0), ns(1000)))
    return spans, trace


@pytest.fixture
def ring(monkeypatch):
    """Stand hand-made spans in for the program's ring."""
    def put(spans):
        monkeypatch.setattr(probe_spans, "tree", lambda: list(spans))
    return put


def test_clock_offset_is_recovered_to_the_nanosecond():
    spans, trace = toy()
    roots = [s for s in spans if s.parent == 0 and s.name == "run"]
    notes = [e for e in trace.host if e[0] == "bench.exe_run"]
    offset, spread, slack = span_idle_overlap.clock_offset(roots, notes)
    assert abs(offset - OFFSET) < 1.0 and spread < 1.0
    # each annotation outlasts its run by 5 us: how early, at most, the
    # offset places a span
    assert slack == pytest.approx(5e3, abs=1.0)


def test_exposure_by_hand(ring):
    spans, trace = toy()
    ring(spans)
    ev = Ev(trace)
    # run A [20, 180] meets idle [0, 100]: 80 us; run B [420, 560] meets
    # idle [400, 500]: 80 us; the 10 us pause at 250 is under HOST_GAP_NS
    got = span_idle_overlap.reduce(spec_of("dispatch.exposed_ms_per_step"),
                                   ev)
    assert got == pytest.approx((80 + 80) / 1e3 / 2, abs=1e-6)
    # the same reader on another span: h2d [40, 80] is all idle, [440, 460]
    # too: 60 us over two steps
    h2d = dict(spec_of("dispatch.exposed_ms_per_step"), match=["h2d"],
               idle_table=False)
    assert span_idle_overlap.reduce(h2d, Ev(trace)) == pytest.approx(
        60 / 1e3 / 2, abs=1e-6)
    # a span that never occurred waited for nothing: 0, not None
    assert span_idle_overlap.reduce(
        spec_of("input.exposed_ms_per_step"), Ev(trace)) == 0.0
    # the idle table: by innermost span of the calling thread, adding up to
    # the device's idle time (the other thread's produce is not in it)
    line, = ev.said
    table = json.loads(line.split("s: ", 1)[1].split("; together")[0])
    want = {"none": (20 + 20 + 100) * 1e-6,      # [0,20] [400,420] [900,1000]
            "run(self)": (10 + 10 + 10 + 10) * 1e-6,
            "feed_prep(self)": (10 + 10 + 10 + 10) * 1e-6,
            "h2d": (40 + 20) * 1e-6,
            "dispatch": 20 * 1e-6,               # B's [480, 500]
            "between_ops": 10 * 1e-6}
    assert table == pytest.approx(want, abs=1e-9)
    assert list(table) == sorted(table, key=lambda k: -table[k])
    assert "together 0.000310s of the first device's 0.000310s idle" in line
    assert "clock offset from 2 pairs, spread 0 ns, slack 5000 ns" in line


def test_a_nested_span_counts_once(ring):
    spans, trace = toy()
    me = threading.get_ident()
    # run_fused with k == 1 delegates to run: a run inside a run
    spans.insert(0, Node("run", at(25), 150e-6, me, 10, 1))
    ring(spans)
    got = span_idle_overlap.reduce(spec_of("dispatch.exposed_ms_per_step"),
                                   Ev(trace))
    assert got == pytest.approx((80 + 80) / 1e3 / 2, abs=1e-6)


def test_offsets_that_disagree_raise(ring):
    spans, trace = toy(lead_us=(0.0, 25.0))      # 25 us apart, limit 20
    ring(spans)
    with pytest.raises(ValueError, match="MAX_OFFSET_SPREAD_NS"):
        span_idle_overlap.reduce(spec_of("dispatch.exposed_ms_per_step"),
                                 Ev(trace))
    # within the limit it reports, from the median offset: 1 us short of
    # the true one, so both runs reach 1 us further into their idle gaps
    spans, trace = toy(lead_us=(0.0, 2.0))
    ring(spans)
    got = span_idle_overlap.reduce(spec_of("dispatch.exposed_ms_per_step"),
                                   Ev(trace))
    assert got == pytest.approx((81 + 81) / 1e3 / 2, abs=1e-6)


def test_one_torn_pair_among_many_does_not_condemn_the_run():
    me = threading.get_ident()
    roots = [Node("run", at(100 * i), 50e-6, me, i + 1, 0) for i in range(8)]
    leads = [1.0, 1.2, 0.9, 1.1, 300.0, 1.0, 1.3, 0.8]   # us; one preempted
    notes = [("bench.exe_run", ns(100 * i - lead), ns(100 * i + 55))
             for i, lead in enumerate(leads)]
    offset, spread, _ = span_idle_overlap.clock_offset(roots, notes)
    assert abs(offset - (OFFSET - 1.05e3)) < 1.0 and spread < 400


def test_a_span_outside_its_annotation_raises(ring):
    spans, trace = toy()
    # the second run outlives its annotation by 100 us: a wrong pairing
    spans[-1] = spans[-1]._replace(dur=240e-6)
    ring(spans)
    with pytest.raises(ValueError, match="does not lie inside"):
        span_idle_overlap.reduce(spec_of("dispatch.exposed_ms_per_step"),
                                 Ev(trace))


def test_fewer_roots_than_annotations_raises(ring):
    spans, trace = toy()
    ring([s for s in spans if s.id != 5])
    with pytest.raises(ValueError, match="root spans"):
        span_idle_overlap.reduce(spec_of("dispatch.exposed_ms_per_step"),
                                 Ev(trace))


@pytest.mark.parametrize("case", ["no_trace", "no_tree", "other_thread"])
def test_nothing_to_read_is_none_not_an_error(ring, case):
    """What the parent commit gives: flat tuples, so no tree."""
    spans, trace = toy(me=threading.get_ident() + (case == "other_thread"))
    ring([] if case == "no_tree" else spans)
    ev = Ev(None if case == "no_trace" else trace)
    for name in ("dispatch.exposed_ms_per_step",
                 "dispatch.exposed_ms_per_step.examples",
                 "input.exposed_ms_per_step"):
        assert span_idle_overlap.reduce(spec_of(name), ev) is None
    assert ev.said == []


def test_probe_spans_reads_the_programs_ring():
    from paddle_tpu.observability import timeline
    timeline.clear()
    with timeline.phase("outer_probe", cat="test"):
        with timeline.phase("inner_probe", cat="test"):
            pass
    inner, outer = probe_spans.tree()
    assert (inner.name, outer.name) == ("inner_probe", "outer_probe")
    assert inner.parent == outer.id and outer.parent == 0
    assert inner.tid == outer.tid == threading.get_ident()
    assert outer.t0 <= inner.t0 and inner.dur <= outer.dur
    # entries without the tree's fields (an older program) are left out
    with timeline._lock:
        timeline._spans.append(("flat", "test", 1.0, 0.5, None, 7))
    assert [s.name for s in probe_spans.tree()] == ["inner_probe",
                                                    "outer_probe"]
    timeline.clear()


def test_phase_total_sums_the_registry():
    from paddle_tpu.observability import timeline
    spec = dict(spec_of("compile.trace_lower_s"), match=["unit_lower"],
                cat="bench_test")
    assert phase_total.reduce(spec, None) is None       # never observed
    timeline.record_span("unit_lower", 1.0, 0.25, cat="bench_test")
    timeline.record_span("unit_lower", 2.0, 0.5, cat="bench_test")
    timeline.record_span("unit_lower", 2.0, 8.0, cat="another_cat")
    timeline.record_span("unit_other", 2.0, 8.0, cat="bench_test")
    timeline.clear()                    # the ring may wrap; the sum stays
    assert phase_total.reduce(spec, None) == pytest.approx(0.75)
    real = spec_of("compile.trace_lower_s")
    assert (real["match"], real["cat"]) == (["trace_lower"], "executor")


def test_exposed_metrics_state_their_pairing():
    for name in ("dispatch.exposed_ms_per_step",
                 "dispatch.exposed_ms_per_step.examples",
                 "input.exposed_ms_per_step"):
        spec = spec_of(name)
        assert spec["pair"] == "bench.exe_run"
        # the limit on the pairs' spread is the reader's, stated once
        assert "max_offset_spread_ns" not in spec
    assert 0 < span_idle_overlap.MAX_OFFSET_SPREAD_NS <= tr.HOST_GAP_NS
    # the cells with one pair say what their offset rests on
    for name in ("dispatch.exposed_ms_per_step.examples",
                 "input.exposed_ms_per_step"):
        assert "one pair" in spec_of(name)["doc"]
    # one of a cell's exposed metrics prints the idle table, not each
    assert spec_of("dispatch.exposed_ms_per_step")["idle_table"]
    assert spec_of("dispatch.exposed_ms_per_step.examples")["idle_table"]
    assert not spec_of("input.exposed_ms_per_step").get("idle_table")
