"""The benchmark's trace -> metrics reduction, checked without a device:
interval arithmetic on hand-made events (so every expected number can be
worked out by eye), the scope join on HLO lines as the TPU compiler wrote
them, and a profiler file recorded on the CPU (benchmark/fixtures)."""
import os

import pytest

from benchmark import trace as tr
from benchmark.reducers import (collective, module_ms, roofline_share,
                                scope_time_share)

FIX = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark", "fixtures")


def hlo(name="step_tpu_v5e.hlo.txt"):
    with open(os.path.join(FIX, name)) as f:
        return tr.parse_hlo(f.read())


class Ev:
    """The part of run.py's Evidence the trace readers use."""

    def __init__(self, trace, hlo, steps=1, cell=None, peaks=None):
        self.trace, self.hlo, self.traced_steps = trace, hlo, steps
        self.cell, self.peaks, self.said = cell, peaks, []
        self.say = self.said.append


def toy_trace():
    """One device, window [0, 1000): three ops, a gap of 100 and one of 300
    under the host's loss read, then an all-reduce (async span 600-900)
    whose first 100 ns overlap a fusion and whose -done op waits 150."""
    ops = [("%fusion.1 = f32[8] fusion(%p)", 0, 200),
           ("%fused_attention_21.1 = bf16[96,2048,64] custom-call(%a)",
            200, 300),
           ("%copy.2900 = f32[1] copy(%p)", 400, 500),        # gap 300-400
           ("%all-reduce-start.1 = f32[8] all-reduce-start(%g)", 590, 600),
           ("%subtract_convert_fusion.153 = f32[2] fusion(%a)", 600, 700),
           ("%all-reduce-done.1 = f32[8] all-reduce-done(%s)", 750, 900)]
    async_line = [("%all-reduce-start.1 = f32[8] all-reduce-start(%g)",
                   600, 900)]
    modules = [("jit_step(123)", 0, 500), ("jit_step(123)", 590, 900),
               ("jit_other(7)", 950, 960)]
    host = [("bench.exe_run", 0, 120), ("bench.loss_read", 120, 480),
            ("bench.exe_run", 480, 560), ("bench.final_sync", 560, 1000)]
    return tr.Trace({"/device:TPU:0": {tr.OPS_LINE: ops,
                                       tr.ASYNC_LINE: async_line,
                                       tr.MODULES_LINE: modules}},
                    host, (0.0, 1000.0))


def test_union_subtract_and_length():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)]
    assert tr.length(u) == 6
    assert tr.subtract([(0, 10)], u) == [(3, 5), (8, 10)]
    assert tr.subtract(u, [(0, 10)]) == []
    assert tr.subtract([(0, 4), (6, 9)], [(1, 2), (3, 7)]) == \
        [(0, 1), (2, 3), (7, 9)]


def test_busy_idle_and_window():
    t = toy_trace()
    # 0-300, 400-500, 590-700, 750-900
    assert tr.busy_ns(t.first_device()) == 300 + 100 + 110 + 150
    assert tr.busy_s(t) == pytest.approx(660e-9)
    assert t.window_s == pytest.approx(1000e-9)


def test_clip_cuts_events_to_the_window():
    t = toy_trace()
    t.window = (100.0, 650.0)
    c = tr.clip(t)
    names = [e[0].split(" ")[0] for e in c.first_device()[tr.OPS_LINE]]
    assert names == ["%fusion.1", "%fused_attention_21.1", "%copy.2900",
                     "%all-reduce-start.1", "%subtract_convert_fusion.153"]
    assert c.first_device()[tr.OPS_LINE][0][1:] == (100.0, 200.0)
    assert c.first_device()[tr.OPS_LINE][-1][1:] == (600.0, 650.0)
    assert tr.busy_ns(c.first_device()) == 200 + 100 + 10 + 50


def test_scope_join_on_tpu_hlo_lines():
    h = hlo()
    assert h["fused_attention_21.1"].scope == "fused_attention#21"
    assert h["fused_attention_21.1"].opcode == "custom-call"
    assert h["fused_attention_21.1"].target == "tpu_custom_call"
    bwd = h["jvp__.12"]                    # the backward kernel's instruction
    assert tr.op_type(bwd.scope) == "fused_attention_grad"
    assert bwd.target == "tpu_custom_call" and bwd.opcode == "custom-call"
    assert tr.op_type(h["subtract_convert_fusion.153"].scope) == "adam"
    assert h["subtract_convert_fusion.153"].opcode == "fusion"
    assert h["copy.2900"].scope is None and h["copy.2900"].opcode == "copy"
    assert h["copy-start.691"].opcode == "copy-start"      # tuple-shaped
    # GSPMD's all-reduce that implements the masked-position gather keeps
    # the gather's scope; the combined gradient all-reduce carries the scope
    # of one of the gradients it sums
    assert tr.op_type(h["all-reduce"].scope) == "gather"
    assert tr.op_type(h["all-reduce.157"].scope) == "mul_grad"
    assert h["all-reduce.157"].opcode == "all-reduce"
    # event names: the instruction's text, or its bare name
    assert tr.instruction("%fusion.12 = bf16[8]{0} fusion(%x)") == "fusion.12"
    assert tr.instruction("fusion.12") == "fusion.12"
    assert tr.scope("%copy.2900 = f32[1] copy(%p)", h) == tr.UNATTRIBUTED
    assert tr.scope("%nowhere.1 = f32[1] add(%p)", h) == tr.UNATTRIBUTED
    assert tr.is_collective("%all-reduce.157 = (bf16[8]) all-reduce(%x)", h)
    assert tr.is_collective("all-gather-start.3", {})      # by name alone
    assert not tr.is_collective("%copy-start.691 = (s32[8]) copy-start()", h)


def test_time_by_op_type_and_the_unattributed_bucket():
    by = tr.time_by_op_type(toy_trace().time_by_scope(hlo()))
    assert by == {"unattributed": 200 + 100 + 10 + 150,   # fusion.1, copy,
                  "fused_attention": 100, "adam": 100}    # the all-reduce
    assert sum(by.values()) == tr.busy_ns(toy_trace().first_device())


def test_scope_time_share_reader():
    ev = Ev(toy_trace(), hlo())
    share = scope_time_share.reduce({"match": ["fused_attention*#*"]}, ev)
    assert share == pytest.approx(100 * 100 / 660)
    both = scope_time_share.reduce(
        {"match": ["adam#*", "unattributed"]}, ev)
    assert both == pytest.approx(100 * 560 / 660)
    assert scope_time_share.reduce({"match": ["conv2d#*"]}, ev) == 0.0
    assert scope_time_share.reduce({"match": ["adam#*"]},
                                   Ev(None, None)) is None


def test_exposed_collective_overlap():
    t, h = toy_trace(), hlo()
    # in flight: start op 590-600, async span 600-900 (covers the done op)
    assert tr.collective_intervals(t.first_device(), h) == [(590, 900)]
    # another op runs 600-700: exposed = 590-600 and 700-900
    assert tr.exposed_collective_ns(t.first_device(), h) == 10 + 200
    ev = Ev(t, h, steps=2)
    assert collective.reduce({"what": "time_share"}, ev) == \
        pytest.approx(100 * 310 / 660)
    assert collective.reduce({"what": "exposed_ms_per_step"}, ev) == \
        pytest.approx(210 / 1e6 / 2)
    one_chip = toy_trace()
    lines = one_chip.first_device()
    lines[tr.OPS_LINE] = lines[tr.OPS_LINE][:3]
    lines[tr.ASYNC_LINE] = []
    assert collective.reduce({"what": "time_share"},
                             Ev(one_chip, h)) is None


def test_step_module_and_idle_gaps():
    t = toy_trace()
    name, runs = tr.step_module(t.first_device())
    assert name == "jit_step(123)" and runs == [500, 310]
    assert module_ms.reduce({}, Ev(t, hlo())) == pytest.approx(405 / 1e6)
    # 60 ns, so that the toy's gaps count as the host's
    gaps = dict(tr.idle_gaps(t, host_gap_ns=60))
    # 300-400 under loss_read; 500-590: 60 of it under exe_run, 30 under
    # final_sync; 700-750 is short: between ops; 900-1000 under final_sync
    assert gaps == {"bench.loss_read": pytest.approx(100e-9),
                    "bench.exe_run": pytest.approx(90e-9),
                    "between_ops": pytest.approx(50e-9),
                    "bench.final_sync": pytest.approx(100e-9)}
    assert dict(tr.idle_gaps(t)) == {"between_ops": pytest.approx(340e-9)}
    b = tr.breakdown(t, hlo())
    again = tr.Trace.from_json(t.to_json())        # what --dump-trace keeps
    assert tr.breakdown(again, hlo()) == b
    assert b["device_ops"][0] == ["unattributed", pytest.approx(460e-9)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_roofline_share_reader_and_its_error():
    from benchmark import flops
    cell = {"model": {"hidden_size": 768, "num_hidden_layers": 12},
            "params": {"batch": 8, "seq": 2048}, "chips": 1}
    peaks = flops.peaks("TPU v5 lite")
    need = flops.flash_attention(cell["model"], cell["params"])
    least, bound = flops.roofline_seconds(need, peaks)
    assert bound == "flops"
    spec = {"name": "flash_attention_roofline", "need": "flash_attention",
            "match": ["fused_attention#*", "fused_attention_grad#*"],
            "custom_call_target": "tpu_custom_call"}

    def trace_with_kernel(ns):
        return tr.Trace({"/device:TPU:0": {tr.OPS_LINE: [
            ("%fused_attention_21.1 = bf16[8] custom-call(%a)", 0, ns),
            ("%subtract_convert_fusion.153 = f32[2] fusion(%a)", ns, ns + 9),
        ]}}, [], (0.0, ns + 9.0))
    ev = Ev(trace_with_kernel(2 * least * 1e9), hlo(), 1, cell, peaks)
    assert roofline_share.reduce(spec, ev) == pytest.approx(50.0)
    assert "bound by flops" in ev.said[0]
    with pytest.raises(ValueError, match="of the roofline"):
        roofline_share.reduce(
            spec, Ev(trace_with_kernel(0.5 * least * 1e9), hlo(), 1, cell,
                     peaks))
    no_kernel = Ev(toy_trace(), {}, 1, cell, peaks)
    assert roofline_share.reduce(spec, no_kernel) is None


def test_recorded_cpu_profile_loads_and_joins():
    t = tr.load(os.path.join(FIX, "cpu_rehearsal.xplane.pb"), rehearsal=True)
    assert [e[0] for e in t.host] == ["bench.exe_run", "bench.loss_read"] * 2
    lo, hi = t.window
    assert all(lo <= a <= b <= hi for _, a, b in t.host)
    ops = t.first_device()[tr.OPS_LINE]
    assert ops and all(lo <= a <= b <= hi for _, a, b in ops)
    by = tr.time_by_op_type(t.time_by_scope(hlo("cpu_rehearsal.hlo.txt")))
    assert {"mul", "relu", "adam"} <= set(by) and by["adam"] > 0
    assert 0 < tr.busy_s(t) <= t.window_s
    with pytest.raises(ValueError, match="no device plane"):
        tr.load(os.path.join(FIX, "cpu_rehearsal.xplane.pb"))
