"""What a compiled step's peak is made of (``observability/memory.py``): the
gauges every compile miss sets -- allocator marks, state by class, XLA's
totals, the compile order -- their retirement, the ``post_compile`` span, and
the on-demand live set at the peak with the pieces it is computed from."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.observability import memory, timeline
from paddle_tpu.observability.metrics import REGISTRY, MetricsRegistry


def _train_program(dim=6, optimizer=None):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [dim], "float32")
        label = fluid.data("label", [1], "int64")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(fluid.layers.fc(x, 8, act="relu"), 4), label))
        (optimizer or fluid.optimizer.Adam(0.01)).minimize(loss)
    return main, startup, loss


def _run(main, startup, loss, dim=6, exe=None, steps=1):
    exe = exe or fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((3, dim), "float32"),
            "label": np.zeros((3, 1), "int64")}
    for _ in range(steps):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    return exe, scope


def _label(program):
    return f"{id(program)}:v{program._version}"


def _gauge(name, **labels):
    fam = REGISTRY.get(name)
    key = tuple(sorted((k, str(v)) for k, v in labels.items()))
    child = fam.children.get(key) if fam is not None else None
    return None if child is None else child.value


# ---------------------------------------------------- at every compile miss --

def test_gauges_present_after_a_compile_miss():
    main, startup, loss = _train_program()
    exe, _ = _run(main, startup, loss)
    label = _label(main)
    mem = memory.compiled_step(label).memory()
    assert _gauge("program_temp_bytes", program=label) == mem["temp"]
    assert _gauge("program_alias_bytes", program=label) == mem["alias"] > 0
    assert _gauge("program_xla_peak_bytes", program=label) == mem["xla_peak"]
    # arg + out + temp - alias keeps its formula and its readers
    assert _gauge("program_peak_bytes", program=label) == (
        mem["argument"] + mem["output"] + mem["temp"] - mem["alias"])
    by_class = {c: _gauge("program_state_bytes", program=label,
                          **{"class": c})
                for c in ("parameter", "optimizer", "other", "feed")}
    # fc 6x8 + 8 + 8x4 + 4 floats; Adam keeps two moments and two beta
    # powers a parameter; the learning rate is the one other variable
    assert by_class["parameter"] == 4 * (6 * 8 + 8 + 8 * 4 + 4)
    assert by_class["optimizer"] == 2 * by_class["parameter"] + 4 * 2 * 4
    assert by_class["other"] == 4
    assert by_class["feed"] == 3 * 6 * 4 + 3 * 4        # int64 -> int32
    # what the step takes in is what XLA counts as its arguments (the run
    # counter, 4 bytes, is dropped where nothing draws from it, as here)
    assert sum(by_class.values()) == mem["argument"]
    # the CPU backend has no memory_stats(): the marks come from the
    # live_arrays fallback, which keeps no reserved pool
    assert _gauge("program_allocator_bytes", program=label,
                  stat="peak_in_use") >= _gauge(
        "program_allocator_bytes", program=label, stat="in_use") > 0
    assert _gauge("program_allocator_bytes", program=label,
                  stat="peak_reserved") is None
    # the train step compiled after its startup program
    assert _gauge("program_compile_seq", program=label) > _gauge(
        "program_compile_seq", program=_label(startup))


def test_state_classes_come_from_the_program_not_from_names():
    main, _, _ = _train_program(
        optimizer=fluid.optimizer.Momentum(0.1, momentum=0.9))
    gb = main.global_block()
    classes = memory.state_classes(main)
    params = {p.name for p in gb.all_parameters()}
    assert {n for n, c in classes.items() if c == "parameter"} == params
    velocity = {n for n, c in classes.items() if c == "optimizer"}
    assert len(velocity) == len(params)
    for op in gb.ops:
        if op.type == "momentum":
            assert op.inputs["Velocity"][0] in velocity
            assert op.inputs["LearningRate"][0] not in classes


def test_allocator_marks_are_the_fullest_devices():
    reg = MetricsRegistry()
    snapshot = {
        "tpu:0": {"bytes_in_use": 5.0, "peak_bytes": 7.0,
                  "peak_bytes_reserved": 1.0},
        "tpu:1": {"bytes_in_use": 1.0, "peak_bytes": 6.0,
                  "peak_bytes_reserved": 4.0}}
    assert memory.update_allocator_gauges(snapshot, "p", reg) \
        is snapshot["tpu:1"]
    got = {dict(k)["stat"]: c.value
           for k, c in reg.get("program_allocator_bytes").items()}
    assert got == {"in_use": 1.0, "peak_in_use": 6.0, "peak_reserved": 4.0}
    assert memory.update_allocator_gauges({}, "q", reg) is None


def test_gauges_retired_on_eviction(monkeypatch):
    monkeypatch.setattr(fluid.Executor, "_CACHE_CAP", 1)
    exe = fluid.Executor()
    first = _train_program()
    _run(*first, exe=exe)
    label = _label(first[0])
    assert memory.compiled_step(label) is not None
    second = _train_program()
    _run(*second, exe=exe)          # its startup program evicts the first
    assert memory.compiled_step(label) is None
    assert memory.compiled_step(_label(second[0])) is not None
    for name, labels in (
            ("program_compile_seq", {}), ("program_alias_bytes", {}),
            ("program_xla_peak_bytes", {}),
            ("program_state_bytes", {"class": "parameter"}),
            ("program_allocator_bytes", {"stat": "in_use"})):
        assert _gauge(name, program=label, **labels) is None, name
        assert _gauge(name, program=_label(second[0]), **labels) is not None
    exe.close()
    assert memory.compiled_step(_label(second[0])) is None
    assert _gauge("program_state_bytes", program=_label(second[0]),
                  **{"class": "parameter"}) is None


def test_no_gauges_for_a_step_without_an_executable():
    """The dispatch-time TypeError fallback drops the AOT executable and
    runs through lazy jit: such a step has nothing to ask."""
    main, startup, loss = _train_program()
    exe, _ = _run(main, startup, loss)
    step = exe._cache[next(reversed(exe._cache))]
    args = ({}, {}, {}, np.uint32(0))
    step.executable = None
    reg = MetricsRegistry()
    assert memory.update_program_memory_gauges(step, "lazy", reg) is None
    exe._post_compile_telemetry(step, main, "lazy", {}, [], [], None, args)
    for name in ("program_state_bytes", "program_allocator_bytes",
                 "program_compile_seq", "program_alias_bytes"):
        fam = REGISTRY.get(name)
        assert not [k for k in fam.children if ("program", "lazy") in k]
    assert memory.compiled_step("lazy") is None
    assert memory.peak_live_set("lazy") is None


def test_a_failed_walk_of_the_state_leaves_the_gauges_unset():
    """Like the other compile-miss setters, the walk of what the step takes
    in never raises into ``Executor.run``."""
    main, startup, loss = _train_program()
    exe, _ = _run(main, startup, loss)
    step = exe._cache[next(reversed(exe._cache))]
    reg = MetricsRegistry()
    assert memory.update_state_gauges(step, main, (None, {}, {}), "p",
                                      reg) is None

    class Refuses:
        def shard_shape(self, shape):
            raise ValueError("a restored executable's sharding")
    from types import SimpleNamespace as NS
    args = ({"w": np.ones(3, "float32")}, {}, {}, np.uint32(0))
    for cuts in ((None, {}, {}), ({"w": Refuses()}, {}, {})):
        fake = NS(executable=NS(input_shardings=[cuts]))
        assert memory.update_state_gauges(fake, main, args, "p", reg) is None
    assert reg.get("program_state_bytes") is None
    # the rest of the bookkeeping still lands
    seq = memory.note_compiled_step(step, main, "p", (None,), {}, reg)
    assert reg.get("program_compile_seq").children[
        (("program", "p"),)].value == seq
    assert memory.compiled_step("p") is not None
    memory.retire_program("p", reg)
    assert memory.compiled_step("p") is None


def test_post_compile_is_a_child_of_run_and_lands_in_phase_seconds():
    main, startup, loss = _train_program()
    fam = REGISTRY.get("phase_seconds")
    key = (("cat", "executor"), ("phase", "post_compile"))
    before = fam.children[key].count if fam and key in fam.children else 0
    timeline.clear()
    _run(main, startup, loss, steps=2)
    spans = timeline.spans()
    runs = {s.id: s for s in spans if s.name == "run"}
    posts = [s for s in spans if s.name == "post_compile"]
    assert len(posts) == 2 and len(runs) == 3    # two misses, three runs
    for s in posts:
        assert s.cat == "executor" and s.parent in runs
        assert s.args["program"] == runs[s.parent].args["program"]
        # after the compile span of the same run, not inside it
        comp = [c for c in spans if c.name == "compile"
                and c.parent == s.parent]
        assert len(comp) == 1 and comp[0].t0 + comp[0].dur <= s.t0
    child = REGISTRY.get("phase_seconds").children[key]
    assert child.count == before + 2
    assert child.sum >= sum(s.dur for s in posts) * 0.999


def test_the_live_set_is_never_computed_on_the_runs_path(monkeypatch):
    called = []
    for name in ("peak_live_set", "live_set_from_hlo", "_computation_peak",
                 "parse_hlo_computations"):
        monkeypatch.setattr(memory, name, lambda *a, _n=name, **k:
                            called.append(_n))
    main, startup, loss = _train_program()
    _run(main, startup, loss, steps=3)
    assert called == []


# -------------------------------------------------- the handle, the pieces --

def test_compiled_step_handle():
    main, startup, loss = _train_program()
    assert memory.compiled_step(_label(main)) is None
    exe, _ = _run(main, startup, loss)
    handle = memory.compiled_step(_label(main))
    assert handle.label == _label(main)
    assert memory.compiled_step(_label(startup)).label == _label(startup)
    assert "ENTRY" in handle.hlo_text() and "is_scheduled=true" in \
        handle.hlo_text()
    assert set(handle.memory()) == {"argument", "output", "temp", "alias",
                                    "xla_peak"}
    other, _, _ = _train_program()
    assert memory.compiled_step(_label(other)) is None
    # weak: an executor that goes without close() leaves nothing behind
    del exe, handle
    import gc
    gc.collect()
    assert memory.compiled_step(_label(main)) is None


@pytest.mark.parametrize("shape, want", [
    ("f32[128,768]{1,0}", 128 * 768 * 4),
    ("bf16[20,768]{1,0:T(8,128)(2,1)}", 24 * 768 * 2),      # rows to 8
    ("bf16[4,4096,64]{2,1,0:T(8,128)(2,1)}", 4 * 4096 * 128 * 2),
    ("f32[16777216,16]{0,1:T(8,128)}", 16777216 * 16 * 4),  # minor is dim 0
    ("f32[1]{0:T(128)}", 128 * 4),
    ("f32[128,12,128,128]{2,3,1,0:T(8,128)S(1)}", 0),       # not in HBM
    ("pred[128,128,768]{2,1,0:T(8,128)(4,1)}", 128 * 128 * 768),
    ("(f32[8,128]{1,0:T(8,128)S(1)}, f32[8,128]{1,0:T(8,128)}, u32[]{:S(2)})",
     8 * 128 * 4),
    ("((f32[512,768]{1,0}), f32[128,768]{1,0}, s32[])", (512 + 128) * 768 * 4
     + 4),
])
def test_hbm_bytes_of_a_shape(shape, want):
    assert memory.hbm_bytes(shape) == want


def test_buffers_shared_with_an_operand():
    tpu = ('kind=kCustom, backend_config={"x":[],"aliasing_operands":'
           '{"lists":[{"indices":["0","3"]},{"indices":["1","2","4"]}]}}')
    assert memory._shared_with_operand(tpu, 3) == [(0, 0), (1, 1)]
    assert memory._shared_with_operand(
        'backend_config={"aliasing_operands":{"lists":[]}}', 3) == []
    assert memory._shared_with_operand(
        "output_to_operand_aliasing={{0}: (1, {}), {1}: (2, {})}, x", 3) == [
            (0, 1), (1, 2)]
    assert memory._shared_with_operand(
        "output_to_operand_aliasing={{}: (0, {})}", 1) == [(0, 0)]


_HLO = """HloModule jit_step, is_scheduled=true

%body (p: (f32[1024], f32[1024])) -> (f32[1024], f32[1024]) {
  %p = (f32[1024]{0}, f32[1024]{0}) parameter(0)
  %a = f32[1024]{0} get-tuple-element(%p), index=0
  %t = f32[4096]{0} broadcast(%a), dimensions={}, metadata={op_name="jit(step)/while#3/scale#0/mul"}
  %r = f32[1024]{0} slice(%t), slice={[0:1024]}, metadata={op_name="jit(step)/while#3/scale#0/mul"}
  ROOT %out = (f32[1024]{0}, f32[1024]{0}) tuple(%r, %a)
}

%cond (p.1: (f32[1024], f32[1024])) -> pred[] {
  %p.1 = (f32[1024]{0}, f32[1024]{0}) parameter(0)
  ROOT %c = pred[] constant(true)
}

ENTRY %main (w: f32[1024], x: f32[1024]) -> (f32[1024], f32[]) {
  %w = f32[1024]{0} parameter(0)
  %x = f32[1024]{0} parameter(1)
  %act = f32[2048]{0} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(step)/mul#1/dot"}
  %view = f32[2048]{0} bitcast(%act)
  %spill = f32[2048]{0} copy(%view)
  %pair = (f32[1024]{0}, f32[1024]{0}) tuple(%w, %x)
  %loop = (f32[1024]{0}, f32[1024]{0}) while(%pair), condition=%cond, body=%body, metadata={op_name="jit(step)/while#3"}
  %g = f32[2048]{0} fusion(%spill, %view), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/mul_grad#5/dot"}
  %new_w = f32[1024]{0} fusion(%g, %w), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/sgd#6/sub"}
  %loss = f32[]{:T(128)} fusion(%g), kind=kLoop, calls=%f4, metadata={op_name="jit(step)/mean#2/reduce"}
  ROOT %res = (f32[1024]{0}, f32[]{:T(128)}) tuple(%new_w, %loss)
}
"""


def test_liveness_over_a_hand_written_schedule():
    from paddle_tpu.observability.attribution import parse_hlo_computations
    comps, entry, _ = parse_hlo_computations(_HLO)
    found = memory.live_set_from_hlo(comps, entry)
    # at the while: act (its bitcast is used later), its spilled copy, and
    # the body's own broadcast; parameters, the loop's in-place state and
    # what leaves through the root are no temporaries
    assert found["instruction"] == "loop" and found["position"] == 6
    live = {i: (n, s, x) for i, n, s, x in found["live"]}
    assert live == {"act": (8192.0, "mul#1", 1),
                    "spill": (8192.0, "mul#1", 1),      # its operand's scope
                    "t": (16384.0, "scale#0", 3)}       # idx of the while
    assert found["bytes"] == 32768.0
    assert memory._first_backward(comps[entry]) == 5


_LOOP = """HloModule jit_step, is_scheduled=true

ENTRY %main (w: f32[1024], x: f32[1024]) -> f32[1024] {
  %w = f32[1024]{0} parameter(0)
  %x = f32[1024]{0} parameter(1)
  %stack = f32[4,1024]{1,0} custom-call(), custom_call_target="AllocateBuffer"
  %zeros = f32[1024]{0} broadcast(), dimensions={}
  %other = f32[1024]{0} broadcast(), dimensions={}
  %in = (f32[1024]{0}, f32[4,1024]{1,0}) tuple(%x, %stack)
  %fwd = (f32[1024]{0}, f32[4,1024]{1,0}) while(%in), condition=%c, body=%b, metadata={op_name="jit(step)/scan#3/while"}
  %kept = f32[4,1024]{1,0} get-tuple-element(%fwd), index=1
  %back = (f32[1024]{0}, f32[4,1024]{1,0}) tuple(%zeros, %kept)
  %bwd = (f32[1024]{0}, f32[4,1024]{1,0}) while(%back), condition=%c, body=%b2, metadata={op_name="jit(step)/scan_grad#7/transpose(jvp())/while"}
  %sum = f32[1024]{0} get-tuple-element(%bwd), index=0
  ROOT %new_w = f32[1024]{0} fusion(%sum, %w, %other), kind=kLoop, calls=%f, metadata={op_name="jit(step)/sgd#9/sub"}
}
"""


def test_a_buffer_made_for_a_loop_takes_the_loops_scope():
    """The stack a forward ``while`` fills and the zeros a backward one adds
    into carry no ``op_name``: they are the loop's, forward and backward; a
    buffer that enters no loop keeps none."""
    from paddle_tpu.observability.attribution import parse_hlo_computations
    comps, entry, _ = parse_hlo_computations(_LOOP)
    named = memory._scope_table(comps[entry])
    assert named["stack"] == ("scan#3", 3)
    assert named["zeros"] == ("scan_grad#7", 7)
    assert named["kept"] == ("scan#3", 3)           # a view of the forward's
    assert named["other"] == (None, None)


_ASYNC = """HloModule jit_step, is_scheduled=true

ENTRY %main (w: f32[1024], x: f32[1024]) -> (f32[1024], f32[4096]) {
  %w = f32[1024]{0} parameter(0)
  %x = f32[1024]{0} parameter(1)
  %probs = f32[4096]{0:S(1)} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(step)/softmax#1/exp"}
  %evict = (f32[4096]{0}, f32[4096]{0:S(1)}, u32[]{:S(2)}) copy-start(%probs)
  %held = f32[4096]{0} copy-done(%evict)
  %upd = f32[1024]{0} custom-call(%w, %held), custom_call_target="k", output_to_operand_aliasing={{}: (0, {})}, metadata={op_name="jit(step)/sgd#4/k"}
  %gsum = f32[4096]{0} all-reduce-start(%held), metadata={op_name="jit(step)/mul_grad#3/psum"}
  %g = f32[4096]{0} all-reduce-done(%gsum)
  %scratch = (f32[512]{0}, f32[256]{0}) fusion(%held, %g), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/mul_grad#3/dot"}
  %part = f32[512]{0} get-tuple-element(%scratch), index=0
  %out = f32[4096]{0} fusion(%g, %part), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/mul_grad#3/add"}
  ROOT %res = (f32[1024]{0}, f32[4096]{0}) tuple(%upd, %out)
}
"""


def test_liveness_over_async_pairs_on_chip_memory_and_aliases():
    """What made the chip's schedules reconcile: a leaf in ``S(1)`` takes no
    HBM; a ``copy-start`` evicts it into a buffer of its own, which its
    ``-done`` views; a result that aliases an operand defines nothing; one
    element of a tuple-shaped fusion dies before the other."""
    from paddle_tpu.observability.attribution import parse_hlo_computations
    comps, entry, _ = parse_hlo_computations(_ASYNC)
    found = memory.live_set_from_hlo(comps, entry)
    # at %scratch: the evicted copy (under the softmax's scope, its
    # operand's), the all-reduce's result and both elements of the fusion;
    # not %probs (on-chip), not %upd (w's buffer), not %out (leaves through
    # the root)
    assert found["instruction"] == "scratch" and found["position"] == 8
    assert sorted(found["live"]) == [
        ("evict", 16384.0, "softmax#1", 1), ("gsum", 16384.0, "mul_grad#3", 3),
        ("scratch", 1024.0, "mul_grad#3", 3),
        ("scratch", 2048.0, "mul_grad#3", 3)]
    assert found["bytes"] == 2 * 16384.0 + 2048.0 + 1024.0
    assert memory._first_backward(comps[entry]) == 3


def test_peak_live_set_of_a_small_program():
    main, startup, loss = _train_program(dim=64)
    exe, _ = _run(main, startup, loss, dim=64)
    found = memory.peak_live_set(_label(main))
    assert found["program"] == _label(main)
    assert found["source"] == "scheduled_hlo"
    assert found["temp_bytes"] == memory.compiled_step(
        _label(main)).memory()["temp"]
    assert found["peak_bytes"] == sum(b["bytes"] for b in found["buffers"])
    assert found["coverage"] == found["peak_bytes"] / found["temp_bytes"]
    sizes = [b["bytes"] for b in found["buffers"]]
    assert sizes == sorted(sizes, reverse=True)
    assert {b["phase"] for b in found["buffers"]} <= {"forward", "backward",
                                                      None}
    assert 0 <= found["position"]["index"] < found["position"]["of"]
    assert memory.peak_live_set("no such program") is None


def test_a_live_set_that_does_not_reconcile_says_so(monkeypatch):
    """Outside the band the one source is still returned, marked: the
    readers leave the metrics out and no other estimate stands in."""
    main, startup, loss = _train_program(dim=64)
    exe, _ = _run(main, startup, loss, dim=64)      # holds the step alive
    monkeypatch.setattr(memory, "live_set_from_hlo", lambda comps, entry: {
        "bytes": 1.0, "position": 0, "n": 1, "instruction": "x",
        "live": [("x", 1.0, None, None)]})
    found = memory.peak_live_set(_label(main))
    assert found["source"] == "scheduled_hlo" and not found["reconciled"]
    assert found["coverage"] == 1.0 / found["temp_bytes"] < \
        memory.RECONCILED[0]
    assert found["buffers"] == [{"instruction": "x", "bytes": 1.0,
                                 "scope": None, "phase": None}]


@pytest.mark.parametrize("reserved,reconciled", [
    (None, False), (1.1, True), (2.0, False)],
    ids=["no_pool", "adds_up_to_the_pool", "another_program_s_mark"])
def test_a_live_set_is_held_against_the_reserved_pool_too(monkeypatch,
                                                          reserved,
                                                          reconciled):
    """Where the listed bytes miss XLA's count of its temporaries (a step
    whose work sits in ``while``s), they are held against the device's high
    mark of its reserved pool; the coverage stays the share of XLA's
    count."""
    main, startup, loss = _train_program(dim=64)
    exe, _ = _run(main, startup, loss, dim=64)      # holds the step alive
    monkeypatch.setattr(memory, "live_set_from_hlo", lambda comps, entry: {
        "bytes": 1.0, "position": 0, "n": 1, "instruction": "x",
        "live": [("x", 1.0, None, None)]})
    monkeypatch.setattr(memory, "_reserved_high_mark", lambda: reserved)
    found = memory.peak_live_set(_label(main))
    assert found["coverage"] == 1.0 / found["temp_bytes"] < \
        memory.RECONCILED[0]
    assert found["reserved_bytes"] == reserved
    assert found["reconciled"] is reconciled


def test_obs_report_renders_the_new_parts_in_its_memory_section():
    from tools.obs_report import render_memory
    from paddle_tpu.observability.export import to_dict
    main, startup, loss = _train_program()
    _run(main, startup, loss)
    text = render_memory(to_dict())
    lines = text.splitlines()
    at = next(i for i, ln in enumerate(lines) if _label(main) in ln)
    assert "aliased" in lines[at] and "XLA's own peak" in lines[at]
    assert lines[at + 1].strip().startswith("takes in, a device: parameter")
    assert "optimizer" in lines[at + 1] and "feed" in lines[at + 1]
    assert "allocator before its first run (compile" in lines[at + 2]
    assert "peak_reserved" not in lines[at + 2]        # the CPU keeps none
    # programs in the order they compiled; one section, as before
    assert text.count("== Device memory ==") == 1
    start = next(i for i, ln in enumerate(lines) if _label(startup) in ln)
    assert start < at
    assert "PADDLE_TPU_OBS" not in render_memory({"families": []})
