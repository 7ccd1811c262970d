"""The seven per-layer metrics of set-up (PR 53): each data file resolves
through the reader the benchmark already had, against what the program
records; and a rehearsal of two cells prints every metric of set-up the cell
lists."""
import importlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LABEL = "[cpu-rehearsal on cpu, not a chip run] "
KERNEL_CELLS = {
    "bert_base.pretrain_s512", "bert_base.pretrain_s2048",
    "bert_base.pretrain_s4096", "olmoe_1b_7b.pretrain_s4096",
    "lfm2_8b_a1b.pretrain_s4096", "granite_4_0_h_micro.pretrain_s4096",
    "laguna_s_2_1.pretrain_s4096", "qwen3_next_80b_a3b.pretrain_s4096",
    "glm_4_7_flash.pretrain_s4096", "kimi_linear_48b_a3b.pretrain_s4096"}


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _record(registry, timeline):
    """What a run's set-up leaves, by hand: two compiled programs' worth."""
    registry.gauge("process_uptime_seconds", at="import_start").set(0.5)
    registry.gauge("process_uptime_seconds", at="first_executor").set(9.25)
    for name, secs in (("minimize", 1.5), ("clone", 0.25),
                       ("with_strategy", 0.125), ("append_backward", 0.5)):
        timeline.record_span(name, 1.0, secs, cat="build")
    timeline.record_span("place_state", 1.0, 64.0, cat="build")
    for name, secs in (("jaxpr_trace", 2.0), ("jaxpr_trace", 3.0),
                       ("mlir_lower", 1.0), ("mlir_lower", 0.5),
                       ("backend_compile", 4.0), ("cache_load", 32.0)):
        timeline.record_span(name, 1.0, secs, cat="jax")
    timeline.record_span("jaxpr_trace", 1.0, 128.0, cat="executor")
    for program, role, op, family, secs in (
            ("1:v0", "eval", "fused_attention", "kernel", 0.25),
            ("2:v0", "train", "fused_attention_grad", "kernel", 0.5),
            ("2:v0", "train", "mul", "xla", 8.0)):
        registry.counter("lowering_seconds_total", program=program, role=role,
                         op_type=op, family=family).inc(secs)
    for program, role, part, secs in (("1:v0", "eval", "total", 2.0),
                                      ("1:v0", "eval", "cache_load", 0.75),
                                      ("2:v0", "train", "cache_load", 0.5),
                                      ("2:v0", "train", "total", 6.5),
                                      ("2:v0", "train", "trace", 3.0)):
        registry.gauge("program_compile_seconds", program=program, role=role,
                       part=part).set(secs)


WANT = {"setup.before_executor_s": 9.25, "build.backward_s": 2.375,
        "compile.trace_s": 5.0, "compile.lower_s": 1.5,
        "compile.cache_load_s": 1.25, "compile.kernel_trace_s": 0.75,
        "compile.train_step_s": 6.5}


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_resolves_through_its_reader(name, monkeypatch):
    """The file names a reader the benchmark has, and the reader finds in
    the program's registry and ring what the file says it reads; nothing
    where the program records none of it, as a parent commit."""
    from paddle_tpu.observability import metrics, timeline
    fresh = metrics.MetricsRegistry()
    for module in (metrics, timeline):
        monkeypatch.setattr(module, "REGISTRY", fresh)
    # the recorder keeps its histogram handles by registry generation
    monkeypatch.setattr(timeline, "_handles", {})
    monkeypatch.setattr(timeline, "_handles_generation", -1)
    spec = _json("benchmark", "layer_metrics", name + ".json")
    entry, = [m for m in _json("BENCHMARK.json")["per_layer"]
              if m["name"] == name]
    assert spec["reducer"] in ("phase_total", "registry_count")
    assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                 "moves")} == {
        k: entry[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert (entry["better"], entry["moves"], entry["unit"]) == (
        "lower", "setup_s", "s")
    assert set(entry.get("workloads", KERNEL_CELLS)) == KERNEL_CELLS
    assert ("workloads" in entry) == (name == "compile.kernel_trace_s")
    reducer = importlib.import_module(f"benchmark.reducers.{spec['reducer']}")
    assert reducer.reduce(spec, None) is None
    _record(fresh, timeline)
    assert reducer.reduce(spec, None) == WANT[name]


def _run(cell, devices, trace, cache):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(cache))
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    for attempt in range(2):
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", cell, "--seed", "3000000019", "--seconds", "1",
             "--trace", str(trace), "--cpu-rehearsal"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
        # on a loaded CPU the harness's clock pairing (span_idle_overlap)
        # refuses a traced rehearsal now and then: not what is tested here
        if r.returncode == 3 or "span_idle_overlap" not in r.stderr:
            break
    assert r.returncode == 3, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1][len(LABEL):])


@pytest.mark.parametrize("cell,devices", [
    ("olmoe_1b_7b.pretrain_s4096", 1), ("bert_base.pretrain_s128_dp4", 4)])
def test_rehearsal_prints_every_setup_metric_the_cell_lists(cell, devices,
                                                            tmp_path):
    """A traced rehearsal whose compiles hit the persistent cache (the run
    before it filled a temporary one) prints all eleven metrics that move
    ``setup_s`` -- ``compile.kernel_trace_s`` only in a cell that lists it
    -- and they hold together as the chip's must."""
    listed = [m["name"] for m in _json("BENCHMARK.json")["per_layer"]
              if m["moves"] == "setup_s"
              and cell in m.get("workloads", [cell])]
    assert set(WANT) - {"compile.kernel_trace_s"} <= set(listed)
    assert ("compile.kernel_trace_s" in listed) == (cell in KERNEL_CELLS)
    _run(cell, devices, 0, tmp_path)
    got = {k: v["value"] for k, v in
           _run(cell, devices, 1, tmp_path)["metrics"].items()}
    assert not [n for n in listed if n not in got]
    assert got["compile.cache_misses"] == 0
    assert got["compile.trace_s"] + got["compile.lower_s"] \
        <= got["compile.trace_lower_s"]
    assert 0 < got["compile.cache_load_s"] <= got["compile.backend_s"]
    assert got["compile.train_step_s"] > 0
    assert 0 < got["build.backward_s"] < got["setup.before_executor_s"]
    if "compile.kernel_trace_s" in listed:
        assert 0 < got["compile.kernel_trace_s"] <= got["compile.trace_s"]
