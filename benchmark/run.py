#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json`` on the TPU this is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --workload <name> --cpu-rehearsal   # debugging only

The cell is found by name: its entry in ``BENCHMARK.json`` (configuration,
chips), ``benchmark/workloads/<name>.json`` (job kind, its parameters, the
layout), the configuration's file, and for every metric the cell reports
``benchmark/layer_metrics/<metric>.json``. This file holds no list of cells,
configurations or metrics.

The last line of stdout is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (and ``breakdown`` in a traced run):
the end-to-end metrics with ``--trace 0``, the per-layer metrics with
``--trace 1``. Losses, versions, cache state and the traced window's own
throughput go on earlier lines. Without a TPU, or with fewer chips than the
cell asks for, it exits 2 and prints no result. ``--cpu-rehearsal`` runs the
``rehearsal`` sizes of the data files on the CPU, labels every line, exits 3
and is never a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()           # set-up is counted from here

import argparse                          # noqa: E402
import importlib                         # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import sys                               # noqa: E402
import tempfile                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_LABEL = ""


def say(msg: str = "") -> None:
    """An earlier line: labelled in a rehearsal, stamped with the seconds
    since the process started."""
    print(f"{_LABEL}[{time.perf_counter() - T_START:7.2f}s] {msg}",
          flush=True)


def read_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(name: str, rehearsal: bool) -> dict:
    """Everything that defines the cell, from the data files."""
    bench = read_json("BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = read_json(cfg_entry["file"])
    mix = read_json("benchmark", "workloads", name + ".json")
    model = {k: v for k, v in config.items() if k != "rehearsal"}
    params = dict(mix["params"])
    if rehearsal:
        model.update(config.get("rehearsal", {}))
        params.update(mix.get("rehearsal", {}))

    def reported(kind):
        return [m for m in bench[kind]
                if name in m.get("workloads", [name])]
    return {"name": name, "chips": entry["chips"], "config": entry["config"],
            "run_seconds": bench["run_seconds"],
            "job": mix["job"], "layout": mix.get("layout"),
            "traced_window": mix["traced_window"],
            "builder": config["builder"], "reference": config["reference"],
            "flops": config.get("flops"), "model": model, "params": params,
            "end_to_end": reported("end_to_end"),
            "per_layer": reported("per_layer")}


class Evidence:
    """What the per-layer readers may read (``benchmark/reducers``)."""

    def __init__(self, cell, peaks, setup_counters, window, say):
        from benchmark import probe
        self.cell, self.peaks, self.say = cell, peaks, say
        self.setup_counters = setup_counters
        self.spans = probe.spans(window["t0"], window["t1"])
        self.steps = window["steps"]
        self.window_s = window["t1"] - window["t0"]
        self.trace = self.hlo = None
        self.traced_steps = 0


def end_to_end(cell, unit, window, setup_s, peak_bytes, peaks) -> dict:
    """The numbers a user of the system sees, by metric name."""
    rate = window["units"] / (window["t1"] - window["t0"])
    out = {"setup_s": setup_s, "peak_hbm_gb": peak_bytes / 1e9,
           f"{unit}_per_s": rate}
    if cell["flops"] and peaks:
        from benchmark import flops
        need = getattr(flops, cell["flops"])(cell["model"], cell["params"])
        out["mfu"] = 100.0 * rate * need["per_token"] / (
            cell["chips"] * peaks["bf16_flops_per_s"])
    return out


def per_layer(cell, ev) -> dict:
    """Every per-layer metric the cell lists, through its own reader; a
    reader that finds nothing to read returns None and the metric is left
    out."""
    from benchmark import trace as tr
    metrics = {}
    for m in cell["per_layer"]:
        spec = read_json("benchmark", "layer_metrics", m["name"] + ".json")
        reducer = importlib.import_module(
            f"benchmark.reducers.{spec['reducer']}")
        value = reducer.reduce(spec, ev)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # the scope shares cut busy time by Program op, the collective share by
    # HLO opcode: two cuts of the same time, so only the first adds up
    shares = {k: v["value"] for k, v in metrics.items()
              if ".time_share" in k and not k.startswith("collective.")}
    say(f"time_share metrics: {json.dumps(shares)}; together "
        f"{sum(shares.values()):.2f}% of device busy time")
    busy = tr.busy_ns(ev.trace.first_device())
    by = tr.time_by_op_type(ev.trace.time_by_scope(ev.hlo))
    say("device time by op type, % of busy: " + json.dumps(
        {k: round(100 * v / busy, 2) for k, v in
         sorted(by.items(), key=lambda kv: -kv[1])} if busy else {}))
    return metrics


def traced_window(job, s, cell, ev, window, rehearsal, dump_to) -> None:
    """A short sub-window of the same loop under the profiler; what it
    attempted and logged joins the untraced window's, the trace and the
    step's HLO go into the evidence."""
    import jax
    from benchmark import probe, trace as tr
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the annotations, not every call
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            traced = job.measure(s, **cell["traced_window"])
        jax.profiler.stop_trace()
        say("profiler stopped")
        ev.trace = tr.load(tr.newest_xplane(trace_dir), rehearsal)
    rate = window["units"] / (window["t1"] - window["t0"])
    t_el = traced["t1"] - traced["t0"]
    say(f"traced window {t_el:.3f}s: {traced['steps']} steps, "
        f"{traced['units'] / t_el:.1f} {s.built['unit']}/s under the "
        f"profiler against {rate:.1f} without")
    say("trace: window %.3fs; " % ev.trace.window_s + "; ".join(
        f"{p} " + ", ".join(f"{ln} {len(es)}" for ln, es in lines.items())
        for p, lines in sorted(ev.trace.devices.items())))
    for k in ("attempted", "failed"):
        window[k] += traced[k]
    window["losses"] += traced["losses"]
    ev.traced_steps = traced["steps"]
    hlo_text = probe.step_hlo(s.exe)
    ev.hlo = tr.parse_hlo(hlo_text)
    if dump_to:
        import gzip
        os.makedirs(os.path.dirname(os.path.abspath(dump_to)), exist_ok=True)
        with gzip.open(dump_to, "wt") as f:
            json.dump({"trace": ev.trace.to_json(), "hlo": hlo_text,
                       "traced_steps": ev.traced_steps}, f)


def judge(s, cell, window, compiled_in_window, devices) -> None:
    """The checks of ``correct`` that follow the windows (the reference
    check and the single train signature were settled in set-up)."""
    from benchmark import probe
    s.checks["no_compile_in_window"] = compiled_in_window == (0, 0)
    values = [v for _, v in window["losses"]]
    s.checks["loss_finite_and_falling"] = bool(
        all(v == v and abs(v) != float("inf") for v in values)
        and values[-1] < s.first_loss)
    now = probe.bytes_in_use(devices)
    if cell["chips"] > 1:       # the state really lives on every chip
        s.checks["bytes_grew_on_every_device"] = all(
            a > b for a, b in zip(now, s.bytes_before))
    say(f"bytes in use by device: {s.bytes_before} -> {now}")
    say(f"memory_stats of device 0: {json.dumps(devices[0].memory_stats())}; "
        f"XLA's analysis of the train step: "
        f"{json.dumps(probe.step_memory(s.exe))}")
    say(f"checks: {json.dumps(s.checks)}")


def main(argv=None) -> int:
    global _LABEL
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="debug on the CPU at the data files' rehearsal "
                         "sizes: every line labelled, exit code 3, never a "
                         "result")
    ap.add_argument("--dump-trace", metavar="FILE", default=None,
                    help="with --trace 1, also write the loaded trace and "
                         "the step's HLO there (gzip JSON), to work on the "
                         "reduction without the chip")
    args = ap.parse_args(argv)
    rehearsal = args.cpu_rehearsal
    cell = load_cell(args.workload, rehearsal)
    seconds = args.seconds if args.seconds is not None else (
        2.0 if rehearsal else float(cell["run_seconds"]))

    import jax
    import jaxlib
    dev = jax.devices()[0]
    if rehearsal:
        _LABEL = f"[cpu-rehearsal on {dev.platform}, not a chip run] "
        if dev.platform == "tpu":
            ap.error("--cpu-rehearsal on a TPU: run without it")
        if jax.device_count() < cell["chips"]:
            ap.error(f"the cell's layout needs {cell['chips']} devices: set "
                     f"XLA_FLAGS=--xla_force_host_platform_device_count="
                     f"{cell['chips']}")
    elif dev.platform != "tpu" or jax.device_count() < cell["chips"]:
        print(f"benchmark: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {jax.device_count()} x {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2

    from paddle_tpu.utils import compile_cache
    from benchmark import flops, probe, trace as tr
    cache_dir = compile_cache.arm()
    # every program of a run is worth keeping: the second run of a cell in
    # a checkout has to find them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    watch = probe.CompileWatch()
    peaks = None if rehearsal else flops.peaks(dev.device_kind)
    tune = probe.tuning_state()
    say(f"cell {cell['name']} seed {args.seed} seconds {seconds} trace "
        f"{args.trace}; jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"{dev.platform} {dev.device_kind} x{jax.device_count()}")
    say(f"compile cache {cache_dir} "
        f"({compile_cache.entry_count(cache_dir)} entries at start); "
        f"autotune mode {tune['mode']}, {tune['decisions']} persisted "
        f"decisions at {tune['path']}")

    job = importlib.import_module(f"benchmark.jobs.{cell['job']}")
    s = job.setup(cell, args.seed, say)
    unit, devices = s.built["unit"], s.devices
    setup_counters = watch.snapshot()
    compiles_before = (probe.executor_compiles(), watch.compiles)
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s:.2f}s: {json.dumps(setup_counters)}")

    window = job.measure(s, seconds=seconds)
    peak_bytes = probe.peak_bytes(devices)
    elapsed = window["t1"] - window["t0"]
    say(f"window {elapsed:.3f}s: {window['steps']} steps, {window['units']} "
        f"{unit}, {window['units'] / elapsed:.1f} {unit}/s, "
        f"{window['units'] / elapsed / cell['chips']:.1f} a chip")
    ev = Evidence(cell, peaks, setup_counters, window, say)
    if args.trace:
        traced_window(job, s, cell, ev, window, rehearsal, args.dump_trace)
    say("losses (step, value): " + " ".join(
        f"{k}:{v:.4f}" for k, v in window["losses"]))
    judge(s, cell, window,
          (probe.executor_compiles() - compiles_before[0],
           watch.compiles - compiles_before[1]), devices)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak_bytes}
    result = {"correct": all(s.checks.values()),
              "attempted": window["attempted"], "failed": window["failed"],
              "device": device}
    if args.trace:
        result["metrics"] = per_layer(cell, ev)
        device["busy_s"] = tr.busy_s(ev.trace)
        device["window_s"] = ev.trace.window_s
        result["breakdown"] = tr.breakdown(ev.trace, ev.hlo)
    else:
        values = end_to_end(cell, unit, window, setup_s, peak_bytes, peaks)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"] if m["name"] in values}
    s.close()
    print(_LABEL + json.dumps(result), flush=True)
    return 3 if rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
