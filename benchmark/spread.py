#!/usr/bin/env python3
"""Measure a cell the way the driver does: sets of runs of the benchmark's
command, each run a new process with another ``--seed``, and for every
end-to-end metric the median and the spread of each set (the distance between
the quartiles over the median). Bounds are set from the wider spread.

    chiprun -- python3 benchmark/spread.py --workload <name> [--sets 2] [--runs 6] [--traced 1]

This parent never touches JAX (a chip belongs to one process at a time). It
prints one line for each run and a summary, and writes everything, the
traced runs' result lines and breakdowns included, to
``chiprun_out/spread.<workload>.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def one_run(bench, workload, seed, seconds, trace, extra=()):
    cmd = bench["command"] + ["--workload", workload, "--seed", str(seed),
                              "--seconds", str(seconds), "--trace",
                              str(trace), *extra]
    t0 = time.perf_counter()
    r = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    lines = r.stdout.strip().splitlines()
    if r.returncode != 0 or not lines:
        print(r.stdout[-3000:], r.stderr[-3000:], sep="\n", file=sys.stderr)
        raise SystemExit(f"run failed ({r.returncode}): {' '.join(cmd)}")
    return json.loads(lines[-1]), lines[:-1], wall


def spread(values):
    """Distance between the quartiles over the median."""
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--traced", type=int, default=1,
                    help="traced runs after the sets (their trace is dumped "
                         "beside the summary)")
    ap.add_argument("--seed", type=int, default=100)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    record = {"workload": args.workload, "seconds": seconds, "sets": [],
              "traced": []}
    try:
        measure(args, bench, seconds, out_dir, record)
    finally:        # a failed run still leaves what the others measured
        with open(os.path.join(out_dir, f"spread.{args.workload}.json"),
                  "w") as f:
            json.dump(record, f, indent=1)
    return 0


def measure(args, bench, seconds, out_dir, record) -> None:
    seed = args.seed
    for s in range(args.sets):
        runs = []
        for _ in range(args.runs):
            result, lines, wall = one_run(bench, args.workload, seed,
                                          seconds, 0)
            seed += 1
            runs.append(result)
            if not record["sets"] and len(runs) <= 2:   # cold, then warm
                for ln in lines:
                    print("  | " + ln[:1500], flush=True)
            print(f"set {s} seed {seed - 1} wall {wall:.1f}s correct "
                  f"{result['correct']} failed {result['failed']} "
                  + " ".join(f"{k}={v['value']:.6g}"
                             for k, v in result["metrics"].items()),
                  flush=True)
        summary = {}
        # the first run of a checkout compiles: its set-up is recorded apart
        for name in runs[0]["metrics"]:
            values = [r["metrics"][name]["value"] for r in runs]
            if name == "setup_s" and s == 0 and len(values) > 2:
                summary["setup_s_first_run"] = values[0]
                values = values[1:]
            summary[name] = {"median": statistics.median(values),
                             "spread": spread(values) if len(values) > 1
                             else None, "values": values}
        record["sets"].append({"runs": runs, "summary": summary})
        print(f"set {s} summary: " + json.dumps(
            {k: (v if not isinstance(v, dict) else
                 {"median": v["median"], "spread": v["spread"]})
             for k, v in summary.items()}), flush=True)
    for _ in range(args.traced):
        dump = os.path.join(out_dir, f"trace.{args.workload}.json.gz")
        result, lines, wall = one_run(bench, args.workload, seed, seconds, 1,
                                      ("--dump-trace", dump))
        seed += 1
        record["traced"].append({"result": result, "lines": lines})
        print(f"traced seed {seed - 1} wall {wall:.1f}s", flush=True)
        for ln in lines:
            print("  | " + ln[:1500], flush=True)
        print("  | " + json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
