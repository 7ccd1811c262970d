"""ResNet-50 train-step roofline: per-kernel-class time x bytes x bandwidth.

VERDICT r4 #1 deliverable: profiles the compiled train step on the attached
TPU, joins the xplane device timeline with the optimized HLO (fusion
operands/outputs, deduped), and prints the table that bounds what ANY
implementation of train-mode-BN ResNet-50 can achieve on this chip --
writes ROOFLINE_RESNET.json next to the repo's bench artifacts.

Usage:  python tools/roofline_resnet.py  (needs a real TPU; ~2 min)
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4,
                "pred": 1, "s8": 1, "u8": 1}


def shape_bytes(s: str) -> int:
    total = 0
    for t, dims in re.findall(r"(bf16|f32|f16|s32|u32|pred|s8|u8)\[([\d,]*)\]",
                              s):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[t]
    return total


def build_and_profile(batch=128, image=224, trace_dir="/tmp/roofline_trace",
                      iters=10):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0
    startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [image, image, 3], "bfloat16")
        label = fluid.data("label", [1], "int64")
        loss, acc, _ = resnet.resnet50(img, label, num_classes=1000,
                                       data_format="NHWC",
                                       conv1_space_to_depth=True)
        fluid.optimizer.Momentum(0.1, 0.9).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {"img": jax.numpy.asarray(rng.randn(batch, image, image, 3),
                                     dtype="bfloat16"),
            "label": rng.randint(0, 1000, (batch, 1)).astype(np.int32)}
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[], return_numpy=False)
        step = list(exe._cache.values())[-1]
        mut_names, ro_names = step.state_in_names
        mut = {n: scope.find_var(n) for n in mut_names}
        ro = {n: scope.find_var(n) for n in ro_names}
        comp = step.fn.lower(mut, ro, dict(feed), 0).compile()
        hlo = comp.as_text()
        cur = comp(mut, ro, dict(feed), 0)
        jax.block_until_ready(cur)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        for _ in range(iters):
            cur = comp({n: cur[1][n] for n in mut_names}, ro, dict(feed), 0)
        jax.block_until_ready(cur)
        jax.profiler.stop_trace()
    return hlo, trace_dir, iters


def analyze(hlo: str, trace_dir: str, iters: int, peak_hbm_gbps: float):
    shape_of = {}
    for m in re.finditer(r"%([\w\.\-]+) = (\(?[a-z0-9]+\[[^=]*?) ", hlo):
        shape_of[m.group(1)] = m.group(2)
    fus, bodies, instr = {}, {}, {}
    for m in re.finditer(
            r"%([\w\.\-]*fusion[\w\.]*) = ([^\n]*?) fusion\(([^)]*)\), "
            r"kind=(\w+), calls=%?([\w\.\-]+)", hlo):
        name, outshape, operands, kind, called = m.groups()
        ops = sorted(set(o.strip().lstrip("%") for o in operands.split(",")))
        fus[name] = (outshape.strip(), kind, called, ops)
    for m in re.finditer(r"%([\w\.\-]+) \([^)]*\) -> [^\{]+ \{", hlo):
        name = m.group(1)
        start = m.end()
        end = hlo.find("\n}", start)
        bodies[name] = hlo[start:end]
    for m in re.finditer(
            r"%([\w\.\-]+) = ([^\n]*?) "
            r"(reduce|copy|select-and-scatter|convolution)\(([^)]*)\)", hlo):
        name, outshape, kind, operands = m.groups()
        ops = sorted(set(o.strip().lstrip("%") for o in operands.split(",")
                         if o.strip().startswith("%")))
        instr[name] = (outshape.strip(), kind, ops)

    tr = sorted(glob.glob(trace_dir + "/plugins/profile/*/*.trace.json.gz"))[-1]
    with gzip.open(tr, "rt") as f:
        t = json.load(f)
    procs = {e["pid"]: e["args"].get("name", "") for e in t["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    dev = [p for p, n in procs.items() if "TPU" in n]
    dur = collections.Counter()
    for e in t["traceEvents"]:
        if e.get("pid") in dev and e.get("ph") == "X":
            dur[e["name"]] += e.get("dur", 0)

    cats = collections.defaultdict(lambda: [0.0, 0])
    for name, us in dur.items():
        if name.startswith("jit_") or re.fullmatch(r"\d+", name):
            continue
        if name in fus:
            outshape, kind, called, ops = fus[name]
            b = shape_bytes(outshape) + sum(
                shape_bytes(shape_of.get(o, "")) for o in ops)
            cat = ("conv fusion" if "convolution(" in bodies.get(called, "")
                   else "elementwise fusion")
        elif name in instr:
            outshape, kind, ops = instr[name]
            b = shape_bytes(outshape) + sum(
                shape_bytes(shape_of.get(o, "")) for o in ops)
            cat = kind
        else:
            b = 0
            cat = "other (" + re.sub(r"[\.\d]+$", "", name) + ")"
        cats[cat][0] += us / iters
        cats[cat][1] += b
    rows = []
    for cat, (us, b) in sorted(cats.items(), key=lambda kv: -kv[1][0]):
        rows.append({"category": cat, "ms_per_step": round(us / 1e3, 3),
                     "gb_per_step": round(b / 1e9, 3),
                     "achieved_gbps": round(b / (us * 1e-6) / 1e9, 1)
                     if us else None})
    tot_us = sum(c[0] for c in cats.values())
    tot_b = sum(c[1] for c in cats.values())
    floor_ms = tot_b / (peak_hbm_gbps * 1e9) * 1e3
    return rows, tot_us / 1e3, tot_b / 1e9, floor_ms


def main():
    import jax
    from paddle_tpu.utils import device_peak_hbm_bw
    kind = jax.devices()[0].device_kind
    if jax.devices()[0].platform != "tpu":
        raise SystemExit(f"roofline_resnet reads a TPU trace; JAX found "
                         f"{jax.devices()[0].platform!r} ({kind})")
    peak_hbm = device_peak_hbm_bw(kind) / 1e9

    hlo, trace_dir, iters = build_and_profile()
    rows, step_ms, total_gb, floor_ms = analyze(hlo, trace_dir, iters,
                                                peak_hbm)
    from paddle_tpu.utils import program_flops  # noqa: F401 (doc pointer)
    out = {
        "device_kind": kind,
        "peak_hbm_gbps": peak_hbm,
        "step_ms": round(step_ms, 2),
        "total_gb_per_step": round(total_gb, 2),
        "perfect_impl_floor_ms": round(floor_ms, 2),
        "note": ("floor = total deduped bytes at 100% HBM peak; any "
                 "implementation that moves these bytes cannot beat it. "
                 "See ROOFLINE_RESNET.md for the conclusion."),
        "rows": rows,
    }
    print(f"{'category':<34}{'ms/step':>9}{'GB/step':>9}{'GB/s':>8}")
    for r in rows:
        print(f"{r['category']:<34}{r['ms_per_step']:9.2f}"
              f"{r['gb_per_step']:9.2f}"
              f"{(r['achieved_gbps'] or 0):8.0f}")
    print(f"{'TOTAL':<34}{step_ms:9.2f}{total_gb:9.2f}")
    print(f"perfect-implementation floor: {total_gb:.1f} GB / "
          f"{peak_hbm:.0f} GB/s = {floor_ms:.1f} ms")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "ROOFLINE_RESNET.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", path)


if __name__ == "__main__":
    main()
