"""Pipeline-vs-data-parallel wall-clock comparison (VERDICT r3 #2).

The regime where pipeline parallelism wins is a deep homogeneous stack with a
global batch too small to feed every device efficiently: at one example per
device, pure dp's per-device matmuls are sliver-shaped and every device holds
(and updates) the full weight set, while dp x pp halves the per-device weight
traffic and doubles the per-device batch. This bench runs a deep fc stack at
global batch 8 on an 8-device mesh and times

  - dp8      : pure data parallelism, one example per device, vs
  - dp4 x pp2: 4-way dp with the stack split into 2 temporal stages
               (GPipe schedule, ops/pipeline_op.py + parallel/pipeline.py);
               each device holds half the stack's weights.

Runs on 8 virtual CPU devices, which main() configures itself (the same
harness the dry run uses): `python bench_pipeline.py`. Its step times are
host-mesh times and say nothing about a chip; nothing here has run on one.

Prints one JSON line per layout plus a comparison line.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


LAYERS = 16
WIDTH = 1024
BATCH = 8
MICRO = 2
STEPS = 20


def build(pp_stages):
    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0
    startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [WIDTH], "float32")
        label = fluid.data("label", [1], "int64")
        h = fluid.layers.fc(x, WIDTH, act="relu")
        for i in range(LAYERS):
            if pp_stages:
                with fluid.device_guard(f"stage:{i // (LAYERS // pp_stages)}"):
                    h = fluid.layers.fc(h, WIDTH, act="tanh")
            else:
                h = fluid.layers.fc(h, WIDTH, act="tanh")
        logits = fluid.layers.fc(h, 8)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        if pp_stages:
            opt = fluid.optimizer.PipelineOptimizer(
                fluid.optimizer.SGD(0.01), num_microbatches=MICRO,
                schedule="temporal")
            opt.minimize(loss)
        else:
            fluid.optimizer.SGD(0.01).minimize(loss)
    return main, startup, loss


def run(layout):
    import jax
    import paddle_tpu as fluid
    pp = 2 if layout == "dp4xpp2" else None
    main, startup, loss = build(pp)
    if layout == "dp8":
        strat = fluid.DistributedStrategy(mesh_shape={"dp": 8})
    else:
        strat = fluid.DistributedStrategy(
            mesh_shape={"dp": 4, "pp": 2},
            param_rules=fluid.optimizer.PipelineOptimizer.pp_param_rules())
    cp = fluid.CompiledProgram(main).with_strategy(strat)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(BATCH, WIDTH).astype("float32"),
            "label": rng.randint(0, 8, (BATCH, 1)).astype("int64")}
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(3):
            exe.run(cp, feed=feed, fetch_list=[], return_numpy=False)
        # drain async dispatch before timing by fetching a real value
        np.asarray(exe.run(cp, feed=feed, fetch_list=[loss])[0])
        t0 = time.perf_counter()
        for _ in range(STEPS):
            exe.run(cp, feed=feed, fetch_list=[], return_numpy=False)
        lv, = exe.run(cp, feed=feed, fetch_list=[loss])
        dt = (time.perf_counter() - t0) / (STEPS + 1)
    return dt, float(np.asarray(lv).reshape(()))


def main():
    # this bench is the CPU dry run of the two layouts: 8 virtual CPU
    # devices (JAX_PLATFORMS=cpu + jax_num_cpu_devices), the same set-up as
    # __graft_entry__.dryrun_multichip. Its times are host-mesh times.
    from __graft_entry__ import configure_cpu_mesh
    configure_cpu_mesh(8)
    import jax
    from paddle_tpu.utils import compile_cache
    compile_cache.arm()
    results = {}
    for layout in ("dp8", "dp4xpp2"):
        dt, lv = run(layout)
        results[layout] = dt
        print(json.dumps({"metric": f"pipeline_bench_{layout}_step_ms",
                          "value": round(dt * 1e3, 2), "unit": "ms",
                          "platform": jax.devices()[0].platform,
                          "device_kind": jax.devices()[0].device_kind,
                          "loss": round(lv, 4),
                          "config": f"{LAYERS}x{WIDTH} fc stack, batch "
                                    f"{BATCH}, microbatches {MICRO}"}))
    speedup = results["dp8"] / results["dp4xpp2"]
    print(json.dumps({"metric": "pipeline_vs_dp_speedup",
                      "value": round(speedup, 3),
                      "unit": "x (dp8 step time / dp4xpp2 step time)",
                      "pp_wins": speedup > 1.0}))


if __name__ == "__main__":
    main()
