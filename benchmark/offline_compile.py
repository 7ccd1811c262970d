#!/usr/bin/env python3
"""Scratch: compile a cell's train step at real size for a TPU v5e that is
described, not attached (``on-chip-measurement`` guide, section 2), and print
XLA's memory analysis and the kernels and collectives in the program.

    JAX_PLATFORMS=cpu python3 benchmark/offline_compile.py [--hlo-dir DIR] [<workload> ...]

Costs no chip time: run it before a chip call whenever a cell's shapes
change. Nothing runs, so it says nothing about results or times, and it is
never reported as a chip run. The program is steered from here, not through
an option of its own: the executor's AOT compile is intercepted to take the
jitted step and its arguments, ``pallas_mode.on_tpu`` is made to say what it
will say on the chip, and the layout's mesh is built from the described
devices.
"""
from __future__ import annotations

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


class _Captured(Exception):
    pass


def compile_cell(name: str, topo, hlo_dir=None) -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh, SingleDeviceSharding

    from paddle_tpu.compiler import DistributedStrategy
    from paddle_tpu.core.executor import Executor
    from paddle_tpu.ops import pallas_mode
    from benchmark import run
    from benchmark.jobs import common

    cell = run.load_cell(name, rehearsal=False)
    taken = {}

    def capture(self, key, compiled, args):
        taken["fn"], taken["args"] = compiled.fn, args
        raise _Captured()

    def build_mesh(self, devices=None):
        sizes = list(self.mesh_shape.values())
        n = int(np.prod(sizes))
        return Mesh(np.array(topo.devices[:n]).reshape(sizes),
                    tuple(self.mesh_shape))

    saved = (Executor._aot_compile, DistributedStrategy.build_mesh,
             pallas_mode.on_tpu)
    DistributedStrategy.build_mesh = build_mesh
    pallas_mode.on_tpu = lambda: True
    try:
        s = common.Session(cell, 0, lambda msg: None)   # startup runs (CPU)
        Executor._aot_compile = capture
        rng = np.random.RandomState(0)
        feed = s.builder.batch(s.model, s.params, rng)
        try:
            s.exe.run(s.program, feed=feed, fetch_list=[s.loss],
                      scope=s.scope)
        except _Captured:
            pass
        # a layout's jit carries its own in_shardings; one chip is named
        kw = {} if cell["layout"] else {
            "sharding": SingleDeviceSharding(topo.devices[0])}

        def spec(x):
            x = x if hasattr(x, "dtype") else np.asarray(x)
            return jax.ShapeDtypeStruct(x.shape, x.dtype, **kw)
        args = jax.tree_util.tree_map(spec, taken["args"])
        compiled = taken["fn"].lower(*args).compile()
    finally:
        (Executor._aot_compile, DistributedStrategy.build_mesh,
         pallas_mode.on_tpu) = saved
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(hlo_dir, name + ".hlo.txt"), "w") as f:
            f.write(text)
    ops = re.findall(r"\s([a-z][a-z0-9\-]*)\(", text)
    count = lambda k: sum(1 for o in ops if o == k)     # noqa: E731
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return {"cell": name,
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "predicted_peak_hbm_gb_one_program": peak / 1e9,
            "mosaic_kernels": text.count(
                'custom_call_target="tpu_custom_call"'),
            "collectives": {k: count(k) + count(k + "-start") for k in
                            ("all-reduce", "all-gather", "reduce-scatter",
                             "all-to-all", "collective-permute")
                            if count(k) + count(k + "-start")}}


def main(argv) -> int:
    import argparse
    import json
    import jax
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--hlo-dir", default=None,
                    help="also write each step's optimized HLO text there")
    ap.add_argument("workloads", nargs="*")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.workloads or [w["name"] for w in
                     json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
                     ["workloads"]]
    for name in names:
        print(json.dumps(compile_cell(name, topo, args.hlo_dir)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
