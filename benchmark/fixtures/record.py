#!/usr/bin/env python3
"""How ``cpu_rehearsal.xplane.pb`` and ``cpu_rehearsal.hlo.txt`` were
recorded: two steps of a toy jitted program whose ops sit in named scopes of
the executor's form, under the benchmark's annotations, on the CPU.

    JAX_PLATFORMS=cpu python3 benchmark/fixtures/record.py

The reduction's tests read the pair through ``trace.load(..., rehearsal=True)``:
plane and line structure, annotation capture, window clipping and the scope
join are exercised on a real profiler file. Its times mean nothing.
"""
import glob
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def step(w, x):
    with jax.named_scope("mul#0"):
        h = x @ w
    with jax.named_scope("relu#1"):
        h = jnp.maximum(h, 0.0)
    with jax.named_scope("adam#2"):
        w = w - 1e-3 * (x.T @ h)
    return w, h.sum()


def main():
    w, x = jnp.ones((256, 256)), jnp.ones((128, 256))
    compiled = jax.jit(step).lower(w, x).compile()
    compiled(w, x)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.exe_run"):
                    w, loss = compiled(w, x)
                with jax.profiler.TraceAnnotation("bench.loss_read"):
                    float(loss)
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        shutil.copy(path, os.path.join(HERE, "cpu_rehearsal.xplane.pb"))
    with open(os.path.join(HERE, "cpu_rehearsal.hlo.txt"), "w") as f:
        f.write(compiled.as_text())


if __name__ == "__main__":
    main()
