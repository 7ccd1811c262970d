"""A training job fed from host memory: a ring of seeded host batches goes
through ``Executor.run`` as numpy, every step fetches the loss, and the host
reads it every ``loss_read_every`` steps -- what a user's training loop does.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import probe
from benchmark.jobs import common


def setup(cell: dict, seed: int, say) -> common.Session:
    s = common.Session(cell, seed, say)
    rng = np.random.RandomState(seed)
    s.ring = [s.builder.batch(s.model, s.params, rng)
              for _ in range(s.params["ring"])]
    s.checks["reference"] = common.reference_check(
        s, s.builder.batch(s.model, s.params, rng))
    # warm-up: the train step compiles once, and reading the loss on the
    # host must not make a second signature of it
    before = probe.executor_compiles()
    for _ in range(2):
        out = s.exe.run(s.program, feed=s.ring[s.step % len(s.ring)],
                        fetch_list=[s.loss], scope=s.scope,
                        return_numpy=False)
        s.step += 1
        value = common.loss_value(out[0])
        if s.first_loss is None:
            s.first_loss = value
    s.sync()
    compiles = probe.executor_compiles() - before
    say(f"warm-up: 2 steps, {compiles} executor compile of the train step, "
        f"first loss {s.first_loss:.4f}")
    s.checks["one_train_signature"] = compiles == 1
    return s


def measure(s: common.Session, seconds: float = None,
            steps: int = None) -> dict:
    """Dispatch steps until ``seconds`` have passed (or ``steps`` are done),
    looking at the clock only where the loss is read; the window closes when
    the device has finished the last step."""
    import jax
    note = jax.profiler.TraceAnnotation
    every, ring = s.params["loss_read_every"], s.ring
    losses, done = [], 0
    s.sync()
    t0 = time.perf_counter()
    while True:
        for _ in range(every):
            with note("bench.next_batch"):
                feed = ring[s.step % len(ring)]
            with note("bench.exe_run"):
                out = s.exe.run(s.program, feed=feed, fetch_list=[s.loss],
                                scope=s.scope, return_numpy=False)
            s.step += 1
            done += 1
        with note("bench.loss_read"):
            losses.append((s.step, common.loss_value(out[0])))
        if common.window_over(t0, done, seconds, steps):
            break
    with note("bench.final_sync"):
        s.sync()
    t1 = time.perf_counter()
    bad = sum(1 for _, v in losses if not np.isfinite(v))
    return {"t0": t0, "t1": t1, "steps": done,
            "units": done * s.units_per_step, "attempted": done,
            "failed": bad, "losses": losses}
