"""What every training job does before its window: build the cell's Program,
run its startup program on the device from the seed, place it on the cell's
layout, and hold its test-mode clone to the configuration's plain reference.
"""
from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark import probe


class Session:
    """One cell, built and started: the program's objects plus what the
    measured loop counts."""

    def __init__(self, cell: dict, seed: int, say):
        import paddle_tpu as fluid

        self.cell, self.say = cell, say
        self.model, self.params = cell["model"], cell["params"]
        self.builder = importlib.import_module(
            f"benchmark.programs.{cell['builder']}")
        self.built = self.builder.build(self.model, self.params)
        probe.seed_programs(self.built["startup"], self.built["main"], seed)
        self.loss = self.built["loss"]
        self.units_per_step = self.built["units_per_step"]
        self.devices = probe.devices(cell["chips"])
        self.bytes_before = probe.bytes_in_use(self.devices)
        self.exe = fluid.Executor()
        self.scope = fluid.Scope()
        t0 = time.perf_counter()
        self.exe.run(self.built["startup"], scope=self.scope)
        self.sync()
        say(f"startup program: {len(self.scope.var_names())} state vars on "
            f"the device in {time.perf_counter() - t0:.2f}s")
        self.program = self.place(self.built["main"])
        self.step = 0           # train steps dispatched so far
        self.first_loss = None  # loss of the first step after startup
        self.checks = {}        # name -> bool, all must hold for `correct`
        self.cleanups = []      # what the job opened, closed in close()

    def place(self, program):
        """The Program itself on one chip; under the cell's layout, the
        CompiledProgram a user would hand to the same Executor."""
        layout = self.cell.get("layout")
        if not layout:
            return program
        import paddle_tpu as fluid
        strategy = fluid.DistributedStrategy(
            mesh_shape=dict(layout["mesh_shape"]),
            data_rules=[(pat, tuple(spec))
                        for pat, spec in layout.get("data_rules", [])])
        return fluid.CompiledProgram(program).with_strategy(strategy)

    def sync(self):
        """Wait until every state variable's last write has happened."""
        import jax
        jax.block_until_ready(
            [self.scope.find_var(n) for n in self.scope.var_names()])

    def close(self):
        self.exe.close()
        for cleanup in self.cleanups:
            cleanup()


def reference_check(s: Session, batch: dict) -> bool:
    """The test-mode clone of the cell's Program (dropout off, on the cell's
    layout) against the configuration's plain reference, same weights read
    from the scope, same seeded batch: the mean loss and every position's."""
    ref_mod = importlib.import_module(
        f"benchmark.references.{s.cell['reference']}")
    tol = ref_mod.tolerance(s.model)
    names = s.built["check"]["loss"] + s.built["check"]["each"]
    t0 = time.perf_counter()
    got = s.exe.run(s.place(s.built["test"]), feed=batch, fetch_list=names,
                    scope=s.scope)
    weights = [s.scope.find_var(n) for n in s.built["params"]]
    want = ref_mod.loss(weights, batch, s.model, s.params)
    got_loss = float(np.asarray(got[0], np.float32).reshape(-1)[0])
    got_each = np.concatenate(
        [np.asarray(g, np.float32).reshape(-1) for g in got[1:]])
    want_loss = float(want["loss"])
    want_each = np.asarray(want["each"], np.float32)
    err_loss = abs(got_loss - want_loss) / abs(want_loss)
    err_each = float(np.abs(got_each - want_each).max()
                     / np.abs(want_each).max())
    ok = bool(np.isfinite(got_each).all()
              and err_loss <= tol["loss"] and err_each <= tol["each"])
    s.say(f"reference check: program {got_loss:.6f} reference "
          f"{want_loss:.6f}; relative error of the mean {err_loss:.3e} "
          f"(tolerance {tol['loss']:.1e}), of the worst of "
          f"{got_each.size} positions {err_each:.3e} (tolerance "
          f"{tol['each']:.1e}): {'ok' if ok else 'FAILED'} "
          f"in {time.perf_counter() - t0:.2f}s")
    return ok


def loss_value(fetched) -> float:
    return float(np.asarray(fetched, np.float32).reshape(-1)[0])


def window_over(t0: float, done: int, seconds, steps) -> bool:
    """Whether a measured loop that began at ``t0`` and has completed
    ``done`` steps has reached its target, given in seconds or in steps."""
    return (steps is not None and done >= steps) or (
        seconds is not None and time.perf_counter() - t0 >= seconds)
