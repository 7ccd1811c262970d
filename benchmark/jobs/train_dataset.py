"""A training job fed from files: MultiSlot text part files written during
set-up from the seed -> ``QueueDataset`` (native parse on ``parser_threads``
threads) -> the executor's prefetch thread -> ``train_from_dataset``, epoch
after epoch until the window ends. The epoch in progress is finished; what
completed is counted.
"""
from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from benchmark import probe
from benchmark.jobs import common


def write_multislot(path: str, cols: list) -> None:
    """One MultiSlot text file, ``v v v;v v;...`` a line, from column
    matrices, without a Python loop over rows: every value becomes a
    fixed-width run of digits in a byte matrix (integers zero-padded to the
    widest, floats in [0, 1) as ``0.dddd``), which is the line layout the
    reference's CTR data has apart from the padding."""
    pieces = []
    for c, col in enumerate(cols):
        col = np.asarray(col)
        if np.issubdtype(col.dtype, np.integer):
            digits = max(1, len(str(int(col.max()))))
            scaled, lead = col.astype(np.int64), b""
        else:
            digits, lead = 4, b"0."
            scaled = np.rint(col.astype(np.float64) * 10 ** digits) \
                .astype(np.int64)
        rows, width = scaled.shape
        cell = np.empty((rows, width, len(lead) + digits + 1), np.uint8)
        cell[:, :, :len(lead)] = np.frombuffer(lead, np.uint8)
        for d in range(digits):
            cell[:, :, len(lead) + d] = \
                scaled // 10 ** (digits - 1 - d) % 10 + ord("0")
        cell[:, :, -1] = ord(" ")
        block = cell.reshape(rows, -1)
        block[:, -1] = ord(";") if c < len(cols) - 1 else ord("\n")
        pieces.append(block)
    with open(path, "wb") as f:
        f.write(np.concatenate(pieces, axis=1).tobytes())


def dataset(s: common.Session, paths: list):
    import paddle_tpu as fluid
    ds = fluid.DatasetFactory().create_dataset("QueueDataset")
    ds.set_batch_size(s.params["batch"])
    ds.set_thread(s.params["parser_threads"])
    ds.set_use_var(s.built["feed_vars"])
    ds.set_filelist(paths)
    return ds


def setup(cell: dict, seed: int, say) -> common.Session:
    from paddle_tpu import native
    if not native.available():
        raise RuntimeError("the native slot parser did not build (g++?)")
    s = common.Session(cell, seed, say)
    p = s.params
    rng = np.random.RandomState(seed)
    t0 = time.perf_counter()
    s.rows = p["steps_per_epoch"] * p["batch"]
    cols = s.builder.rows(s.model, p, rng, s.rows)
    slots = [cols[v.name] for v in s.built["feed_vars"]]
    s.tmp = tempfile.TemporaryDirectory(prefix="bench_parts_")
    s.cleanups.append(s.tmp.cleanup)
    per = s.rows // p["part_files"]
    paths = []
    for k in range(p["part_files"]):
        paths.append(os.path.join(s.tmp.name, f"part-{k:03d}.txt"))
        write_multislot(paths[-1], [c[k * per:(k + 1) * per] for c in slots])
    first = os.path.join(s.tmp.name, "first-batch.txt")
    write_multislot(first, [c[:p["batch"]] for c in slots])
    size = sum(os.path.getsize(q) for q in paths)
    say(f"part files: {s.rows} rows in {len(paths)} files, {size} bytes, "
        f"written in {time.perf_counter() - t0:.2f}s")
    s.dataset = dataset(s, paths)
    s.checks["reference"] = common.reference_check(
        s, s.builder.batch(s.model, p, rng))
    # warm-up through the path the window uses: one file of one batch, so
    # the train step compiles with the dataset's own feed signature and the
    # value that comes back is the loss of the first step after startup
    before = probe.executor_compiles()
    out = s.exe.train_from_dataset(s.program, dataset=dataset(s, [first]),
                                   scope=s.scope, fetch_list=[s.loss])
    s.step += 1
    s.first_loss = common.loss_value(out[0])
    s.sync()
    compiles = probe.executor_compiles() - before
    say(f"warm-up: 1 step from a one-batch file, {compiles} executor "
        f"compile of the train step, first loss {s.first_loss:.4f}")
    s.checks["one_train_signature"] = compiles == 1
    return s


def measure(s: common.Session, seconds: float = None,
            steps: int = None) -> dict:
    """Whole epochs until ``seconds`` have passed (or ``steps`` are done).
    ``train_from_dataset`` returns the last step's loss as numpy, which is
    the epoch's one read of the device; steps are counted by the
    executor's own run counter, so a row that reached no step shows."""
    import jax
    note = jax.profiler.TraceAnnotation
    batch = s.params["batch"]
    losses, done, epochs = [], 0, 0
    s.sync()
    t0 = time.perf_counter()
    while True:
        runs = probe.executor_runs()
        with note("bench.exe_run"):
            out = s.exe.train_from_dataset(s.program, dataset=s.dataset,
                                           scope=s.scope,
                                           fetch_list=[s.loss])
        with note("bench.epoch_turnover"):
            ran = probe.executor_runs() - runs
            s.step += ran
            done += ran
            epochs += 1
            losses.append((s.step, common.loss_value(out[0])))
        if common.window_over(t0, done, seconds, steps):
            break
    with note("bench.final_sync"):
        s.sync()
    t1 = time.perf_counter()
    offered = epochs * s.rows
    s.say(f"epochs {epochs}, steps {done}, examples {done * batch} of "
          f"{offered} rows offered")
    s.checks["examples_equal_rows_times_epochs"] = done * batch == offered
    bad = sum(1 for _, v in losses if not np.isfinite(v))
    return {"t0": t0, "t1": t1, "steps": done, "units": done * batch,
            "attempted": offered,
            "failed": offered - done * batch + bad * batch,
            "losses": losses}
