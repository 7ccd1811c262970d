"""Fault-tolerant data plane (ISSUE 14): streaming ingestion with source
retry, poison-record quarantine, and exact mid-stream resume
(paddle_tpu/data/streaming.py + the shared dataset_factory policies)."""
import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.data import (FileTailSource, GeneratorSource, PoisonFeed,
                             SocketSource, SourceLost, StreamingDataset)
from paddle_tpu.observability import journal
from paddle_tpu.resilience import faults, recovery
from paddle_tpu.utils.clock import FakeClock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    recovery.clear_preemption()
    yield
    faults.clear()
    recovery.clear_preemption()


@pytest.fixture()
def xy_vars():
    main = fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main,
                                                        fluid.Program()):
        x = fluid.data("x", [2], "float32")
        y = fluid.data("y", [1], "int64")
    return x, y


def _write_stream(path, n, start=0):
    with open(path, "w") as f:
        for i in range(start, start + n):
            f.write(f"{i} {i + 0.5};{i % 3}\n")


def _make_ds(x, y, batch=4, **kw):
    ds = StreamingDataset(**kw)
    ds.set_use_var([x, y])
    ds.set_batch_size(batch)
    return ds


# ----------------------------------------------------- the fluid.data shim --

def test_data_module_shim_preserves_fluid_data():
    """Importing paddle_tpu.data rebinds the `data` attribute from the
    input-layer function to the package; the callable-module shim keeps
    BOTH surfaces working (this suite imported the package above)."""
    assert "paddle_tpu.data" in sys.modules
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        v = fluid.data("shim_x", [3], "float32")   # still callable
    assert v.name == "shim_x" and tuple(v.shape) == (-1, 3)
    assert fluid.data.StreamingDataset is StreamingDataset
    assert isinstance(
        fluid.DatasetFactory().create_dataset("StreamingDataset"),
        StreamingDataset)


# ------------------------------------------------------------ file sources --

def test_file_source_batches_and_state(tmp_path, xy_vars):
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    _write_stream(p, 10)
    ds = _make_ds(x, y)
    ds.add_source(FileTailSource(p))
    batches = list(ds._iter_batches())
    assert len(batches) == 3                      # 4 + 4 + 2 remainder
    assert batches[0]["x"].shape == (4, 2)
    assert batches[0]["y"].dtype == np.int64
    np.testing.assert_allclose(batches[2]["x"][-1], [9, 9.5])
    st = ds.stream_state()
    assert st["records"] == 10 and st["dead_letters"] == 0
    assert st["sources"][p] == os.path.getsize(p)


def test_file_tail_follow_picks_up_appends(tmp_path, xy_vars):
    x, y = xy_vars
    p = str(tmp_path / "tail.txt")
    _write_stream(p, 3)
    ds = _make_ds(x, y, batch=3)
    src = ds.add_source(FileTailSource(p, follow=True, poll_interval=0.01))
    ds.set_epoch_bound(steps=2)
    it = iter(ds._iter_batches())
    first = next(it)
    np.testing.assert_allclose(first["x"][0], [0, 0.5])

    def appender():
        time.sleep(0.05)
        with open(p, "a") as f:
            for i in range(3, 6):
                f.write(f"{i} {i + 0.5};{i % 3}\n")

    t = threading.Thread(target=appender)
    t.start()
    second = next(it)
    t.join()
    np.testing.assert_allclose(second["x"][0], [3, 3.5])
    assert src.stop.is_set() or list(it) == []    # epoch bound ends it


def test_watermark_seek_resumes_exactly(tmp_path, xy_vars):
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    _write_stream(p, 12)
    ds = _make_ds(x, y)
    ds.add_source(FileTailSource(p))
    full = list(ds._iter_batches())
    ds2 = _make_ds(x, y)
    ds2.add_source(FileTailSource(p))
    ds2.seek(ds.watermark(1))
    rest = list(ds2._iter_batches())
    assert len(rest) == len(full) - 1
    for a, b in zip(full[1:], rest):
        np.testing.assert_array_equal(a["x"], b["x"])
        np.testing.assert_array_equal(a["y"], b["y"])


def test_cross_epoch_continuity_no_loss(xy_vars):
    """Read-ahead rows an epoch bound strands are re-read next epoch --
    nothing is dropped between bounded epochs over one unbounded source."""
    x, y = xy_vars
    gen = GeneratorSource(lambda: (f"{i} {i};0\n" for i in range(10 ** 9)),
                          name="gen")
    ds = _make_ds(x, y, batch=2)
    ds.add_source(gen)
    ds.set_epoch_bound(steps=3)
    e1 = list(ds._iter_batches())
    e2 = list(ds._iter_batches())
    assert len(e1) == len(e2) == 3
    np.testing.assert_allclose(e1[-1]["x"][-1], [5, 5])
    np.testing.assert_allclose(e2[0]["x"][0], [6, 6])


# ------------------------------------------------------- retry / SourceLost --

def test_source_retry_is_byte_identical(tmp_path, xy_vars):
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    _write_stream(p, 16)
    ds = _make_ds(x, y)
    ds.add_source(FileTailSource(p))
    clean = list(ds._iter_batches())

    faults.install("exc@read:prob=0.3:seed=5:times=0")
    ds2 = _make_ds(x, y, clock=FakeClock(), retry_seed=0)
    ds2.add_source(FileTailSource(p))
    flaky = list(ds2._iter_batches())
    faults.clear()
    assert len(flaky) == len(clean)
    for a, b in zip(clean, flaky):
        np.testing.assert_array_equal(a["x"], b["x"])
    retries = journal.recent(event="source_retry")
    assert retries and retries[-1]["source"] == p
    assert "UNAVAILABLE" in retries[-1]["error"]


def test_source_lost_is_typed_never_a_hang(tmp_path, xy_vars):
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    _write_stream(p, 8)
    faults.install("exc@read:times=0")            # every read fails
    ds = _make_ds(x, y, clock=FakeClock(), max_retries=3, retry_seed=0)
    ds.add_source(FileTailSource(p, name="flaky"))
    with pytest.raises(SourceLost) as ei:
        list(ds._iter_batches())
    assert ei.value.source == "flaky" and ei.value.attempts == 3
    lost = journal.recent(event="source_lost")
    assert lost and lost[-1]["source"] == "flaky"


def test_idle_timeout_bounds_a_silent_source(tmp_path, xy_vars):
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    _write_stream(p, 2)
    clock = FakeClock()
    ds = _make_ds(x, y, batch=2, clock=clock, idle_timeout=5.0)
    ds.add_source(FileTailSource(p, follow=True, poll_interval=0.5))
    with pytest.raises(SourceLost, match="idle_timeout"):
        # 2 records make one batch; then the tail stays silent while the
        # reader's polls advance the fake clock past the idle deadline
        list(ds._iter_batches())


def test_vanished_file_retries_then_recovers(tmp_path, xy_vars):
    """A source whose file does not exist yet retries (OSError is
    transient) and delivers once the file appears."""
    x, y = xy_vars
    p = str(tmp_path / "late.txt")
    ds = _make_ds(x, y, batch=2, retry_backoff=0.01, max_retries=8)
    ds.add_source(FileTailSource(p))

    def creator():
        time.sleep(0.1)
        _write_stream(p, 4)

    t = threading.Thread(target=creator)
    t.start()
    batches = list(ds._iter_batches())
    t.join()
    assert len(batches) == 2
    assert journal.recent(event="source_retry")


# ------------------------------------------------------- poison quarantine --

def test_streaming_quarantine_attributes_source(tmp_path, xy_vars):
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    with open(p, "w") as f:
        f.write("0 0.5;0\nGARBAGE;;;\n1 1.5;1\nnot a; number\n2 2.5;2\n")
    dl = str(tmp_path / "dead.jsonl")
    ds = _make_ds(x, y, batch=3)
    ds.add_source(FileTailSource(p, name="clicks"))
    ds.set_bad_sample_policy("quarantine", dead_letter_path=dl)
    batches = list(ds._iter_batches())
    assert len(batches) == 1 and batches[0]["x"].shape == (3, 2)
    recs = [json.loads(ln) for ln in open(dl)]
    assert len(recs) == 2
    assert all(r["where"].startswith("clicks:") for r in recs)
    assert {r["reason"] for r in recs} == {"slot_count", "parse_error"}
    assert ds.stream_state()["dead_letters"] == 2


def test_poison_ceiling_escalates_typed(tmp_path, xy_vars):
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    with open(p, "w") as f:
        for i in range(30):
            f.write(f"{i} {i};0\n" if i % 2 else "JUNK;;;\n")
    ds = _make_ds(x, y, batch=4)
    ds.add_source(FileTailSource(p))
    ds.set_bad_sample_policy("quarantine",
                             dead_letter_path=str(tmp_path / "d.jsonl"),
                             max_poison_rate=0.3, poison_floor=10)
    with pytest.raises(PoisonFeed) as ei:
        list(ds._iter_batches())
    assert ei.value.quarantined >= 3 and ei.value.total >= 10


def test_corrupt_read_fault_drives_quarantine(tmp_path, xy_vars):
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    _write_stream(p, 6)
    faults.install("corrupt@read:step=2")
    dl = str(tmp_path / "dead.jsonl")
    ds = _make_ds(x, y, batch=5)
    ds.add_source(FileTailSource(p, name="src"))
    ds.set_bad_sample_policy("quarantine", dead_letter_path=dl)
    batches = list(ds._iter_batches())
    faults.clear()
    assert len(batches) == 1 and batches[0]["x"].shape == (5, 2)
    recs = [json.loads(ln) for ln in open(dl)]
    assert len(recs) == 1 and "CORRUPT" in recs[0]["line"]


# ------------------------------------------------------------ socket source --

class _LineServer(threading.Thread):
    """Serves canned lines over TCP; optionally drops the connection
    after ``cut_after`` lines, then serves the remainder to the next
    connection (the reconnect drill)."""

    def __init__(self, lines, cut_after=None):
        super().__init__(daemon=True)
        self.lines = lines
        self.cut_after = cut_after
        self.srv = socket.create_server(("127.0.0.1", 0))
        self.port = self.srv.getsockname()[1]
        self.served = 0

    def run(self):
        while self.served < len(self.lines):
            conn, _ = self.srv.accept()
            try:
                n = 0
                for ln in self.lines[self.served:]:
                    if self.cut_after is not None and n >= self.cut_after:
                        break   # drop the connection mid-stream
                    conn.sendall(ln.encode())
                    self.served += 1
                    n += 1
                self.cut_after = None
            finally:
                conn.close()
        self.srv.close()


def test_socket_source_reconnects_after_drop(xy_vars):
    x, y = xy_vars
    lines = [f"{i} {i + 0.5};{i % 3}\n" for i in range(8)]
    server = _LineServer(lines, cut_after=4)
    server.start()
    ds = _make_ds(x, y, batch=4, retry_backoff=0.01, max_retries=8)
    ds.add_source(SocketSource("127.0.0.1", server.port, name="sock"))
    ds.set_epoch_bound(steps=2)
    batches = list(ds._iter_batches())
    server.join(timeout=5)
    assert len(batches) == 2
    np.testing.assert_allclose(batches[1]["x"][-1], [7, 7.5])
    assert journal.recent(event="source_retry")


# ---------------------------------------------- trainstate + exact resume --

def _mlp(dim=4, seed=0):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [dim], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, dim))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def test_stream_watermark_rides_trainstate(tmp_path):
    from paddle_tpu.utils.checkpointer import Checkpointer
    main, startup, loss = _mlp()
    x_var = main.global_block().vars["x"]
    p = str(tmp_path / "s.txt")
    with open(p, "w") as f:
        for i in range(12):
            f.write(" ".join(f"{(i * 4 + j) * 0.01:.4f}"
                             for j in range(4)) + "\n")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, str(tmp_path / "ck"),
                          save_interval_steps=1)
        g = recovery.StepGuardian(exe, main, checkpointer=ck)
        ds = StreamingDataset()
        ds.add_source(FileTailSource(p, name="stream"))
        ds.set_use_var([x_var])
        ds.set_batch_size(3)
        g.train_from_dataset(dataset=ds, fetch_list=[loss])
        g.close()
    with open(str(tmp_path / "ck" / "ckpt-3" / "trainstate.json")) as f:
        doc = json.load(f)
    assert doc["batch"] == 4 and "fuse_steps" not in doc
    assert doc["stream"]["sources"]["stream"] == os.path.getsize(p)
    assert doc["stream"]["records"] == 12


def test_emergency_save_keeps_committed_position(tmp_path):
    """With save_interval > 1, a preemption between staging the next
    chunk and running it must persist the LAST COMPLETED batch position
    (the pending-commit fix), not the position of the step that never
    ran."""
    from paddle_tpu.utils.checkpointer import Checkpointer
    main, startup, loss = _mlp()
    x_var = main.global_block().vars["x"]
    p = str(tmp_path / "s.txt")
    with open(p, "w") as f:
        for i in range(8):
            f.write(" ".join("0.1" for _ in range(4)) + "\n")
    faults.install("preempt:step=2")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, str(tmp_path / "ck"),
                          save_interval_steps=100)
        g = recovery.StepGuardian(exe, main, checkpointer=ck)
        ds = StreamingDataset()
        ds.add_source(FileTailSource(p, name="stream"))
        ds.set_use_var([x_var])
        ds.set_batch_size(1)
        with pytest.raises(recovery.Preempted) as ei:
            g.train_from_dataset(dataset=ds, fetch_list=[loss])
    saved = ei.value.saved_step
    assert saved is not None
    with open(str(tmp_path / "ck" / f"ckpt-{saved}" /
                  "trainstate.json")) as f:
        doc = json.load(f)
    # batches consumed == steps completed == saved_step + 1; the staged
    # position of the never-run step must NOT have leaked into the doc
    assert doc["batch"] == saved + 1, doc
    assert doc["stream"]["records"] == saved + 1, doc


def test_stream_chaos_acceptance_in_process(tmp_path):
    """The ISSUE-14 acceptance: exc@read(p=0.1) + poison burst + preempt
    mid-stream -> typed-everything, attributed dead letters,
    byte-identical post-restore losses, live metric series (the same leg
    --selftest folds into tier-1)."""
    from paddle_tpu.resilience.__main__ import run_stream_chaos
    s = run_stream_chaos(steps=8, batch=3, dim=4, seed=11,
                         poison_rate=0.1, read_fault_prob=0.1,
                         preempt_step=3, work_dir=str(tmp_path),
                         hermetic=True)
    assert s["ok"], s
    assert s["byte_identical"] and s["dead_letters_attributed"]
    assert s["metrics_live"] and s["resumed"]
    assert s["steps_completed"] == 8


# -------------------------------------------------- goodput / prefetch ties --

@pytest.mark.smoke
def test_slow_source_shows_up_as_feed_wait(xy_vars):
    """Prefetch-stall attribution: a deliberately slow source must appear
    as feed_wait lost-seconds in the goodput ledger (pins the PR-9 cause
    mapping against the new streaming path)."""
    from paddle_tpu.observability import goodput
    x, y = xy_vars

    def slow_lines():
        for i in range(8):
            time.sleep(0.03)
            yield f"{i} {i};0\n"

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.data("x", [2], "float32")
        yv = fluid.data("y", [1], "int64")
        loss = fluid.layers.mean(fluid.layers.fc(xv, 4))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ds = StreamingDataset()
        ds.add_source(GeneratorSource(slow_lines, name="slow"))
        ds.set_use_var([xv, yv])
        ds.set_batch_size(2)
        with goodput.run_ledger() as led:
            exe.train_from_dataset(main, ds, fetch_list=[loss])
        rep = led.report()
    assert rep.lost.get("feed_wait", 0.0) > 0.05, rep.lost


def test_prefetch_abort_stops_reader_threads(tmp_path, xy_vars):
    """An abandoned epoch (consumer stops early) winds the stream reader
    threads down via the executor prefetch loop's abort() hook."""
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    _write_stream(p, 4)
    ds = _make_ds(x, y, batch=2)
    ds.add_source(FileTailSource(p, follow=True, poll_interval=0.01))
    exe = fluid.Executor()
    before = {t for t in threading.enumerate()}
    gen = exe._prefetch_batches(ds._iter_batches(), depth=2)
    got = next(iter(gen))
    assert got["x"].shape == (2, 2)
    gen.close()     # abandons the epoch; finally calls batches.abort()
    deadline = time.time() + 5
    while time.time() < deadline:
        leaked = [t for t in set(threading.enumerate()) - before
                  if t.is_alive() and t.name.startswith("stream-read")]
        if not leaked:
            break
        time.sleep(0.02)
    assert not leaked, leaked


# ------------------------------------------------------ zero-overhead guard --

@pytest.mark.smoke
def test_zero_overhead_without_streaming_import():
    """A finite-dataset run with no streaming import and faults disarmed
    opens no extra files, spawns no lasting threads, and never pulls
    paddle_tpu.data (subprocess: sibling tests import it here)."""
    script = r"""
import sys, threading, builtins
import numpy as np
import paddle_tpu as fluid

assert "paddle_tpu.data" not in sys.modules, "eager streaming import"
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.data("x", [2], "float32")
    loss = fluid.layers.mean(fluid.layers.fc(x, 4))
ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
ds.set_use_var([x]); ds.set_batch_size(2)
ds._samples = [(np.ones(2, "float32"),) for _ in range(6)]
exe = fluid.Executor()
exe.run(startup)
exe.train_from_dataset(main, ds, fetch_list=[loss])   # warm the cache
before = set(threading.enumerate())
opened = []
real_open = builtins.open
builtins.open = lambda *a, **k: (opened.append(a[0] if a else k),
                                 real_open(*a, **k))[1]
try:
    exe.train_from_dataset(main, ds, fetch_list=[loss])
finally:
    builtins.open = real_open
new = {t for t in set(threading.enumerate()) - before if t.is_alive()}
assert not new, f"epoch leaked threads: {new}"
assert not opened, f"epoch opened files: {opened}"
assert "paddle_tpu.data" not in sys.modules, "epoch imported streaming"
print("GUARD-OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=600,
                       cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "GUARD-OK" in r.stdout


# -------------------------------------------------------------- CLI surface --

def test_stream_chaos_cli(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.resilience", "--stream",
         "--steps", "6", "--batch", "3", "--dim", "4", "--seed", "3",
         "--format", "json", "--ckpt", str(tmp_path)],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout)
    assert out["ok"] and out["byte_identical"]


# ------------------------------------------------- review-hardening pins --

def test_poison_ceiling_survives_resume(tmp_path, xy_vars):
    """seek() restores the parse-attempt denominator with the dead-letter
    count: a resumed run over a healthy low-poison feed must NOT trip the
    ceiling by dividing prior-run quarantines by post-resume parses."""
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    with open(p, "w") as f:
        for i in range(100):
            f.write("JUNK;;;\n" if i % 50 == 10 else f"{i} {i};0\n")

    def make():
        ds = _make_ds(x, y, batch=7)
        ds.add_source(FileTailSource(p, name="s"))
        ds.set_bad_sample_policy(
            "quarantine", dead_letter_path=str(tmp_path / "d.jsonl"),
            max_poison_rate=0.10, poison_floor=10)
        return ds

    ds = make()
    ds.set_epoch_bound(steps=8)
    first = list(ds._iter_batches())          # ~2% poison: under ceiling
    assert len(first) == 8
    ds2 = make()
    ds2.seek(ds.watermark(8))
    rest = list(ds2._iter_batches())          # must not raise PoisonFeed
    assert sum(b["x"].shape[0] for b in first + rest) == 98


def test_follow_source_survives_epochs(tmp_path, xy_vars):
    """A follow=True tail source keeps tailing in a SECOND epoch (its
    stop flag is cleared on reopen) and picks up data appended between
    epochs."""
    x, y = xy_vars
    p = str(tmp_path / "t.txt")
    _write_stream(p, 4)
    ds = _make_ds(x, y, batch=2)
    ds.add_source(FileTailSource(p, follow=True, poll_interval=0.01))
    ds.set_epoch_bound(steps=2)
    e1 = list(ds._iter_batches())
    assert len(e1) == 2
    with open(p, "a") as f:
        for i in range(4, 8):
            f.write(f"{i} {i + 0.5};{i % 3}\n")
    e2 = list(ds._iter_batches())
    assert len(e2) == 2
    np.testing.assert_allclose(e2[0]["x"][0], [4, 4.5])


def test_multi_epoch_quarantine_does_not_duplicate(tmp_path, xy_vars):
    """Re-parsing the same finite files across epochs dead-letters each
    poison line ONCE (file + counters), including across writer
    instances (the on-disk entries seed the dedup)."""
    from paddle_tpu.observability.metrics import REGISTRY
    x, y = xy_vars
    p = str(tmp_path / "q.txt")
    with open(p, "w") as f:
        f.write("0 0;0\nBROKEN;;;\n1 1;1\n2 2;2\n")
    dl = str(tmp_path / "dead.jsonl")

    def run_epoch():
        ds = fluid.DatasetFactory().create_dataset("QueueDataset")
        ds.set_use_var([x, y])
        ds.set_batch_size(3)
        ds.set_filelist([p])
        ds.set_bad_sample_policy("quarantine", dead_letter_path=dl)
        return list(ds._iter_batches())

    fam = REGISTRY.counter("samples_quarantined_total",
                           reason="slot_count")
    before = fam.value
    for _ in range(3):                        # 3 epochs, fresh writers
        batches = run_epoch()
        assert sum(b["x"].shape[0] for b in batches) == 3
    recs = [json.loads(ln) for ln in open(dl)]
    assert len(recs) == 1, recs               # one entry, not three
    assert fam.value - before == 1


def test_aborted_step_never_leaks_staged_position(tmp_path):
    """A staged batch position whose step raised (here: a preemption at
    the step boundary) must NOT be committed by a later, unrelated
    g.run() -- trainstate would otherwise record a batch that never ran
    and a resume would silently skip it."""
    from paddle_tpu.utils.checkpointer import Checkpointer
    main, startup, loss = _mlp()
    x_var = main.global_block().vars["x"]
    p = str(tmp_path / "s.txt")
    with open(p, "w") as f:
        for _ in range(6):
            f.write(" ".join("0.1" for _ in range(4)) + "\n")
    faults.install("preempt:step=2")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, str(tmp_path / "ck"),
                          save_interval_steps=100)
        g = recovery.StepGuardian(exe, main, checkpointer=ck,
                                  handle_signals=False)
        ds = StreamingDataset()
        ds.add_source(FileTailSource(p, name="s"))
        ds.set_use_var([x_var])
        ds.set_batch_size(1)
        with pytest.raises(recovery.Preempted) as ei:
            g.train_from_dataset(dataset=ds, fetch_list=[loss])
        saved = ei.value.saved_step
        # the guardian closed on preemption; a caller that recovers and
        # keeps stepping directly must not flush the dead step's mark
        recovery.clear_preemption()
        exe2 = fluid.Executor()
        ck2 = Checkpointer(exe2, main, str(tmp_path / "ck"))
        start = ck2.restore() + 1
        g2 = recovery.StepGuardian(exe2, main, checkpointer=ck2,
                                   start_step=start, handle_signals=False)
        g2._pending_state = {"epoch": 0, "batch": 999}
        with pytest.raises(recovery.Preempted):
            recovery.request_preemption("test")
            g2.run(feed={"x": np.ones((1, 4), "float32")},
                   fetch_list=[loss])
        recovery.clear_preemption()
        # the staged doc was taken (and dropped), not left to leak
        assert g2._pending_state is None
    with open(str(tmp_path / "ck" / f"ckpt-{saved}" /
                  "trainstate.json")) as f:
        doc = json.load(f)
    assert doc["batch"] == saved + 1 != 999


def test_abort_hook_survives_skip_batches_wrapping(tmp_path, xy_vars):
    """The reader wind-down hook is captured BEFORE islice wrapping: an
    epoch abandoned under skip_batches still stops the stream readers."""
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    _write_stream(p, 6)
    ds = _make_ds(x, y, batch=2)
    ds.add_source(FileTailSource(p, follow=True, poll_interval=0.01))
    exe = fluid.Executor()
    g = recovery.StepGuardian(exe, handle_signals=False)
    before = set(threading.enumerate())

    class Boom(RuntimeError):
        pass

    def cb(n, vals):
        raise Boom()   # abandon the epoch mid-flight

    main, startup, loss = _mlp(dim=2)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with pytest.raises(Boom):
            g.train_from_dataset(program=main, dataset=ds,
                                 fetch_list=[loss], skip_batches=1,
                                 step_cb=cb)
    deadline = time.time() + 5
    leaked = []
    while time.time() < deadline:
        leaked = [t for t in set(threading.enumerate()) - before
                  if t.is_alive() and t.name.startswith("stream-read")]
        if not leaked:
            break
        time.sleep(0.02)
    assert not leaked, leaked


def test_torn_tail_not_consumed_into_watermark(tmp_path, xy_vars):
    """A non-follow FileTailSource leaves an unterminated final line
    unconsumed (it may be a torn in-flight append): the watermark stays
    at the last complete record, and once the line completes a later
    epoch reads the WHOLE record -- never the appended remainder as a
    fresh sample."""
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    with open(p, "w") as f:
        f.write("0 0.5;0\n1 1.5;1\n12 0.5")       # torn tail, no newline
    ds = _make_ds(x, y, batch=2)
    ds.add_source(FileTailSource(p, name="s"))
    batches = list(ds._iter_batches())
    assert len(batches) == 1                       # torn line NOT taken
    np.testing.assert_allclose(batches[0]["x"], [[0, 0.5], [1, 1.5]])
    assert journal.recent(event="stream_torn_tail")
    # the append completes the record; the next epoch reads it whole
    with open(p, "a") as f:
        f.write("25;2\n3 3.5;0\n")
    more = list(ds._iter_batches())
    assert len(more) == 1
    np.testing.assert_allclose(more[0]["x"], [[12.0, 0.525], [3, 3.5]])


def test_epoch_restart_after_preflush_abort_loses_nothing(tmp_path, xy_vars):
    """An epoch that dies BEFORE its first flush (PoisonFeed here) must
    not strand the reader's read-ahead: the next epoch re-reads from the
    source's start position, not from wherever the cursor ran to."""
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    with open(p, "w") as f:
        for _ in range(8):
            f.write("JUNK;;;\n")            # poison burst up front:
        for i in range(6):                  # ceiling trips pre-flush
            f.write(f"{i} {i};0\n")

    def make(rate):
        ds = _make_ds(x, y, batch=2)
        ds.add_source(FileTailSource(p, name="s"))
        ds.set_bad_sample_policy(
            "quarantine", dead_letter_path=str(tmp_path / "d.jsonl"),
            max_poison_rate=rate, poison_floor=4)
        return ds

    ds = make(0.2)
    with pytest.raises(PoisonFeed):
        list(ds._iter_batches())             # dies before any flush
    # operator lifts the ceiling and re-iterates the SAME dataset object
    ds._max_poison_rate = None
    batches = list(ds._iter_batches())
    got = np.concatenate([b["x"] for b in batches])
    np.testing.assert_allclose(got[:, 0], np.arange(6, dtype="float32"))


def test_stream_chaos_runs_without_read_faults(tmp_path):
    """--read-fault-prob 0 means no read faults armed (not an invalid
    0%-probability spec)."""
    from paddle_tpu.resilience.__main__ import run_stream_chaos
    s = run_stream_chaos(steps=6, batch=3, dim=4, seed=2,
                         poison_rate=0.1, read_fault_prob=0.0,
                         preempt_step=2, work_dir=str(tmp_path),
                         hermetic=True)
    assert s["ok"], s
    assert s["events"]["source_retry"] == 0


def test_parse_fault_site_fires(tmp_path, xy_vars):
    """exc@parse routes through the bad-sample policy (quarantine or
    raise); corrupt@parse garbles the record into the quarantine path."""
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    _write_stream(p, 6)

    faults.install("exc@parse:step=1")
    dl = str(tmp_path / "d.jsonl")
    ds = _make_ds(x, y, batch=5)
    ds.add_source(FileTailSource(p, name="s"))
    ds.set_bad_sample_policy("quarantine", dead_letter_path=dl)
    batches = list(ds._iter_batches())
    faults.clear()
    assert len(batches) == 1 and batches[0]["x"].shape == (5, 2)
    recs = [json.loads(ln) for ln in open(dl)]
    assert len(recs) == 1 and "UNAVAILABLE" in recs[0]["error"]

    faults.install("exc@parse:step=0")
    ds2 = _make_ds(x, y, batch=2)        # default policy: raise
    ds2.add_source(FileTailSource(p, name="s"))
    with pytest.raises(ValueError, match="injected parse fault"):
        list(ds2._iter_batches())
    faults.clear()

    faults.install("corrupt@parse:step=3")
    dl3 = str(tmp_path / "d3.jsonl")
    ds3 = _make_ds(x, y, batch=5)
    ds3.add_source(FileTailSource(p, name="s"))
    ds3.set_bad_sample_policy("quarantine", dead_letter_path=dl3)
    batches3 = list(ds3._iter_batches())
    faults.clear()
    assert len(batches3) == 1
    recs3 = [json.loads(ln) for ln in open(dl3)]
    assert len(recs3) == 1 and "CORRUPT" in recs3[0]["line"]


def test_inert_stream_fault_specs_rejected():
    """nan/truncate have no hook at read/parse: arming one would report a
    clean chaos run in which nothing was injected -- rejected typed."""
    with pytest.raises(faults.FaultSpecError):
        faults.parse_spec("nan@read:var=clicks")
    with pytest.raises(faults.FaultSpecError):
        faults.parse_spec("truncate@read")


def test_rearming_quarantine_closes_previous_writer(tmp_path, xy_vars):
    x, y = xy_vars
    ds = _make_ds(x, y)
    ds.set_bad_sample_policy("quarantine",
                             dead_letter_path=str(tmp_path / "a.jsonl"))
    w1 = ds._dead_letter
    w1.write("s:1", "slot_count", "err", "line")      # opens the fd
    assert w1._f is not None
    ds.set_bad_sample_policy("quarantine",
                             dead_letter_path=str(tmp_path / "b.jsonl"))
    assert w1._f is None                              # old fd closed
    assert ds._dead_letter.path.endswith("b.jsonl")


def test_stale_reader_cannot_close_next_epochs_source(tmp_path, xy_vars):
    """The generation guard: a reader surviving a prior epoch's bounded
    join must not close the source the CURRENT epoch reopened."""
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    _write_stream(p, 4)
    ds = _make_ds(x, y, batch=2)
    src = ds.add_source(FileTailSource(p, name="s"))
    list(ds._iter_batches())                      # epoch 1 (gen bumped)
    stale_gen = ds._epoch_gen
    with ds._src_lock:
        ds._epoch_gen += 1                        # "next epoch started"
    src.open(ds.clock)                            # new epoch's handle
    ds._close_source(src, stale_gen)              # stale closer: no-op
    assert src._f is not None
    ds._close_source(src, ds._epoch_gen)          # current gen: closes
    assert src._f is None


def test_socket_quiet_gaps_do_not_churn_reconnects(xy_vars):
    """The connect timeout must not linger as a read timeout: a healthy
    stream with inter-record gaps longer than connect_timeout streams
    through with zero retries."""
    x, y = xy_vars
    lines = [f"{i} {i + 0.5};{i % 3}\n" for i in range(4)]
    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]

    done = threading.Event()

    def serve():
        conn, _ = srv.accept()
        try:
            for i, ln in enumerate(lines):
                if i == 2:
                    time.sleep(0.7)      # gap > connect_timeout
                conn.sendall(ln.encode())
            done.wait(10)    # hold the connection open: EOF would be a
        finally:             # legitimate reconnect, not what we test
            conn.close()
            srv.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    before = len(journal.recent(event="source_retry"))
    ds = _make_ds(x, y, batch=2, retry_backoff=0.01)
    ds.add_source(SocketSource("127.0.0.1", port, name="quiet",
                               connect_timeout=0.3))
    ds.set_epoch_bound(steps=2)
    batches = list(ds._iter_batches())
    done.set()
    t.join(timeout=5)
    assert len(batches) == 2
    np.testing.assert_allclose(batches[1]["x"][-1], [3, 3.5])
    quiet = [e for e in journal.recent(event="source_retry")[before:]
             if e.get("source") == "quiet"]
    assert not quiet, quiet


def test_seek_before_filelist_materialization(tmp_path, xy_vars):
    """The QueueDataset drop-in flow: seek() on a set_filelist() dataset
    (sources not yet materialized) must honor the saved watermarks, not
    silently drop them and replay from byte 0."""
    x, y = xy_vars
    p = str(tmp_path / "s.txt")
    _write_stream(p, 8)
    ds = _make_ds(x, y, batch=2)
    ds.set_filelist([p])
    first = list(ds._iter_batches())
    assert len(first) == 4
    ds2 = _make_ds(x, y, batch=2)
    ds2.set_filelist([p])
    ds2.seek(ds.watermark(2))            # BEFORE any _iter_batches call
    rest = list(ds2._iter_batches())
    assert len(rest) == 2
    np.testing.assert_allclose(rest[0]["x"][0], [4, 4.5])
