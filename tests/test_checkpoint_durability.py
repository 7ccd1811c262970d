"""Durable checkpointing: checksummed saves, completeness-scan size checks,
quarantine + fall-through on corruption, async saves, exact-state resume,
and the ckpt_doctor chaos tool (ISSUE 9).

The reference's auto-checkpoint layer (python/paddle/fluid/incubate/
checkpoint/auto_checkpoint.py) trusts the store; these tests pin the
opposite contract: a checkpoint that merely *exists* is not a resume point
until its recorded sizes and checksums agree, and a corrupt one is
quarantined rather than restored.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import io as pio
from paddle_tpu.utils import fs as fsio
from paddle_tpu.utils.checkpointer import Checkpointer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(seed=3, dim=4):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [dim], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, dim))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _feed(step, dim=4, batch=2):
    rs = np.random.RandomState(1000 + step)
    return {"x": rs.rand(batch, dim).astype("float32")}


def _state_bytes(scope, main):
    """Persistable state as a sorted name->bytes dict (byte-identity probe)."""
    out = {}
    for name, var in main.global_block().vars.items():
        if var.persistable:
            v = scope.find_var(name)
            if v is not None:
                out[name] = np.asarray(v).tobytes()
    return out


def _chunk_files(d):
    return sorted(n for n in fsio.listdir(d) if n.endswith(".npy"))


@pytest.fixture()
def trained_tree(tmp_path):
    """A 3-checkpoint tree (steps 1..3, max_to_keep=3) plus the live scope
    state at each step, for corruption tests to chew on."""
    main, startup, loss = _build()
    scope = fluid.Scope()
    ck_dir = str(tmp_path / "ck")
    states = {}
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, ck_dir, max_to_keep=3)
        for step in (1, 2, 3):
            exe.run(main, feed=_feed(step), fetch_list=[loss])
            ck.save(step)
            states[step] = _state_bytes(scope, main)
        exe.close()
    return {"main": main, "startup": startup, "loss": loss,
            "dir": ck_dir, "states": states}


# -- completeness scan: sizes, not existence (satellite 1) -------------------

def test_manifest_records_bytes_and_crc(trained_tree):
    d = os.path.join(trained_tree["dir"], "ckpt-3")
    with open(os.path.join(d, "__manifest__.json")) as f:
        head = json.load(f)
    assert head["format_version"] == pio.FORMAT_VERSION
    assert head["vars"], "expected persistable vars in the manifest"
    import io as pyio
    import zlib
    for m in head["vars"]:
        for ch in m["chunks"]:
            p = os.path.join(d, ch["file"])
            data = open(p, "rb").read()
            assert ch["bytes"] == len(data)
            assert ch["crc32"] == zlib.crc32(data)
            # layout guard: the chunk file is byte-identical to plain
            # np.save output (new manifest fields, same data format)
            buf = pyio.BytesIO()
            np.save(buf, np.load(p, allow_pickle=False),
                    allow_pickle=False)
            assert data == buf.getvalue()


def test_zero_byte_chunk_is_incomplete(trained_tree):
    main = trained_tree["main"]
    d = os.path.join(trained_tree["dir"], "ckpt-3")
    victim = os.path.join(d, _chunk_files(d)[0])
    open(victim, "wb").close()   # zero-byte chunk still *exists*
    exe = fluid.Executor()
    ck = Checkpointer(exe, main, trained_tree["dir"])
    assert not ck._is_complete(d)
    assert ck.latest_step() == 2   # falls through past the torn step


def test_size_mismatched_chunk_is_incomplete(trained_tree):
    main = trained_tree["main"]
    d = os.path.join(trained_tree["dir"], "ckpt-3")
    victim = os.path.join(d, _chunk_files(d)[0])
    with open(victim, "ab") as f:
        f.write(b"xx")          # grown file: size disagrees with manifest
    exe = fluid.Executor()
    ck = Checkpointer(exe, main, trained_tree["dir"])
    assert not ck._is_complete(d)
    assert ck.latest_step() == 2


def test_verify_checkpoint_report_levels(trained_tree):
    d = os.path.join(trained_tree["dir"], "ckpt-2")
    rep = pio.verify_checkpoint(d, level="crc")
    assert rep["ok"] and all(c["status"] == "ok" for c in rep["chunks"])
    # single flipped bit: size scan passes, crc scan catches it
    victim = os.path.join(d, _chunk_files(d)[0])
    data = bytearray(open(victim, "rb").read())
    data[-1] ^= 0x01
    open(victim, "wb").write(bytes(data))
    assert pio.verify_checkpoint(d, level="size")["ok"]
    rep = pio.verify_checkpoint(d, level="crc")
    assert not rep["ok"]
    assert any(c["status"] == "crc_mismatch" for c in rep["chunks"])


def test_malformed_manifest_is_incomplete_not_a_crash(trained_tree):
    """A manifest that parses as JSON but has the wrong shape (torn write
    caught mid-flush) must scan as incomplete, never raise out of
    latest_step()/restore()."""
    main = trained_tree["main"]
    d = os.path.join(trained_tree["dir"], "ckpt-3")
    p = os.path.join(d, "__manifest__.json")
    for poison in ({"vars": [None], "nranks": 1},
                   {"vars": [{"name": "w", "chunks": [{"index": []}]}],
                    "nranks": 1},
                   {"nranks": 1}):
        with open(p, "w") as f:
            json.dump(poison, f)
        exe = fluid.Executor()
        ck = Checkpointer(exe, main, trained_tree["dir"])
        assert not ck._is_complete(d)
        assert ck.latest_step() == 2


# -- corruption matrix: detect, quarantine, fall through ---------------------

def test_corruption_matrix_bitflip_every_chunk(trained_tree, tmp_path):
    """Bit-flip EACH chunk of the newest checkpoint in turn (fresh copy of
    the tree per victim): the flip passes the size scan, restore() detects
    it via crc, quarantines ckpt-3, and lands on step 2 with step-2's
    exact bytes -- never silently restores garbage."""
    import shutil
    main, startup = trained_tree["main"], trained_tree["startup"]
    src = trained_tree["dir"]
    chunks = _chunk_files(os.path.join(src, "ckpt-3"))
    assert len(chunks) >= 3
    for i, victim in enumerate(chunks):
        tree = str(tmp_path / f"copy{i}")
        shutil.copytree(src, tree)
        p = os.path.join(tree, "ckpt-3", victim)
        data = bytearray(open(p, "rb").read())
        data[len(data) // 2] ^= 0x40
        open(p, "wb").write(bytes(data))
        exe = fluid.Executor()
        ck = Checkpointer(exe, main, tree)
        assert ck._is_complete(os.path.join(tree, "ckpt-3"))  # size scan
        assert ck.latest_step() == 3      # cheap scan cannot see a flip
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            assert ck.restore() == 2, f"victim {victim}"
            assert _state_bytes(scope, main) == trained_tree["states"][2]
        assert os.path.isdir(os.path.join(tree, "ckpt-3.corrupt"))
        assert not os.path.exists(os.path.join(tree, "ckpt-3"))


def test_truncated_manifest_falls_through(trained_tree):
    main, startup = trained_tree["main"], trained_tree["startup"]
    p = os.path.join(trained_tree["dir"], "ckpt-3", "__manifest__.json")
    raw = open(p).read()
    open(p, "w").write(raw[:len(raw) // 2])   # torn JSON
    exe = fluid.Executor()
    ck = Checkpointer(exe, main, trained_tree["dir"])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        assert ck.restore() == 2
        assert _state_bytes(scope, main) == trained_tree["states"][2]


def test_stale_latest_falls_through(trained_tree):
    main, startup = trained_tree["main"], trained_tree["startup"]
    with open(os.path.join(trained_tree["dir"], "LATEST"), "w") as f:
        json.dump({"step": 999999, "time": 0}, f)
    exe = fluid.Executor()
    ck = Checkpointer(exe, main, trained_tree["dir"])
    assert ck.latest_step() == 3
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        assert ck.restore() == 3
        assert _state_bytes(scope, main) == trained_tree["states"][3]


def test_injected_corrupt_fault_roundtrip(tmp_path):
    """The chaos path end to end: a seeded ``corrupt@checkpoint_write``
    fault damages the save's own files; the NEXT process's restore
    detects, quarantines, and falls through to the undamaged step."""
    from paddle_tpu.resilience import faults
    main, startup, loss = _build(seed=5)
    tree = str(tmp_path / "ck")
    scope = fluid.Scope()
    try:
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            ck = Checkpointer(exe, main, tree)
            exe.run(main, feed=_feed(0), fetch_list=[loss])
            ck.save(1)
            want = _state_bytes(scope, main)
            faults.install("corrupt@checkpoint_write:step=2:seed=3")
            exe.run(main, feed=_feed(1), fetch_list=[loss])
            ck.save(2)
            exe.close()
        assert faults.active()[0].fired == 1
    finally:
        faults.clear()
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor()
        exe2.run(startup)
        ck2 = Checkpointer(exe2, main, tree)
        assert ck2.latest_step() == 2     # size scan passes the bit-flip
        assert ck2.restore() == 1         # crc verify does not
        assert _state_bytes(scope2, main) == want
    ev = [e for e in _recent_events("ckpt_quarantine")]
    assert ev and ev[-1]["step"] == 2


def _recent_events(kind):
    from paddle_tpu.observability import journal
    return [e for e in journal.recent() if e.get("event") == kind]


# -- async saves -------------------------------------------------------------

def test_async_save_matches_sync_layout(trained_tree, tmp_path):
    """async_=True writes the exact same checkpoint a sync save writes
    (chunk bytes, manifest entries, trainstate), just off-thread."""
    main = trained_tree["main"]
    startup = trained_tree["startup"]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        cka = Checkpointer(exe, main, str(tmp_path / "a"))
        ckb = Checkpointer(exe, main, str(tmp_path / "b"), async_save=True)
        cka.save(7)
        ckb.save(7)
        ckb.wait()
    da, db = str(tmp_path / "a" / "ckpt-7"), str(tmp_path / "b" / "ckpt-7")
    assert _chunk_files(da) == _chunk_files(db)
    for f in _chunk_files(da):
        assert open(os.path.join(da, f), "rb").read() == \
            open(os.path.join(db, f), "rb").read()
    ma = json.load(open(os.path.join(da, "__manifest__.json")))
    mb = json.load(open(os.path.join(db, "__manifest__.json")))
    assert ma == mb
    assert pio.verify_checkpoint(db, level="crc")["ok"]
    ta = json.load(open(os.path.join(da, "trainstate.json")))
    tb = json.load(open(os.path.join(db, "trainstate.json")))
    assert ta == tb and ta["step"] == 7


def test_async_backpressure_blocks_until_previous_lands(trained_tree,
                                                        tmp_path,
                                                        monkeypatch):
    import threading
    main, startup = trained_tree["main"], trained_tree["startup"]
    gate, started = threading.Event(), threading.Event()
    real = pio.write_snapshot

    def slow(snap, dirname, filename=None):
        started.set()
        assert gate.wait(10)
        return real(snap, dirname, filename)

    monkeypatch.setattr(pio, "write_snapshot", slow)
    tree = str(tmp_path / "ck_bp")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, tree, async_save=True)
        ck.save(1)                      # writer parks on the gate
        assert started.wait(10)
        done = threading.Event()

        def second():
            with fluid.scope_guard(scope):   # scope stack is thread-local
                ck.save(2)              # must block: backpressure
            done.set()

        t = threading.Thread(target=second, daemon=True)
        t.start()
        assert not done.wait(0.3), \
            "second async save did not wait for the first write to land"
        gate.set()
        assert done.wait(10)
        ck.close()
    for step in (1, 2):
        assert pio.verify_checkpoint(
            os.path.join(tree, f"ckpt-{step}"), level="crc")["ok"]


def test_async_error_surfaces_on_next_save_and_wait(trained_tree, tmp_path,
                                                    monkeypatch):
    main, startup = trained_tree["main"], trained_tree["startup"]
    calls = {"n": 0}
    real = pio.write_snapshot

    def flaky(snap, dirname, filename=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("injected: disk full")
        return real(snap, dirname, filename)

    monkeypatch.setattr(pio, "write_snapshot", flaky)
    tree = str(tmp_path / "ck_err")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, tree, async_save=True)
        ck.save(1)                      # writer fails in the background
        with pytest.raises(OSError, match="disk full"):
            ck.save(2)                  # ...and surfaces HERE, not swallowed
        ck.save(2)                      # checkpointer still usable
        ck.wait()
        assert ck.latest_step() == 2
        assert not fsio.exists(os.path.join(tree, "ckpt-1",
                                            "__manifest__.json"))
        ck.close()
    assert _recent_events("ckpt_save_error")


def test_torn_async_save_killed_mid_write_falls_through(trained_tree,
                                                        tmp_path,
                                                        monkeypatch):
    """An async writer that dies mid-write (some chunks written, no
    manifest) leaves an incomplete dir: the error surfaces on wait(), the
    scan rejects the torn step, and restore lands on the previous one."""
    import shutil
    main, startup = trained_tree["main"], trained_tree["startup"]
    tree = str(tmp_path / "ck_torn")
    shutil.copytree(trained_tree["dir"], tree)
    real_write = pio._write_snap

    def torn(dirname, snap):
        real_write(dirname, snap)       # first chunk lands...
        raise OSError("killed mid-write")

    monkeypatch.setattr(pio, "_write_snap", torn)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, tree, async_save=True)
        ck.save(9)
        with pytest.raises(OSError, match="mid-write"):
            ck.wait()
        assert os.path.isdir(os.path.join(tree, "ckpt-9"))  # torn remains
        assert ck.latest_step() == 3    # ...but is not a resume point
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor()
        exe2.run(startup)
        ck2 = Checkpointer(exe2, main, tree)
        assert ck2.restore() == 3
        assert _state_bytes(scope2, main) == trained_tree["states"][3]


def test_async_off_by_default_and_guardian_flushes_on_preempt(tmp_path):
    """async_save defaults to off; under preemption the guardian flushes
    the pending async write synchronously before the emergency save."""
    from paddle_tpu.resilience import recovery
    assert Checkpointer(None, None, str(tmp_path)).async_save is False
    main, startup, loss = _build(seed=9)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, str(tmp_path / "ck"),
                          save_interval_steps=1, async_save=True)
        g = recovery.StepGuardian(exe, main, checkpointer=ck,
                                  handle_signals=False)
        g.run(feed=_feed(0), fetch_list=[loss])
        g.run(feed=_feed(1), fetch_list=[loss])
        recovery.request_preemption("test")
        try:
            with pytest.raises(recovery.Preempted) as pi:
                g.run(feed=_feed(2), fetch_list=[loss])
        finally:
            recovery.clear_preemption()
        assert pi.value.saved_step == 1
        assert ck._writer is None       # pending write flushed
        assert ck.latest_step() == 1
        assert pio.verify_checkpoint(
            str(tmp_path / "ck" / "ckpt-1"), level="crc")["ok"]


def test_failed_async_write_still_emergency_saved_on_preempt(tmp_path,
                                                             monkeypatch):
    """If the pending async write for step N failed, the emergency exit
    must NOT trust the cadence ('N already saved') -- it re-saves N
    synchronously, so Preempted.saved_step names a checkpoint that
    actually exists."""
    from paddle_tpu.resilience import recovery
    main, startup, loss = _build(seed=23)
    fails = {"arm": False}
    real = pio.write_snapshot

    def flaky(snap, dirname, filename=None):
        if fails["arm"]:
            fails["arm"] = False
            raise OSError("injected: store blip")
        return real(snap, dirname, filename)

    monkeypatch.setattr(pio, "write_snapshot", flaky)
    tree = str(tmp_path / "ck")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, tree, save_interval_steps=1,
                          async_save=True)
        g = recovery.StepGuardian(exe, main, checkpointer=ck,
                                  handle_signals=False)
        g.run(feed=_feed(0), fetch_list=[loss])
        fails["arm"] = True             # the save for step 1 will fail
        g.run(feed=_feed(1), fetch_list=[loss])
        recovery.request_preemption("test")
        try:
            with pytest.raises(recovery.Preempted) as pi:
                g.run(feed=_feed(2), fetch_list=[loss])
        finally:
            recovery.clear_preemption()
        assert pi.value.saved_step == 1
    assert pio.verify_checkpoint(os.path.join(tree, "ckpt-1"),
                                 level="crc")["ok"], \
        "emergency save did not rewrite the failed step"


# -- exact resume ------------------------------------------------------------

@pytest.mark.parametrize("old_field", [False, True])
def test_exact_resume_byte_identity(tmp_path, old_field):
    """The pinned exact-resume contract: a run that saves, is killed, and
    resumes from trainstate.json (rng counter + batch position) commits
    byte-identical state to the uninterrupted run.  ``old_field``: the
    file is one an older version wrote, with ``"fuse_steps": 4`` in it --
    a batch count like any other, so the restore ignores the field and
    the resume is exact all the same."""
    from paddle_tpu.resilience.recovery import StepGuardian
    main, startup, loss = _build(seed=13)
    batches = [_feed(i) for i in range(8)]

    class _ListDataset:
        def __init__(self, bs):
            self.batches, self.thread_num = bs, 0

        def _iter_batches(self):
            yield from self.batches

    def fresh():
        main._rng_run_counter = 0
        startup._rng_run_counter = 0

    # run A: uninterrupted epoch
    fresh()
    scope_a = fluid.Scope()
    with fluid.scope_guard(scope_a):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, str(tmp_path / "a"),
                          save_interval_steps=1)
        g = StepGuardian(exe, main, checkpointer=ck, handle_signals=False)
        g.train_from_dataset(dataset=_ListDataset(batches),
                             fetch_list=[loss])
        want = _state_bytes(scope_a, main)
        want_counter = main._rng_run_counter

    # run B phase 1: first half of the epoch, then the process "dies"
    fresh()
    scope_b = fluid.Scope()
    with fluid.scope_guard(scope_b):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, str(tmp_path / "b"),
                          save_interval_steps=1)
        g = StepGuardian(exe, main, checkpointer=ck, handle_signals=False)
        g.train_from_dataset(dataset=_ListDataset(batches[:4]),
                             fetch_list=[loss])
    main._rng_run_counter = 12345       # clobbered by the "crash"
    if old_field:
        ts_path = tmp_path / "b" / "ckpt-3" / "trainstate.json"
        doc = json.loads(ts_path.read_text())
        assert "fuse_steps" not in doc
        ts_path.write_text(json.dumps({**doc, "fuse_steps": 4}))

    # run B phase 2: fresh executor+scope, exact resume from trainstate
    scope_c = fluid.Scope()
    with fluid.scope_guard(scope_c):
        exe2 = fluid.Executor()
        exe2.run(startup)
        ck2 = Checkpointer(exe2, main, str(tmp_path / "b"),
                           save_interval_steps=1)
        start = ck2.restore()
        assert start == 3               # steps 0..3 ran, saved at boundary
        ts = ck2.train_state
        assert ts["batch"] == 4 and ts.get("fuse_steps") == (
            4 if old_field else None)
        assert main._rng_run_counter == 4   # rewound for the exact fold
        g2 = StepGuardian(exe2, main, checkpointer=ck2,
                          handle_signals=False, start_step=start + 1)
        g2.train_from_dataset(dataset=_ListDataset(batches),
                              fetch_list=[loss],
                              skip_batches=ts["batch"],
                              epoch=ts.get("epoch", 0))
        got = _state_bytes(scope_c, main)
        assert main._rng_run_counter == want_counter
    assert got == want                  # byte-identical to uninterrupted
    newest = json.loads(
        (tmp_path / "b" / "ckpt-7" / "trainstate.json").read_text())
    assert newest["batch"] == 8 and "fuse_steps" not in newest


def test_kill_during_async_save_chaos_losses_match(tmp_path):
    """Acceptance: a chaos run preempted while async saves are in flight
    resumes exactly -- post-resume losses equal the uninterrupted run's
    (flush-then-emergency-save keeps the recovery point coherent)."""
    from paddle_tpu.resilience import recovery
    from paddle_tpu.resilience.recovery import StepGuardian
    main, startup, loss = _build(seed=21)

    def run_steps(g, lo, hi, losses):
        for step in range(lo, hi):
            v, = g.run(feed=_feed(step), fetch_list=[loss])
            losses.append(np.asarray(v).tobytes())

    def fresh():
        main._rng_run_counter = 0
        startup._rng_run_counter = 0

    # run A: uninterrupted
    fresh()
    losses_a = []
    scope_a = fluid.Scope()
    with fluid.scope_guard(scope_a):
        exe = fluid.Executor()
        exe.run(startup)
        g = StepGuardian(exe, main, handle_signals=False)
        run_steps(g, 0, 10, losses_a)
        want = _state_bytes(scope_a, main)

    # run B: async saves every step, preempted at step 6 mid-flight
    fresh()
    losses_b = []
    scope_b = fluid.Scope()
    with fluid.scope_guard(scope_b):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, str(tmp_path / "ck"),
                          save_interval_steps=1, async_save=True)
        g = StepGuardian(exe, main, checkpointer=ck, handle_signals=False)
        run_steps(g, 0, 6, losses_b)
        recovery.request_preemption("chaos kill")
        try:
            with pytest.raises(recovery.Preempted) as pi:
                g.run(feed=_feed(6), fetch_list=[loss])
        finally:
            recovery.clear_preemption()
        assert pi.value.saved_step == 5
    main._rng_run_counter = 999         # clobbered by the "crash"
    scope_c = fluid.Scope()
    with fluid.scope_guard(scope_c):
        exe2 = fluid.Executor()
        exe2.run(startup)
        ck2 = Checkpointer(exe2, main, str(tmp_path / "ck"))
        start = ck2.restore()
        assert start == 5
        assert main._rng_run_counter == 6   # exact next fold
        g2 = StepGuardian(exe2, main, checkpointer=ck2,
                          handle_signals=False, start_step=start + 1)
        run_steps(g2, 6, 10, losses_b)
        got = _state_bytes(scope_c, main)
    assert losses_b == losses_a         # byte-equal losses, every step
    assert got == want


def test_executor_skip_batches_fast_forward():
    """Executor.train_from_dataset(skip_batches=N) == running only the
    tail of the epoch."""
    main, startup, loss = _build(seed=17)

    class _ListDataset:
        def __init__(self, bs):
            self.batches, self.thread_num = bs, 0

        def _iter_batches(self):
            yield from self.batches

    batches = [_feed(i) for i in range(6)]
    outs = {}
    for label, kw in (("skip", dict(skip_batches=4)), ("tail", {})):
        main._rng_run_counter = 0
        startup._rng_run_counter = 0
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            ds = _ListDataset(batches if label == "skip" else batches[4:])
            exe.train_from_dataset(main, ds, fetch_list=[loss], **kw)
            outs[label] = _state_bytes(scope, main)
    assert outs["skip"] == outs["tail"]


# -- doctor / CLI / satellites ----------------------------------------------

def test_ckpt_doctor_selftest():
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run([sys.executable, "-m", "tools.ckpt_doctor",
                        "--selftest"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ckpt doctor selftest: OK" in r.stdout


def test_ckpt_doctor_verify_and_fuzz_cli(trained_tree):
    from tools import ckpt_doctor
    rep = ckpt_doctor.verify_tree(trained_tree["dir"], level="crc")
    assert rep["ok"] and rep["latest_complete_step"] == 3
    # text formatting + exit codes through main()
    assert ckpt_doctor.main(["verify", trained_tree["dir"]]) == 0
    d = os.path.join(trained_tree["dir"], "ckpt-3")
    victim = os.path.join(d, _chunk_files(d)[0])
    data = bytearray(open(victim, "rb").read())
    data[0] ^= 0x02
    open(victim, "wb").write(bytes(data))
    assert ckpt_doctor.main(["verify", trained_tree["dir"]]) == 1
    assert ckpt_doctor.main([]) == 2
    # fuzz the (already bit-flipped) tree: every applied case must pass
    rep = ckpt_doctor.fuzz_tree(trained_tree["dir"], seed=5)
    assert rep["ok"], json.dumps(rep, indent=2)


def test_predictor_rejects_unknown_and_mislengthed_inputs(tmp_path):
    from paddle_tpu.inference import Predictor
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 2
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [4], "float32")
        y = fluid.layers.fc(x, 3)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp_path / "m"), ["x"], [y], exe,
                                      main)
    p = Predictor(str(tmp_path / "m"))
    xv = np.ones((2, 4), np.float32)
    p.run({"x": xv})                                    # happy path
    with pytest.raises(ValueError, match="unexpected inputs.*'xx'"):
        p.run({"x": xv, "xx": xv})                      # typo'd extra key
    with pytest.raises(ValueError, match="missing inputs"):
        p.run({})
    with pytest.raises(ValueError, match="2 positional inputs"):
        p.run([xv, xv])                                 # silent-drop before


def test_rotation_never_deletes_restored_step(trained_tree):
    """Rank 0's rotation must not delete the step this process restored
    from, even when it rotates out of the keep window."""
    main, startup, loss = (trained_tree["main"], trained_tree["startup"],
                           trained_tree["loss"])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, trained_tree["dir"], max_to_keep=2)
        assert ck.restore() == 3
        for step in (4, 5, 6):
            exe.run(main, feed=_feed(step), fetch_list=[loss])
            ck.save(step)
    kept = set(fsio.listdir(trained_tree["dir"]))
    assert "ckpt-3" in kept             # restored step survives rotation
    assert "ckpt-5" in kept and "ckpt-6" in kept
    assert "ckpt-4" not in kept         # normal rotation still happens


def test_checkpoint_metrics_and_journal(trained_tree, tmp_path):
    from paddle_tpu.observability.metrics import REGISTRY
    main, startup = trained_tree["main"], trained_tree["startup"]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ck = Checkpointer(exe, main, str(tmp_path / "ck_met"),
                          async_save=True)
        ck.save(1)
        ck.wait()
    ev = [e for e in _recent_events("ckpt_save") if e.get("step") == 1]
    assert ev and ev[-1]["async"] and ev[-1]["bytes"] > 0
    assert ev[-1]["blocked_ms"] >= 0 and ev[-1]["write_ms"] >= 0
    fam = REGISTRY.get("checkpoint_bytes_total")
    assert fam is not None
    fam2 = REGISTRY.get("checkpoint_blocked_seconds")
    assert fam2 is not None


def test_old_format_checkpoint_still_restores(trained_tree):
    """v1 manifests (no format_version / sizes / crcs) restore with checks
    skipped -- forward compatibility for pre-existing checkpoint trees."""
    main = trained_tree["main"]
    d = os.path.join(trained_tree["dir"], "ckpt-3")
    for name in os.listdir(d):
        if name.startswith("__manifest__"):
            p = os.path.join(d, name)
            with open(p) as f:
                doc = json.load(f)
            doc.pop("format_version", None)
            for m in doc["vars"]:
                for ch in m["chunks"]:
                    ch.pop("bytes", None)
                    ch.pop("crc32", None)
            with open(p, "w") as f:
                json.dump(doc, f)
    exe = fluid.Executor()
    ck = Checkpointer(exe, main, trained_tree["dir"])
    assert ck._is_complete(d)
    assert ck.latest_step() == 3
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(trained_tree["startup"])
        assert ck.restore() == 3
        assert _state_bytes(scope, main) == trained_tree["states"][3]
