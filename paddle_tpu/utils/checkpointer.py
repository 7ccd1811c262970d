"""Durable periodic checkpoint rotation + exact resume (reference:
python/paddle/fluid/incubate/checkpoint/auto_checkpoint.py, which wraps
train loops in TrainEpochRange and snapshots to HDFS on a cadence).

TPU-native: builds on io.save_persistables / load_persistables, so multi-host
sharded state round-trips per-process with no gather (io.py chunked format)
and a checkpoint saved under one mesh restores under another
(reshard-on-load). Rotation keeps ``max_to_keep`` steps; a LATEST marker is
written last so a crash mid-save never corrupts the resume point -- and
because ``utils/fs.py`` replace() is copy-then-delete on remote stores (no
atomic rename on object stores), restore() treats LATEST as a hint only:
a missing/corrupt/stale marker degrades to scanning ``ckpt-*`` dirs for the
newest step whose manifests and chunk files are all present.

Durability contract (ISSUE 9):

- **Integrity**: manifests record per-chunk byte size + crc32 at save time
  (io.py FORMAT_VERSION 2).  The completeness scan validates sizes (cheap,
  one stat per chunk); ``restore()`` checksum-verifies every chunk it
  reads, and a corrupt checkpoint is QUARANTINED (renamed
  ``ckpt-N.corrupt``, journaled ``ckpt_quarantine``) so the scan falls
  through to the newest genuinely-complete step instead of restoring
  garbage.
- **Async saves**: ``save(step, async_=True)`` (or ``async_save=True`` at
  construction) blocks only for the d2h state snapshot; serialization,
  writing, LATEST update and rotation happen on a single background
  writer thread.  Overlapping saves apply backpressure (the next save
  blocks until the previous write lands); writer errors surface on the
  NEXT ``save()``/``wait()`` rather than being swallowed; ``wait()`` /
  ``close()`` flush.  Async is single-host only (the writer thread cannot
  join the cross-host barrier choreography) -- multi-host degrades to a
  sync save with a one-time warning.
- **Exact resume**: each checkpoint carries ``trainstate.json`` (step, rng
  run counter, dataset epoch/batch position) so a restored
  run continues on the exact next batch with the exact next rng fold --
  ``restore()`` rewinds the program's rng counter and exposes
  ``.train_state``.
- **Observability**: ``checkpoint_blocked_seconds{mode}`` vs
  ``checkpoint_write_seconds{mode}`` histograms,
  ``checkpoint_bytes_total``, ``checkpoint_corruption_total{kind}``;
  ``ckpt_save`` / ``ckpt_corrupt`` / ``ckpt_quarantine`` journal events.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Optional

from . import fs as _fsio
from ..observability import journal as _journal
from ..observability.metrics import REGISTRY as _OBS

TRAINSTATE_FILE = "trainstate.json"


class Checkpointer:
    """Usage::

        ck = Checkpointer(exe, program, "ckpts", save_interval_steps=100,
                          async_save=True)
        start = ck.restore() + 1          # -1 -> fresh run
        for step in range(start, n_steps):
            exe.run(...)
            ck.maybe_save(step)
        ck.close()                        # flush the pending async write
    """

    def __init__(self, exe, program, dirname: str,
                 save_interval_steps: int = 0, save_interval_secs: float = 0,
                 max_to_keep: int = 3, async_save: bool = False):
        self.exe = exe
        self.program = program
        self.dirname = dirname
        self.save_interval_steps = save_interval_steps
        self.save_interval_secs = save_interval_secs
        import jax
        if save_interval_secs and jax.process_count() > 1:
            raise ValueError(
                "save_interval_secs under multi-host: per-host wall clocks "
                "cross the threshold at different steps and the hosts would "
                "deadlock on the save barrier; use save_interval_steps "
                "(deterministic across hosts)")
        self.max_to_keep = max_to_keep
        self.async_save = bool(async_save)
        self.train_state: Optional[dict] = None   # set by restore()
        self._train_state: dict = {}              # pending, next save's doc
        self._last_save_t = time.time()
        self._last_save_step: Optional[int] = None
        self._restored_step: Optional[int] = None
        self._writer: Optional[threading.Thread] = None
        self._async_error: Optional[BaseException] = None
        self._warned_async_multihost = False

    def _step_dir(self, step) -> str:
        return _fsio.join(self.dirname, f"ckpt-{step}")

    def _is_rank0(self) -> bool:
        import jax
        return jax.process_index() == 0

    # -- saving --------------------------------------------------------------

    def update_train_state(self, **kw):
        """Merge fields (dataset epoch/batch position, ...)
        into the ``trainstate.json`` the NEXT save will write.  The step
        and rng counter are recorded automatically."""
        self._train_state.update(kw)

    def save(self, step: int, async_: Optional[bool] = None,
             train_state: Optional[dict] = None):
        """Write checkpoint ``ckpt-<step>``.

        Sync (default): blocks for the full serialize+write+rotate, exactly
        the historical layout plus the v2 manifest fields.  Async: blocks
        only for the d2h snapshot; a background writer thread does the
        rest.  A still-running previous async write is waited for first
        (backpressure), which is also where its error -- if any --
        surfaces."""
        from .. import io
        from ..parallel.env import barrier
        from ..resilience import faults as _rfaults
        async_ = self.async_save if async_ is None else bool(async_)
        self.wait()   # backpressure + surface the previous writer's error
        if train_state:
            self._train_state.update(train_state)
        if async_:
            import jax
            if jax.process_count() > 1:
                # the writer thread cannot join the cross-host barrier
                # choreography of save_vars (ranks would deadlock against
                # a rank whose writer is slow); degrade loudly, once
                if not self._warned_async_multihost:
                    self._warned_async_multihost = True
                    import warnings
                    warnings.warn(
                        "Checkpointer async saves are single-host only; "
                        "falling back to synchronous saves under "
                        f"{jax.process_count()} processes", UserWarning)
                async_ = False
        if _rfaults._active:
            # fault site: transient checkpoint-write failure, injected
            # before any file is touched so the guardian's retry re-runs a
            # clean save (torn mid-write saves are separately covered by
            # the complete-step scanning in latest_step/_is_complete)
            _rfaults.fire("checkpoint_write", step)
        d = self._step_dir(step)
        ts_doc = self._trainstate_doc(step)
        t0 = time.perf_counter()
        if not async_:
            nbytes = io.save_persistables(self.exe, d, self.program)
            self._finish_save(step, d, ts_doc, barrier)
            dt = time.perf_counter() - t0
            for name in ("checkpoint_blocked_seconds",
                         "checkpoint_write_seconds"):
                _OBS.histogram(
                    name, "checkpoint save time by phase and mode",
                    mode="sync").observe(dt)
            self._note_saved(step, nbytes or 0, blocked=dt, write=dt,
                             async_=False)
            return
        # async: phase 1 (d2h snapshot) is the only blocking part. The
        # ambient scope is resolved HERE, in the caller's thread -- the
        # scope stack is thread-local and the writer thread must never
        # consult its own
        from ..core.executor import global_scope
        snap = io.snapshot_persistables(self.program, scope=global_scope())
        blocked = time.perf_counter() - t0
        _OBS.histogram("checkpoint_blocked_seconds",
                       "checkpoint save time by phase and mode",
                       mode="async").observe(blocked)
        self._writer = threading.Thread(
            target=self._write_async, args=(step, d, snap, ts_doc, blocked),
            name="checkpointer-writer", daemon=True)
        self._writer.start()
        # cadence advances at enqueue time: the save is logically taken at
        # this step; a failed write surfaces on the next save()/wait()
        self._last_save_t = time.time()
        self._last_save_step = step

    def _write_async(self, step, d, snap, ts_doc, blocked):
        from .. import io
        from ..resilience import faults as _rfaults
        t0 = time.perf_counter()
        try:
            nbytes = io.write_snapshot(snap, d)
            self._write_trainstate(d, ts_doc)
            if _rfaults._active:
                _rfaults.mutate_checkpoint(d, step)
            self._publish_and_rotate(step)
            write = time.perf_counter() - t0
            _OBS.histogram("checkpoint_write_seconds",
                           "checkpoint save time by phase and mode",
                           mode="async").observe(write)
            self._note_saved(step, nbytes, blocked=blocked, write=write,
                             async_=True)
        except BaseException as e:   # surfaces on the next save()/wait()
            self._async_error = e
            _journal.emit({"event": "ckpt_save_error", "step": step,
                           "error": f"{type(e).__name__}: {e}"})

    def _finish_save(self, step, d, ts_doc, barrier):
        """Post-chunk-write tail of a sync save: trainstate + fault hook +
        LATEST + barrier + rotation."""
        from ..resilience import faults as _rfaults
        if self._is_rank0():
            self._write_trainstate(d, ts_doc)
        if _rfaults._active:
            _rfaults.mutate_checkpoint(d, step)
        if self._is_rank0():
            with _fsio.open_file(_fsio.join(self.dirname, "LATEST.tmp"),
                                 "w") as f:
                json.dump({"step": step, "time": time.time()}, f)
            _fsio.replace(_fsio.join(self.dirname, "LATEST.tmp"),
                          _fsio.join(self.dirname, "LATEST"))
        # rotation strictly AFTER the post-save barrier: before it, a slow
        # rank may still be reading the dir it restored from (multi-host
        # rotation race) -- rank 0 must not rmtree under a reader
        barrier("checkpointer_save")
        if self._is_rank0():
            self._rotate()
        self._last_save_t = time.time()
        self._last_save_step = step

    def _publish_and_rotate(self, step):
        """Async-writer tail: LATEST + rotation (single-host, no barrier)."""
        with _fsio.open_file(_fsio.join(self.dirname, "LATEST.tmp"),
                             "w") as f:
            json.dump({"step": step, "time": time.time()}, f)
        _fsio.replace(_fsio.join(self.dirname, "LATEST.tmp"),
                      _fsio.join(self.dirname, "LATEST"))
        self._rotate()

    def _rotate(self):
        kept = sorted((int(n.split("-", 1)[1])
                       for n in _fsio.listdir(self.dirname)
                       if n.startswith("ckpt-") and
                       n.split("-", 1)[1].isdigit()), reverse=True)
        for old in kept[self.max_to_keep:]:
            if old == self._restored_step:
                # never rotate the step this process restored from: on a
                # slow shared store another rank (or a diagnostic reader)
                # may still be stitching chunks out of it
                continue
            _fsio.rmtree(self._step_dir(old), ignore_errors=True)

    def _trainstate_doc(self, step) -> dict:
        counter = 0
        if self.program is not None:
            from .. import io
            prog, _ = io._unwrap_program(self.program)
            counter = int(getattr(prog, "_rng_run_counter", 0))
        import jax
        doc = {"format_version": 1, "step": int(step),
               "rng_counter": counter,
               # the world this state was saved under: restore compares it
               # against its own and plans the reshard when they differ
               # (elastic world-size-changing resume, ISSUE 11)
               "world": {"nranks": jax.process_count(),
                         "ndev": jax.device_count()}}
        doc.update(self._train_state)
        return doc

    def _write_trainstate(self, d, doc):
        with _fsio.open_file(_fsio.join(d, TRAINSTATE_FILE), "w") as f:
            json.dump(doc, f)

    def _note_saved(self, step, nbytes, blocked, write, async_):
        _OBS.counter("checkpoint_bytes_total",
                     "chunk bytes written by checkpoint saves").inc(nbytes)
        _journal.emit({"event": "ckpt_save", "step": step,
                       "async": bool(async_), "bytes": int(nbytes),
                       "blocked_ms": round(blocked * 1e3, 3),
                       "write_ms": round(write * 1e3, 3)})

    def wait(self):
        """Block until the in-flight async write (if any) lands; re-raise
        its error here if it failed.  Idempotent."""
        t = self._writer
        if t is not None:
            t.join()
            self._writer = None
        e, self._async_error = self._async_error, None
        if e is not None:
            # the enqueued save never landed: invalidate the cadence so
            # maybe_save fires again promptly and -- critically -- so the
            # guardian's emergency exit re-saves the step it would
            # otherwise believe is already on disk
            self._last_save_step = None
            raise e

    def close(self):
        """Flush the pending async write (errors surface here)."""
        self.wait()

    def maybe_save(self, step: int, train_state: Optional[dict] = None):
        due_steps = (self.save_interval_steps and
                     (self._last_save_step is None or
                      step - self._last_save_step >= self.save_interval_steps))
        due_secs = (self.save_interval_secs and
                    time.time() - self._last_save_t >= self.save_interval_secs)
        if due_steps or due_secs:
            self.save(step, train_state=train_state)

    # -- scanning ------------------------------------------------------------

    def _is_complete(self, d: str) -> bool:
        """True when ``d`` holds a finished save: every rank manifest the
        save promised parses and every chunk file they list is present AT
        ITS RECORDED BYTE SIZE (``io.verify_checkpoint(level="size")`` --
        io.py owns the manifest format, so its verifier is reused rather
        than re-implementing the layout).  A zero-byte or truncated chunk
        -- the torn-write signature of ``fs.replace``'s copy-then-delete
        window on remote stores -- must NOT count as a resume point;
        existence alone proved nothing.  Pre-v2 manifests (no recorded
        sizes) fall back to the existence check so old checkpoints keep
        restoring."""
        from .. import io as _io
        return _io.verify_checkpoint(d, level="size")["ok"]

    def _complete_steps(self):
        """Yield the steps of complete ``ckpt-*`` dirs, newest first.
        Lazy: completeness costs one exists()+stat per chunk file (remote
        round-trips), and the caller usually wants only the newest.
        Quarantined ``ckpt-N.corrupt`` dirs never parse as steps."""
        try:
            names = _fsio.listdir(self.dirname)
        except (OSError, FileNotFoundError):
            return
        steps = set()
        for n in names:
            if n.startswith("ckpt-"):
                try:
                    steps.add(int(n.split("-", 1)[1]))
                except ValueError:
                    continue
        for s in sorted(steps, reverse=True):
            if self._is_complete(self._step_dir(s)):
                yield s

    def latest_step(self) -> int:
        """Step of the newest *complete* checkpoint, or -1.

        The LATEST pointer is the fast path; a missing, torn or corrupt
        LATEST (or one naming an incomplete/deleted/quarantined step dir --
        the remote-store crash window of ``fs.replace``, ADVICE r5)
        degrades to scanning the ``ckpt-*`` dirs for the newest step whose
        manifests and chunk files are all present at their recorded sizes.

        Multi-host: rank 0 decides and broadcasts (mirroring save()'s
        rank0-writes + barrier). Per-rank filesystem probes can race a
        still-propagating save on an object store and disagree -- hosts
        restoring different steps would diverge the SPMD state."""
        import jax
        if jax.process_count() > 1:
            import numpy as np
            from jax.experimental import multihost_utils
            step = self._latest_step_local() if jax.process_index() == 0 \
                else 0
            return int(multihost_utils.broadcast_one_to_all(
                np.int32(step)))
        return self._latest_step_local()

    def _latest_step_local(self) -> int:
        path = _fsio.join(self.dirname, "LATEST")
        step = None
        try:
            if _fsio.exists(path):
                with _fsio.open_file(path) as f:
                    step = int(json.load(f)["step"])
        except (OSError, ValueError, KeyError, TypeError):
            step = None
        if step is not None and self._is_complete(self._step_dir(step)):
            return step
        for s in self._complete_steps():
            return s
        return -1

    # -- restoring -----------------------------------------------------------

    def quarantine(self, step: int, reason: str = "", kind: str = "crc"):
        """Move ``ckpt-<step>`` out of the resume scan's namespace
        (``ckpt-<step>.corrupt``) so ``latest_step()`` falls through to
        the next complete step.  The damaged tree is kept, not deleted --
        it is forensic evidence, and a doctor can still ``verify`` it."""
        src = self._step_dir(step)
        dst = f"{src}.corrupt"
        n = 1
        while _fsio.exists(dst):
            n += 1
            dst = f"{src}.corrupt.{n}"
        try:
            _fsio.move(src, dst)
            moved = True
        except OSError:
            moved = False   # another rank/process won the rename race
        _OBS.counter("checkpoint_quarantine_total",
                     "corrupt checkpoints quarantined").inc()
        _journal.emit({"event": "ckpt_quarantine", "step": step,
                       "kind": kind, "to": dst if moved else None,
                       "reason": reason[:300]})
        return dst if moved else None

    def restore(self, program=None, step: Optional[int] = None) -> int:
        """Load the newest complete checkpoint; returns its step or -1.
        Pass a CompiledProgram to reshard-on-load into a new mesh.

        Every chunk read is checksum-verified against the v2 manifest; a
        corrupt checkpoint is quarantined (renamed ``ckpt-N.corrupt``,
        journaled) and the scan falls through to the next complete step.
        On success the program's rng run counter is rewound to the saved
        value and ``.train_state`` holds the checkpoint's
        ``trainstate.json`` (dataset position for exact resume).

        ``step`` pins an EXACT checkpoint step instead of the newest
        (elastic byte-consistency comparisons, forensic re-runs): a
        missing or corrupt pinned step raises instead of falling through
        -- restoring a different step than asked would silently compare
        apples to oranges."""
        from .. import io
        target = program or self.program
        if step is not None:
            d = self._step_dir(step)
            err = None
            try:
                if not self._is_complete(d):
                    raise FileNotFoundError(
                        f"checkpoint ckpt-{step} at {self.dirname} is "
                        f"missing or incomplete (restore(step={step}) "
                        f"does not fall through)")
                io.load_persistables(self.exe, d, target)
            except (io.CheckpointCorruption, FileNotFoundError,
                    RuntimeError) as e:
                err = e
            # the verdict must be COLLECTIVE like the scanning path's: a
            # rank raising alone while its peers proceed into the next
            # collective would hang the survivors forever
            if self._any_rank_failed(err is not None):
                if err is not None:
                    raise err
                raise io.CheckpointCorruption(
                    f"checkpoint ckpt-{step} failed to restore on "
                    f"another rank (restore(step={step}) does not fall "
                    f"through)", kind="crc", path=d)
            self._apply_trainstate(d, target)
            self._note_world_change(d, target)
            self._last_save_step = step
            self._restored_step = step
            return step
        prev = None
        while True:
            step = self.latest_step()
            if step < 0:
                return -1
            if step == prev:
                # quarantine didn't take (shared store race / permissions):
                # re-raising beats spinning on the same corrupt step
                raise io.CheckpointCorruption(
                    f"checkpoint ckpt-{step} is corrupt and could not be "
                    f"quarantined; remove it from {self.dirname} manually",
                    kind="crc", path=self._step_dir(step))
            prev = step
            d = self._step_dir(step)
            err = None
            try:
                io.load_persistables(self.exe, d, target)
            except io.CheckpointCorruption as e:
                err = e
            # multi-host: the verdict must be COLLECTIVE -- a chunk read
            # by only one rank can be the corrupt one, and a rank looping
            # back into latest_step()'s broadcast alone would hang the job
            # (or ranks would restore different steps and diverge)
            if self._any_rank_failed(err is not None):
                self.quarantine(
                    step, kind=err.kind if err is not None else "crc",
                    reason=str(err) if err is not None
                    else "corrupt on another rank")
                continue
            self._apply_trainstate(d, target)
            self._note_world_change(d, target)
            self._last_save_step = step
            self._restored_step = step
            return step

    def _note_world_change(self, d, target):
        """Elastic resume (ISSUE 11): when the checkpoint's recorded world
        differs from the current one, plan and journal the per-var
        redistribution (``reshard_plan`` + ``elastic_restore`` events).
        Same-world restores skip this entirely -- no planner import, no
        manifest re-read -- and a planning failure never fails the
        restore (the load itself already resharded via io.load_vars)."""
        saved = (self.train_state or {}).get("world")
        if not saved:
            return
        import jax
        cur = {"nranks": jax.process_count(), "ndev": jax.device_count()}
        try:
            same = (int(saved.get("nranks", 0)) == cur["nranks"] and
                    int(saved.get("ndev", 0)) == cur["ndev"])
        except (TypeError, ValueError):
            same = True   # unreadable world record: nothing to compare
        if same:
            return
        from ..resilience import elastic as _elastic
        _elastic.note_world_change(d, saved, cur, program=target)

    def _any_rank_failed(self, failed: bool) -> bool:
        """All-ranks OR of a local verdict (identity single-host).  Every
        rank must call this exactly once per restore attempt -- it is a
        collective under multi-host."""
        import jax
        if jax.process_count() <= 1:
            return failed
        import numpy as np
        from jax.experimental import multihost_utils
        return bool(np.max(multihost_utils.process_allgather(
            np.int32(1 if failed else 0))))

    def _apply_trainstate(self, d, program):
        """Read ``trainstate.json`` (absent on pre-ISSUE-9 checkpoints) and
        rewind the program's rng run counter so the restored run's next
        step uses the exact next rng fold."""
        from .. import io
        self.train_state = None
        path = _fsio.join(d, TRAINSTATE_FILE)
        try:
            if not _fsio.exists(path):
                return
            with _fsio.open_file(path) as f:
                doc = json.load(f)
            counter = doc.get("rng_counter")
        except (OSError, ValueError, KeyError, TypeError) as e:
            import warnings
            warnings.warn(f"unreadable {path}: {type(e).__name__}: {e}; "
                          f"resuming without exact train state", UserWarning)
            return
        self.train_state = doc
        if counter is not None and program is not None:
            prog, _ = io._unwrap_program(program)
            prog._rng_run_counter = int(counter)
