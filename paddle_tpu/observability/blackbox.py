"""Post-mortem black box: crash-time forensics in one atomic bundle.

When a run dies -- StepGuardian out of retries, a ``StepTimeout``, a
nonfinite tensor under ``policy=raise``, a preemption emergency save, a
serving drain-deadline expiry, a worker respawn storm -- the evidence
normally dies with the process.  Armed, the terminal paths call
:func:`maybe_write` which snapshots everything the observability stack
already holds into ``<dir>/postmortem-<ts>/bundle.json``:

- the journal ring tail (every typed event up to the failure),
- the timeline span tail + counters,
- a full metrics dump (includes the device-memory gauges),
- active + recently-resolved SLO alerts,
- per-executor compile keys and the last compile's feed shapes,
- per-program HLO attribution, when attribution is armed.

Arming: ``PADDLE_TPU_OBS_BLACKBOX=<dir>`` (a truthy ``1`` spells the
default ``./postmortems``).  Disarmed, every hook is ONE ``os.environ``
read -- no file opens on any path (guard-tested).  The bundle is written
tmp-then-rename so a crash mid-write never leaves a torn ``bundle.json``,
and writing NEVER raises: forensics must not mask the failure it is
documenting.  ``tools/postmortem.py`` triages a bundle offline.
"""
from __future__ import annotations

import json
import os
import threading
import time
import warnings
from typing import Optional

from . import export as _export
from . import journal as _journal
from . import timeline as _timeline

BLACKBOX_ENV = "PADDLE_TPU_OBS_BLACKBOX"
DEFAULT_DIR = "postmortems"
FORMAT = "paddle_tpu_postmortem_v1"

#: timeline spans kept in a bundle (newest-last)
SPAN_TAIL = 2048
#: bundles one process may write -- a respawn storm or a retry loop must
#: not fill the disk with near-identical forensics
MAX_BUNDLES = 8

_lock = threading.Lock()
_written = 0
_warned = set()


def _warn_once(key, msg: str):
    with _lock:
        if key in _warned:
            return
        _warned.add(key)
    warnings.warn(f"paddle_tpu blackbox: {msg}")


def armed_dir() -> Optional[str]:
    """The bundle base directory, or None when disarmed (one env read)."""
    raw = os.environ.get(BLACKBOX_ENV)
    if raw is None:
        return None
    raw = raw.strip()
    if raw.lower() in _journal.FALSY:
        return None
    if raw.lower() in _journal.TRUTHY:
        return DEFAULT_DIR
    return raw


def _executor_snapshots() -> list:
    from ..core.executor import Executor
    return [e.debug_snapshot() for e in list(Executor._instances)]


def _attribution_snapshots() -> list:
    from . import attribution as _attrib
    if not _attrib.attribution_enabled():
        return []
    out = []
    for (_pid, _ver), (_ref, attrib) in list(_attrib._IR_STORE.items()):
        out.append({
            "program": attrib.label,
            "coverage": attrib.coverage,
            "total_bytes": attrib.total_bytes,
            "model_flops": attrib.model_flops,
            "per_category": {k: dict(v)
                             for k, v in attrib.per_category.items()},
            "top_ops": [{"ir": ir, **info}
                        for ir, info in attrib.top_ops(10)],
        })
    return out


def snapshot(reason: str, error: Optional[BaseException] = None,
             extra: Optional[dict] = None) -> dict:
    """Assemble the bundle document (pure in-memory; no file I/O).
    Every section degrades independently -- a broken provider becomes an
    ``"<section>_error"`` note, never a lost bundle."""
    doc = {
        "format": FORMAT,
        "reason": reason,
        "ts": time.time(),
        "pid": os.getpid(),
        "extra": dict(extra or {}),
    }
    r = _journal.current_rank()
    if r is not None:
        doc["rank"] = r
    if error is not None:
        doc["error"] = {"type": type(error).__name__,
                        "message": str(error)[:2000]}
    for section, build in (
            ("journal", lambda: _journal.recent()),
            ("timeline", lambda: {
                "spans": [{"name": n, "cat": c, "t0": t0, "dur": dur,
                           "args": args, "tid": tid}
                          for (n, c, t0, dur, args, tid)
                          in (s[:6] for s in
                              _timeline.spans()[-SPAN_TAIL:])],
                "counters": _timeline.counters()}),
            ("metrics", _export.to_dict),
            ("alerts", _alerts_doc),
            ("executors", _executor_snapshots),
            ("attribution", _attribution_snapshots)):
        try:
            doc[section] = build()
        except Exception as e:
            doc[section + "_error"] = repr(e)
    return doc


def _alerts_doc() -> dict:
    from . import slo as _slo
    return _slo.alerts_doc()


def write_bundle(reason: str, error: Optional[BaseException] = None,
                 extra: Optional[dict] = None,
                 base_dir: Optional[str] = None) -> Optional[str]:
    """Write one ``postmortem-<ts>/bundle.json`` atomically; returns the
    bundle directory, or None (disarmed, capped, or write failure --
    never an exception: forensics must not mask the real error)."""
    global _written
    try:
        base = base_dir if base_dir is not None else armed_dir()
        if base is None:
            return None
        with _lock:
            if _written >= MAX_BUNDLES:
                return None
            _written += 1
        doc = snapshot(reason, error=error, extra=extra)
        stamp = time.strftime("%Y%m%d-%H%M%S", time.gmtime(doc["ts"]))
        bdir = os.path.join(base, f"postmortem-{stamp}-p{os.getpid()}")
        n = 1
        while os.path.exists(bdir):     # same-second failure in one process
            bdir = os.path.join(
                base, f"postmortem-{stamp}-p{os.getpid()}-{n}")
            n += 1
        os.makedirs(bdir, exist_ok=True)
        tmp = os.path.join(bdir, ".bundle.json.tmp")
        with open(tmp, "w") as f:
            json.dump(doc, f, sort_keys=True, default=str)
        path = os.path.join(bdir, "bundle.json")
        os.replace(tmp, path)
        from .metrics import REGISTRY
        REGISTRY.counter("postmortem_bundles_total",
                         "post-mortem bundles written, by trigger",
                         reason=reason).inc()
        _journal.emit({"event": "postmortem", "reason": reason,
                       "path": path})
        return bdir
    except Exception as e:
        _warn_once(reason, f"bundle write failed for {reason!r}: {e}")
        return None


#: the terminal-path hook spelling: one env read when disarmed
maybe_write = write_bundle


def reset(written_cap: Optional[int] = None):
    """Reset the per-process bundle budget (tests)."""
    global _written, MAX_BUNDLES
    with _lock:
        _written = 0
        _warned.clear()
        if written_cap is not None:
            MAX_BUNDLES = written_cap
