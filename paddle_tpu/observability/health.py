"""Tensor-health watchdog: one compiled any-nonfinite scan per step.

``PADDLE_TPU_OBS_HEALTH=off|warn|raise`` (default off; 0/1 toggle
spellings are accepted too, ``1`` meaning warn) arms a NaN/Inf scan
over everything a step hands back to the host -- fetched outputs/losses
and, with ``PADDLE_TPU_OBS_HEALTH_STATE=1``, the written state (parameters,
optimizer moments, BN stats).  Unlike ``FLAGS_check_nan_inf`` (which pulls
every state var to the host as numpy and checks there), the scan compiles
to a single device program producing one packed bool vector -- one small
device->host transfer per step regardless of how many tensors are watched,
no per-tensor sync.  The first offending tensor is attributed by program id
+ variable name into the run journal (``tensor_nonfinite`` event) and the
``tensor_nonfinite_total`` counter; ``warn`` warns and continues, ``raise``
raises ``FloatingPointError``.

With the mode off (the default) nothing runs: no extra device work, no
sync, no host scan.
"""
from __future__ import annotations

import threading
import warnings
from typing import List, Optional, Sequence, Tuple

from .journal import env_truthy as _env_truthy
from .journal import mode_env as _mode_env

MODES = ("off", "warn", "raise")

# unconsumed nonfinite verdicts keyed by program label, stashed by check()
# for the resilience StepGuardian: the guardian consumes the watchdog's
# per-step finding (take_verdict) instead of paying a second scan, and
# gets the state-var attribution its fetch-only scan could not see.
# Per-program keying means concurrent guardians can neither steal nor
# overwrite each other's findings; the dict is bounded (oldest evicted) so
# verdicts nobody consumes cannot grow it.
_verdict_lock = threading.Lock()
_verdicts: dict = {}
_VERDICT_CAP = 16


def take_verdict(program=None):
    """Return-and-clear the stashed nonfinite verdict
    (``{"program", "where", "vars"}``) for ``program`` (a program label),
    or the most recent one when ``program`` is None.  Returns None when
    there is nothing unconsumed for that program; other programs' verdicts
    are left in place."""
    with _verdict_lock:
        if program is None:
            if not _verdicts:
                return None
            program = next(reversed(_verdicts))
        return _verdicts.pop(program, None)


def _stash_verdict(program, where, bad):
    with _verdict_lock:
        _verdicts.pop(program, None)   # re-insert = most recent
        _verdicts[program] = {"program": program, "where": where,
                              "vars": list(bad)}
        while len(_verdicts) > _VERDICT_CAP:
            _verdicts.pop(next(iter(_verdicts)))
# every sibling env var is a 0/1 toggle (PADDLE_TPU_OBS=1, ..._STATE=1), so
# accept the same spellings here instead of aborting the first Executor.run
# of a user who wrote PADDLE_TPU_OBS_HEALTH=1: truthy -> warn, falsy -> off
def mode() -> str:
    return _mode_env("PADDLE_TPU_OBS_HEALTH", MODES)


def include_state() -> bool:
    return _env_truthy("PADDLE_TPU_OBS_HEALTH_STATE")


def _any_nonfinite(xs):
    """tuple of float arrays -> bool vector, one lane per input.

    jit caches per (len, shapes, dtypes) signature, so a training loop pays
    one compile on the first checked step and a cached dispatch after.
    """
    import jax.numpy as jnp
    return jnp.stack([jnp.logical_not(jnp.all(jnp.isfinite(x))) for x in xs])


_jitted = None


def _scan_fn():
    global _jitted
    if _jitted is None:
        import jax
        _jitted = jax.jit(_any_nonfinite)
    return _jitted


def nonfinite_names(named: Sequence[Tuple[str, object]]) -> List[str]:
    """Names of the non-finite tensors among ``named`` [(name, jax array)].

    Non-float entries (int labels, bool masks) are skipped; the float ones
    go through the single compiled reduction.  Empty watch list -> [].
    """
    import numpy as np

    import jax.numpy as jnp

    watch = []
    for name, v in named:
        dt = getattr(v, "dtype", None)
        # jnp.issubdtype, not np: bf16/fp8 are ml_dtypes extension types
        # numpy's lattice calls non-inexact (a bf16 loss -- the bench
        # default dtype -- would silently escape the scan)
        if dt is not None and jnp.issubdtype(np.dtype(dt), jnp.inexact):
            watch.append((name, v))
    if not watch:
        return []
    if all(isinstance(v, np.ndarray) for _, v in watch):
        # already on host (e.g. Predictor outputs after the d2h sync): a
        # plain numpy check beats a device round-trip
        return [n for n, v in watch if not np.isfinite(v).all()]
    flags = np.asarray(_scan_fn()(tuple(v for _, v in watch)))
    return [watch[i][0] for i in np.flatnonzero(flags)]


def check(named: Sequence[Tuple[str, object]], program: str,
          where: str = "executor", health_mode: Optional[str] = None) -> List[str]:
    """Scan ``named`` tensors; attribute, count, journal, warn/raise.

    Returns the offending names (empty when healthy or mode is off).  The
    caller gates on ``mode() != 'off'`` so the off path costs nothing; the
    ``health_mode`` arg lets it pass the already-read mode down.
    """
    m = health_mode if health_mode is not None else mode()
    if m == "off":
        return []
    bad = nonfinite_names(named)
    if not bad:
        return []
    _stash_verdict(program, where, bad[:8])
    from . import journal as _journal
    from .metrics import REGISTRY
    REGISTRY.counter("tensor_nonfinite_total",
                     "tensors found NaN/Inf by the health watchdog",
                     where=where).inc(len(bad))
    _journal.emit({"event": "tensor_nonfinite", "program": program,
                   "where": where, "var": bad[0], "vars": bad[:8]})
    msg = (f"NaN/Inf detected in {where} output {bad[0]!r} "
           f"(program {program}; {len(bad)} tensor(s) affected: {bad[:8]})")
    if m == "raise":
        raise FloatingPointError(msg)
    warnings.warn(msg)
    return bad
