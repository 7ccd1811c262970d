"""Fleet telemetry: cross-rank aggregation + straggler detection.

At multi-chip scale the first diagnostic question is per-rank skew: one
slow host (thermals, a noisy neighbor, a dying NIC, a stuck input
pipeline) drags every collective, and nothing in single-rank telemetry
says WHICH rank.  This module gives every rank a rank/host-labelled view
of its own warm step cadence and lets rank 0 collect the fleet:

- ``PADDLE_TPU_FLEET=gather``  -- every rank contributes a fixed-width
  numeric row through ``process_allgather`` at a step-count cadence
  (``PADDLE_TPU_FLEET_INTERVAL``, default 32 -- ranks run the same SPMD
  step sequence, so the collective lands aligned); rank 0 runs detection.
- ``PADDLE_TPU_FLEET=scrape``  -- no collective: every rank's metrics
  endpoint (``observability.server``, port base + rank) exports the
  per-rank gauges, and rank 0's background scraper thread polls the peer
  ``/metrics`` pages (``export.parse_prometheus`` -- the same parser the
  tests round-trip) every ``PADDLE_TPU_FLEET_PERIOD`` seconds.  Survives
  backends with no multiprocess collectives and keeps detection off the
  step path entirely.

The step-time signal is warm INTER-STEP wall time (perf_counter deltas
between consecutive executor steps, compile steps excluded), not the
dispatch span: a straggling rank loses time *anywhere* in its loop (input
stall, host contention, an injected hang), and inter-arrival catches all
of it while staying meaningful under async dispatch.

Detection: rank r is flagged when its median warm step time exceeds
``median(others) + k * max(MAD(others), rel_floor * median, abs_floor)``
-- leave-one-out, because in a small fleet the straggler pollutes its own
reference (with 2 ranks a global median+MAD can NEVER flag: the outlier
IS half the distribution).  Flags journal ``straggler`` events, increment
``straggler_total{rank}``, and every collection journals a ``fleet`` event
with the per-rank table that ``tools/obs_report --fleet`` renders.

Off by default: with the env unset ``MONITOR`` stays None and the
executor's per-step hook is a single module-attribute read.
"""
from __future__ import annotations

import collections
import os
import socket as _socket
import threading
import time
from statistics import median as _median
from typing import Dict, List, Optional

from .journal import mode_env as _mode_env

MODES = ("off", "gather", "scrape")
DEFAULT_INTERVAL = 32     # steps between gather-mode collections
DEFAULT_PERIOD = 5.0      # seconds between scrape-mode collections
DEFAULT_K = 4.0           # MAD multiplier
REL_FLOOR = 0.10          # MAD floor as a fraction of the reference median
ABS_FLOOR_MS = 1.0        # MAD floor in milliseconds (host-jitter scale)
MIN_SAMPLES = 4           # a rank needs this many warm intervals to judge
WINDOW = 64               # rolling warm-interval window per rank

#: the armed monitor, or None.  The executor hot path reads exactly this
#: attribute; everything else happens only when a mode is armed.
MONITOR: Optional["FleetMonitor"] = None

_arm_lock = threading.Lock()


def mode() -> str:
    """``PADDLE_TPU_FLEET`` parsed with the shared toggle spellings
    (1/true -> gather, 0/empty/unset -> off; typos raise)."""
    return _mode_env("PADDLE_TPU_FLEET", MODES, truthy="gather")


def maybe_arm() -> Optional["FleetMonitor"]:
    """Executor-construction hook: arm the process-wide monitor when the
    env asks for a mode.  One env read when off; idempotent."""
    global MONITOR
    if MONITOR is not None:
        return MONITOR
    m = mode()
    if m == "off":
        return None
    with _arm_lock:
        if MONITOR is None:
            MONITOR = FleetMonitor(m)
    return MONITOR


def disarm():
    """Tear the monitor down (tests)."""
    global MONITOR
    with _arm_lock:
        mon, MONITOR = MONITOR, None
    if mon is not None:
        mon.close()


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not a number")


def detect_stragglers(rows: List[dict], k: float = DEFAULT_K,
                      rel_floor: float = REL_FLOOR,
                      abs_floor_ms: float = ABS_FLOOR_MS,
                      min_samples: int = MIN_SAMPLES) -> List[dict]:
    """Flag straggling rows (each ``{"rank", "step_ms", "n", ...}``).

    Leave-one-out median + k*MAD over the OTHER ranks' medians, with the
    anomaly detector's floor discipline (a quiet fleet's MAD ~ 0 must not
    flag microseconds of skew).  Returns the flagged rows, each annotated
    with the reference ``median_ms`` / ``mad_ms`` / ``limit_ms``.
    """
    eligible = [r for r in rows
                if r.get("step_ms") is not None
                and int(r.get("n") or 0) >= min_samples]
    if len(eligible) < 2:
        return []
    flagged = []
    for r in eligible:
        others = [float(o["step_ms"]) for o in eligible if o is not r]
        med = _median(others)
        mad = _median([abs(v - med) for v in others])
        limit = med + k * max(mad, rel_floor * med, abs_floor_ms)
        if float(r["step_ms"]) > limit:
            out = dict(r)
            out.update({"median_ms": round(med, 3), "mad_ms": round(mad, 3),
                        "limit_ms": round(limit, 3)})
            flagged.append(out)
    return flagged


def _rank_world():
    from ..parallel import env as _penv
    try:
        return _penv.get_rank(), _penv.get_world_size()
    except Exception:
        return 0, 1


class FleetMonitor:
    """Per-process fleet telemetry: warm inter-step cadence + collection.

    ``on_step`` is the only hot-path entry (deque append + a few compares);
    a collection -- the gather collective or a journal/export round --
    happens every ``interval`` steps (gather mode) or on the rank-0
    scraper thread's clock (scrape mode).
    """

    def __init__(self, fleet_mode: str = "gather",
                 interval: Optional[int] = None,
                 period: Optional[float] = None,
                 k: Optional[float] = None, window: int = WINDOW):
        self.mode = fleet_mode
        self.interval = int(interval if interval is not None else
                            _env_float("PADDLE_TPU_FLEET_INTERVAL",
                                       DEFAULT_INTERVAL))
        if self.interval <= 0:
            raise ValueError(f"fleet interval must be positive, got "
                             f"{self.interval}")
        self.period = float(period if period is not None else
                            _env_float("PADDLE_TPU_FLEET_PERIOD",
                                       DEFAULT_PERIOD))
        self.k = float(k if k is not None else
                       _env_float("PADDLE_TPU_FLEET_K", DEFAULT_K))
        self.rank, self.world = _rank_world()
        self.host = _socket.gethostname()
        self.restarts = int(os.environ.get("PADDLE_RESTART_ATTEMPT", "0")
                            or 0)
        self._lock = threading.Lock()
        self._times: "collections.deque" = collections.deque(maxlen=window)
        self._last_t: Optional[float] = None
        self._last_warm = False
        self._steps = 0
        self._last_boundary = 0
        self._stop = threading.Event()
        self._warned: set = set()
        self._scraper: Optional[threading.Thread] = None
        if self.mode == "scrape" and self.rank == 0:
            if self.world > 1 and not self.peer_endpoints():
                # an armed-but-inert mode must never be silent (PR-3/PR-6
                # rule): without peers, detection only ever sees one rank
                self._warn_once(
                    "peers",
                    "PADDLE_TPU_FLEET=scrape armed but no peer endpoints "
                    "can be derived -- set PADDLE_TPU_OBS_PORT (+ the "
                    "launcher's PADDLE_TRAINER_ENDPOINTS) or "
                    "PADDLE_TPU_FLEET_PEERS, or straggler detection will "
                    "only ever see this rank")
            self._scraper = threading.Thread(
                target=self._scrape_loop, name="paddle-tpu-fleet-scraper",
                daemon=True)
            self._scraper.start()

    def _warn_once(self, key: str, msg: str):
        with self._lock:
            if key in self._warned:
                return
            self._warned.add(key)
        import warnings
        warnings.warn(f"paddle_tpu fleet telemetry: {msg}")

    # ------------------------------------------------------------- hot path
    def on_step(self, warm: bool = True, step: Optional[int] = None):
        """One executor step finished.

        The gather cadence keys on ``step`` -- the program's rng-run
        counter, NOT a raw local call count: the resilience guardian
        rewinds that counter per retry/rollback attempt, so a rank that
        retried a transient failure lands on the same step numbers as its
        peers and the collective stays aligned.  Boundaries fire at most
        once (monotone ``_last_boundary``), so a re-run of an
        already-collected step never issues a second lone allgather."""
        t = time.perf_counter()
        gather_now = False
        with self._lock:
            if self._last_t is not None and warm and self._last_warm:
                self._times.append(t - self._last_t)
            self._last_t = t
            self._last_warm = warm
            self._steps += 1
            done = self._steps if step is None else step + 1
            if self.mode == "gather":
                boundary = done // self.interval
                if boundary > self._last_boundary:
                    self._last_boundary = boundary
                    gather_now = True
        if gather_now:
            try:
                self.collect()
            except Exception as e:
                # telemetry never kills the training step (the scrape loop
                # enforces the same policy); a failing collective here is a
                # symptom the run's own collectives will surface loudly
                self._warn_once("collect",
                                f"fleet collection failed ({e}); straggler "
                                f"detection degraded for this process")

    # ----------------------------------------------------------- snapshots
    def snapshot(self) -> dict:
        """This rank's row: median/MAD warm step ms over the window."""
        with self._lock:
            vals = sorted(self._times)
            steps = self._steps
        row = {"rank": self.rank, "host": self.host, "step_ms": None,
               "mad_ms": None, "n": len(vals), "steps": steps,
               "restarts": self.restarts}
        if vals:
            med = _median(vals)
            row["step_ms"] = round(med * 1e3, 3)
            row["mad_ms"] = round(
                _median([abs(v - med) for v in vals]) * 1e3, 3)
        return row

    def export_local(self):
        """Publish this rank's row as rank/host-labelled gauges (what a
        peer scrape -- or any Prometheus -- reads off ``/metrics``)."""
        from .metrics import REGISTRY
        row = self.snapshot()
        labels = {"rank": str(row["rank"]), "host": row["host"]}
        if row["step_ms"] is not None:
            REGISTRY.gauge("fleet_step_time_ms",
                           "median warm inter-step wall time per rank",
                           **labels).set(row["step_ms"])
            REGISTRY.gauge("fleet_step_time_mad_ms",
                           "MAD of warm inter-step wall time per rank",
                           **labels).set(row["mad_ms"])
        REGISTRY.gauge("fleet_warm_samples",
                       "warm inter-step samples in the rank's window",
                       **labels).set(row["n"])
        REGISTRY.gauge("fleet_steps", "executor steps run by the rank",
                       **labels).set(row["steps"])
        REGISTRY.gauge("fleet_restarts",
                       "elastic restart attempts this rank resumed from",
                       **labels).set(row["restarts"])
        return row

    # ---------------------------------------------------------- collection
    def collect(self, rows: Optional[List[dict]] = None,
                transport: Optional[str] = None) -> List[dict]:
        """One collection round: assemble per-rank rows (gather collective /
        given), then -- on rank 0 -- detect, journal and count stragglers.
        Returns the rows."""
        self.export_local()
        if rows is None:
            if self.mode == "gather" and self.world > 1:
                rows = self._gather_rows()
                transport = transport or "gather"
            else:
                rows = [self.snapshot()]
                transport = transport or "local"
        if self.rank == 0 and rows:
            self._note_fleet(rows, transport or "local")
        return rows

    def _gather_rows(self) -> List[dict]:
        """All ranks' rows via one ``process_allgather`` of a fixed-width
        float row (hostnames don't cross the collective; rank 0's table
        names peers by rank, scrape mode carries hosts)."""
        import numpy as np
        import jax
        if jax.process_count() <= 1:
            # env declares a world the runtime never joined
            # (init_parallel_env not called / coordinator down): armed but
            # inert must never be silent, and a 1-process allgather would
            # masquerade as a healthy 1-rank fleet
            self._warn_once(
                "uninitialized",
                f"PADDLE_TPU_FLEET=gather armed with world={self.world} "
                f"but jax.distributed is not initialized "
                f"(init_parallel_env never ran?); collecting only this "
                f"rank -- straggler detection cannot fire")
            return [self.snapshot()]
        from jax.experimental import multihost_utils
        row = self.snapshot()
        vec = np.array([float(self.rank),
                        -1.0 if row["step_ms"] is None else row["step_ms"],
                        -1.0 if row["mad_ms"] is None else row["mad_ms"],
                        float(row["n"]), float(row["steps"]),
                        float(row["restarts"])], np.float64)
        mat = np.asarray(multihost_utils.process_allgather(vec))
        mat = mat.reshape(-1, vec.size)
        rows = []
        for r in mat:
            rows.append({"rank": int(r[0]), "host": self.host
                         if int(r[0]) == self.rank else f"rank{int(r[0])}",
                         "step_ms": None if r[1] < 0 else round(float(r[1]), 3),
                         "mad_ms": None if r[2] < 0 else round(float(r[2]), 3),
                         "n": int(r[3]), "steps": int(r[4]),
                         "restarts": int(r[5])})
        rows.sort(key=lambda d: d["rank"])
        return rows

    def _note_fleet(self, rows: List[dict], transport: str):
        from . import journal as _journal
        from .metrics import REGISTRY
        flagged = detect_stragglers(rows, k=self.k)
        meds = [r["step_ms"] for r in rows if r.get("step_ms") is not None]
        ev = {"event": "fleet", "transport": transport,
              "n_ranks": len(rows), "ranks": rows,
              "stragglers": [f["rank"] for f in flagged]}
        if meds:
            ev["median_ms"] = round(_median(meds), 3)
            ev["skew"] = (round(max(meds) / min(meds), 3)
                          if min(meds) > 0 else None)
        _journal.emit(ev)
        for f in flagged:
            REGISTRY.counter(
                "straggler_total",
                "straggler verdicts per rank (median + k*MAD exceeded)",
                rank=str(f["rank"])).inc()
            _journal.emit({"event": "straggler", "rank": f["rank"],
                           "host": f.get("host"),
                           "step_ms": f["step_ms"],
                           "median_ms": f["median_ms"],
                           "mad_ms": f["mad_ms"],
                           "limit_ms": f["limit_ms"],
                           "n_ranks": len(rows)})

    # ------------------------------------------------------------- scraping
    def peer_endpoints(self) -> List[str]:
        """Peer ``/metrics`` URLs: ``PADDLE_TPU_FLEET_PEERS`` (comma list of
        host:port) or derived from the launcher contract -- each rank r of
        ``PADDLE_TRAINER_ENDPOINTS`` serves on its host at obs base + r."""
        raw = os.environ.get("PADDLE_TPU_FLEET_PEERS")
        if raw:
            return [f"http://{p.strip()}/metrics"
                    for p in raw.split(",") if p.strip()]
        base = os.environ.get("PADDLE_TPU_OBS_PORT")
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        if not base or not eps:
            return []
        try:
            base = int(base)
        except ValueError:
            return []
        out = []
        for r, ep in enumerate(eps.split(",")):
            if r == self.rank or not ep.strip():
                continue
            host = ep.strip().rsplit(":", 1)[0]
            out.append(f"http://{host}:{base + r}/metrics")
        return out

    def scrape_peers(self, urls: Optional[List[str]] = None,
                     timeout: float = 1.0) -> List[dict]:
        """Rank 0's pull path: fetch each peer's ``/metrics``, parse with
        ``export.parse_prometheus``, and lift the fleet_* gauges back into
        rows.  Unreachable peers are skipped (a dead rank must not kill
        the monitor -- its absence IS the signal, visible as a missing
        row in the fleet table)."""
        import urllib.request
        from .export import parse_prometheus
        rows = []
        for url in (urls if urls is not None else self.peer_endpoints()):
            try:
                with urllib.request.urlopen(url, timeout=timeout) as resp:
                    text = resp.read().decode("utf-8", errors="replace")
            except Exception:
                continue
            rows.extend(_rows_from_samples(parse_prometheus(text)))
        return rows

    def _scrape_loop(self):
        while not self._stop.wait(self.period):
            try:
                # drop any scraped copy of our own row (an explicit
                # PADDLE_TPU_FLEET_PEERS list naturally includes rank 0's
                # endpoint; a duplicated row would bias every other rank's
                # leave-one-out reference and overcount n_ranks)
                rows = [self.snapshot()] + [
                    r for r in self.scrape_peers()
                    if r.get("rank") != self.rank]
                rows.sort(key=lambda d: (d.get("rank") is None,
                                         d.get("rank")))
                self.collect(rows=rows, transport="scrape")
            except Exception:
                pass   # telemetry never kills the process

    def close(self):
        self._stop.set()
        if self._scraper is not None:
            self._scraper.join(timeout=self.period + 2)


def _rows_from_samples(samples: Dict) -> List[dict]:
    """parse_prometheus output -> per-(rank, host) fleet rows."""
    by_rank: Dict[tuple, dict] = {}
    fields = {"fleet_step_time_ms": "step_ms",
              "fleet_step_time_mad_ms": "mad_ms",
              "fleet_warm_samples": "n", "fleet_steps": "steps",
              "fleet_restarts": "restarts"}
    for (name, labels), value in samples.items():
        field = fields.get(name)
        if field is None:
            continue
        ld = dict(labels)
        if "rank" not in ld:
            continue
        key = (ld["rank"], ld.get("host", "?"))
        row = by_rank.setdefault(
            key, {"rank": int(ld["rank"]), "host": ld.get("host", "?"),
                  "step_ms": None, "mad_ms": None, "n": 0, "steps": 0,
                  "restarts": 0})
        if field in ("n", "steps", "restarts"):
            row[field] = int(value)
        else:
            row[field] = round(float(value), 3)
    return [by_rank[k] for k in sorted(by_rank)]
