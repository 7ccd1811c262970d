"""Multi-process launcher (reference python/paddle/distributed/launch.py:147).

Spawns one training process per host-slot with the env-var contract that
parallel/env.py reads (COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID, plus
the reference-compatible PADDLE_TRAINER_* names). On a real TPU pod each host
runs one process (the TPU runtime owns all local chips); this launcher exists
for localhost simulation and CPU-mesh testing::

    JAX_PLATFORMS=cpu python -m paddle_tpu.parallel.launch --nproc 2 \
        train.py --lr 0.1

Nothing here partitions a host's chips between its ranks: on a TPU host,
N > 1 local ranks all open the same chips and the second one dies at
start-up (libtpu's lockfile error, ~15 s on a v5e, PR 21); the monitor then
stops the rest and says why.
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch(nproc: int, script_argv, coordinator: str = None,
           devices_per_proc: int = None, log_dir: str = None,
           poll_interval: float = 0.5, max_restarts: int = 0,
           restart_backoff: float = 1.0, restart_backoff_max: float = 30.0,
           elastic: bool = False, min_ranks: int = None,
           healthy_reset_secs: float = 600.0, controller=None,
           max_preempt_restarts: int = 1000):
    """Spawn ``nproc`` copies of ``script_argv``; returns exit codes.

    Failure handling (reference heart_beat_monitor.h:38 analog for the
    launcher): ranks are monitored while running -- when one dies with a
    nonzero code, the survivors (which would otherwise hang in the next
    collective forever) are terminated and the dead rank's log tail is
    printed with its rank id.

    ``max_restarts`` > 0 is the elastic-recovery mode (SCOPE.md 5.3: jax
    cannot resize a live mesh, so elasticity = fast restart): after a
    failed attempt the WHOLE job is relaunched with
    ``PADDLE_RESTART_ATTEMPT`` incremented; training scripts resume from
    their latest checkpoint (``utils.Checkpointer.restore()``, which loads
    ``latest_step()``). An EXPLICIT ``coordinator`` address is kept
    verbatim across restarts (external peers agreed on it); the default
    localhost endpoints are refreshed to dodge TIME_WAIT.

    Two restart refinements (ISSUE 11):

    - an attempt whose only non-zero exits are
      ``resilience.PREEMPTED_EXIT`` (a rank left via the resumable
      ``Preempted`` path) is a CLEAN elastic event: it relaunches without
      consuming the restart budget and without growing the backoff.
      ``max_preempt_restarts`` bounds the total clean restarts (a
      workload preempted every few seconds forever must eventually hand
      the exit codes back instead of looping);
    - the backoff attempt counter resets after ``healthy_reset_secs`` of
      attempt uptime, so a failure late in a long run pays the base
      delay, not the 30 s cap it would have inherited from incidents
      hours ago.

    ``elastic=True`` arms world-size-changing recovery: after a failed
    attempt a shrink-vs-wait policy (``controller``, default
    :class:`resilience.elastic.ElasticController` consuming the goodput
    ledger and straggler verdicts) may relaunch the SURVIVING ranks at a
    smaller world size (never below ``min_ranks``) with a re-derived
    ``PADDLE_TRAINER_ENDPOINTS``/rank map, or grow back toward the
    nominal ``nproc`` on a later restart.  Ranks read their current
    world from ``PADDLE_TRAINERS_NUM`` as always; the nominal size rides
    along as ``PADDLE_NOMINAL_TRAINERS_NUM``.  Resizes journal
    ``elastic_decision`` events and move the ``elastic_world_size``
    gauge / ``elastic_resizes_total{direction}`` counter.

    Each rank gets a DISTINCT endpoint (endpoints[0] is the coordinator),
    matching the reference's launcher contract where user code indexes
    PADDLE_TRAINER_ENDPOINTS[rank].
    """
    if max_restarts < 0:
        raise ValueError(f"max_restarts must be >= 0, got {max_restarts}")
    import random
    import time

    from ..resilience.elastic import PREEMPTED_EXIT

    if elastic and controller is None:
        from ..resilience.elastic import ElasticController
        # one "healthy interval" for both consumers: the backoff ladder
        # reset here and the controller's transient/grow classification
        controller = ElasticController(nproc, min_ranks=min_ranks or 1,
                                       healthy_secs=healthy_reset_secs)

    # Restart DOWNTIME (kill -> respawned job) is measured, not just
    # counted: the goodput ledger needs elastic-restart seconds as a named
    # loss cause.  t0 is stamped when a failed attempt's ranks are all
    # reaped; the clock stops when the NEXT attempt's ranks are all
    # spawned (the ranks' own re-init/compile shows up in their journals
    # as compile time, attributed separately).
    down = {"t0": None, "attempt": 0}

    def _respawned():
        if down["t0"] is None:
            return
        downtime = time.perf_counter() - down["t0"]
        down["t0"] = None
        from ..observability import journal as _journal
        from ..observability.metrics import REGISTRY as _OBS
        _OBS.counter("lost_seconds_total",
                     "goodput ledger: wall-clock seconds lost, by cause",
                     cause="elastic_restart").inc(downtime)
        _journal.emit({"event": "elastic_restart_downtime",
                       "attempt": down["attempt"],
                       "downtime_s": round(downtime, 3)})

    cur = nproc
    budget_used = 0       # real failures only; clean preempt exits are free
    clean_used = 0        # bounded separately by max_preempt_restarts
    backoff_attempt = 0   # resets on clean events / healthy intervals
    attempt = 0           # monotone, exported as PADDLE_RESTART_ATTEMPT
    while True:
        if elastic:
            from ..observability.metrics import REGISTRY as _OBS
            _OBS.gauge("elastic_world_size",
                       "current world size of the elastic launch").set(cur)
        t_attempt = time.perf_counter()
        codes, terminated = _launch_once(
            cur, script_argv, coordinator, devices_per_proc, log_dir,
            poll_interval, attempt, spawned_cb=_respawned,
            nominal_nproc=nproc if elastic else None)
        runtime = time.perf_counter() - t_attempt
        if all(c == 0 for c in codes):
            if controller is not None:
                controller.note_success()
            return codes
        # A rank that exited through the resumable Preempted path
        # (PREEMPTED_EXIT) asked for a relaunch, it didn't fail; ranks
        # the MONITOR terminated are collateral of whoever died first.
        # The attempt is clean when nothing else went wrong.
        bad = [r for r, c in enumerate(codes) if c != 0]
        culprits = [r for r in bad
                    if codes[r] is not None and codes[r] != PREEMPTED_EXIT
                    and r not in terminated]
        clean = not culprits and any(codes[r] == PREEMPTED_EXIT
                                     for r in bad)
        if not clean:
            budget_used += 1
            if budget_used > max_restarts:
                return codes
        else:
            clean_used += 1
            if max_restarts <= 0 and not elastic:
                # restarts never enabled: keep the historical contract
                # and hand the codes back instead of resuming forever
                return codes
            if clean_used > max_preempt_restarts:
                sys.stderr.write(
                    f"[paddle_tpu.launch] {clean_used - 1} clean preempt "
                    f"restarts exhausted max_preempt_restarts; giving "
                    f"the exit codes back\n")
                return codes
        # Backoff bookkeeping: clean events and attempts that ran healthy
        # for a while restart the ladder at the base delay -- a failure
        # late in a long run must not start at the cap.
        if clean or runtime >= healthy_reset_secs:
            backoff_attempt = 0
        backoff_attempt += 1
        culprit = next(
            (r for r in culprits if codes[r] is not None and codes[r] > 0),
            culprits[0] if culprits else (bad[0] if bad else None))
        from ..resilience.recovery import backoff_delay
        delay = backoff_delay(backoff_attempt, restart_backoff,
                              restart_backoff_max, random)
        from ..observability import journal as _journal
        from ..observability.metrics import REGISTRY as _OBS
        _OBS.counter("elastic_restarts_total",
                     "whole-job elastic restarts by the launcher").inc()
        _journal.emit({"event": "elastic_restart", "attempt": attempt + 1,
                       "max_restarts": max_restarts,
                       "budget_used": budget_used, "clean": clean,
                       "failed_rank": culprit,
                       "exit_codes": list(codes),
                       "backoff_s": round(delay, 3)})
        nxt = cur
        if controller is not None:
            decision = controller.decide(cur, codes, runtime,
                                         culprits=culprits, clean=clean)
            # the floor binds whatever controller produced the target --
            # a custom policy must not shrink below the documented
            # min_ranks contract
            nxt = max(min_ranks or 1, min(nproc,
                                          int(decision.target_nproc)))
            if nxt != cur:
                direction = "shrink" if nxt < cur else "grow"
                _OBS.counter("elastic_resizes_total",
                             "elastic world-size changes by direction",
                             direction=direction).inc()
                sys.stderr.write(
                    f"[paddle_tpu.launch] elastic {direction}: "
                    f"{cur} -> {nxt} ranks ({decision.reason})\n")
        sys.stderr.write(
            f"[paddle_tpu.launch] attempt {attempt} "
            f"{'preempted (clean)' if clean else 'failed'} (rank "
            f"{culprit if culprit is not None else '?'}); restarting the "
            f"job from the latest checkpoint in {delay:.1f}s at "
            f"{nxt} rank(s) ({budget_used}/{max_restarts} restarts "
            f"used)\n")
        cur = nxt
        down["t0"] = time.perf_counter()
        down["attempt"] = attempt + 1
        time.sleep(delay)
        attempt += 1


def _launch_once(nproc, script_argv, coordinator, devices_per_proc, log_dir,
                 poll_interval, attempt, spawned_cb=None,
                 nominal_nproc=None):
    """One attempt at ``nproc`` ranks.  Returns ``(codes, terminated)``
    where ``terminated`` is the set of ranks the MONITOR killed (collateral
    of another rank's death -- the restart loop must not blame them)."""
    import time
    if coordinator:
        host, port0 = coordinator.rsplit(":", 1)
        eps = [coordinator] + [f"{host}:{_free_port()}"
                               for _ in range(nproc - 1)]
    else:
        eps = [f"127.0.0.1:{_free_port()}" for _ in range(nproc)]
    coordinator = eps[0]
    endpoints = ",".join(eps)
    log_dir = log_dir or os.path.join(os.getcwd(), "launch_logs")
    os.makedirs(log_dir, exist_ok=True)
    if os.environ.get("PADDLE_TPU_WARMSTORE"):
        # armed warm store: one directory scan in the launcher warms the
        # OS page cache for every rank about to consult the store (ranks
        # all read the same root; rank 0 is the only writer). Env checked
        # before the import -- a disarmed launch never loads the package.
        try:
            from .. import warmstore as _ws
            _ws.prefetch()
        except Exception:
            pass
    procs, logs = [], []
    for rank in range(nproc):
        env = dict(os.environ)
        env.update({
            "COORDINATOR_ADDRESS": coordinator,
            "NUM_PROCESSES": str(nproc),
            "PROCESS_ID": str(rank),
            # reference launcher contract (distributed/launch.py:147)
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(nproc),
            "PADDLE_TRAINER_ENDPOINTS": endpoints,
            "PADDLE_CURRENT_ENDPOINT": eps[rank],
            "PADDLE_RESTART_ATTEMPT": str(attempt),
        })
        if nominal_nproc is not None:
            # elastic mode: the CURRENT world is PADDLE_TRAINERS_NUM; the
            # size the job was asked for rides along so workloads can
            # adapt (e.g. re-arm a chaos fault only at full size)
            env["PADDLE_ELASTIC"] = "1"
            env["PADDLE_NOMINAL_TRAINERS_NUM"] = str(nominal_nproc)
        if devices_per_proc:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count="
                                f"{devices_per_proc}").strip()
        log_path = os.path.join(log_dir, f"rank{rank}.log" if attempt == 0
                                else f"rank{rank}.attempt{attempt}.log")
        logs.append(log_path)
        lf = open(log_path, "wb")
        try:
            procs.append(subprocess.Popen([sys.executable] + list(script_argv),
                                          env=env, stdout=lf, stderr=lf))
        finally:
            lf.close()   # the child holds its own copy of the fd
    if spawned_cb is not None:
        spawned_cb()   # all ranks spawned: the restart-downtime clock stops
    # monitor: a dead rank must not leave the others hanging in a collective
    while True:
        codes = [p.poll() for p in procs]
        bad = [r for r, c in enumerate(codes) if c not in (None, 0)]
        if bad:
            terminated = {r for r, c in enumerate(codes) if c is None}
            for r, p in enumerate(procs):
                if codes[r] is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()   # reap: no zombies, returncode always set
            # reclassify: a rank that was still running at the poll
            # snapshot but whose final code is neither our SIGTERM/
            # SIGKILL nor a clean/preempted exit crashed ON ITS OWN in
            # the race window -- it must stay blamable, not be excused
            # as monitor collateral
            import signal as _sig
            terminated = {r for r in terminated
                          if procs[r].returncode in
                          (0, -_sig.SIGTERM, -_sig.SIGKILL)}
            r = bad[0]
            tail = b""
            try:
                with open(logs[r], "rb") as f:
                    tail = f.read()[-4000:]
            except OSError:
                pass
            sys.stderr.write(
                f"\n[paddle_tpu.launch] rank {r} died with exit code "
                f"{codes[r]}; terminated {len(terminated)} "
                f"surviving rank(s). Log tail ({logs[r]}):\n"
                f"{tail.decode(errors='replace')}\n")
            if nproc > 1 and b"libtpu" in tail and (
                    b"lockfile" in tail or b"already in use" in tail):
                # measured on a v5e host (PR 21): the second rank to open
                # the TPU dies like this within seconds
                sys.stderr.write(
                    "[paddle_tpu.launch] the ranks of one host inherit one "
                    "environment and all opened the same TPU chips; a chip "
                    "belongs to one process. On a TPU host run ONE process "
                    "that drives every local chip as a mesh "
                    "(CompiledProgram.with_strategy); this launcher's "
                    "local ranks are for the CPU simulation "
                    "(JAX_PLATFORMS=cpu, --devices_per_proc N).\n")
            return [p.returncode for p in procs], terminated
        if all(c is not None for c in codes):
            return list(codes), set()
        time.sleep(poll_interval)


def main():
    ap = argparse.ArgumentParser("paddle_tpu.parallel.launch")
    ap.add_argument("--nproc", type=int, default=1)
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--devices_per_proc", type=int, default=None)
    ap.add_argument("--log_dir", default=None)
    ap.add_argument("--max_restarts", type=int, default=0,
                    help="restart the whole job up to N times on failure "
                         "(resume from your Checkpointer); ranks exiting "
                         "with resilience.PREEMPTED_EXIT (75) restart "
                         "without consuming this budget")
    ap.add_argument("--restart_backoff", type=float, default=1.0,
                    help="base seconds between elastic restarts; doubles "
                         "per attempt with jitter, capped at 30s")
    ap.add_argument("--elastic", action="store_true",
                    help="allow world-size-changing restarts: a "
                         "shrink-vs-wait policy may relaunch the "
                         "surviving ranks at N-k (>= --min_ranks) or grow "
                         "back toward N on a later restart")
    ap.add_argument("--min_ranks", type=int, default=None,
                    help="elastic floor: never shrink below this many "
                         "ranks (default 1)")
    ap.add_argument("--healthy_reset_secs", type=float, default=600.0,
                    help="an attempt that ran at least this long resets "
                         "the restart-backoff ladder to the base delay")
    ap.add_argument("script", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.script:
        ap.error("no training script given")
    codes = launch(args.nproc, args.script, args.coordinator,
                   args.devices_per_proc, log_dir=args.log_dir,
                   max_restarts=args.max_restarts,
                   restart_backoff=args.restart_backoff,
                   elastic=args.elastic, min_ranks=args.min_ranks,
                   healthy_reset_secs=args.healthy_reset_secs)
    # any non-clean rank (nonzero, signal-killed => negative, unreaped =>
    # None) must fail the launch: max() would mask -11 behind a clean 0
    sys.exit(0 if all(c == 0 for c in codes) else 1)


if __name__ == "__main__":
    main()
