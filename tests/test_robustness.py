"""Multi-host robustness tests (VERDICT r2 #8): dead-rank diagnosis in the
launcher, bounded rendezvous in init_parallel_env, op creation-stack on
executor errors (reference heart_beat_monitor.h:38, op_call_stack.cc:1)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as fluid


def test_launch_reports_dead_rank(tmp_path):
    """Rank 1 dies mid-run: the launcher must kill the survivor (which would
    otherwise hang in the rendezvous/collective), return, and leave a log
    naming the dead rank."""
    from paddle_tpu.parallel.launch import launch
    script = tmp_path / "dier.py"
    script.write_text(textwrap.dedent("""
        import os, sys, time
        rank = int(os.environ["PROCESS_ID"])
        if rank == 1:
            print("rank 1 failing now", flush=True)
            sys.exit(3)
        time.sleep(60)   # rank 0 would hang forever without the monitor
    """))
    import time
    t0 = time.time()
    codes = launch(2, [str(script)], log_dir=str(tmp_path / "logs"),
                   poll_interval=0.2)
    assert time.time() - t0 < 30, "launcher failed to detect the dead rank"
    assert codes[1] == 3
    assert codes[0] != 0 or codes[0] is None  # terminated, not clean exit
    log = (tmp_path / "logs" / "rank1.log").read_text()
    assert "rank 1 failing now" in log


def test_launch_distinct_endpoints(tmp_path):
    """Each rank gets its own endpoint; endpoints[rank] ==
    PADDLE_CURRENT_ENDPOINT (advisor r2 finding on the launcher contract)."""
    from paddle_tpu.parallel.launch import launch
    script = tmp_path / "epcheck.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        eps = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
        assert len(set(eps)) == len(eps), f"duplicate endpoints: {eps}"
        assert os.environ["PADDLE_CURRENT_ENDPOINT"] == eps[rank]
        assert os.environ["COORDINATOR_ADDRESS"] == eps[0]
    """))
    codes = launch(2, [str(script)], log_dir=str(tmp_path / "logs"))
    assert codes == [0, 0], (tmp_path / "logs" / "rank0.log").read_text()


def test_init_parallel_env_times_out_cleanly():
    """A missing peer must produce an actionable error naming the coordinator
    within the deadline, not an indefinite hang."""
    code = textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, %r)
        from paddle_tpu.parallel import env as penv
        try:
            penv.init_parallel_env(coordinator_address="127.0.0.1:59999",
                                   num_processes=2, process_id=1,
                                   timeout_seconds=5)
        except RuntimeError as e:
            assert "127.0.0.1:59999" in str(e), str(e)
            assert "rank 1/2" in str(e), str(e)
            assert "could not reach" in str(e), str(e)
            print("CLEAN_TIMEOUT")
    """) % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         timeout=120)
    assert b"CLEAN_TIMEOUT" in out.stdout, out.stderr[-1500:]


def test_executor_error_names_user_code_line():
    """Lowering failures carry the op's creation stack (op_call_stack.cc)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        q = fluid.data("q", [2, 8, 4], "float32")
        bad = fluid.layers.fused_attention(q, q, q, impl="ring")  # needs sp mesh
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        with pytest.raises(RuntimeError) as ei:
            exe.run(main, feed={"q": np.zeros((2, 2, 8, 4), "float32")},
                    fetch_list=[bad])
    msg = str(ei.value)
    assert "op created at" in msg
    assert "test_robustness.py" in msg, msg


def test_monitored_run_failure_accounting():
    from paddle_tpu.parallel.env import monitored_run
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ValueError("transient")
        return "ok"

    seen = []
    run = monitored_run(flaky, max_consecutive_failures=3,
                        on_failure=seen.append)
    assert run() is None and run() is None and run() == "ok"
    assert seen == [1, 2]

    def always():
        raise ValueError("fatal")

    run2 = monitored_run(always, max_consecutive_failures=2)
    assert run2() is None
    with pytest.raises(ValueError):
        run2()


def test_launch_elastic_restart(tmp_path):
    """max_restarts: a rank that crashes on the first attempt is recovered
    by a whole-job relaunch (fresh ports, PADDLE_RESTART_ATTEMPT bumped) —
    the restart-from-checkpoint elasticity mode (SCOPE.md 5.3) — after an
    exponential backoff, with the restart journaled (failed rank +
    attempt number)."""
    import time
    from paddle_tpu.observability import journal
    from paddle_tpu.parallel.launch import launch
    script = tmp_path / "flaky.py"
    script.write_text(
        "import os, sys\n"
        "attempt = int(os.environ['PADDLE_RESTART_ATTEMPT'])\n"
        "rank = int(os.environ['PADDLE_TRAINER_ID'])\n"
        "marker = os.path.join(%r, f'seen_a{attempt}_r{rank}')\n"
        "open(marker, 'w').close()\n"
        "if attempt == 0 and rank == 1:\n"
        "    sys.exit(3)   # simulated hardware failure on first attempt\n"
        "print('done', attempt, rank)\n" % str(tmp_path))
    t0 = time.time()
    codes = launch(2, [str(script)], log_dir=str(tmp_path / "logs"),
                   max_restarts=1, restart_backoff=0.05)
    assert codes == [0, 0]
    # both attempts actually ran: attempt 0 crashed, attempt 1 completed
    assert (tmp_path / "seen_a0_r1").exists()
    assert (tmp_path / "seen_a1_r0").exists()
    assert (tmp_path / "seen_a1_r1").exists()
    evs = [e for e in journal.recent(event="elastic_restart")
           if e.get("ts", 0) >= t0]
    assert len(evs) == 1
    assert evs[0]["failed_rank"] == 1 and evs[0]["attempt"] == 1
    assert evs[0]["backoff_s"] > 0


def test_launch_elastic_budget_exhausted(tmp_path):
    """A permanently-failing job stops after max_restarts and reports the
    failure code instead of looping forever; each restart backs off
    exponentially (attempt N's base delay doubles attempt N-1's)."""
    import time
    from paddle_tpu.observability import journal
    from paddle_tpu.parallel.launch import launch
    script = tmp_path / "dead.py"
    script.write_text("import sys; sys.exit(7)\n")
    t0 = time.time()
    codes = launch(2, [str(script)], log_dir=str(tmp_path / "logs"),
                   max_restarts=2, restart_backoff=0.05)
    assert any(c == 7 for c in codes)
    evs = [e for e in journal.recent(event="elastic_restart")
           if e.get("ts", 0) >= t0]
    assert [e["attempt"] for e in evs] == [1, 2]
    # jitter is in [0.5x, 1.5x); the journaled value is round(delay, 3),
    # so pad the upper bound by the rounding quantum
    assert 0.5 * 0.05 <= evs[0]["backoff_s"] <= 1.5 * 0.05 + 5e-4
    assert 0.5 * 0.10 <= evs[1]["backoff_s"] <= 1.5 * 0.10 + 5e-4


def _sgd_mlp(dim=4, seed=0):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [dim], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, dim))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_executor_close_idempotent_and_signal_safe():
    """ISSUE 6 satellite: double-close, close-before-run, and a close fired
    from a SIGTERM handler mid-loop must not raise, and the executor stays
    usable afterwards (the preemption path closes at a step boundary)."""
    import signal

    fluid.Executor().close()   # close before any run: no-op, no raise
    main, startup, loss = _sgd_mlp()
    feed = {"x": np.ones((2, 4), "float32")}
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        exe.close()
        exe.close()            # double close: idempotent
        out, = exe.run(main, feed=feed, fetch_list=[loss])  # reusable
        assert np.isfinite(out).all()

        closed_by_signal = []

        def handler(signum, frame):
            exe.close()        # close-during-run from the SIGTERM path
            closed_by_signal.append(signum)

        old = signal.signal(signal.SIGTERM, handler)
        try:
            for i in range(3):
                exe.run(main, feed=feed, fetch_list=[loss])
                if i == 1:
                    signal.raise_signal(signal.SIGTERM)
        finally:
            signal.signal(signal.SIGTERM, old)
        assert closed_by_signal == [signal.SIGTERM]
        out, = exe.run(main, feed=feed, fetch_list=[loss])
        assert np.isfinite(out).all()


def test_chaos_end_to_end_recovery(tmp_path):
    """ISSUE 6 acceptance: NaN at step 3 + transient dispatch fault at
    step 5 + simulated SIGTERM at step 7 on a small MLP. The run completes
    all configured steps (skip + retry + emergency-checkpoint + resume),
    every recovery act is journaled, and the emergency checkpoint restores
    to the right step."""
    import time
    from paddle_tpu.observability import journal
    from paddle_tpu.resilience import StepGuardian, faults, recovery
    from paddle_tpu.utils.checkpointer import Checkpointer

    total = 10
    main, startup, loss = _sgd_mlp(dim=4, seed=11)
    feed = {"x": np.ones((2, 4), "float32")}
    ck_dir = str(tmp_path / "ck")
    t0 = time.time()
    faults.clear()
    recovery.clear_preemption()
    scope = fluid.Scope()
    losses = []
    try:
        faults.install(f"nan:step=3:var={loss.name};exc@dispatch:step=5;"
                       f"preempt:step=7")
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            ck = Checkpointer(exe, main, ck_dir)
            g = StepGuardian(exe, main, checkpointer=ck,
                             nonfinite_policy="skip", max_retries=3,
                             retry_backoff=0.01, retry_seed=1)
            step, preempted = 0, None
            while step < total:
                try:
                    vals = g.run(feed=feed, fetch_list=[loss])
                except recovery.Preempted as p:
                    preempted = p
                    break
                losses.append(np.asarray(vals[0]).reshape(-1)[0])
                step += 1
            # the preempt fault fired during step 7; the guardian exited at
            # the NEXT step boundary with an emergency save of step 7
            assert preempted is not None and step == 8
            assert preempted.saved_step == 7
            assert ck.latest_step() == 7

            # resume exactly where the emergency checkpoint left off (a
            # real preemption restarts the process; same mechanics)
            recovery.clear_preemption()
            exe2 = fluid.Executor()
            ck2 = Checkpointer(exe2, main, ck_dir)
            start = ck2.restore() + 1
            assert start == 8
            g2 = StepGuardian(exe2, main, checkpointer=ck2,
                              nonfinite_policy="skip", start_step=start,
                              handle_signals=False)
            while step < total:
                vals = g2.run(feed=feed, fetch_list=[loss])
                losses.append(np.asarray(vals[0]).reshape(-1)[0])
                step += 1
            g2.close()
        assert step == total and len(losses) == total
        # step 3's loss was the injected NaN; everything else is finite
        assert np.isnan(losses[3])
        assert np.isfinite(np.asarray(losses[:3] + losses[4:])).all()
        evs = [e for e in journal.recent() if e.get("ts", 0) >= t0]
        skips = [e for e in evs if e.get("event") == "skip"]
        retries = [e for e in evs if e.get("event") == "retry"]
        preempts = [e for e in evs if e.get("event") == "preempt"]
        assert [e["step"] for e in skips] == [3]
        assert retries and all(e["site"] == "dispatch" for e in retries)
        assert [e["step"] for e in retries] == [5]
        assert len(preempts) == 1 and preempts[0]["saved_step"] == 7
        faulted = [e for e in evs if e.get("event") == "fault"]
        assert {e["kind"] for e in faulted} == {"nan", "exc", "preempt"}
    finally:
        faults.clear()
        recovery.clear_preemption()
