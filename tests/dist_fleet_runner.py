"""Two-rank fleet-telemetry rank script (launched by
test_fleet_telemetry.py): each rank trains the same tiny MLP with fleet
monitoring armed, rank 1 artificially slowed by an injected
``hang@dispatch`` fault (resilience.faults -- the per-step sleep every
real straggler looks like), and rank 0 must flag EXACTLY rank 1.

Transports (argv[4]):

- ``scrape``: no collectives -- each rank runs its own metrics endpoint
  (``PADDLE_TPU_OBS_PORT`` base + rank) and rank 0's scraper thread polls
  peer ``/metrics`` pages.  Runs on any backend, CPU included.
- ``gather``: ``jax.distributed`` + ``process_allgather`` rows at a step
  cadence.  Needs a backend with multiprocess collectives (skipif-gated).

Rank 0 prints ``STRAGGLERS:<json>`` (sorted flagged ranks) and
``FLEET:<json>`` (the last per-rank table) for the parent to assert on.
"""
import json
import os
import sys
import time


def main():
    rank = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]          # coordinator (gather) -- unused for scrape
    mode = sys.argv[4]
    obs_base = int(sys.argv[5])
    slow_ms = float(sys.argv[6]) if len(sys.argv) > 6 else 30.0

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=2")
    # launcher contract: rank/world discovery + peer host derivation
    os.environ["NUM_PROCESSES"] = str(nproc)
    os.environ["PROCESS_ID"] = str(rank)
    os.environ["PADDLE_TRAINER_ENDPOINTS"] = ",".join(
        f"127.0.0.1:{9000 + r}" for r in range(nproc))
    os.environ["PADDLE_TPU_FLEET"] = mode
    os.environ["PADDLE_TPU_FLEET_INTERVAL"] = "8"
    os.environ["PADDLE_TPU_FLEET_PERIOD"] = "0.25"
    if mode == "scrape":
        os.environ["PADDLE_TPU_OBS_PORT"] = str(obs_base)
        os.environ["PADDLE_TPU_OBS_HOST"] = "127.0.0.1"
    if rank == 1:
        # the straggler: every dispatch sleeps -- thermals / noisy
        # neighbor / stuck input pipeline, as one injectable fault
        os.environ["PADDLE_TPU_FAULTS"] = \
            f"hang@dispatch:seconds={slow_ms / 1e3}:times=0"

    import jax
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.observability import fleet, journal

    if mode == "gather":
        from paddle_tpu.parallel import env as penv
        penv.init_parallel_env(coordinator_address=f"127.0.0.1:{port}",
                               num_processes=nproc, process_id=rank)

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
        x = fluid.data("x", [32], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 32))
        fluid.optimizer.SGD(0.01).minimize(loss)
    feed = {"x": np.random.RandomState(rank).rand(8, 32).astype("float32")}

    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor()
        exe.run(startup)
        n_steps = 64
        for _ in range(n_steps):
            exe.run(main_p, feed=feed, fetch_list=[loss])
        assert fleet.MONITOR is not None, "fleet monitor never armed"
        if rank == 0:
            if mode == "gather":
                # collections already fired inside the step loop at the
                # interval cadence (collectives -- every rank participated
                # in lockstep; a lone post-loop collect() would deadlock)
                verdicts = journal.recent(event="straggler")
            else:
                # scrape mode: collections ride the background scraper's
                # clock -- wait for one that saw every rank AND flagged
                deadline = time.time() + 30
                verdicts = []
                while time.time() < deadline:
                    time.sleep(0.3)
                    verdicts = journal.recent(event="straggler")
                    fleets = journal.recent(event="fleet")
                    if verdicts and fleets and \
                            fleets[-1].get("n_ranks", 0) == nproc:
                        break
            flagged = sorted({e["rank"] for e in verdicts})
            print("STRAGGLERS:" + json.dumps(flagged), flush=True)
            fleets = journal.recent(event="fleet")
            print("FLEET:" + json.dumps(fleets[-1] if fleets else None),
                  flush=True)
        else:
            # keep the straggler's endpoint alive until rank 0 has
            # certainly scraped it (scrape mode has no barrier)
            if mode == "scrape":
                time.sleep(3.0)
    print("DONE", flush=True)


if __name__ == "__main__":
    main()
