"""Multi-process distributed trainer script (the reference's dist_mnist.py
runtime_main pattern, tests/unittests/test_dist_base.py:409): launched by
test_multihost.py as N processes on localhost; prints per-step losses as JSON
on the last stdout line for the parent to compare against the single-process
baseline."""
import json
import os
import sys


def main():
    rank = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]
    ckpt_dir = sys.argv[4] if len(sys.argv) > 4 else None

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.parallel import env as penv

    if nproc > 1:
        penv.init_parallel_env(coordinator_address=f"127.0.0.1:{port}",
                               num_processes=nproc, process_id=rank)

    def build():
        main_p, startup = fluid.Program(), fluid.Program()
        main_p.random_seed = 21
        startup.random_seed = 21
        with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
            x = fluid.data("x", [32], "float32")
            label = fluid.data("label", [1], "int64")
            h = fluid.layers.fc(x, 64, act="relu")
            logits = fluid.layers.fc(h, 10)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, label))
            fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)
        return main_p, startup, loss

    main_p, startup, loss = build()
    bs = fluid.BuildStrategy()
    if ckpt_dir:
        # ZeRO mode so optimizer state is dp-sharded -> per-host chunk files
        bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    cp = fluid.CompiledProgram(main_p, build_strategy=bs) \
        .with_data_parallel(loss_name=loss.name)

    rng = np.random.RandomState(0)  # same global batch stream on every rank
    W = rng.randn(32, 10).astype("float32")

    def global_batch():
        gx = rng.randn(64, 32).astype("float32")
        gy = np.argmax(gx @ W, 1)[:, None].astype("int64")
        return gx, gy

    exe = fluid.Executor()
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(5):
            gx, gy = global_batch()
            # per-host slice of the global batch
            lx = penv.shard_batch(gx, rank, nproc)
            ly = penv.shard_batch(gy, rank, nproc)
            lv, = exe.run(cp, feed={"x": lx, "label": ly}, fetch_list=[loss])
            losses.append(float(np.asarray(lv).reshape(())))
        if ckpt_dir:
            fluid.io.save_persistables(exe, ckpt_dir, cp)
    print("LOSSES:" + json.dumps(losses), flush=True)

    if ckpt_dir:
        # resume the run under a *different* mesh (dp x mp tensor parallel):
        # reshard-on-load must stitch the dp-sharded checkpoint into mp shards
        main2, startup2, loss2 = build()
        strat = fluid.DistributedStrategy(
            mesh_shape={"dp": max(1, (4 * nproc) // 2), "mp": 2},
            param_rules=[(r"fc_0\.w_0", (None, "mp")),
                         (r"fc_1\.w_0", ("mp", None))])
        cp2 = fluid.CompiledProgram(main2).with_strategy(strat)
        ck_losses = []
        with fluid.scope_guard(fluid.Scope()):
            fluid.io.load_persistables(exe, ckpt_dir, cp2)
            for _ in range(2):
                gx, gy = global_batch()
                lx = penv.shard_batch(gx, rank, nproc)
                ly = penv.shard_batch(gy, rank, nproc)
                lv, = exe.run(cp2, feed={"x": lx, "label": ly},
                              fetch_list=[loss2])
                ck_losses.append(float(np.asarray(lv).reshape(())))
        print("CKPT_LOSSES:" + json.dumps(ck_losses), flush=True)


if __name__ == "__main__":
    main()
