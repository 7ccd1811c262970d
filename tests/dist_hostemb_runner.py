"""Multi-process host-table trainer (launched by test_multihost.py).

Default mode — single pserver: under multi-host GSPMD, jax gathers callback
operands to process 0, runs the callback there alone, and broadcasts the
result — process 0's host RAM is the parameter server (the classic pserver
topology, reference transpiler/distribute_transpiler.py:3.3 call stack)
with ZERO extra code. The parent asserts parity with the 1-process run and
that only rank 0's table was touched.

argv[4] == "shard" — ROW-SHARDED pservers: the table's rows partition
across processes (host_embedding(row_shard_axis="host") over a
{host, dp} mesh; reference distribute_transpiler.py:990 param blocks);
each process stores only rows [lo, hi) and BOTH ranks apply pushes.
"""
import json
import os
import sys


def main():
    rank = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]
    sharded = len(sys.argv) > 4 and sys.argv[4] == "shard"
    tname = "sh_tbl" if sharded else "mh_tbl"

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.parallel import env as penv
    from paddle_tpu.ops import host_table as ht

    if nproc > 1:
        penv.init_parallel_env(coordinator_address=f"127.0.0.1:{port}",
                               num_processes=nproc, process_id=rank)

    VOCAB, DIM, F = 64, 8, 4
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = 11
    startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
        ids = fluid.data("ids", [F], "int64")
        y = fluid.data("y", [1], "float32")
        emb = fluid.layers.host_embedding(
            ids, (VOCAB, DIM), name=tname, optimizer="sgd",
            learning_rate=0.2, seed=3,
            row_shard_axis="host" if sharded else None)
        pred = fluid.layers.fc(fluid.layers.reshape(emb, [-1, F * DIM]), 1)
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.elementwise_sub(pred, y)))
        fluid.optimizer.SGD(0.1).minimize(loss)
    if sharded:
        n_dev = 4 * nproc
        strat = fluid.DistributedStrategy(
            mesh_shape={"host": nproc, "dp": n_dev // nproc},
            data_rules=[("ids|y", (("host", "dp"),))], data_axis="dp")
        cp = fluid.CompiledProgram(main_p).with_strategy(strat)
    else:
        cp = fluid.CompiledProgram(main_p).with_data_parallel(
            loss_name=loss.name)

    rng = np.random.RandomState(5)  # same global stream on every rank
    truth = rng.randn(VOCAB).astype(np.float32)

    exe = fluid.Executor()
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(6):
            gids = rng.randint(0, VOCAB, (8, F)).astype(np.int64)
            gy = truth[gids].sum(1, keepdims=True).astype(np.float32)
            lids = penv.shard_batch(gids, rank, nproc)
            ly = penv.shard_batch(gy, rank, nproc)
            lv, = exe.run(cp, feed={"ids": lids, "y": ly}, fetch_list=[loss])
            losses.append(float(np.asarray(lv).reshape(())))
    t = ht.get_table(tname)
    print("LOSSES:" + json.dumps(losses), flush=True)
    print("ROWS:" + str(t.table.shape[0]), flush=True)
    print("RANGE:" + json.dumps([t.row_lo, t.row_hi]), flush=True)
    print("PUSHES:" + str(t.push_count), flush=True)


if __name__ == "__main__":
    main()
