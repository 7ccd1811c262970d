"""Multi-host Checkpointer rank script (launched by test_multihost.py):
N processes train a ZeRO-sharded MLP under a Checkpointer (per-rank chunk
manifests, rank0 LATEST + post-barrier rotation), then restore into a fresh
scope and print a state digest -- the parent asserts the digests agree
across ranks and the surviving tree passes the crc verifier.

A 5th argv ``shrink-restore`` is the elastic world-shrink variant (ISSUE
11): a SINGLE fresh process restores the checkpoint the N-proc run wrote
-- a 2-proc -> 1-proc world change -- asserting the restore re-plans the
shards (``reshard_plan``/``elastic_restore`` journal events), and
continues training with a finite loss."""
import hashlib
import json
import os
import sys


def main():
    rank = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]
    ckpt_dir = sys.argv[4]
    mode = sys.argv[5] if len(sys.argv) > 5 else ""

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.parallel import env as penv
    from paddle_tpu.utils.checkpointer import Checkpointer

    if nproc > 1:
        penv.init_parallel_env(coordinator_address=f"127.0.0.1:{port}",
                               num_processes=nproc, process_id=rank)

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 31
    with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
        x = fluid.data("x", [16], "float32")
        label = fluid.data("label", [1], "int64")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(fluid.layers.fc(x, 32, act="relu"), 8), label))
        fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)
    bs = fluid.BuildStrategy()
    # ZeRO: optimizer state dp-sharded -> every rank writes its own chunks
    bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    cp = fluid.CompiledProgram(main_p, build_strategy=bs) \
        .with_data_parallel(loss_name=loss.name)

    rng = np.random.RandomState(0)   # same global batch stream on all ranks
    W = rng.randn(16, 8).astype("float32")

    def feed():
        gx = rng.randn(32, 16).astype("float32")
        gy = np.argmax(gx @ W, 1)[:, None].astype("int64")
        return {"x": penv.shard_batch(gx, rank, nproc),
                "label": penv.shard_batch(gy, rank, nproc)}

    def digest(scope):
        """Per-rank digest: np.asarray raises on non-fully-addressable
        (cross-host ZeRO) arrays, so those hash their local unique shards
        (+ index) instead -- saved vs restored must agree per rank."""
        h = hashlib.sha256()
        for name in sorted(main_p.global_block().vars):
            v = scope.find_var(name)
            if v is None or not main_p.global_block().vars[name].persistable:
                continue
            h.update(name.encode())
            if hasattr(v, "addressable_shards") and \
                    not getattr(v, "is_fully_addressable", True):
                seen = set()
                for sh in sorted(v.addressable_shards,
                                 key=lambda s: str(s.index)):
                    if sh.replica_id != 0 or str(sh.index) in seen:
                        continue
                    seen.add(str(sh.index))
                    h.update(str(sh.index).encode())
                    h.update(np.ascontiguousarray(
                        np.asarray(sh.data)).tobytes())
            else:
                h.update(np.ascontiguousarray(np.asarray(v)).tobytes())
        return h.hexdigest()

    if mode == "shrink-restore":
        # elastic shrink: this 1-proc world restores the 2-proc ZeRO
        # checkpoint; the restore path must re-plan the shards for the
        # new world (journaled) and training must continue
        from paddle_tpu.observability import journal as pjournal
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            ck = Checkpointer(exe, cp, ckpt_dir)
            got = ck.restore()
            plans = [e for e in pjournal.recent(event="reshard_plan")]
            notes = [e for e in pjournal.recent(event="elastic_restore")]
            loss_val = float(__import__("numpy").asarray(
                exe.run(cp, feed=feed(), fetch_list=[loss])[0]).reshape(-1)[0])
            print("SHRINK:" + json.dumps({
                "restored": got,
                "saved_world": (ck.train_state or {}).get("world"),
                "reshard_plans": len(plans),
                "plan_actions": plans[-1].get("actions") if plans else None,
                "elastic_restores": len(notes),
                "loss": loss_val}), flush=True)
        return

    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        ck = Checkpointer(exe, cp, ckpt_dir, max_to_keep=2)
        for step in range(3):
            exe.run(cp, feed=feed(), fetch_list=[loss])
            ck.save(step)   # 3 saves + max_to_keep=2: rotation under load
        saved_digest = digest(fluid.global_scope())
        assert ck.latest_step() == 2, ck.latest_step()

    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        ck2 = Checkpointer(exe, cp, ckpt_dir)
        got = ck2.restore()
        assert got == 2, got
        assert ck2.train_state is not None and \
            ck2.train_state["step"] == 2, ck2.train_state
        restored_digest = digest(fluid.global_scope())

    print("DIGESTS:" + json.dumps({
        "rank": rank, "saved": saved_digest, "restored": restored_digest,
    }), flush=True)


if __name__ == "__main__":
    main()
