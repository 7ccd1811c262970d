"""Host-resident embedding table (the parameter-server analog, SCOPE gap #1).

Reference behaviors covered: distributed lookup table pull/push
(transpiler/distribute_transpiler.py:1594), server-side optimizer application
(listen_and_serv optimize blocks), async communicator queueing
(operators/distributed/communicator.h:276), checkpoint of server-held tables
(io.py:328 _save_distributed_persistables).
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.initializer import NumpyArrayInitializer
from paddle_tpu.layer_helper import ParamAttr
from paddle_tpu.ops import host_table as ht


VOCAB, DIM, FIELDS = 40, 6, 3


def _fresh(name):
    ht.drop_table(name)
    return name


def _build(table_kind, name, w0, fc_w, lr=0.1, **table_kw):
    """A tiny regression model over an embedding of kind 'host'|'device'."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data("ids", shape=[FIELDS], dtype="int64")
        y = layers.data("y", shape=[1], dtype="float32")
        if table_kind == "host":
            emb = layers.host_embedding(ids, (VOCAB, DIM), name=name,
                                        optimizer="sgd", learning_rate=lr,
                                        initializer=w0, **table_kw)
        else:
            emb = layers.embedding(
                ids, (VOCAB, DIM),
                param_attr=ParamAttr(name="dev_w",
                                     initializer=NumpyArrayInitializer(w0)))
        flat = layers.reshape(emb, [-1, FIELDS * DIM])
        pred = layers.fc(flat, 1, param_attr=ParamAttr(
            name="fc_w", initializer=NumpyArrayInitializer(fc_w)),
            bias_attr=False)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    return main, startup, loss


def _feeds(steps, seed=7):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(steps):
        # duplicate ids inside a batch on purpose: exercises the merge-add
        ids = rng.randint(0, VOCAB, size=(4, FIELDS)).astype(np.int64)
        ids[0, 0] = ids[1, 0]
        out.append({"ids": ids, "y": rng.randn(4, 1).astype(np.float32)})
    return out


def test_host_vs_device_update_parity():
    """Server-side SGD on the host table == on-device dense scatter-add SGD."""
    rng = np.random.RandomState(0)
    w0 = rng.uniform(-0.1, 0.1, (VOCAB, DIM)).astype(np.float32)
    fc_w = rng.uniform(-0.1, 0.1, (FIELDS * DIM, 1)).astype(np.float32)

    name = _fresh("parity_tbl")
    h_main, h_start, h_loss = _build("host", name, w0, fc_w)
    d_main, d_start, d_loss = _build("device", name, w0, fc_w)

    exe = fluid.Executor()
    scope_h, scope_d = fluid.Scope(), fluid.Scope()
    feeds = _feeds(5)
    with fluid.scope_guard(scope_h):
        exe.run(h_start)
        h_losses = [float(exe.run(h_main, feed=f, fetch_list=[h_loss])[0])
                    for f in feeds]
    with fluid.scope_guard(scope_d):
        exe.run(d_start)
        d_losses = [float(exe.run(d_main, feed=f, fetch_list=[d_loss])[0])
                    for f in feeds]
        dev_w = np.asarray(scope_d.find_var("dev_w"))

    np.testing.assert_allclose(h_losses, d_losses, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(ht.get_table(name).table, dev_w,
                               rtol=2e-5, atol=1e-6)
    assert ht.get_table(name).push_count == len(feeds)
    ht.drop_table(name)


def test_push_op_in_backward_program():
    """Transpiler-style assertion: the backward pass contains the push op."""
    name = _fresh("desc_tbl")
    main, _, _ = _build("host", name,
                        np.zeros((VOCAB, DIM), np.float32),
                        np.zeros((FIELDS * DIM, 1), np.float32))
    types = [op.type for op in main.global_block().ops]
    assert "host_lookup_table" in types and "host_push_grad" in types
    # push consumes the loss cotangent of the lookup output
    push = next(op for op in main.global_block().ops
                if op.type == "host_push_grad")
    assert push.attrs["table_name"] == name
    ht.drop_table(name)


def test_adagrad_server_optimizer():
    name = _fresh("ada_tbl")
    t = ht.create_table(name, 10, 4, optimizer="adagrad", lr=0.5,
                        initializer=np.zeros((10, 4), np.float32))
    g = np.ones((2, 4), np.float32)
    t.push(np.array([3, 3]), g)  # merged: row 3 sees grad 2.0
    # adagrad: accum = 4, update = 0.5 * 2 / sqrt(4) = 0.5
    np.testing.assert_allclose(t.table[3], -0.5, rtol=1e-6)
    assert np.abs(t.table[[0, 1, 2, 4]]).sum() == 0
    ht.drop_table(name)


def test_memmap_beyond_ram_mode(tmp_path):
    name = _fresh("mm_tbl")
    t = ht.create_table(name, 100, 8, optimizer="sgd", lr=1.0,
                        mmap_dir=str(tmp_path))
    assert isinstance(t.table, np.memmap)
    before = t.table[5].copy()
    t.push(np.array([5]), np.ones((1, 8), np.float32))
    np.testing.assert_allclose(t.table[5], before - 1.0, rtol=1e-6)
    ht.drop_table(name)


def test_async_updates_flush():
    name = _fresh("async_tbl")
    t = ht.create_table(name, 20, 4, optimizer="sgd", lr=1.0,
                        initializer=np.zeros((20, 4), np.float32),
                        async_updates=True)
    for _ in range(10):
        t.push(np.array([1]), np.ones((1, 4), np.float32))
    t.flush()
    np.testing.assert_allclose(t.table[1], -10.0, rtol=1e-6)
    ht.drop_table(name)


def test_save_load_roundtrip(tmp_path):
    name = _fresh("ckpt_tbl")
    t = ht.create_table(name, 12, 3, optimizer="adagrad", lr=0.1)
    t.push(np.array([2, 7]), np.ones((2, 3), np.float32))
    snap = t.table.copy()
    t.save(str(tmp_path))
    t.push(np.array([2]), np.ones((1, 3), np.float32))
    assert not np.allclose(t.table, snap)
    t.load(str(tmp_path))
    np.testing.assert_allclose(t.table, snap)
    assert t.push_count == 1
    ht.drop_table(name)


def test_shape_mismatch_rejected():
    name = _fresh("shape_tbl")
    ht.create_table(name, 10, 4)
    with pytest.raises(ValueError, match="already exists"):
        ht.create_table(name, 10, 8)
    ht.drop_table(name)


def test_out_of_range_ids_raise():
    """Out-of-range ids must raise (host-side check), not silently clamp to
    the last row (advisor r3: clamp corruption is untraceable in a
    beyond-HBM table)."""
    from paddle_tpu.ops.host_table import HostTable
    t = HostTable("oor", vocab_size=8, dim=2)
    with pytest.raises(IndexError, match="out of range"):
        t.gather(np.array([3, 8]))
    with pytest.raises(IndexError, match="out of range"):
        t.push(np.array([-1]), np.ones((1, 2), np.float32))
    # in-range still works
    assert t.gather(np.array([0, 7])).shape == (2, 2)


def test_row_sharded_lookup_matches_unsharded():
    """row_shard_axis: the shard_map psum lookup over a 'host' axis matches
    the plain single-table path exactly, training included (the SCOPE gap-#1
    mechanism: per-device callbacks against row partitions; single-process
    simulation -- the multi-host runner covers the per-process split)."""
    import jax
    from paddle_tpu.ops import host_table as ht

    def run(sharded):
        tname = f"rs_{'s' if sharded else 'p'}"
        ht.drop_table(tname)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 4
        startup.random_seed = 4
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            ids = fluid.data("ids", [4], "int64")
            y = fluid.data("y", [1], "float32")
            emb = fluid.layers.host_embedding(
                ids, (32, 8), name=tname, optimizer="sgd",
                learning_rate=0.2, seed=7,
                row_shard_axis="host" if sharded else None)
            pred = fluid.layers.fc(fluid.layers.reshape(emb, [-1, 32]), 1)
            loss = fluid.layers.mean(fluid.layers.square(
                fluid.layers.elementwise_sub(pred, y)))
            fluid.optimizer.SGD(0.1).minimize(loss)
        strat = fluid.DistributedStrategy(
            mesh_shape={"host": 2, "dp": 2},
            data_rules=[("ids|y", ("dp",))], data_axis="dp")
        cp = fluid.CompiledProgram(main).with_strategy(strat)
        rng = np.random.RandomState(2)
        truth = rng.randn(32).astype(np.float32)
        exe = fluid.Executor()
        out = []
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for _ in range(5):
                gids = rng.randint(0, 32, (8, 4)).astype("int64")
                gy = truth[gids].sum(1, keepdims=True).astype("float32")
                lv, = exe.run(cp, feed={"ids": gids, "y": gy},
                              fetch_list=[loss])
                out.append(float(np.asarray(lv).reshape(())))
        table = np.array(ht.get_table(tname).table)
        ht.drop_table(tname)
        return out, table

    plain_losses, plain_table = run(False)
    shard_losses, shard_table = run(True)
    np.testing.assert_allclose(plain_losses, shard_losses, rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(plain_table, shard_table, rtol=1e-4,
                               atol=1e-6)


def test_pull_push_hoisting_removes_callbacks():
    """Round 5: eligible pulls/pushes are hoisted OUT of the compiled
    program (the reference PS schedule: pull -> device step -> push) so no
    jax callback remains in the hot path (a host gather before the step
    costs less than a mid-program callback). The rewritten program
    must hold zero host_lookup_table/host_push_grad ops, the lookup output
    becomes a feed, and training still updates the table (parity with the
    in-graph path is pinned by test_host_vs_device_update_parity, which
    runs through the hoist)."""
    from paddle_tpu.ops.host_table import hoist_host_pulls

    rng = np.random.RandomState(0)
    w0 = rng.uniform(-0.1, 0.1, (VOCAB, DIM)).astype(np.float32)
    fc_w = rng.uniform(-0.1, 0.1, (FIELDS * DIM, 1)).astype(np.float32)
    name = _fresh("hoist_tbl")
    main, startup, loss = _build("host", name, w0, fc_w)

    p2, pulls, pushes = hoist_host_pulls(main)
    assert len(pulls) == 1 and len(pushes) == 1
    types = [o.type for o in p2.global_block().ops]
    assert "host_lookup_table" not in types
    assert "host_push_grad" not in types
    # original program untouched (the executor caches the rewrite)
    assert "host_lookup_table" in [o.type for o in main.global_block().ops]
    out_name = pulls[0][2]
    assert p2.global_block().var(out_name).is_data

    # executor path end to end: table updates happen via the post-run push
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        before = ht.get_table(name).table.copy()
        for f in _feeds(3, seed=1):
            exe.run(main, feed=f, fetch_list=[loss])
        after = ht.get_table(name).table
    assert not np.allclose(before, after)
    assert ht.get_table(name).push_count == 3
    ht.drop_table(name)


def test_pruned_eval_does_not_train_the_table():
    """use_prune eval (infer_from_dataset semantics) over a hoisted
    host-table program must not push: the table stays byte-identical
    (review r5: the hoisted push must respect fetch-graph pruning the way
    the in-graph push op did)."""
    rng = np.random.RandomState(2)
    w0 = rng.uniform(-0.1, 0.1, (VOCAB, DIM)).astype(np.float32)
    fc_w = rng.uniform(-0.1, 0.1, (FIELDS * DIM, 1)).astype(np.float32)
    name = _fresh("evalsafe_tbl")
    main, startup, loss = _build("host", name, w0, fc_w)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        before = ht.get_table(name).table.copy()
        f = _feeds(1, seed=3)[0]
        exe.run(main, feed=f, fetch_list=[loss], use_prune=True)
        np.testing.assert_array_equal(ht.get_table(name).table, before)
        assert ht.get_table(name).push_count == 0
        # a real train step does push
        exe.run(main, feed=f, fetch_list=[loss])
        assert ht.get_table(name).push_count == 1
    ht.drop_table(name)


def test_pruned_eval_of_unrelated_branch_needs_no_ids():
    """A pruned eval over a branch that never touches the host embedding
    must neither require the ids feed nor gather rows (review r5: pulls
    are filtered against the pruned program)."""
    rng = np.random.RandomState(4)
    w0 = rng.uniform(-0.1, 0.1, (VOCAB, DIM)).astype(np.float32)
    name = _fresh("branch_tbl")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data("ids", shape=[FIELDS], dtype="int64")
        z = layers.data("z", shape=[4], dtype="float32")
        emb = layers.host_embedding(ids, (VOCAB, DIM), name=name,
                                    initializer=w0)
        flat = layers.reshape(emb, [-1, FIELDS * DIM])
        pred = layers.fc(flat, 1)
        side = layers.mean(layers.square(z))     # independent branch
        loss = layers.mean(pred) + side
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        # no ids in the feed: pruned to `side`, the pull must be skipped
        sv, = exe.run(main, feed={"z": np.ones((2, 4), np.float32)},
                      fetch_list=[side], use_prune=True)
        np.testing.assert_allclose(float(np.asarray(sv).reshape(())), 1.0,
                                   rtol=1e-6)
        assert ht.get_table(name).push_count == 0
    ht.drop_table(name)


def test_async_updates_multiple_tables():
    """Two async host tables in ONE program: each table owns its queue and
    worker (the async communicator is per-table, reference
    communicator.h:276 per-var queues); both receive their pushes and both
    flush cleanly."""
    rng = np.random.RandomState(5)
    w0 = rng.uniform(-0.1, 0.1, (VOCAB, DIM)).astype(np.float32)
    na, nb = _fresh("async_a"), _fresh("async_b")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data("ids", shape=[FIELDS], dtype="int64")
        y = layers.data("y", shape=[1], dtype="float32")
        ea = layers.host_embedding(ids, (VOCAB, DIM), name=na,
                                   initializer=w0, async_updates=True)
        eb = layers.host_embedding(ids, (VOCAB, DIM), name=nb,
                                   initializer=w0, async_updates=True)
        flat = layers.reshape(layers.elementwise_add(ea, eb),
                              [-1, FIELDS * DIM])
        pred = layers.fc(flat, 1, bias_attr=False)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        before_a = ht.get_table(na).table.copy()
        before_b = ht.get_table(nb).table.copy()
        for f in _feeds(4, seed=6):
            exe.run(main, feed=f, fetch_list=[loss])
        ht.get_table(na).flush()
        ht.get_table(nb).flush()
    assert ht.get_table(na).push_count == 4
    assert ht.get_table(nb).push_count == 4
    assert not np.allclose(ht.get_table(na).table, before_a)
    assert not np.allclose(ht.get_table(nb).table, before_b)
    ht.drop_table(na)
    ht.drop_table(nb)
