"""Comm layer (ISSUE 15): quantized gradient collectives (bf16/int8 +
error feedback behind ``DistributedStrategy.comm_compression``) and the
spec-to-spec redistribution planner (``comm.plan_transfer`` shared by the
PT046 lint, the ``reshard`` op lowering and the elastic host reshard).

The convergence-parity pins run REAL dp training in-process (conftest
forces 8 host CPU devices): the explicit-dp shard_map path with nothing
compressed is byte-identical to the GSPMD baseline, int8+error-feedback
tracks the f32 loss curve within the pinned tolerance, bf16 is
byte-stable across runs, and world=1 compressed is byte-identical to
``off`` (the short-circuit pin)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import comm
from paddle_tpu.comm import compress, cost, reshard, rewrite
from paddle_tpu.framework import Program
from paddle_tpu.observability.metrics import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

jax = pytest.importorskip("jax")


# ------------------------------------------------------------ quantizer --

def test_int8_quantize_round_trip_bound():
    import jax.numpy as jnp
    rs = np.random.RandomState(3)
    x = (rs.randn(2048) * 7).astype("float32")
    q, s = compress.quantize_int8(jnp.asarray(x))
    assert str(np.asarray(q).dtype) == "int8"
    back = np.asarray(compress.dequantize_int8(q, s))
    # symmetric 8-bit: error bounded by half a quantization step
    assert np.abs(back - x).max() <= np.abs(x).max() / 254.0 + 1e-7


def test_int8_quantize_zero_and_constant():
    import jax.numpy as jnp
    q, s = compress.quantize_int8(jnp.zeros(32))
    assert float(np.abs(np.asarray(
        compress.dequantize_int8(q, s))).max()) == 0.0
    q2, s2 = compress.quantize_int8(jnp.full((8,), 3.5, jnp.float32))
    assert np.allclose(np.asarray(compress.dequantize_int8(q2, s2)), 3.5,
                       rtol=1e-2)


# ------------------------------------------------------------ cost model --

def test_wire_byte_formulas():
    nb = 1 << 20
    assert cost.wire_bytes("allreduce", nb, 8) == int(2 * 7 / 8 * nb)
    assert cost.wire_bytes("allgather", nb, 8) == int(7 / 8 * nb)
    assert cost.wire_bytes("dynamic_slice", nb, 8) == 0
    assert cost.wire_bytes("allreduce", nb, 1) == 0   # world 1: no wire
    assert 3.9 <= cost.compression_ratio(nb, "float32", "int8", 8) <= 4.0
    assert cost.compression_ratio(nb, "float32", "bf16") == 2.0
    assert cost.compression_ratio(nb, "float32", "off") == 1.0


# -------------------------------------------------------------- planner --

def test_plan_transfer_decomposition_table():
    P, S = reshard.plan_transfer, reshard.ShardSpec
    f32 = "float32"
    assert P([48, 8], f32, S(0, 4), S(0, 4)).kind == "keep"
    p = P([48, 8], f32, S(None), S(0, 4))
    assert (p.kind, p.collectives, p.wire_bytes) == \
        ("slice", ["dynamic_slice"], 0)
    p = P([48, 8], f32, S(0, 4), S(None))
    assert (p.kind, p.collectives) == ("gather", ["all_gather"])
    assert p.wire_bytes == cost.wire_bytes("all_gather", 48 * 8 * 4, 4)
    # nested world-multiplying split: local slices, zero communication
    p = P([48, 8], f32, S(0, 4), S(0, 8))
    assert (p.kind, p.wire_bytes) == ("slice", 0)
    # world-dividing merge: a gather
    assert P([48, 8], f32, S(0, 8), S(0, 4)).kind == "gather"
    # shard dim moves at equal count: one all_to_all
    p = P([48, 8], f32, S(0, 4), S(1, 4))
    assert (p.kind, p.collectives) == ("alltoall", ["all_to_all"])
    # boundary-incompatible (the 8 -> 6 elastic case): gather + local slice
    p = P([48, 8], f32, S(0, 8), S(0, 6))
    assert (p.kind, p.collectives) == \
        ("redistribute", ["all_gather", "dynamic_slice"])
    assert p.wire_bytes == cost.wire_bytes("all_gather", 48 * 8 * 4, 8)


def test_plan_transfer_region_input_and_permute():
    regions4 = reshard.regions_for([48, 8], reshard.ShardSpec(0, 4))
    p = reshard.plan_transfer([48, 8], "float32", regions4, regions4)
    assert p.kind == "keep" and p.steps == []
    rot = regions4[1:] + regions4[:1]
    p2 = reshard.plan_transfer([48, 8], "float32", regions4, rot)
    assert p2.kind == "permute" and p2.collectives == ["collective_permute"]


def test_apply_transfer_device_round_trips():
    """The lowering door: gather / slice / alltoall executed with real
    collectives on a 4-device CPU mesh reproduce the array exactly."""
    import jax.numpy as jnp  # noqa: F401
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as JP
    from jax import shard_map
    ck = {"check_vma": False}
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    x = np.arange(48 * 8, dtype=np.float32).reshape(48, 8)
    S = reshard.ShardSpec

    def run(plan, in_spec, out_spec, val):
        fn = jax.jit(shard_map(
            lambda xl: reshard.apply_transfer(xl, plan, "dp"),
            mesh=mesh, in_specs=in_spec, out_specs=out_spec, **ck))
        return np.asarray(fn(jax.device_put(
            val, NamedSharding(mesh, in_spec))))

    gather = reshard.plan_transfer(x.shape, "float32", S(0, 4), S(None))
    assert np.array_equal(run(gather, JP("dp"), JP(), x), x)
    sl = reshard.plan_transfer(x.shape, "float32", S(None), S(0, 4))
    assert np.array_equal(run(sl, JP(), JP("dp"), x), x)
    a2a = reshard.plan_transfer(x.shape, "float32", S(0, 4), S(1, 4))
    assert np.array_equal(run(a2a, JP("dp", None), JP(None, "dp"), x), x)


def test_reshard_op_is_a_collective():
    from paddle_tpu.ops.collective import COLLECTIVE_OPS, is_collective
    assert is_collective("reshard")
    assert COLLECTIVE_OPS["reshard"]["comm"] == "reshard"


# -------------------------------------------------------------- rewrite --

def _toy_program(grad_shape=(256, 256)):
    p = Program()
    gb = p.global_block()
    gb.create_parameter("w", grad_shape, "float32")
    gb.create_var("w@GRAD", grad_shape, "float32")
    gb.create_var("lr", (1,), "float32", persistable=True)
    gb.append_op("matmul", inputs={"X": ["w"], "Y": ["w"]},
                 outputs={"Out": ["w@GRAD"]}, infer_shape=False)
    gb.append_op("sgd", inputs={"Param": ["w"], "Grad": ["w@GRAD"],
                                "LearningRate": ["lr"]},
                 outputs={"ParamOut": ["w"]}, infer_shape=False)
    return p


def _cp(p, mode, dp=2, min_bytes=0, reduce_mode=False):
    ds = fluid.DistributedStrategy(mesh_shape={"dp": dp})
    ds.comm_compression = mode
    ds.comm_compress_min_bytes = min_bytes
    bs = fluid.BuildStrategy()
    if reduce_mode:
        bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    return fluid.CompiledProgram(p, build_strategy=bs).with_strategy(ds)


def test_rewrite_inserts_sync_and_residual_idempotently():
    p = _toy_program()
    cp = _cp(p, "int8")
    info = rewrite.sync_program(p, cp)
    assert info["compressed"] == ["w@GRAD"]
    syncs = [op for op in p.global_block().ops
             if op.attr(rewrite.SYNC_ATTR)]
    assert len(syncs) == 1 and syncs[0].type == "c_allreduce_avg"
    assert syncs[0].attr("comm_compress") == "int8"
    res = p.global_block().vars[compress.residual_name("w@GRAD")]
    assert res.persistable and res.shape == (2, 256, 256)
    # sync op sits AFTER the grad's producer, BEFORE the optimizer
    ops = [op.type for op in p.global_block().ops]
    assert ops.index("c_allreduce_avg") == ops.index("sgd") - 1
    v = p._version
    assert rewrite.sync_program(p, cp) == info
    assert p._version == v    # warm re-sync: zero mutation


def test_rewrite_strips_on_mode_off_and_world_1():
    p = _toy_program()
    rewrite.sync_program(p, _cp(p, "int8"))
    assert any(op.attr(rewrite.SYNC_ATTR) for op in p.global_block().ops)
    assert rewrite.sync_program(p, _cp(p, "off")) is None
    assert not any(op.attr(rewrite.SYNC_ATTR)
                   for op in p.global_block().ops)
    assert not any(compress.is_residual(n) for n in p.global_block().vars)
    # world 1: the short-circuit -- never rewritten at all
    p2 = _toy_program()
    assert rewrite.sync_program(p2, _cp(p2, "int8", dp=1)) is None
    assert not any(op.attr(rewrite.SYNC_ATTR)
                   for op in p2.global_block().ops)


def test_rewrite_falls_back_under_zero_and_respects_floor():
    p = _toy_program()
    with pytest.warns(UserWarning, match="ReduceStrategy.Reduce"):
        assert rewrite.sync_program(
            p, _cp(p, "int8", reduce_mode=True)) is None
    # floor: tensor below min_bytes syncs explicitly but uncompressed
    p2 = _toy_program()
    info = rewrite.sync_program(p2, _cp(p2, "int8", min_bytes=1 << 30))
    assert info is not None and info["compressed"] == []
    op, = [o for o in p2.global_block().ops if o.attr(rewrite.SYNC_ATTR)]
    assert op.attr("comm_compress") == "off"
    assert "ResidualIn" not in op.inputs


def test_comm_compress_tunable_choice():
    from paddle_tpu import tuning
    small = {"nbytes": 1024, "dtype": "float32", "world": 4,
             "mode": "int8", "min_bytes": 65536}
    big = dict(small, nbytes=1 << 20)
    ch = tuning.get_choice("comm.compress")
    assert ch.candidates(small) == ["off"]      # under the floor: no 'on'
    assert ch.candidates(big) == ["off", "on"]
    assert tuning.decide("comm.compress", small, allow_search=False) == "off"
    assert tuning.decide("comm.compress", big, allow_search=False) == "on"
    assert ch.candidates(dict(big, world=1)) == ["off"]
    # an externally measured decision overrides the default
    tuning.record_decision("comm.compress", big, "off",
                           timings={"on": 2.0, "off": 1.0})
    assert tuning.decide("comm.compress", big, allow_search=False) == "off"


def test_strategy_knob_validation_and_round_trip():
    ds = fluid.DistributedStrategy(mesh_shape={"dp": 4},
                                   comm_compression="bf16")
    with pytest.raises(ValueError, match="comm_compression"):
        ds.comm_compression = "fp8"
    ds.comm_compress_min_bytes = 123
    d = ds.to_dict()
    ds2 = fluid.DistributedStrategy.from_dict(d)
    assert ds2.comm_compression == "bf16"
    assert ds2.comm_compress_min_bytes == 123
    # the knob keys the executor's compile cache
    p = _toy_program()
    s1 = fluid.CompiledProgram(p).with_strategy(ds).strategy_signature()
    ds3 = fluid.DistributedStrategy.from_dict(d)
    ds3.comm_compression = "off"
    s2 = fluid.CompiledProgram(p).with_strategy(ds3).strategy_signature()
    assert s1 != s2


# ------------------------------------------------- end-to-end training --

def _build_mlp(seed=11):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [32], "float32")
        label = fluid.data("label", [1], "int64")
        h = fluid.layers.fc(x, 64, act="relu")
        logits = fluid.layers.fc(h, 10)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)
    return main, startup, loss


def _train(mode, dp=2, steps=10, min_bytes=0):
    main, startup, loss = _build_mlp()
    cp = _cp(main, mode, dp=dp, min_bytes=min_bytes)
    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    W = rng.randn(32, 10).astype("float32")
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(steps):
            gx = rng.randn(16, 32).astype("float32")
            gy = np.argmax(gx @ W, 1)[:, None].astype("int64")
            lv, = exe.run(cp, feed={"x": gx, "label": gy},
                          fetch_list=[loss], return_numpy=True)
            losses.append(np.asarray(lv).reshape(()))
    return np.asarray(losses, np.float32)


def test_explicit_dp_uncompressed_matches_gspmd_exactly():
    """The formulation swap alone (implicit GSPMD reduction -> explicit
    per-shard grads + c_allreduce_avg) must not move the numbers: with
    every tensor under the floor the loss curve is byte-identical."""
    off = _train("off")
    explicit = _train("int8", min_bytes=1 << 30)
    assert off.tobytes() == explicit.tobytes()


def test_int8_error_feedback_convergence_parity():
    """The acceptance pin: int8 + error feedback tracks the f32 loss
    curve within the pinned tolerance (measured 6e-4 over 10 steps on
    this workload; pinned at 5e-3 for cross-platform slack)."""
    off = _train("off")
    i8 = _train("int8")
    assert np.abs(i8 - off).max() <= 5e-3, np.abs(i8 - off).max()
    # and it genuinely compressed: residuals existed, metrics flowed
    fam = REGISTRY.get("comm_bytes_total")
    assert fam is not None
    kinds = {dict(labels) ["kind"]: c.value for labels, c in fam.items()
             if dict(labels)["dtype"] == "int8"}
    assert kinds.get("allreduce", 0) > 0


def test_bf16_mode_tracks_and_is_byte_stable():
    off = _train("off")
    b1 = _train("bf16")
    b2 = _train("bf16")
    assert b1.tobytes() == b2.tobytes()     # deterministic across runs
    assert np.abs(b1 - off).max() <= 5e-3


def test_world_1_compressed_is_byte_identical_to_off():
    off = _train("off", dp=1)
    i8 = _train("int8", dp=1)
    assert off.tobytes() == i8.tobytes()


def test_compress_ratio_gauge_exported():
    fam = REGISTRY.get("comm_compress_ratio")
    assert fam is not None
    vals = [c.value for _, c in fam.items()]
    assert vals and vals[0] > 1.0


def test_residuals_survive_in_scope_and_skip_checkpoints(tmp_path):
    """Residual state persists across steps in the scope (error feedback
    needs it) but never lands in a checkpoint: its (ndp, ...) shape pins
    the world size, and a fresh zero residual after restore is
    harmless."""
    main, startup, loss = _build_mlp()
    cp = _cp(main, "int8")
    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    W = rng.randn(32, 10).astype("float32")
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        for _ in range(3):
            gx = rng.randn(16, 32).astype("float32")
            gy = np.argmax(gx @ W, 1)[:, None].astype("int64")
            exe.run(cp, feed={"x": gx, "label": gy}, fetch_list=[loss])
        res_names = [n for n in sc.var_names() if compress.is_residual(n)]
        assert res_names, "residuals must live in the scope"
        r = np.asarray(sc.find_var(res_names[0]))
        assert r.shape[0] == 2 and np.abs(r).max() > 0   # real feedback
        fluid.io.save_persistables(exe, str(tmp_path), cp)
    saved = [f for f in os.listdir(tmp_path)]
    assert not any("comm_residual" in f for f in saved), saved


def test_knob_off_strips_rewrite_through_executor():
    """Review regression: turning comm_compression back OFF on an
    already-rewritten program must strip the rewrite at the next run and
    revert to the GSPMD path -- not keep quantizing forever."""
    main, startup, loss = _build_mlp()
    ds = fluid.DistributedStrategy(mesh_shape={"dp": 2})
    ds.comm_compression = "int8"
    ds.comm_compress_min_bytes = 0
    cp = fluid.CompiledProgram(main).with_strategy(ds)
    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    W = rng.randn(32, 10).astype("float32")

    def step():
        gx = rng.randn(16, 32).astype("float32")
        gy = np.argmax(gx @ W, 1)[:, None].astype("int64")
        lv, = exe.run(cp, feed={"x": gx, "label": gy}, fetch_list=[loss])
        return np.asarray(lv).reshape(())

    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        step()
        assert getattr(main, "_comm_explicit", None) is not None
        ds.comm_compression = "off"
        step()
        assert getattr(main, "_comm_explicit", None) is None
        assert not any(op.attr(rewrite.SYNC_ATTR)
                       for op in main.global_block().ops)


def test_explicit_mode_batch_fetch_matches_gspmd():
    """Review regression: a fetch with a batch dim (per-row predictions)
    must come back as the FULL global batch under the explicit-dp path,
    exactly like the GSPMD fetch -- not a per-shard slice of
    cross-sample pmeans."""
    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.data("x", [8], "float32")
            y = fluid.layers.fc(x, 4)
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGD(0.01).minimize(loss)
        return main, startup, y, loss

    feed = {"x": np.arange(16 * 8, dtype=np.float32).reshape(16, 8)}

    def run(mode):
        main, startup, y, loss = build()
        ds = fluid.DistributedStrategy(mesh_shape={"dp": 2})
        ds.comm_compression = mode
        ds.comm_compress_min_bytes = 1 << 30   # nothing compresses
        cp = fluid.CompiledProgram(main).with_strategy(ds)
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            out, = exe.run(cp, feed=feed, fetch_list=[y])
        return np.asarray(out)

    gspmd = run("off")
    explicit = run("int8")
    assert gspmd.shape == (16, 4)
    assert explicit.shape == (16, 4)
    np.testing.assert_allclose(explicit, gspmd, rtol=1e-6)


def test_permute_plan_carries_real_mapping():
    """Review regression: an arbitrary rank reassignment (not a rotation)
    must ride the plan as explicit ppermute pairs."""
    regions = reshard.regions_for([48, 8], reshard.ShardSpec(0, 3))
    swapped = [regions[1], regions[0], regions[2]]   # swap ranks 0 and 1
    p = reshard.plan_transfer([48, 8], "float32", regions, swapped)
    assert p.kind == "permute"
    s, = p.steps
    # src rank 0's region is now owned by dst rank 1 and vice versa
    assert sorted(s.perm) == [[0, 1], [1, 0], [2, 2]]


def test_stale_residual_rezeroed_on_world_resize():
    """Review regression: a residual left in the scope at an old world
    size (e.g. staged by a sync before the world changed) must be
    re-zeroed to the new (ndp, ...) shape at run time, not dispatched
    stale.  (Device state from an old mesh is a fresh-process/restore
    flow -- residuals are the one state the executor owns end to end.)"""
    main, startup, loss = _build_mlp()
    # stage the rewrite at world 2, seeding a (2, ...) residual var
    rewrite.sync_program(main, _cp(main, "int8", dp=2))
    res = next(n for n in main.global_block().vars
               if compress.is_residual(n))
    stale = np.ones(tuple(main.global_block().vars[res].shape), "float32")
    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    W = rng.randn(32, 10).astype("float32")
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        sc.set_var(res, stale)           # world-2-shaped host residual
        cp4 = _cp(main, "int8", dp=4)    # world is now 4
        gx = rng.randn(16, 32).astype("float32")
        gy = np.argmax(gx @ W, 1)[:, None].astype("int64")
        exe.run(cp4, feed={"x": gx, "label": gy}, fetch_list=[loss])
        assert np.shape(sc.find_var(res))[0] == 4


def test_orphan_gradient_falls_back_to_gspmd():
    """Review regression: an optimizer Grad input no global-block op
    writes (fed external gradients) cannot be synced in-step -- the
    rewrite must fall back to GSPMD with a warning, not crash."""
    p = Program()
    gb = p.global_block()
    gb.create_parameter("w", (64, 64), "float32")
    gb.create_var("g_ext", (64, 64), "float32", is_data=True)
    gb.create_var("lr", (1,), "float32", persistable=True)
    gb.append_op("sgd", inputs={"Param": ["w"], "Grad": ["g_ext"],
                                "LearningRate": ["lr"]},
                 outputs={"ParamOut": ["w"]}, infer_shape=False)
    with pytest.warns(UserWarning, match="no\\s+global-block producer"):
        assert rewrite.sync_program(p, _cp(p, "int8")) is None
    assert not any(op.attr(rewrite.SYNC_ATTR) for op in gb.ops)


def test_explicit_mode_static_batch_fetch_matches_gspmd():
    """Review regression: a batch-carrying fetch with a STATIC declared
    leading dim (append_batch_size=False style) must also reassemble the
    full global batch, not fall into the pmean branch."""
    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.data("x", [16, 8], "float32",
                           append_batch_size=False)
            y = fluid.layers.fc(x, 4)
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGD(0.01).minimize(loss)
        return main, startup, y, loss

    feed = {"x": np.arange(16 * 8, dtype=np.float32).reshape(16, 8)}

    def run(mode):
        main, startup, y, loss = build()
        cp = _cp(main, mode, dp=2, min_bytes=1 << 30)
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            out, = exe.run(cp, feed=feed, fetch_list=[y])
        return np.asarray(out)

    gspmd = run("off")
    explicit = run("int8")
    assert gspmd.shape == explicit.shape == (16, 4)
    np.testing.assert_allclose(explicit, gspmd, rtol=1e-6)


def test_explicit_mode_dropout_draws_per_shard_streams():
    """Review regression: stochastic ops under the explicit path fold
    the shard index into the key (identical masks across dp shards would
    correlate the noise); the run must train with finite losses."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [32], "float32")
        label = fluid.data("label", [1], "int64")
        h = fluid.layers.dropout(fluid.layers.fc(x, 64, act="relu"), 0.5)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(h, 10), label))
        fluid.optimizer.SGD(0.05).minimize(loss)
    cp = _cp(main, "int8", dp=2)
    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(4):
            gx = rng.randn(16, 32).astype("float32")
            gy = rng.randint(0, 10, (16, 1)).astype("int64")
            lv, = exe.run(cp, feed={"x": gx, "label": gy},
                          fetch_list=[loss])
            assert np.isfinite(np.asarray(lv)).all()


# ------------------------------------------------------------- bench leg --

def test_bench_comm_sweep_rows_and_reductions(tmp_path):
    """The --comm-sweep leg: one row per (size, mode) with effective
    (pre-compression) bandwidth and the cost model's on-wire reduction --
    int8 ~4x, bf16 2x (the TPU-expected gain the CPU-flat host
    documents)."""
    sys.path.insert(0, REPO)
    import bench
    out = tmp_path / "sweep.json"
    doc = bench.bench_comm_sweep(sizes_mb=(1,), out_path=str(out))
    assert "error" not in doc, doc
    assert [r["mode"] for r in doc["rows"]] == ["off", "bf16", "int8"]
    by = {r["mode"]: r for r in doc["rows"]}
    assert by["off"]["wire_reduction_vs_f32"] == 1.0
    assert by["bf16"]["wire_reduction_vs_f32"] == 2.0
    assert by["int8"]["wire_reduction_vs_f32"] >= 3.9
    assert all(r["effective_gbps"] > 0 for r in doc["rows"])
    import json as _json
    assert _json.load(open(out))["wire_reduction_bf16"] == 2.0


def test_bench_comm_artifact_checked_in():
    """BENCH_COMM_r01.json (the recorded sweep round) demonstrates the
    acceptance gain: >=1.9x on-wire reduction at >=16 MB for int8 (the
    bandwidth-flat-CPU clause; on TPU the effective-bandwidth column
    carries the same factor)."""
    import json as _json
    doc = _json.load(open(os.path.join(REPO, "BENCH_COMM_r01.json")))
    assert doc["n_devices"] >= 2
    at16 = [r for r in doc["rows"]
            if r["mbytes"] >= 16 and r["mode"] == "int8"]
    assert at16 and all(r["wire_reduction_vs_f32"] >= 1.9 for r in at16)
    assert {r["mbytes"] for r in doc["rows"]} >= {1, 16, 256}


# ------------------------------------------------------------------ CLI --

@pytest.mark.smoke
def test_cli_selftest():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-m", "paddle_tpu.comm",
                          "--selftest"], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 failure(s)" in out.stdout
