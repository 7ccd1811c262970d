"""Distributed static analysis (PT04x) + static memory planner (PT05x):
every new code pinned by a minimal program, the bundled model zoo verified
clean under dp8/mp/pp strategies, the planner's estimate pinned within 2x
of XLA's memory_analysis() on mnist/resnet/transformer, the executor gate's
strategy pass-through and PADDLE_TPU_MEM_BUDGET, the CLI --strategy/
--mem-budget/--baseline doors, README codes-table drift, and the multihost
demonstration that a PT041 program really deadlocks/errors multi-rank."""
import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis
from paddle_tpu.analysis import Severity, VerificationError
from paddle_tpu.analysis.__main__ import main as cli_main
from paddle_tpu.framework import Program

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes(diags):
    return {d.code for d in diags}


def errors(diags):
    return [d for d in diags if d.severity == Severity.ERROR]


def dp8():
    return fluid.DistributedStrategy(mesh_shape={"dp": 8})


# ------------------------------------------------------------ PT040 pins --

def test_pt040_collective_axis_not_in_mesh():
    p = Program()
    b = p.global_block()
    b.create_var("x", (8, 4), "float32", is_data=True)
    b.append_op("c_allreduce_sum", inputs={"X": ["x"]},
                outputs={"Out": ["y"]}, attrs={"axis_name": "mp"},
                infer_shape=False)
    diags = analysis.verify(p, strategy=dp8())
    d = next(d for d in diags if d.code == "PT040")
    assert d.severity == "error" and d.var == "mp"
    # same program, mesh that HAS the axis: clean
    ok = fluid.DistributedStrategy(mesh_shape={"dp": 2, "mp": 4})
    assert "PT040" not in codes(analysis.verify(p, strategy=ok))
    # and without a strategy the check has no mesh to judge against
    assert "PT040" not in codes(analysis.verify(p))


def test_pt040_default_axis_and_temporal_pipeline():
    # default axis_name is "dp"; an mp-only mesh misses it
    p = Program()
    b = p.global_block()
    b.create_var("x", (8, 4), "float32", is_data=True)
    b.append_op("c_allreduce_sum", inputs={"X": ["x"]},
                outputs={"Out": ["y"]}, infer_shape=False)
    mp_only = fluid.DistributedStrategy(mesh_shape={"mp": 8},
                                        data_axis="mp")
    assert "PT040" in codes(analysis.verify(p, strategy=mp_only))
    # temporal_pipeline communicates over its "axis" attr (default "pp")
    p2 = Program()
    b2 = p2.global_block()
    b2.create_var("x", (8, 4), "float32", is_data=True)
    b2.append_op("temporal_pipeline", inputs={"X": ["x"]},
                 outputs={"Out": ["y"]},
                 attrs={"sub_block": 0, "num_stages": 2},
                 infer_shape=False)
    assert "PT040" in codes(analysis.verify(p2, strategy=dp8()))
    pp = fluid.DistributedStrategy(mesh_shape={"dp": 4, "pp": 2})
    assert "PT040" not in codes(analysis.verify(p2, strategy=pp))


# ------------------------------------------------------------ PT041 pins --

def _cond_with_collective(coll="c_allreduce_sum", while_instead=False,
                          max_iters=None):
    p = Program()
    gb = p.global_block()
    gb.create_var("x", (8, 4), "float32", is_data=True)
    gb.create_var("c", (1,), "bool", is_data=True)
    sub = p._create_block()
    sub.append_op(coll, inputs={"X": ["x"]}, outputs={"Out": ["r"]},
                  infer_shape=False)
    p._rollback()
    if while_instead:
        attrs = {"sub_block": sub.idx, "cond_name": "c",
                 "x_names": ["x", "c"], "out_names": ["r"]}
        if max_iters is not None:
            attrs["max_iters"] = max_iters
        gb.append_op("while", inputs={"X": ["x", "c"]},
                     outputs={"Out": ["o"]}, attrs=attrs, infer_shape=False)
    else:
        gb.append_op("conditional_block",
                     inputs={"Cond": ["c"], "X": ["x"]},
                     outputs={"Out": ["o"]},
                     attrs={"sub_block": sub.idx, "x_names": ["x"],
                            "out_names": ["r"]}, infer_shape=False)
    return p


def test_pt041_collective_in_cond_branch():
    diags = analysis.verify(_cond_with_collective())
    d = next(d for d in diags if d.code == "PT041")
    assert d.severity == "error" and d.op_type == "c_allreduce_sum"
    assert "deadlock" in d.message


def test_pt041_collective_in_unbounded_while():
    assert "PT041" in codes(analysis.verify(
        _cond_with_collective(while_instead=True)))


def test_pt041_bounded_while_is_uniform():
    """max_iters lowers to a masked scan of fixed length: every rank runs
    every iteration, the collective stays synchronized -- no finding."""
    assert "PT041" not in codes(analysis.verify(
        _cond_with_collective(while_instead=True, max_iters=5)))


def test_pt041_divergence_is_transitive():
    """A scan nested inside a cond branch is still divergent context."""
    p = Program()
    gb = p.global_block()
    gb.create_var("x", (8, 4), "float32", is_data=True)
    gb.create_var("c", (1,), "bool", is_data=True)
    cond_blk = p._create_block()
    p._rollback()
    scan_blk = p._create_block()
    scan_blk.append_op("c_allreduce_sum", inputs={"X": ["x"]},
                       outputs={"Out": ["r"]}, infer_shape=False)
    p._rollback()
    cond_blk.append_op("scan", inputs={"Init": ["x"]},
                       outputs={"Out": ["s"]},
                       attrs={"sub_block": scan_blk.idx,
                              "carry_names": ["x"], "out_names": ["r"]},
                       infer_shape=False)
    gb.append_op("conditional_block", inputs={"Cond": ["c"], "X": ["x"]},
                 outputs={"Out": ["o"]},
                 attrs={"sub_block": cond_blk.idx, "x_names": ["x"],
                        "out_names": ["s"]}, infer_shape=False)
    assert "PT041" in codes(analysis.verify(p))
    # the same scan at the top level is uniform: no finding
    p2 = Program()
    gb2 = p2.global_block()
    gb2.create_var("x", (8, 4), "float32", is_data=True)
    sb = p2._create_block()
    sb.append_op("c_allreduce_sum", inputs={"X": ["x"]},
                 outputs={"Out": ["r"]}, infer_shape=False)
    p2._rollback()
    gb2.append_op("scan", inputs={"Init": ["x"]}, outputs={"Out": ["s"]},
                  attrs={"sub_block": sb.idx, "carry_names": ["x"],
                         "out_names": ["r"]}, infer_shape=False)
    assert "PT041" not in codes(analysis.verify(p2))


# ------------------------------------------------------------ PT042 pins --

def _staged_program(stage1_extra_collective):
    p = Program()
    b = p.global_block()
    b.create_var("x", (8, 4), "float32", is_data=True)
    with fluid.framework.device_guard("stage:0"):
        b.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["h0"]})
        b.append_op("c_allreduce_sum", inputs={"X": ["h0"]},
                    outputs={"Out": ["r0"]}, infer_shape=False)
    with fluid.framework.device_guard("stage:1"):
        b.append_op("relu", inputs={"X": ["r0"]}, outputs={"Out": ["h1"]},
                    infer_shape=False)
        if stage1_extra_collective:
            b.append_op("c_allreduce_sum", inputs={"X": ["h1"]},
                        outputs={"Out": ["r1"]}, infer_shape=False)
            b.append_op("c_allreduce_max", inputs={"X": ["r1"]},
                        outputs={"Out": ["r2"]}, infer_shape=False)
        else:
            b.append_op("c_allreduce_sum", inputs={"X": ["h1"]},
                        outputs={"Out": ["r1"]}, infer_shape=False)
    return p


def test_pt042_stage_collective_mismatch():
    diags = analysis.verify(_staged_program(stage1_extra_collective=True))
    d = next(d for d in diags if d.code == "PT042")
    assert d.severity == "error"
    assert "stage 1" in d.message and "stage 0" in d.message


def test_pt042_matching_stages_clean():
    assert "PT042" not in codes(analysis.verify(
        _staged_program(stage1_extra_collective=False)))


# ----------------------------------------------------- PT043/044/045 pins --

def test_pt043_rule_names_unknown_axis():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [16], "float32")
        y = fluid.layers.fc(x, 8)
        loss = fluid.layers.mean(y)
    strat = fluid.DistributedStrategy(
        mesh_shape={"dp": 8}, param_rules=[(r"fc_0\.w_0$", ("tp",))])
    diags = analysis.verify(main, fetch_names=[loss.name], strategy=strat)
    d = next(d for d in diags if d.code == "PT043")
    assert d.severity == "error" and d.var == "fc_0.w_0"


def test_pt044_spec_on_missing_dim():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [16], "float32")
        y = fluid.layers.fc(x, 8)
        loss = fluid.layers.mean(y)
    # 3 spec entries on a 2-D weight: the compiler silently replicates
    strat = fluid.DistributedStrategy(
        mesh_shape={"dp": 2, "mp": 4},
        param_rules=[(r"fc_0\.w_0$", (None, None, "mp"))])
    diags = analysis.verify(main, fetch_names=[loss.name], strategy=strat)
    assert any(d.code == "PT044" and d.var == "fc_0.w_0" for d in diags)
    # data rule with an entry beyond the var's rank
    strat2 = fluid.DistributedStrategy(
        mesh_shape={"dp": 8}, data_rules=[(r"^x$", ("dp", None, "dp"))])
    assert any(d.code == "PT044" and d.var == "x" for d in
               analysis.verify(main, fetch_names=[loss.name],
                               strategy=strat2))


def test_pt044_derived_accumulator_exempt():
    """A name-prefix rule that also matches Adam's lower-rank beta-pow
    accumulators must not fire PT044 on them: the compiler's documented
    behavior is to replicate those (compiler.py state_sharding)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [16], "float32")
        y = fluid.layers.fc(x, 8)
        loss = fluid.layers.mean(y)
        fluid.optimizer.Adam(0.01).minimize(loss)
    # matches fc_0.w_0 AND its derived accumulators by prefix
    strat = fluid.DistributedStrategy(
        mesh_shape={"dp": 2, "mp": 4},
        param_rules=[(r"fc_0\.w_0", (None, "mp"))])
    diags = analysis.verify(main, feed_names=["x"],
                            fetch_names=[loss.name], strategy=strat)
    assert not any(d.code == "PT044" for d in diags), \
        [d.format() for d in diags if d.code == "PT044"]


def test_pt045_uneven_divisibility():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [16], "float32")
        y = fluid.layers.fc(x, 10)  # weight [16, 10]
        loss = fluid.layers.mean(y)
    # 10 % 4 != 0: sharding the output dim over mp=4 is illegal
    strat = fluid.DistributedStrategy(
        mesh_shape={"dp": 2, "mp": 4},
        param_rules=[(r"fc_0\.w_0$", (None, "mp"))])
    diags = analysis.verify(main, fetch_names=[loss.name], strategy=strat)
    d = next(d for d in diags if d.code == "PT045")
    assert d.severity == "error" and d.var == "fc_0.w_0"
    # 16 % 4 == 0: sharding the input dim is fine
    ok = fluid.DistributedStrategy(
        mesh_shape={"dp": 2, "mp": 4},
        param_rules=[(r"fc_0\.w_0$", ("mp", None))])
    assert "PT045" not in codes(
        analysis.verify(main, fetch_names=[loss.name], strategy=ok))


def test_pt045_data_batch_divisibility_with_batch():
    p = Program()
    b = p.global_block()
    b.create_var("x", (-1, 4), "float32", is_data=True)
    b.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]})
    # batch 12 does not divide dp=8 -> error; without batch: unknowable
    assert "PT045" in codes(analysis.verify(p, strategy=dp8(), batch=12))
    assert "PT045" not in codes(analysis.verify(p, strategy=dp8()))
    assert "PT045" not in codes(analysis.verify(p, strategy=dp8(),
                                                batch=16))


# ------------------------------------------------------------ PT046 pins --

def _reduce_strategy_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [16], "float32")
        y = fluid.layers.fc(x, 8)
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGD(0.01).minimize(loss)
    return main, loss


def test_pt046_reduce_params_regather_warn():
    main, loss = _reduce_strategy_program()
    bs = fluid.BuildStrategy()
    bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    bs.reduce_params = True
    cp = fluid.CompiledProgram(main, build_strategy=bs).with_strategy(
        fluid.DistributedStrategy(mesh_shape={"dp": 8}))
    diags = analysis.verify(main, feed_names=["x"],
                            fetch_names=[loss.name], strategy=cp)
    d = next(d for d in diags if d.code == "PT046")
    assert d.severity == "warn"
    assert "all-gather" in d.message and "bytes re-gathered" in d.message
    # fc_0.w_0 is 16x8 f32 = 512 bytes; the estimate counts it
    assert "fc_0.w_0" in d.message
    # plain AllReduce mode: no warning
    cp2 = fluid.CompiledProgram(main).with_strategy(
        fluid.DistributedStrategy(mesh_shape={"dp": 8}))
    assert "PT046" not in codes(analysis.verify(
        main, feed_names=["x"], fetch_names=[loss.name], strategy=cp2))


def test_pt047_hardcoded_batch_pins_world_size():
    """Elastic-incompatibility lint: a data var whose batch dim is
    hardcoded to a multiple of the current dp degree works today but
    breaks on the first resize -- warn before the first kill."""
    p = Program()
    b = p.global_block()
    b.create_var("x", (16, 4), "float32", is_data=True)   # 16 % 8 == 0
    b.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]})
    diags = analysis.verify(p, strategy=dp8())
    d = next(d for d in diags if d.code == "PT047")
    assert d.severity == "warn" and d.var == "x"
    assert "elastic" in d.message and "-1" in d.message
    # dynamic batch dim: resize-safe, no warning
    p2 = Program()
    b2 = p2.global_block()
    b2.create_var("x", (-1, 4), "float32", is_data=True)
    b2.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]})
    assert "PT047" not in codes(analysis.verify(p2, strategy=dp8()))
    # indivisible batch is PT045's error, not a second PT047
    p3 = Program()
    b3 = p3.global_block()
    b3.create_var("x", (12, 4), "float32", is_data=True)   # 12 % 8 != 0
    b3.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]})
    d3 = analysis.verify(p3, strategy=dp8())
    assert "PT045" in codes(d3) and "PT047" not in codes(d3)


def test_pt047_needs_explicit_mesh_and_sharded_batch():
    p = Program()
    b = p.global_block()
    b.create_var("x", (16, 4), "float32", is_data=True)
    b.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]})
    # default mesh (dp = device count): unknown statically, no warning
    assert "PT047" not in codes(analysis.verify(
        p, strategy=fluid.DistributedStrategy()))
    # batch dim explicitly replicated by a data rule: resize-safe
    unsharded = fluid.DistributedStrategy(
        mesh_shape={"dp": 8}, data_rules=[(r"^x$", (None, None))])
    got = codes(analysis.verify(p, strategy=unsharded))
    assert "PT047" not in got, got


def test_pt046_regather_message_carries_priced_plan():
    """ISSUE 15: the PT046 finding names the concrete collective plan
    (the shared comm.plan_transfer decomposition) with priced per-device
    wire bytes -- and prices the compressed variant when the strategy
    sets comm_compression."""
    main, loss = _reduce_strategy_program()
    bs = fluid.BuildStrategy()
    bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    bs.reduce_params = True
    ds = fluid.DistributedStrategy(mesh_shape={"dp": 8})
    cp = fluid.CompiledProgram(main, build_strategy=bs).with_strategy(ds)
    diags = analysis.verify(main, feed_names=["x"],
                            fetch_names=[loss.name], strategy=cp)
    d = next(d for d in diags if d.code == "PT046")
    assert "plan per param per step" in d.message
    assert "all_gather" in d.message and "B/device" in d.message
    # fc_0.w_0 is 16x8 f32 = 512 B; all_gather at dp=8 = (7/8)*512 = 448
    assert "448" in d.message, d.message
    # with compression set, the compressed pricing rides along
    ds.comm_compression = "bf16"
    cp2 = fluid.CompiledProgram(main, build_strategy=bs).with_strategy(ds)
    diags2 = analysis.verify(main, feed_names=["x"],
                             fetch_names=[loss.name], strategy=cp2)
    d2 = next(d for d in diags2 if d.code == "PT046")
    assert "compressed (bf16)" in d2.message


# ------------------------------------------------------------ PT048 pins --

def test_pt048_int8_unsupported_grad_dtype_warns():
    """comm_compression=int8 + a gradient dtype outside the quantizer's
    support: the lowering silently stays uncompressed -- PT048 makes it
    visible at lint time."""
    p = Program()
    b = p.global_block()
    b.create_var("w", (64, 64), "float64", persistable=True)
    b.create_var("w@GRAD", (64, 64), "float64")
    b.create_var("lr", (1,), "float32", persistable=True)
    b.append_op("matmul", inputs={"X": ["w"], "Y": ["w"]},
                outputs={"Out": ["w@GRAD"]}, infer_shape=False)
    b.append_op("sgd", inputs={"Param": ["w"], "Grad": ["w@GRAD"],
                               "LearningRate": ["lr"]},
                outputs={"ParamOut": ["w"]}, infer_shape=False)
    ds = fluid.DistributedStrategy(mesh_shape={"dp": 4})
    ds.comm_compression = "int8"
    diags = analysis.verify(p, strategy=ds)
    d = next(d for d in diags if d.code == "PT048")
    assert d.severity == "warn" and d.var == "w@GRAD"
    assert "float64" in d.message and "uncompressed" in d.message
    # supported dtype: no warning
    p2 = Program()
    b2 = p2.global_block()
    b2.create_var("w", (64, 64), "float32", persistable=True)
    b2.create_var("w@GRAD", (64, 64), "float32")
    b2.create_var("lr", (1,), "float32", persistable=True)
    b2.append_op("matmul", inputs={"X": ["w"], "Y": ["w"]},
                 outputs={"Out": ["w@GRAD"]}, infer_shape=False)
    b2.append_op("sgd", inputs={"Param": ["w"], "Grad": ["w@GRAD"],
                                "LearningRate": ["lr"]},
                 outputs={"ParamOut": ["w"]}, infer_shape=False)
    assert "PT048" not in codes(analysis.verify(p2, strategy=ds))
    # mode off/bf16: int8-specific check never fires
    ds2 = fluid.DistributedStrategy(mesh_shape={"dp": 4})
    assert "PT048" not in codes(analysis.verify(p, strategy=ds2))


def test_pt048_explicit_allreduce_input_dtype():
    p = Program()
    b = p.global_block()
    b.create_var("x", (8, 4), "int64", is_data=True)
    b.append_op("c_allreduce_sum", inputs={"X": ["x"]},
                outputs={"Out": ["y"]}, infer_shape=False)
    ds = fluid.DistributedStrategy(mesh_shape={"dp": 4})
    ds.comm_compression = "int8"
    diags = analysis.verify(p, strategy=ds)
    assert any(d.code == "PT048" and d.var == "x" for d in diags)


def test_memplan_accounts_comm_residual_overhead():
    """The static planner adds the error-feedback residual bytes
    comm_compression will materialize (1/ndp per device) -- before the
    rewrite runs, so a budget check prices the real footprint."""
    main, loss = _reduce_strategy_program()
    ds = fluid.DistributedStrategy(mesh_shape={"dp": 8})
    base = analysis.estimate_program_memory(
        main, feed_names=["x"], fetch_names=[loss.name],
        strategy=ds, batch=8)
    ds2 = fluid.DistributedStrategy(mesh_shape={"dp": 8})
    ds2.comm_compression = "int8"
    ds2.comm_compress_min_bytes = 0
    est = analysis.estimate_program_memory(
        main, feed_names=["x"], fetch_names=[loss.name],
        strategy=ds2, batch=8)
    # fc grads: 16x8 w + 8 b = 136 floats = 544 B of residual per device
    assert est.arg_bytes == base.arg_bytes + 544, \
        (est.arg_bytes, base.arg_bytes)


def test_pt046_unshardable_state_warn():
    """Reduce mode with an accumulator no dim of which divides dp: the
    ZeRO memory win silently doesn't happen -- warn."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [9], "float32")
        y = fluid.layers.fc(x, 9)   # weight [9, 9]: 9 % 8 != 0, 9 > 8
        loss = fluid.layers.mean(y)
        fluid.optimizer.Momentum(0.01, 0.9).minimize(loss)
    bs = fluid.BuildStrategy()
    bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    bs.reduce_params = True
    cp = fluid.CompiledProgram(main, build_strategy=bs).with_strategy(
        fluid.DistributedStrategy(mesh_shape={"dp": 8}))
    diags = analysis.verify(main, feed_names=["x"],
                            fetch_names=[loss.name], strategy=cp)
    assert any(d.code == "PT046" and "replicated" in d.message
               for d in diags)


# -------------------------------------------------- PT010 collective fix --

def test_collective_is_never_dead():
    """A psum whose output feeds only a stage boundary (nothing in THIS
    program) is a synchronization point, not dead code: pruning it on one
    rank desynchronizes the others."""
    p = Program()
    b = p.global_block()
    b.create_var("x", (8, 4), "float32", is_data=True)
    b.append_op("c_allreduce_sum", inputs={"X": ["x"]},
                outputs={"Out": ["boundary"]}, infer_shape=False)
    b.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]})
    diags = analysis.verify(p, fetch_names=["y"])
    assert not any(d.code == "PT010" and d.op_type == "c_allreduce_sum"
                   for d in diags)
    # an ordinary op in the same position is still (correctly) dead
    p2 = Program()
    b2 = p2.global_block()
    b2.create_var("x", (8, 4), "float32", is_data=True)
    b2.append_op("sigmoid", inputs={"X": ["x"]}, outputs={"Out": ["dead"]})
    b2.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]})
    assert any(d.code == "PT010" for d in
               analysis.verify(p2, fetch_names=["y"]))


# ------------------------------------------------------------ PT05x pins --

def _mem_program():
    p = Program()
    b = p.global_block()
    b.create_var("x", (-1, 256), "float32", is_data=True)
    b.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["h"]})
    b.append_op("sigmoid", inputs={"X": ["h"]}, outputs={"Out": ["y"]})
    return p


def test_pt050_estimate_report():
    diags = analysis.verify(_mem_program(), feed_names=["x"],
                            fetch_names=["y"], batch=4,
                            passes=analysis.default_passes() + ["memplan"])
    d = next(d for d in diags if d.code == "PT050")
    assert d.severity == "info"
    assert "estimated peak" in d.message and "top live" in d.message


def test_pt051_budget_exceeded_and_not():
    p = _mem_program()
    # x+h+y at batch 4: 3 * 4*256*4B = 12 KB; a 1 KB budget trips
    diags = analysis.verify(p, feed_names=["x"], fetch_names=["y"],
                            batch=4, mem_budget=1024)
    d = next(d for d in diags if d.code == "PT051")
    assert d.severity == "error" and "exceeds the memory budget" in d.message
    # a generous budget does not
    diags = analysis.verify(p, feed_names=["x"], fetch_names=["y"],
                            batch=4, mem_budget=1 << 30)
    assert "PT051" not in codes(diags) and "PT050" in codes(diags)


def test_mem_budget_engages_planner_under_explicit_pass_subset():
    """A CI gate narrowing --passes must not silently lose the PT051 OOM
    check: a budget appends memplan to any explicit subset."""
    p = _mem_program()
    diags = analysis.verify(p, feed_names=["x"], fetch_names=["y"],
                            batch=4, mem_budget=16, passes=["dataflow"])
    assert "PT051" in codes(diags)


def test_pt052_assumed_batch():
    p = _mem_program()
    diags = analysis.verify(p, feed_names=["x"], fetch_names=["y"],
                            mem_budget=1 << 30)
    assert "PT052" in codes(diags)
    assert "PT052" not in codes(analysis.verify(
        p, feed_names=["x"], fetch_names=["y"], batch=4,
        mem_budget=1 << 30))


def test_estimate_accounts_liveness_donation_and_sharding():
    """Quantitative pin on the estimator itself: exact byte accounting on
    a hand-sized program."""
    p = Program()
    b = p.global_block()
    b.create_var("x", (8, 100), "float32", is_data=True)      # 3200 B
    b.create_var("w", (100, 100), "float32", persistable=True)  # 40 kB
    b.append_op("mul", inputs={"X": ["x"], "Y": ["w"]},
                outputs={"Out": ["h"]})                        # h: 3200 B
    b.append_op("relu", inputs={"X": ["h"]}, outputs={"Out": ["h2"]})
    b.append_op("relu", inputs={"X": ["h2"]}, outputs={"Out": ["y"]})
    est = analysis.estimate_program_memory(p, feed_names=["x"],
                                           fetch_names=["y"])
    # args: x + w; peak temps: h + h2 live together at op 1 (h dies after
    # op 1, h2 after op 2, y never -- fetch)
    assert est.arg_bytes == 8 * 100 * 4 + 100 * 100 * 4
    assert est.temp_bytes == 2 * 8 * 100 * 4
    assert est.peak_bytes == est.arg_bytes + est.temp_bytes
    assert est.top[0]["name"] == "w" and est.top[0]["kind"] == "state"

    # donated state: an in-place persistable update costs nothing extra
    p2 = Program()
    b2 = p2.global_block()
    b2.create_var("x", (8, 100), "float32", is_data=True)
    b2.create_var("w", (100, 100), "float32", persistable=True)
    b2.append_op("mul", inputs={"X": ["x"], "Y": ["w"]},
                 outputs={"Out": ["h"]})
    b2.append_op("scale", inputs={"X": ["w"]}, outputs={"Out": ["w"]},
                 attrs={"scale": 0.99}, infer_shape=False)
    est2 = analysis.estimate_program_memory(p2, feed_names=["x"],
                                            fetch_names=["h"])
    assert est2.arg_bytes == est.arg_bytes
    assert est2.temp_bytes == 8 * 100 * 4  # h only; w update aliases w

    # sharding divisors: dp8 divides the batch-carrying buffers by 8
    p3 = Program()
    b3 = p3.global_block()
    b3.create_var("x", (-1, 100), "float32", is_data=True)
    b3.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]})
    full = analysis.estimate_program_memory(p3, feed_names=["x"],
                                            fetch_names=["y"], batch=64)
    shard = analysis.estimate_program_memory(p3, feed_names=["x"],
                                             fetch_names=["y"], batch=64,
                                             strategy=dp8())
    assert full.peak_bytes == 8 * shard.peak_bytes


# --------------------------------------- estimate vs XLA (acceptance pin) --

def _xla_vs_static(main, startup, feeds, fetch_vars):
    from paddle_tpu.observability import memory as obsmem
    from paddle_tpu.observability.metrics import REGISTRY
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feeds, fetch_list=fetch_vars)
    compiled = list(exe._cache.values())[-1]
    parts = obsmem.update_program_memory_gauges(compiled, "acc_test")
    if parts is None:
        pytest.skip("backend lacks memory_analysis()")
    batch = analysis.infer_batch(main,
                                 {k: np.shape(v) for k, v in feeds.items()})
    est = analysis.estimate_program_memory(
        main, feed_names=list(feeds),
        fetch_names=[v.name if not isinstance(v, str) else v
                     for v in fetch_vars], batch=batch)
    # the comparison gauge landed at compile time (executor wiring)
    label = f"{id(main)}:v{main._version}"
    snap = {f["name"]: f for f in
            __import__("paddle_tpu.observability.export",
                       fromlist=["to_dict"]).to_dict()["families"]}
    static_fam = snap.get("program_static_peak_bytes")
    assert static_fam is not None and any(
        s["labels"].get("program") == label
        for s in static_fam["samples"]), "static gauge not set at compile"
    ratio_fam = snap.get("program_static_peak_ratio")
    assert ratio_fam is not None and any(
        s["labels"].get("program") == label
        for s in ratio_fam["samples"]), "ratio gauge not set at compile"
    return est.peak_bytes / parts["peak_bytes"]


def test_static_estimate_within_2x_mnist():
    from paddle_tpu.models import mnist
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [1, 28, 28], "float32")
        label = fluid.data("label", [1], "int64")
        loss, acc, _ = mnist.conv_net(img, label)
        fluid.optimizer.Adam(0.001).minimize(loss)
    rng = np.random.RandomState(0)
    ratio = _xla_vs_static(
        main, startup,
        {"img": rng.randn(8, 1, 28, 28).astype("float32"),
         "label": rng.randint(0, 10, (8, 1)).astype("int64")}, [loss])
    assert 0.5 <= ratio <= 2.0, f"mnist static/XLA peak ratio {ratio}"


def test_static_estimate_within_2x_resnet():
    from paddle_tpu.models import resnet
    resnet._DEPTHS[8] = [1, 1, 1, 1]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [3, 32, 32], "float32")
        label = fluid.data("label", [1], "int64")
        loss, acc, _ = resnet.resnet(img, label, depth=8, num_classes=10)
        fluid.optimizer.Momentum(0.1, 0.9).minimize(loss)
    rng = np.random.RandomState(0)
    ratio = _xla_vs_static(
        main, startup,
        {"img": rng.randn(4, 3, 32, 32).astype("float32"),
         "label": rng.randint(0, 10, (4, 1)).astype("int64")}, [loss])
    assert 0.5 <= ratio <= 2.0, f"resnet static/XLA peak ratio {ratio}"


def _small_transformer():
    from paddle_tpu.models import transformer
    cfg = transformer.TransformerConfig(
        src_vocab=64, trg_vocab=64, hidden=32, n_layers=2, n_heads=4,
        ffn_hidden=64, max_len=12, dropout=0.0)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 4
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        S = 8
        src = fluid.data("src", [S], "int64")
        spos = fluid.data("spos", [S], "int64")
        smask = fluid.data("smask", [S], "float32")
        trg = fluid.data("trg", [S], "int64")
        tpos = fluid.data("tpos", [S], "int64")
        tmask = fluid.data("tmask", [S], "float32")
        lbl = fluid.data("lbl", [S], "int64")
        loss, _ = transformer.transformer(src, spos, smask, trg, tpos,
                                          tmask, lbl, cfg,
                                          label_smooth_eps=0.1)
        fluid.optimizer.Adam(0.01).minimize(loss)
    return main, startup, loss


def _transformer_feeds(B=4, S=8):
    rng = np.random.RandomState(0)
    pos = np.tile(np.arange(S), (B, 1)).astype("int64")
    return {"src": rng.randint(0, 64, (B, S)).astype("int64"),
            "spos": pos, "smask": np.ones((B, S), "float32"),
            "trg": rng.randint(0, 64, (B, S)).astype("int64"),
            "tpos": pos, "tmask": np.ones((B, S), "float32"),
            "lbl": rng.randint(0, 64, (B, S)).astype("int64")}


def test_static_estimate_within_2x_transformer():
    main, startup, loss = _small_transformer()
    ratio = _xla_vs_static(main, startup, _transformer_feeds(), [loss])
    assert 0.5 <= ratio <= 2.0, f"transformer static/XLA peak ratio {ratio}"


# --------------------------------------------------- model zoo x strategy --

def _mp_rules_for(program, size=4):
    """Exact-name rules sharding dim 0 of every parameter that divides the
    mp axis -- what a user hand-writing tensor-parallel rules does."""
    import re
    rules = []
    for prm in program.all_parameters():
        if prm.ndim >= 1 and isinstance(prm.shape[0], int) and \
                prm.shape[0] >= size and prm.shape[0] % size == 0:
            rules.append((f"^{re.escape(prm.name)}$", ("mp",)))
    return rules


@functools.lru_cache(maxsize=None)
def _zoo_program(name):
    """(main, feed names, fetch names) per bundled model, built once."""
    build = {
        "mnist": _zoo_mnist, "resnet": _zoo_resnet, "vgg": _zoo_vgg,
        "transformer": _zoo_transformer, "bert": _zoo_bert,
        "deepfm": _zoo_deepfm, "yolov3": _zoo_yolov3,
        "retinanet": _zoo_retinanet, "faster_rcnn": _zoo_faster_rcnn,
        "mask_rcnn": _zoo_mask_rcnn,
    }[name]
    return build()


def _zoo_mnist():
    from paddle_tpu.models import mnist
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [1, 28, 28], "float32")
        label = fluid.data("label", [1], "int64")
        loss, acc, _ = mnist.conv_net(img, label)
        fluid.optimizer.Adam(0.001).minimize(loss)
    return main, ["img", "label"], [loss.name]


def _zoo_resnet():
    from paddle_tpu.models import resnet
    resnet._DEPTHS[8] = [1, 1, 1, 1]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [3, 32, 32], "float32")
        label = fluid.data("label", [1], "int64")
        loss, _, _ = resnet.resnet(img, label, depth=8, num_classes=10)
        fluid.optimizer.Momentum(0.1, 0.9).minimize(loss)
    return main, ["img", "label"], [loss.name]


def _zoo_vgg():
    from paddle_tpu.models import vgg
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [3, 32, 32], "float32")
        label = fluid.data("label", [1], "int64")
        loss, acc, _ = vgg.vgg16(img, label, num_classes=10, use_bn=True)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    return main, ["img", "label"], [loss.name]


def _zoo_transformer():
    main, startup, loss = _small_transformer()
    return main, list(_transformer_feeds()), [loss.name]


def _zoo_bert():
    from paddle_tpu.models import bert
    cfg = bert.BertConfig(vocab_size=128, hidden=32, n_layers=2, n_heads=4,
                          max_seq_len=16, dropout=0.1)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        src = fluid.data("src_ids", [16], "int64")
        pos = fluid.data("pos_ids", [16], "int64")
        sent = fluid.data("sent_ids", [16], "int64")
        mask = fluid.data("input_mask", [16], "float32")
        mpos = fluid.data("mask_pos", [1], "int64")
        mlabel = fluid.data("mask_label", [1], "int64")
        nsp = fluid.data("nsp_label", [1], "int64")
        total, _, _ = bert.pretrain(src, pos, sent, mask, mpos, mlabel,
                                    nsp, cfg)
        fluid.optimizer.Adam(0.005).minimize(total)
    return main, ["src_ids", "pos_ids", "sent_ids", "input_mask",
                  "mask_pos", "mask_label", "nsp_label"], [total.name]


def _zoo_deepfm():
    from paddle_tpu.models import deepfm
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 2
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = fluid.data("ids", [8], "int64")
        dense = fluid.data("dense", [4], "float32")
        label = fluid.data("label", [1], "int64")
        loss, auc_var, prob = deepfm.deepfm(
            ids, dense, label, num_fields=8, vocab_size=1000, embed_dim=8,
            hidden=(32, 32))
        fluid.optimizer.Adam(0.01).minimize(loss)
    return main, ["ids", "dense", "label"], [loss.name]


def _zoo_yolov3():
    from paddle_tpu.models import yolov3
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [3, 64, 64], "float32")
        gt_box = fluid.data("gt_box", [6, 4], "float32")
        gt_label = fluid.data("gt_label", [6], "int32")
        loss = yolov3.yolov3(img, gt_box, gt_label, scale=0.25,
                             stage_blocks=(1, 1, 1, 1, 1), num_classes=4)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    return main, ["img", "gt_box", "gt_label"], [loss.name]


def _zoo_retinanet():
    from paddle_tpu.models import retinanet
    N = 8
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 0
    A = dict(append_batch_size=False)
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [N, 3, 64, 64], "float32", **A)
        gt_box = fluid.data("gt_box", [N, 2, 4], "float32", **A)
        gt_label = fluid.data("gt_label", [N, 2], "int32", **A)
        im_info = fluid.data("im_info", [N, 3], "float32", **A)
        total, _, _ = retinanet.retinanet(
            img, gt_box, gt_label, im_info, batch_size=N, scale=0.1,
            levels=2, num_classes=5, n_convs=1)
        fluid.optimizer.Adam(1e-3).minimize(total)
    return main, ["img", "gt_box", "gt_label", "im_info"], [total.name]


def _zoo_faster_rcnn():
    from paddle_tpu.models import faster_rcnn
    N = 8
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 0
    A = dict(append_batch_size=False)
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [N, 3, 64, 64], "float32", **A)
        gt_box = fluid.data("gt_box", [N, 3, 4], "float32", **A)
        gt_label = fluid.data("gt_label", [N, 3], "int32", **A)
        im_info = fluid.data("im_info", [N, 3], "float32", **A)
        total, _, _ = faster_rcnn.faster_rcnn(
            img, gt_box, gt_label, im_info, batch_size=N, scale=0.125,
            stage_blocks=(1, 1, 1), num_classes=5, anchor_sizes=(32, 64),
            aspect_ratios=(1.0,), post_nms_top_n=16)
        fluid.optimizer.Adam(1e-3).minimize(total)
    return main, ["img", "gt_box", "gt_label", "im_info"], [total.name]


def _zoo_mask_rcnn():
    from paddle_tpu.models import mask_rcnn
    N, G = 8, 2
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 0
    A = dict(append_batch_size=False)
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [N, 3, 64, 64], "float32", **A)
        gt_box = fluid.data("gt_box", [N, G, 4], "float32", **A)
        gt_label = fluid.data("gt_label", [N, G], "int32", **A)
        gt_masks = fluid.data("gt_masks", [N, G, 32, 32], "float32", **A)
        im_info = fluid.data("im_info", [N, 3], "float32", **A)
        total, _, _, _ = mask_rcnn.mask_rcnn(
            img, gt_box, gt_label, gt_masks, im_info, batch_size=N,
            scale=0.1, levels=2, num_classes=4, post_nms_top_n=12,
            roi_resolution=4, mask_resolution=4)
        fluid.optimizer.Adam(1e-3).minimize(total)
    return main, ["img", "gt_box", "gt_label", "gt_masks", "im_info"], \
        [total.name]


_ZOO = ["mnist", "resnet", "vgg", "transformer", "bert", "deepfm",
        "yolov3", "retinanet", "faster_rcnn", "mask_rcnn"]


@pytest.mark.parametrize("model", _ZOO)
@pytest.mark.parametrize("strat_name", ["dp8", "mp", "pp"])
def test_model_zoo_distributed_clean(model, strat_name):
    """Every bundled model x {dp8, mp, pp}: zero PT04x/PT05x errors.
    The mp strategy shards dim 0 of every cleanly-divisible parameter
    (what hand-written tensor-parallel rules do); pp adds a pipeline axis
    next to dp. Batch 8 divides every mesh's data axis."""
    main, feeds, fetches = _zoo_program(model)
    if strat_name == "dp8":
        strat = fluid.DistributedStrategy(mesh_shape={"dp": 8})
    elif strat_name == "mp":
        strat = fluid.DistributedStrategy(
            mesh_shape={"dp": 2, "mp": 4},
            param_rules=_mp_rules_for(main, size=4))
    else:
        strat = fluid.DistributedStrategy(mesh_shape={"pp": 2, "dp": 4})
    diags = analysis.verify(main, feed_names=feeds, fetch_names=fetches,
                            passes=["distributed", "memplan"],
                            strategy=strat, batch=8)
    errs = errors(diags)
    assert errs == [], analysis.format_diagnostics(errs)
    assert "PT050" in codes(diags)  # the planner did report


# ---------------------------------------------------------- executor gate --

def test_gate_passes_strategy_through(monkeypatch):
    """PADDLE_TPU_VALIDATE=raise + CompiledProgram: the PT04x checks see
    the wrapper's strategy and abort before compile."""
    monkeypatch.setenv("PADDLE_TPU_VALIDATE", "raise")
    p = Program()
    b = p.global_block()
    b.create_var("x", (-1, 4), "float32", is_data=True)
    b.append_op("c_allreduce_sum", inputs={"X": ["x"]},
                outputs={"Out": ["y"]}, attrs={"axis_name": "mp"},
                infer_shape=False)
    cp = fluid.CompiledProgram(p).with_strategy(
        fluid.DistributedStrategy(mesh_shape={"dp": 8}))
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        with pytest.raises(VerificationError, match="PT040"):
            exe.run(cp, feed={"x": np.ones((8, 4), "float32")},
                    fetch_list=["y"])
    # the same bare Program (no strategy) has no mesh to check against
    exe2 = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        out, = exe2.run(p, feed={"x": np.ones((8, 4), "float32")},
                        fetch_list=["y"])
    assert np.asarray(out).shape == (8, 4)


def test_gate_mem_budget_env(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_VALIDATE", "raise")
    monkeypatch.setenv("PADDLE_TPU_MEM_BUDGET", "1")
    p = Program()
    b = p.global_block()
    b.create_var("x", (-1, 4), "float32", is_data=True)
    b.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]})
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        with pytest.raises(VerificationError, match="PT051"):
            exe.run(p, feed={"x": np.ones((8, 4), "float32")},
                    fetch_list=["y"])
    # generous budget passes, and the planner report journals as info only
    monkeypatch.setenv("PADDLE_TPU_MEM_BUDGET", "1G")
    exe2 = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        out, = exe2.run(p, feed={"x": np.ones((8, 4), "float32")},
                        fetch_list=["y"])
    assert np.asarray(out).shape == (8, 4)


def test_mem_budget_env_arms_gate_without_validate(monkeypatch):
    """Exporting only PADDLE_TPU_MEM_BUDGET must not be silently inert:
    the budget alone arms the gate in warn mode (VALIDATE=raise upgrades
    it to an abort)."""
    monkeypatch.delenv("PADDLE_TPU_VALIDATE", raising=False)
    monkeypatch.setenv("PADDLE_TPU_MEM_BUDGET", "1")
    p = Program()
    b = p.global_block()
    b.create_var("x", (-1, 4), "float32", is_data=True)
    b.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]})
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        with pytest.warns(UserWarning, match="PT051"):
            out, = exe.run(p, feed={"x": np.ones((8, 4), "float32")},
                           fetch_list=["y"])
    assert np.asarray(out).shape == (8, 4)  # warn mode: run proceeds


def test_gate_rejects_malformed_mem_budget(monkeypatch):
    # loud even when VALIDATE is unset: a typo'd budget must not mean
    # "no budget"
    monkeypatch.delenv("PADDLE_TPU_VALIDATE", raising=False)
    monkeypatch.setenv("PADDLE_TPU_MEM_BUDGET", "lots")
    p = Program()
    b = p.global_block()
    b.create_var("x", (-1, 4), "float32", is_data=True)
    b.append_op("relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]})
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        with pytest.raises(ValueError, match="PADDLE_TPU_MEM_BUDGET"):
            exe.run(p, feed={"x": np.ones((8, 4), "float32")},
                    fetch_list=["y"])


# -------------------------------------------------------------------- CLI --

def _buggy_prog_file(tmp_path):
    p = Program()
    b = p.global_block()
    b.create_var("x", (8, 4), "float32", is_data=True)
    b.append_op("c_allreduce_sum", inputs={"X": ["x"]},
                outputs={"Out": ["y"]}, attrs={"axis_name": "mp"},
                infer_shape=False)
    f = tmp_path / "prog.json"
    f.write_text(p.to_json())
    return f


def test_cli_strategy_file(tmp_path, capsys):
    f = _buggy_prog_file(tmp_path)
    strat = tmp_path / "strat.json"
    strat.write_text(json.dumps({"mesh_shape": {"dp": 8}}))
    rc = cli_main([str(f), "--strategy", str(strat), "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any(d["code"] == "PT040" for d in out["findings"])
    # a strategy whose mesh has the axis: clean of PT040
    strat.write_text(json.dumps({"mesh_shape": {"dp": 2, "mp": 4}}))
    rc = cli_main([str(f), "--strategy", str(strat), "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert not any(d["code"] == "PT040" for d in out["findings"])


def test_cli_strategy_with_build_knobs(tmp_path, capsys):
    main, loss = _reduce_strategy_program()
    f = tmp_path / "prog.json"
    f.write_text(main.to_json())
    strat = tmp_path / "strat.json"
    strat.write_text(json.dumps({"mesh_shape": {"dp": 8},
                                 "reduce_strategy": "Reduce",
                                 "reduce_params": True}))
    cli_main([str(f), "--strategy", str(strat), "--fetch", loss.name,
              "--feed", "x", "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert any(d["code"] == "PT046" for d in out["findings"])


def test_cli_mem_budget_and_batch(tmp_path, capsys):
    p = _mem_program()
    f = tmp_path / "prog.json"
    f.write_text(p.to_json())
    rc = cli_main([str(f), "--feed", "x", "--fetch", "y",
                   "--batch", "4", "--mem-budget", "1K"])
    out = capsys.readouterr().out
    assert rc == 1 and "PT051" in out
    rc = cli_main([str(f), "--feed", "x", "--fetch", "y",
                   "--batch", "4", "--mem-budget", "1G"])
    out = capsys.readouterr().out
    assert rc == 0 and "PT050" in out and "PT052" not in out


def test_cli_baseline_gates_new_findings_only(tmp_path, capsys):
    f = _buggy_prog_file(tmp_path)
    strat = tmp_path / "strat.json"
    strat.write_text(json.dumps({"mesh_shape": {"dp": 8}}))
    base = tmp_path / "accepted.keys"
    # 1. record the current findings as accepted
    rc = cli_main([str(f), "--strategy", str(strat),
                   "--baseline", str(base), "--update-baseline"])
    assert rc == 0 and base.exists()
    capsys.readouterr()
    # 2. unchanged program: everything suppressed, exit 0
    rc = cli_main([str(f), "--strategy", str(strat),
                   "--baseline", str(base)])
    out = capsys.readouterr().out
    assert rc == 0 and "suppressed" in out
    # 3. a NEW bug appears: only it surfaces, exit 1
    p = Program.from_json(f.read_text())
    p.global_block().append_op("relu", inputs={"X": ["ghost"]},
                               outputs={"Out": ["z"]}, infer_shape=False)
    f.write_text(p.to_json())
    rc = cli_main([str(f), "--strategy", str(strat),
                   "--baseline", str(base)])
    out = capsys.readouterr().out
    assert rc == 1 and "PT001" in out and "PT040" not in out
    # 4. byte-stable: regenerating an unchanged baseline is a no-op diff
    f2 = _buggy_prog_file(tmp_path)
    cli_main([str(f2), "--strategy", str(strat),
              "--baseline", str(base), "--update-baseline"])
    capsys.readouterr()
    first = base.read_bytes()
    cli_main([str(f2), "--strategy", str(strat),
              "--baseline", str(base), "--update-baseline"])
    capsys.readouterr()
    assert base.read_bytes() == first


def test_cli_malformed_baseline_is_loud(tmp_path, capsys):
    f = _buggy_prog_file(tmp_path)
    base = tmp_path / "bad.keys"
    base.write_text("{not json\n")
    rc = cli_main([str(f), "--baseline", str(base)])
    assert rc == 2
    assert "baseline" in capsys.readouterr().out


# ------------------------------------------------------------- docs drift --

def test_readme_codes_table_in_sync():
    """README embeds the auto-generated codes_table(); regenerating must be
    a no-op (python -m paddle_tpu.analysis --codes is the source)."""
    readme = open(os.path.join(REPO, "README.md")).read()
    begin = "<!-- analysis-codes-table:begin"
    end = "<!-- analysis-codes-table:end -->"
    assert begin in readme and end in readme, \
        "README lost the analysis codes-table markers"
    block = readme.split(begin, 1)[1].split(end, 1)[0]
    block = block.split("```text", 1)[1].split("```", 1)[0].strip("\n")
    assert block == analysis.codes_table(), (
        "README codes table drifted from codes_table(); regenerate with "
        "`python -m paddle_tpu.analysis --codes`")


# ----------------------------------------------------------- ci_lint tier --

@pytest.mark.smoke
def test_ci_lint_selftest():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "ci_lint.py"),
                        "--selftest"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ci_lint selftest: OK" in r.stdout


# ------------------------------------------- multihost deadlock evidence --

from test_multihost import (_free_port,  # noqa: E402
                            _ranks_would_run_cpu,  # noqa: F401 (the skipif
                            # string condition evaluates in THIS module's
                            # namespace)
                            requires_multiprocess_backend)

_DIVERGENT_RUNNER = os.path.join(os.path.dirname(__file__),
                                 "dist_divergent_runner.py")


@requires_multiprocess_backend
def test_divergent_collective_deadlocks_multirank():
    """The program shape PT041 flags (collective under a rank-divergent
    branch) must demonstrably deadlock or error when actually run
    multi-rank -- the detector's claim, reproduced. A clean COMPLETED from
    every rank would mean PT041 cries wolf."""
    port = _free_port()
    env = dict(os.environ)
    # ranks start on the machine's default platform (see
    # test_multihost._ranks_would_run_cpu: under tier-1 this test skips)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    procs = [subprocess.Popen(
        [sys.executable, _DIVERGENT_RUNNER, str(r), "2", str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env)
        for r in range(2)]
    outs, completed_clean = [], True
    try:
        for p in procs:
            try:
                out, err = p.communicate(timeout=90)
                outs.append(out.decode() + err.decode())
                if p.returncode != 0 or "COMPLETED" not in out.decode():
                    completed_clean = False
            except subprocess.TimeoutExpired:
                # the deadlock: ranks parked in a collective their peer
                # never entered
                completed_clean = False
                p.kill()
                p.communicate()
                outs.append("<deadlocked: killed after timeout>")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    assert not completed_clean, (
        "divergent-collective program completed cleanly on both ranks -- "
        "PT041 would be a false positive:\n" + "\n----\n".join(outs))
    # the control run (uniform branch) must complete on both ranks, so the
    # failure above is attributable to the divergence, not the harness
    port = _free_port()
    procs = [subprocess.Popen(
        [sys.executable, _DIVERGENT_RUNNER, str(r), "2", str(port),
         "uniform"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env)
        for r in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0 and b"COMPLETED" in out, (
            f"uniform control run failed rc={p.returncode}:\n"
            f"{err.decode()[-2000:]}")


@pytest.mark.slow
def test_divergent_collective_hangs_single_process():
    """Deadlock evidence that runs on ANY machine: one process, 4 virtual
    CPU devices. Half the mesh enters the psum, half never does -- the
    rendezvous can't complete and the process hangs (killed after a
    timeout); the uniform control completes. Slow tier: the positive case
    costs its full timeout by construction."""
    env = dict(os.environ)

    def run(mode, timeout):
        p = subprocess.Popen(
            [sys.executable, _DIVERGENT_RUNNER, "0", "1", "0"] +
            ([mode] if mode else []),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env)
        try:
            out, err = p.communicate(timeout=timeout)
            return p.returncode, out.decode() + err.decode()
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate()
            return None, "<hung: killed after timeout>"

    rc, out = run("uniform", timeout=240)
    assert rc == 0 and "COMPLETED" in out, f"control run broken: {out[-800:]}"
    rc, out = run(None, timeout=45)
    assert rc != 0 or "COMPLETED" not in out, (
        "divergent-collective program completed cleanly -- PT041 would be "
        "a false positive:\n" + out[-800:])


def test_divergent_runner_program_is_flagged_statically():
    """The exact IR the multirank runner demonstrates deadlocking is the
    IR PT041 flags (keeps the runner and the detector honest together)."""
    sys.path.insert(0, os.path.dirname(__file__))
    try:
        import dist_divergent_runner as runner
    finally:
        sys.path.pop(0)
    p = runner.build_ir_program()
    diags = analysis.verify(p)
    assert any(d.code == "PT041" and d.severity == "error" for d in diags)
