"""A sharding the program declares (``Variable.declare_sharding``, set by
the layer that knows which axis its state is split over): what
``CompiledProgram.state_sharding`` returns, for the parameter and for the
optimizer's accumulators of its shape; that it survives a clone and
``io.save`` / ``load`` under another mesh; that a ``param_rules`` entry of
the same rank that disagrees is an error naming both; and that a startup
program creates such state split."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.initializer import Normal
from paddle_tpu.layer_helper import ParamAttr


def build(axis="dp", experts=8):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [32, 16], "float32", append_batch_size=False)
        out, _ = layers.moe_ffn(
            x, experts, 2, 8, param_attr=ParamAttr(initializer=Normal(0, 0.3)),
            name="moe", expert_axis=axis)
        loss = layers.mean(layers.square(out))
        fluid.optimizer.Adam(0.01).minimize(loss)
    return main, startup, loss


def strategy(shape, **kw):
    return fluid.DistributedStrategy(mesh_shape=shape,
                                     data_rules=[("x", ("dp",))], **kw)


STACKED = ("moe_gate_w", "moe_up_w", "moe_down_w")


def test_state_sharding_returns_the_declared_split():
    main, _, _ = build()
    for name in STACKED:
        assert main.global_block().var(name).sharding == ("dp", None, None)
    assert main.global_block().var("moe_router_w").sharding is None
    cp = fluid.CompiledProgram(main).with_strategy(strategy({"dp": 4}))
    names = [v.name for v in main.list_vars() if v.persistable]
    moments = [n for n in names if "moe_gate_w_moment" in n]
    assert len(moments) == 2
    for name in (*STACKED, *moments):
        assert cp.state_sharding(name).spec == P("dp", None, None), name
    for name in [n for n in names if "beta1_pow" in n] + ["moe_router_w"]:
        assert cp.state_sharding(name).spec == P(), name
    # a mesh without the axis holds the dimension whole
    other = fluid.CompiledProgram(main).with_strategy(
        fluid.DistributedStrategy(mesh_shape={"mp": 2}, data_axis="mp"))
    assert other.state_sharding("moe_gate_w").spec == P(None, None, None)


def test_the_declaration_survives_a_clone_and_the_serialized_form():
    main, _, _ = build()
    for program in (main.clone(for_test=True),
                    fluid.Program.from_json(main.to_json())):
        assert program.global_block().var("moe_up_w").sharding == (
            "dp", None, None)


def test_a_disagreeing_param_rule_raises_and_names_both():
    main, _, _ = build()
    cp = fluid.CompiledProgram(main).with_strategy(strategy(
        {"dp": 4}, param_rules=[("moe_gate_w$", (None, None, "dp"))]))
    with pytest.raises(ValueError) as e:
        cp.state_sharding("moe_gate_w")
    assert "declares the split ('dp', None, None)" in str(e.value)
    assert "(None, None, 'dp')" in str(e.value)
    # an agreeing rule, and a rule of another rank (a derived variable's
    # name prefix), are no error
    agree = fluid.CompiledProgram(main).with_strategy(strategy(
        {"dp": 4}, param_rules=[("moe_gate_w", ("dp", None, None))]))
    assert agree.state_sharding("moe_gate_w").spec == P("dp", None, None)


def test_a_startup_program_creates_declared_state_split_on_the_mesh_it_names():
    main, startup, loss = build()
    startup.state_mesh_shape = {"dp": 4}
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    w = scope.find_var("moe_gate_w")
    assert w.shape == (8, 16, 8)
    assert {s.data.shape for s in w.addressable_shards} == {(2, 16, 8)}
    assert len(scope.find_var("moe_router_w").addressable_shards) == 4
    # the same four devices the strategy of that shape takes: a step finds
    # the state where it lies, and moves nothing
    cp = fluid.CompiledProgram(main).with_strategy(strategy({"dp": 4}))
    assert w.sharding.is_equivalent_to(cp.state_sharding("moe_gate_w"), 3)
    x = np.random.RandomState(0).randn(32, 16).astype("float32")
    exe.run(cp, feed={"x": x}, fetch_list=[loss], scope=scope)
    # no mesh named: created on the default device, as ever, whatever the
    # host has
    _, startup, _ = build()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    assert len(scope.find_var("moe_gate_w").devices()) == 1
    # experts the named mesh does not divide: an error that names both
    _, startup, _ = build(experts=6)
    startup.state_mesh_shape = {"dp": 4}
    with pytest.raises(ValueError, match="moe_gate_w.*state_mesh_shape"):
        exe.run(startup, scope=fluid.Scope())


def test_save_and_load_under_another_mesh(tmp_path):
    d = str(tmp_path / "ckpt")
    x = np.random.RandomState(0).randn(32, 16).astype("float32")
    exe = fluid.Executor()
    main, startup, loss = build()
    cp = fluid.CompiledProgram(main).with_strategy(strategy({"dp": 4}))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(cp, feed={"x": x}, fetch_list=[loss])
        fluid.io.save_persistables(exe, d, cp)
        want = exe.run(cp, feed={"x": x}, fetch_list=[loss])[0]
    main2, _, loss2 = build()
    cp2 = fluid.CompiledProgram(main2).with_strategy(strategy({"dp": 2}))
    with fluid.scope_guard(fluid.Scope()):
        fluid.io.load_persistables(exe, d, cp2)
        w = fluid.global_scope().find_var("moe_down_w")
        assert w.sharding.spec == P("dp", None, None)
        assert w.addressable_shards[0].data.shape == (4, 8, 16)
        got = exe.run(cp2, feed={"x": x}, fetch_list=[loss2])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)
