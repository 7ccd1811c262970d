"""Executor tests (analog of reference test_executor_and_mul.py etc.)."""
import os

import numpy as np
import pytest

import paddle_tpu as fluid


def test_run_simple_program():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.data("x", [3], "float32")
        y = fluid.layers.scale(x, scale=2.0, bias=1.0)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        out, = exe.run(main, feed={"x": np.ones((2, 3), "float32")},
                       fetch_list=[y])
    np.testing.assert_allclose(out, np.full((2, 3), 3.0), rtol=1e-6)


def test_startup_then_main_with_params():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [4], "float32")
        y = fluid.layers.fc(x, 2)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        out, = exe.run(main, feed={"x": np.ones((5, 4), "float32")},
                       fetch_list=[y])
    assert out.shape == (5, 2)


def test_uninitialized_param_error():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [4], "float32")
        y = fluid.layers.fc(x, 2)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        try:
            exe.run(main, feed={"x": np.ones((5, 4), "float32")},
                    fetch_list=[y])
            assert False, "expected error"
        except RuntimeError as e:
            assert "startup" in str(e)


def test_compile_cache_reuse_and_invalidation():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.data("x", [3], "float32")
        y = fluid.layers.scale(x, scale=2.0)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(main, feed={"x": np.ones((2, 3), "float32")}, fetch_list=[y])
        assert len(exe._cache) == 1
        exe.run(main, feed={"x": np.ones((2, 3), "float32")}, fetch_list=[y])
        assert len(exe._cache) == 1  # hit
        exe.run(main, feed={"x": np.ones((4, 3), "float32")}, fetch_list=[y])
        assert len(exe._cache) == 2  # new batch size -> new entry


def test_state_mutation_batch_norm_stats():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [4, 8, 8], "float32")
        y = fluid.layers.batch_norm(x, momentum=0.5)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        mean_name = [n for n in scope.var_names() if "global" in n][0]
        before = np.asarray(scope.find_var(mean_name)).copy()
        exe.run(main, feed={"x": np.random.RandomState(0)
                            .randn(2, 4, 8, 8).astype("float32") + 5.0},
                fetch_list=[y])
        after = np.asarray(scope.find_var(mean_name))
    assert not np.allclose(before, after), "running stats must update"


# ------------------------------------------- one step, one dispatch body --

def _dropout_mlp(seed=5):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [8], "float32")
        label = fluid.data("label", [1], "int64")
        h = fluid.layers.dropout(fluid.layers.fc(x, 16, act="relu"), 0.25)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(h, 4), label))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _dropout_mlp_losses(how, monkeypatch, steps=4):
    """Losses of ``steps`` steps of the dropout MLP from a fresh start, run
    through the builder ``how`` names, and how often that traced the main
    program's global block."""
    from paddle_tpu.core import executor as executor_mod
    main, startup, loss = _dropout_mlp()
    main._rng_run_counter = startup._rng_run_counter = 0
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.rand(8, 8).astype("float32"),
              "label": rng.randint(0, 4, (8, 1)).astype("int64")}
             for _ in range(steps)]
    traced = []
    real = executor_mod.trace_block

    def counting(block, *args, **kwargs):
        if block is main.global_block():
            traced.append(sorted(k for k in ("mesh", "gspmd_mesh")
                                 if kwargs.get(k) is not None))
        return real(block, *args, **kwargs)

    monkeypatch.setattr(executor_mod, "trace_block", counting)
    target = main
    if how in ("gspmd_dp2", "explicit_dp2"):
        ds = fluid.DistributedStrategy(mesh_shape={"dp": 2})
        if how == "explicit_dp2":
            ds.comm_compression = "int8"
            ds.comm_compress_min_bytes = 0
        target = fluid.CompiledProgram(main).with_strategy(ds)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        losses = np.asarray([
            exe.run(target, feed=f, fetch_list=[loss])[0].reshape(())
            for f in feeds])
    return losses.astype(np.float32), traced


@pytest.mark.parametrize("how", ["plain", "gspmd_dp2", "explicit_dp2"])
def test_every_builder_traces_the_one_step_once(how, monkeypatch):
    """Plain jit, GSPMD over dp=2 and ``shard_map`` over dp=2 wrap one step
    definition: a compile traces the main program's global block exactly
    once, with the mesh keyword of its builder, and the losses are the plain
    step's: bit for bit where the plain step runs again from a fresh start
    (the same per-step keys); over a mesh only statistically, because both dp
    steps draw each shard's dropout mask on the device that uses it, with
    the shard's index folded into the key (the explicit-dp step into the
    step's key, the GSPMD step in ``LowerCtx.bernoulli_mask``'s island), so
    their masks -- and only they -- differ from the plain step's."""
    plain, _ = _dropout_mlp_losses("plain", monkeypatch)
    monkeypatch.undo()
    losses, traced = _dropout_mlp_losses(how, monkeypatch)
    mesh_kw = {"gspmd_dp2": ["gspmd_mesh"], "explicit_dp2": ["mesh"]}
    assert traced == [mesh_kw.get(how, [])]
    assert np.isfinite(losses).all()
    if how == "plain":
        assert losses.tobytes() == plain.tobytes()
    else:
        assert not np.allclose(losses, plain, rtol=2e-4, atol=1e-5)
        np.testing.assert_allclose(losses, plain, rtol=0.2)


def test_import_loads_no_kernel_and_no_optional_subsystem():
    """``import paddle_tpu`` is part of every run's set-up: it loads no
    Pallas module of JAX (about a second each process) and none of the
    subsystems a training run reaches only when asked."""
    import subprocess
    import sys
    code = (
        "import sys, paddle_tpu\n"
        "subsystems = ('paddle_tpu.tuning', 'paddle_tpu.warmstore',\n"
        "              'paddle_tpu.serving', 'paddle_tpu.online')\n"
        "print(sorted(m for m in sys.modules\n"
        "             if (m.startswith('jax') and 'pallas' in m)\n"
        "             or any(m == s or m.startswith(s + '.')\n"
        "                    for s in subsystems)))\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "[]", out.stdout


def test_the_executor_names_no_kernel_familys_telemetry():
    """The seam between an op's lowering and the executor: a lowering names
    its own metric (``LowerCtx.report``) and one pass publishes them all
    (``observability/lowerings.py``), so a new kernel family edits its op
    file and one row of that module's table, not the file every program's
    compile shares. ``core/executor.py`` imports no per-family telemetry
    module and ``_post_compile_telemetry`` takes no kind out of the
    Program's reports by name."""
    import inspect
    import re

    from paddle_tpu.core import executor
    from paddle_tpu.observability import lowerings
    families = r"(attention|moe|ssm|masks|loss|rotary)"
    source = inspect.getsource(executor)
    assert not re.findall(
        r"observability\." + families + r"\b|observability import "
        + families + r"\b", source)
    body = inspect.getsource(executor.Executor._post_compile_telemetry)
    assert "_lowering_notes.pop(" not in body
    assert body.count("_lowering_notes") == 1 and "lowerings.publish(" in body
    assert not [family for family in lowerings.FAMILIES if family in source]


def test_cache_keys_of_run_element_by_element(monkeypatch):
    """The executor's cache key, in its documented order: (program id,
    program version, feed signature, fetch names, seed, XLA options flag,
    strategy signature, tuning token).  Slot 6 is ``()`` for a plain
    Program and the wrapper's ``strategy_signature()`` under a strategy.
    The warm store derives its keys from these positions."""
    from paddle_tpu import flags, tuning
    monkeypatch.delenv("PADDLE_TPU_OBS_HEALTH", raising=False)
    main, startup, loss = _dropout_mlp(seed=9)
    feed = {"x": np.ones((8, 8), "float32"),
            "label": np.zeros((8, 1), "int64")}
    sig = (("label", (8, 1), "int64"), ("x", (8, 8), "float32"))
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        run_key = next(reversed(exe._cache))
        dp2 = fluid.CompiledProgram(main).with_strategy(
            fluid.DistributedStrategy(mesh_shape={"dp": 2}))
        exe.run(dp2, feed=feed, fetch_list=[loss])
        dp2_key = next(reversed(exe._cache))
    head = (id(main), main._version, sig, (loss.name,), 9,
            flags.get_flag("xla_compiler_options"))
    assert len(run_key) == len(dp2_key) == 8
    for i, want in enumerate(head + ((), tuning.state_token())):
        assert run_key[i] == want, (i, run_key[i], want)
    assert dp2.strategy_signature() != ()
    for i, want in enumerate(head + (dp2.strategy_signature(),
                                     tuning.state_token())):
        assert dp2_key[i] == want, (i, dp2_key[i], want)
    assert exe._cache[run_key].executable is not None
    assert exe._cache[dp2_key].executable is not None
