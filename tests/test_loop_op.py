"""``layers.Scan(steps=...)`` over the ``scan`` op (ops/control_flow.py): a
sub-block applied ``steps`` times on weights that exist once. Against plain
``jax.numpy`` on seeded weights: the emitted states, every weight's gradient
as the sum over its uses, with and without recomputation inside the
sub-block (``RecomputeOptimizer`` cutting the loop's body); what
the Program holds whatever ``steps`` is; what the forward keeps for the
backward and how often the body is traced, as the lowerings report them;
how an instruction's ``op_name`` tells forward, recomputed forward and
backward apart; ``RecomputeOptimizer`` raising by name for a checkpoint
that cuts nothing; and ``layers.Scan`` over sequences through the same kept
pullback, an integer carry among its state."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.observability import attribution
from paddle_tpu.observability.metrics import REGISTRY

LAYERS, WIDTH, ROWS = 3, 16, 8


def built(steps, recompute=False, checkpoints=None):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [ROWS, WIDTH], "float32", append_batch_size=False)
        loop = layers.Scan(time_major=True, steps=steps)
        cut = []
        with loop.step():
            h = loop.memory(x)
            y = h
            for i in range(LAYERS):
                y = layers.tanh(layers.fc(
                    y, WIDTH, param_attr=fluid.ParamAttr(name=f"w{i}"),
                    bias_attr=False))
                cut.append(y)
            loop.update_memory(h, y)
            loop.step_output(y)
        states = loop()
        loss = layers.mean(layers.square(states))
        test = main.clone(for_test=True)
        optimizer = fluid.optimizer.SGD(0.1)
        if recompute:
            optimizer = fluid.optimizer.RecomputeOptimizer(
                optimizer)._set_checkpoints(
                    cut if checkpoints is None else checkpoints(cut))
        _, pairs = optimizer.minimize(loss)
    return {"main": main, "startup": startup, "test": test, "loss": loss,
            "states": states, "final": loop.finals[0], "pairs": pairs}


def reference(x, w, steps):
    h, outs = x, []
    for _ in range(steps):
        for i in range(LAYERS):
            h = jnp.tanh(h @ w[f"w{i}"])
        outs.append(h)
    return jnp.stack(outs)


def ran(b, steps):
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(b["startup"], scope=scope)
    x = np.random.RandomState(0).randn(ROWS, WIDTH).astype("float32")
    w = {f"w{i}": np.array(scope.find_var(f"w{i}")) for i in range(LAYERS)}
    want = reference(x, w, steps)
    want_grads = jax.grad(
        lambda w: jnp.mean(jnp.square(reference(x, w, steps))))(w)
    got = exe.run(b["main"], feed={"x": x}, scope=scope,
                  fetch_list=[b["loss"], b["states"], b["final"]]
                  + [g for _, g in b["pairs"]])
    label = f"{id(b['main'])}:v{b['main']._version}"
    exe.close()
    return {"got": got, "want": want, "want_grads": want_grads,
            "label": label}


def gauge(name, label):
    family = REGISTRY.get(name)
    return [child.value for labels, child in family.items()
            if dict(labels)["program"] == label]


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["kept", "recomputed"])
def test_states_and_every_weights_gradient_match_plain_jax(recompute):
    b = built(3, recompute)
    r = ran(b, 3)
    loss, states, final = r["got"][:3]
    np.testing.assert_allclose(states, r["want"], atol=1e-6)
    np.testing.assert_allclose(final, r["want"][-1], atol=1e-6)
    assert float(loss[0]) == pytest.approx(
        float(jnp.mean(jnp.square(r["want"]))), rel=1e-6)
    # each weight is used 3 times: its gradient is the sum over the uses
    for (p, _), g in zip(b["pairs"], r["got"][3:]):
        np.testing.assert_allclose(g, r["want_grads"][p.name], atol=1e-7)
    # the body was traced once whatever `steps` is; the grad op calls what
    # the forward kept and traces none
    assert gauge("loop_stack_lowerings_total", r["label"]) == [1.0]


def test_the_program_holds_the_body_once_whatever_steps_is():
    two, four = built(2), built(4)
    for key in ("main", "startup", "test"):
        kinds = [[op.type for op in blk.ops] for blk in two[key].blocks]
        assert kinds == [[op.type for op in blk.ops]
                         for blk in four[key].blocks]
    assert [p.name for p in two["main"].all_parameters()] == \
        [p.name for p in four["main"].all_parameters()] == ["w0", "w1", "w2"]
    op = next(o for o in four["main"].global_block().ops if o.type == "scan")
    assert op.attr("steps") == 4 and op.input("Static") == ["w0", "w1", "w2"]
    # the grad maker marked the op to keep what scan_grad reads, declared as
    # step scopes (no array: no dtype to hold it to); the test clone, taken
    # before, keeps nothing
    assert op.attr("keep") and len(op.output("Kept")) == 1
    kept = four["main"].global_block().var(op.output("Kept")[0])
    assert kept.type == fluid.framework.VarType.STEP_SCOPES
    clone = next(o for o in four["test"].global_block().ops
                 if o.type == "scan")
    assert not clone.attr("keep") and "Kept" not in clone.outputs
    grad = next(o for o in four["main"].global_block().ops
                if o.type == "scan_grad")
    assert grad.input("Kept") == op.output("Kept")


def test_recomputation_cuts_the_loops_sub_block_and_keeps_less():
    plain, cut = built(3), built(3, recompute=True)
    assert [op.type for op in cut["main"].global_block().ops] == \
        [op.type for op in plain["main"].global_block().ops]
    sub = cut["main"].blocks[next(
        o for o in cut["main"].global_block().ops
        if o.type == "scan").attr("sub_block")]
    assert [op.type for op in sub.ops] == ["remat_segment"] * LAYERS
    kept = {}
    for name, b in (("plain", plain), ("cut", cut)):
        kept[name] = gauge("loop_kept_bytes", ran(b, 3)["label"])[0]
    # with every layer's output a checkpoint: steps x layers layer inputs
    assert kept["cut"] == 3 * LAYERS * ROWS * WIDTH * 4
    assert kept["plain"] > 2 * kept["cut"]


def test_a_checkpoint_that_cuts_nothing_raises_by_name():
    with pytest.raises(ValueError, match="no_such_variable"):
        built(3, recompute=True,
              checkpoints=lambda cut: cut + ["no_such_variable"])
    # the first op's output alone: its segment would hold a single op, and
    # there is no other
    with pytest.raises(ValueError, match=r"no segment.*fc_0.tmp_0"):
        built(3, recompute=True, checkpoints=lambda cut: ["fc_0.tmp_0"])
    # the same beside checkpoints that do cut: fine, as it always was
    built(3, recompute=True, checkpoints=lambda cut: cut + ["fc_0.tmp_0"])


def test_a_scan_needs_its_length_and_every_memory_updated():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [ROWS, WIDTH], "float32", append_batch_size=False)
        for steps, error in ((2, "never updated"), (None, "or steps")):
            loop = layers.Scan(steps=steps)
            with loop.step():
                h = loop.memory(x)
                if steps is None:
                    loop.update_memory(h, layers.tanh(h))
            with pytest.raises(ValueError, match=error):
                loop()
    with pytest.raises(ValueError, match="steps >= 1"):
        layers.Scan(steps=0)


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/scan#3/jvp()/while/body/closed_call/remat_segment#0/"
     "mul#1/dot_general", ("scan#3", "forward")),
    ("jit(step)/scan_grad#14/transpose(jvp())/while/body/closed_call/"
     "remat_segment#0/remat_segment#0/checkpoint/rematted_computation/"
     "mul#1/dot_general", ("scan_grad#14", "recompute")),
    ("jit(step)/scan_grad#14/transpose(jvp())/while/body/closed_call/"
     "remat_segment#0/remat_segment#0/checkpoint/mul#1/transpose",
     ("scan_grad#14", "backward")),
    ("jit(step)/ssd_scan#4/dot_general", (None, "forward")),
    ("jit(step)/mul_grad#11/dot_general", (None, "backward")),
    ("jit(step)/mul#5/dot_general", (None, "forward")),
    ("jit(step)/broadcast_in_dim", None),
])
def test_an_instructions_phase_is_read_from_its_op_name(op_name, want):
    assert attribution.op_phase(op_name) == want


def test_instruction_phases_of_a_compiled_loop_step():
    """The train step of a recomputing loop, compiled here: its HLO holds
    forward, recomputed and backward instructions of the SAME Program op
    (``mul#0`` of a segment), and ``instruction_phases`` tells them apart."""
    from paddle_tpu.observability import memory
    b = built(3, recompute=True)
    label = ran(b, 3)["label"]
    step = memory.compiled_step(label)
    if step is None:            # the executor was closed: compile again
        exe, scope = fluid.Executor(), fluid.Scope()
        exe.run(b["startup"], scope=scope)
        exe.run(b["main"], feed={"x": np.zeros((ROWS, WIDTH), "float32")},
                scope=scope, fetch_list=[b["loss"]])
        step = memory.compiled_step(label)
    found = attribution.instruction_phases(step.hlo_text())
    phases = {phase for loop, phase in found.values() if loop}
    assert phases == {"forward", "recompute", "backward"}
    assert {loop.split("#")[0] for loop, _ in found.values() if loop} == \
        {"scan", "scan_grad"}


def recurrence(time_major, counted):
    """``h_t = tanh(x_t w + h_{t-1} u)`` over ``layers.Scan``, with an
    int32 step counter among the memories where ``counted``."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 0
    shape = [5, ROWS, WIDTH] if time_major else [ROWS, 5, WIDTH]
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", shape, "float32", append_batch_size=False)
        x.stop_gradient = False
        h0 = fluid.data("h0", [ROWS, WIDTH], "float32",
                        append_batch_size=False)
        h0.stop_gradient = False
        t0 = layers.fill_constant([1], "int32", 0)
        scan = layers.Scan(time_major=time_major)
        with scan.step():
            x_t = scan.step_input(x)
            h_prev = scan.memory(h0)
            if counted:
                t = scan.memory(t0)
                scan.update_memory(t, layers.increment(t, 1, in_place=False))
            h = layers.tanh(layers.elementwise_add(
                layers.fc(x_t, WIDTH, bias_attr=False,
                          param_attr=fluid.ParamAttr(name="w")),
                layers.fc(h_prev, WIDTH, bias_attr=False,
                          param_attr=fluid.ParamAttr(name="u"))))
            scan.update_memory(h_prev, h)
            scan.step_output(h)
        out = scan()
        loss = layers.mean(layers.square(out))
        last = layers.mean(scan.finals[0])
        loss = layers.elementwise_add(loss, last)
        _, pairs = fluid.optimizer.SGD(0.1).minimize(loss)
        wanted = fluid.gradients([loss], [x, h0])
    return {"main": main, "startup": startup, "loss": loss, "out": out,
            "pairs": pairs, "wanted": wanted, "finals": scan.finals}


@pytest.mark.parametrize("counted", [False, True], ids=["float", "counted"])
@pytest.mark.parametrize("time_major", [False, True],
                         ids=["batch_major", "time_major"])
def test_a_scan_over_sequences_runs_backward_from_what_it_kept(time_major,
                                                               counted):
    b = recurrence(time_major, counted)
    kinds = [op.type for op in b["main"].global_block().ops]
    # minimize's backward and gradients()' call the one pullback
    assert kinds.count("scan") == 1 and kinds.count("scan_grad") == 2
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(b["startup"], scope=scope)
    rng = np.random.RandomState(1)
    x = rng.randn(*b["main"].global_block().var("x").shape).astype("float32")
    h0 = rng.randn(ROWS, WIDTH).astype("float32")
    w, u = (np.array(scope.find_var(n)) for n in ("w", "u"))

    def want(x, h0, w, u):
        xs = x if time_major else jnp.swapaxes(x, 0, 1)
        h, outs = h0, []
        for x_t in xs:
            h = jnp.tanh(x_t @ w + h @ u)
            outs.append(h)
        return jnp.mean(jnp.square(jnp.stack(outs))) + jnp.mean(h)

    grads = jax.grad(want, argnums=(0, 1, 2, 3))(x, h0, w, u)
    by_name = dict(zip(("x", "h0", "w", "u"), grads))
    got = exe.run(b["main"], feed={"x": x, "h0": h0}, scope=scope,
                  fetch_list=[b["loss"]] + [g for _, g in b["pairs"]]
                  + b["wanted"] + b["finals"][1:])
    label = f"{id(b['main'])}:v{b['main']._version}"
    exe.close()
    assert float(got[0][0]) == pytest.approx(float(want(x, h0, w, u)),
                                             rel=1e-6)
    for (p, _), g in zip(b["pairs"], got[1:3]):
        np.testing.assert_allclose(g, by_name[p.name], atol=1e-7)
    np.testing.assert_allclose(got[3], by_name["x"], atol=1e-7)
    np.testing.assert_allclose(got[4], by_name["h0"], atol=1e-7)
    if counted:
        assert int(got[5][0]) == 5
    assert gauge("loop_stack_lowerings_total", label) == [1.0]


def test_a_double_gradient_through_a_scan_is_computed_from_its_inputs():
    """A gradient penalty on a recurrence: ``scan_grad_grad`` lowers
    ``scan_grad`` again under ``jax.vjp``, where the kept pullback's
    residuals would be constants; it is computed from the inputs there."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [ROWS, 5, WIDTH], "float32",
                       append_batch_size=False)
        x.stop_gradient = False
        h0 = fluid.data("h0", [ROWS, WIDTH], "float32",
                        append_batch_size=False)
        scan = layers.Scan()
        with scan.step():
            h_prev = scan.memory(h0)
            h = layers.tanh(layers.elementwise_add(layers.fc(
                scan.step_input(x), WIDTH, bias_attr=False,
                param_attr=fluid.ParamAttr(name="w")), h_prev))
            scan.update_memory(h_prev, h)
            scan.step_output(h)
        first, = fluid.gradients([layers.mean(layers.square(scan()))], [x])
        second, = fluid.gradients([layers.mean(layers.square(first))], [x])
    kinds = [op.type for op in main.global_block().ops]
    assert kinds.count("scan_grad_grad") == 1
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    xv = rng.randn(ROWS, 5, WIDTH).astype("float32")
    hv = rng.randn(ROWS, WIDTH).astype("float32")
    w = np.array(scope.find_var("w"))

    def loss(x):
        h, outs = hv, []
        for t in range(5):
            h = jnp.tanh(x[:, t] @ w + h)
            outs.append(h)
        return jnp.mean(jnp.square(jnp.stack(outs, 1)))

    def penalty(x):
        return jnp.mean(jnp.square(jax.grad(loss)(x)))

    got = exe.run(main, feed={"x": xv, "h0": hv}, scope=scope,
                  fetch_list=[first, second])
    exe.close()
    for g, want in zip(got, (jax.grad(loss)(xv), jax.grad(penalty)(xv))):
        np.testing.assert_allclose(g, want, rtol=0,
                                   atol=1e-5 * np.abs(want).max())
