"""The gated ``rms_norm`` (``ops/decoder_ops.py`` given a ``Gate``;
``ops/pallas_norm.py``) through ``layers.*`` -> ``Program`` -> ``Executor``:
both lowerings (the kernels in the interpreter, the closed forms composed)
against a float32 ``rmsnorm(x) * scale * silu(z)`` written here, forward and
the three gradients; the registered grad op lowers no forward; what the op
refuses and what it counts; and an ``rms_norm`` without a gate is what it
was."""
import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

import lowering_reports
import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import registry
from paddle_tpu.initializer import NumpyArrayInitializer
from paddle_tpu.ops import pallas_mode, pallas_norm
from test_decoder_ops import close, rng, run_with_grads

EPS = 1e-6


def form(x, z, w, zero_centered):
    """The two-op form's mathematics in float32: the norm over a head's
    values, its scale, then the gate."""
    unit = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    return unit * (1.0 + w if zero_centered else w) * jax.nn.silu(
        z.reshape(x.shape))


def operands(dtype, heads, dim, wide, tokens=32):
    """x [tokens, heads, dim], the gate in X's shape or (``wide``) as the
    projection writes it, a scale away from 1 and from 0: in ``dtype``."""
    x = rng(0).randn(tokens, heads, dim) * 1.7
    z = rng(1).randn(*((tokens, heads * dim) if wide else x.shape))
    w = 0.4 * rng(2).randn(dim) + 0.8
    return tuple(np.asarray(jnp.asarray(a, dtype)) for a in (x, z, w))


def gated(w, zero_centered, impl):
    def build(xv, zv):
        return layers.rms_norm(
            xv, EPS, fluid.ParamAttr(
                name="w", initializer=NumpyArrayInitializer(w)),
            zero_centered=zero_centered, gate=zv, impl=impl)
    return build


@pytest.mark.parametrize("wide", [True, False], ids=["wide", "as_x"])
@pytest.mark.parametrize("zero_centered", [False, True])
@pytest.mark.parametrize("dim", [128, 256])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("impl", ["pallas", "composed"])
def test_gated_rms_norm_equals_the_float32_form_and_its_gradients(
        impl, dtype, dim, zero_centered, wide):
    """``pallas`` runs the kernel bodies in the interpreter
    (tests/conftest.py). The reference reads the operands as the op got
    them (rounded to ``dtype``) and computes in float32; float32 operands
    are held to ``tests/test_pallas_delta.py``'s 1e-4 of the largest value,
    bfloat16 ones to the roundings around the op: the output's one (2^-8),
    and for a gradient the cotangent's going in and its own going out
    (2^-7)."""
    x, z, w = operands(dtype, 4, dim, wide)
    out, grads, _, g, _ = run_with_grads(
        gated(w, zero_centered, impl), {"x": x, "z": z}, ["x", "z", "w"])
    assert str(out.dtype) == dtype and out.shape == x.shape
    assert [str(a.dtype) for a in grads] == [dtype] * 3
    assert [a.shape for a in grads] == [x.shape, z.shape, w.shape]
    f = [jnp.asarray(a, jnp.float32) for a in (x, z, w)]
    tol, grad_tol = (1e-4, 1e-4) if dtype == "float32" else (2.0 ** -8,
                                                             2.0 ** -7)
    want = form(*f, zero_centered)
    np.testing.assert_allclose(np.asarray(out, np.float32), want, rtol=0,
                               atol=tol * np.abs(want).max())
    # the loss reads the output as the op rounded it
    want_grads = jax.grad(
        lambda *v: jnp.sum(form(*v, zero_centered) * g), (0, 1, 2))(*f)
    for name, got, ref in zip("xzw", grads, want_grads):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), ref, rtol=0,
            atol=grad_tol * np.abs(ref).max(), err_msg=name)


@pytest.mark.parametrize("dtype,ulp", [("float32", 0), ("bfloat16", 2.0 ** -7)])
def test_the_kernels_equal_the_composed_form_on_its_operands(dtype, ulp):
    """Kernel bodies and composed form are one expression
    (``pallas_norm.forward`` / ``backward``): on the same operands float32
    results agree to the last bits a contraction may move (XLA's CPU
    backend compiles the interpreter's kernel as one fusion), bfloat16 ones
    to the one unit in the last place those bits may tip. Rows in several
    chunks and blocks, and a block that is no multiple of a chunk."""
    for tokens in (1024, 48):
        x, z, w = (jnp.asarray(a) for a in operands(dtype, 2, 128, True,
                                                    tokens))
        wf = w.astype(jnp.float32)
        dy = jnp.asarray(rng(3).randn(*x.shape), x.dtype)
        f = [a.astype(jnp.float32) for a in (x, z.reshape(x.shape))]
        want = pallas_norm.forward(*f, wf, EPS).astype(x.dtype)
        got = pallas_norm.gated_norm(x, z, wf, EPS, True)
        dx, dz, terms = pallas_norm.backward(*f, wf, dy.astype(jnp.float32),
                                             EPS)
        grads = jax.vjp(lambda *v: pallas_norm.gated_norm(*v, EPS, True),
                        x, z, wf)[1](dy)
        for a, b in ((got, want), (grads[0], dx.astype(x.dtype)),
                     (grads[1], dz.astype(z.dtype).reshape(z.shape))):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=ulp,
                atol=2e-6 * float(jnp.abs(b.astype(jnp.float32)).max()))
        ref = jnp.sum(terms, (0, 1))
        np.testing.assert_allclose(grads[2], ref, rtol=0,
                                   atol=1e-5 * float(jnp.abs(ref).max()))


def test_the_views_the_kernels_take():
    # the projection's [T, heads * D] beside [T, heads, D]: read as it is
    assert pallas_norm.wide_view((8192, 32, 128), (8192, 4096)) == (8192, 32)
    assert pallas_norm.wide_view((8192, 32, 128), (8192, 32, 128)) == (
        262144, 1)
    assert pallas_norm.wide_view((64, 128), (64, 128)) == (64, 1)
    assert pallas_norm.wide_view((2, 64, 4, 128), (128, 512)) == (128, 4)
    assert pallas_norm.supports(8192, 128) and pallas_norm.supports(16, 256)
    assert not pallas_norm.supports(8192, 64)
    assert not pallas_norm.supports(8200, 128)
    assert pallas_norm._chunks(4096) == (512, 8)
    assert pallas_norm._chunks(1024) == (512, 2)
    assert pallas_norm._chunks(48) == (48, 1)


def _grad_jaxpr(x, z, w, attrs):
    """The registered ``rms_norm_grad`` lowering traced over X, Gate,
    Scale, Y and the cotangent."""
    spec = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (x, z, w, x, x)]
    return jax.make_jaxpr(
        lambda X, Gate, Scale, Y, G: registry.get("rms_norm_grad").lower(
            registry.LowerCtx(attrs), {
                "X": [X], "Gate": [Gate], "Scale": [Scale], "Y": [Y],
                "Y@GRAD": [G]}))(*spec)


@pytest.mark.parametrize("impl", ["pallas", "composed"])
def test_the_grad_op_lowers_no_forward(impl):
    """It reads X, Gate, Scale and the cotangent, not Y; on the kernels it
    is one ``pallas_call`` (the backward's), composed it holds one
    ``rsqrt`` a row and no forward product."""
    x, z, w = operands("bfloat16", 4, 128, True)
    jaxpr = _grad_jaxpr(x, z, w, {"epsilon": EPS, "impl": impl,
                                  "__fwd_out_slots__": ["Y"]})
    x_in, z_in, w_in, y_in, g_in = jaxpr.jaxpr.invars
    used = {v for eqn in jaxpr.jaxpr.eqns for v in eqn.invars
            if isinstance(v, jax.extend.core.Var)}
    assert y_in not in used and {x_in, z_in, w_in, g_in} <= used
    text = str(jaxpr)
    assert text.count("pallas_call[") == (1 if impl == "pallas" else 0)
    assert text.count("rsqrt") == 1 and text.count("logistic") == 1
    assert sorted(tuple(v.aval.shape) for v in jaxpr.jaxpr.outvars) == \
        sorted([x.shape, z.shape, w.shape])


def test_what_the_gated_form_refuses(monkeypatch):
    x, z, w = operands("float32", 4, 128, True)
    small = operands("float32", 4, 8, True)
    with pytest.raises(Exception, match="impl='pallas' needs a Gate"):
        run_with_grads(gated(small[2], False, "pallas"),
                       {"x": small[0], "z": small[1]}, [])
    with pytest.raises(Exception, match="has not X's"):
        run_with_grads(gated(w, False, "auto"), {"x": x, "z": z[:, :-128]},
                       [])
    # off a TPU and outside the harness: as the other kernel families
    monkeypatch.setattr(pallas_mode, "TEST_INTERPRET", False)
    with pytest.raises(Exception, match="runs only on a TPU"):
        run_with_grads(gated(w, False, "pallas"), {"x": x, "z": z}, [])
    out, _, _, _, _ = run_with_grads(gated(w, False, "auto"),
                                     {"x": x, "z": z}, [])
    close(out, form(*(jnp.asarray(a) for a in (x, z, w)), False))


def gated_lowering_counts():
    from paddle_tpu.observability.metrics import REGISTRY
    out = {}
    for k, c in (REGISTRY.get("rms_norm_gated_lowering_total") or {}).items():
        key = tuple(dict(k)[n] for n in ("impl", "direction", "head_dim"))
        out[key] = out.get(key, 0) + c.value
    return out


@pytest.mark.parametrize("dim,interpreter,impl", [
    (128, True, "pallas"),      # the interpreter stands in
    (128, False, "composed"),   # off a TPU
    (8, True, "composed")])     # a shape the kernels leave
def test_gated_lowering_total_counts_one_forward_and_one_backward(
        dim, interpreter, impl, monkeypatch):
    monkeypatch.setattr(pallas_mode, "TEST_INTERPRET", interpreter)
    x, z, w = operands("float32", 2, dim, True)
    before = gated_lowering_counts()
    run_with_grads(gated(w, False, "auto"), {"x": x, "z": z}, ["x"])
    now = gated_lowering_counts()
    assert {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)} == {
                (impl, "forward", str(dim)): 1,
                (impl, "backward", str(dim)): 1}


def test_an_ungated_rms_norm_is_what_it_was_and_reports_nothing():
    """Without a ``Gate`` the op traces to the parent's expression, its
    grad op is the generic one (``jax.vjp`` over that expression), and no
    row of the gated form's counter is written."""
    def parents(x, scale):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(
            jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + EPS)
        y = y * (1.0 + scale.astype(jnp.float32))
        return y.astype(x.dtype)
    spec = (jax.ShapeDtypeStruct((32, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((128,), jnp.bfloat16))
    attrs = {"epsilon": EPS, "zero_centered": True}
    main = fluid.Program()
    ctx = registry.LowerCtx(attrs, salt=1, program=main)
    now = jax.make_jaxpr(lambda x, s: registry.get("rms_norm").lower(
        ctx, {"X": [x], "Scale": [s]})["Y"][0])(*spec)
    assert str(now) == str(jax.make_jaxpr(parents)(*spec))
    back = jax.make_jaxpr(
        lambda x, s, g: registry.get("rms_norm_grad").lower(
            registry.LowerCtx(dict(attrs, __fwd_out_slots__=["Y"]), salt=1,
                              program=main),
            {"X": [x], "Scale": [s], "Y@GRAD": [g]}))(*spec, spec[0])
    want = jax.make_jaxpr(
        lambda x, s, g: jax.vjp(parents, x, s)[1](g))(*spec, spec[0])
    assert [str(e.primitive) for e in back.jaxpr.eqns] == [
        str(e.primitive) for e in want.jaxpr.eqns]
    assert "pallas_call" not in str(back)
    assert not main._lowering_notes
    assert lowering_reports.read(
        lowering_reports.publish(main), "rms_norm_gated_lowering_total",
        "impl") == {}
    x = rng().randn(32, 128).astype("float32")
    before = gated_lowering_counts()
    run_with_grads(lambda xv: layers.rms_norm(xv, EPS), {"x": x}, ["x"])
    assert gated_lowering_counts() == before


def test_gated_kernel_ops_metric_reads_nine_on_the_cells_counters():
    """``norm.gated_kernel_ops`` (a data file on ``registry_count``) agrees
    with its ``BENCHMARK.json`` entry, is the cells' with a gated norm
    (Qwen3-Next's first; Kimi Linear's since PR 51), and
    over the counters the cell's two compiled programs add on the chip
    (three forward ops in the check's test clone, three forward and three
    backward in the train step) reads 9; composed ops are left out, and a
    parent without the counter reads None rather than raising."""
    import importlib
    import json
    import os
    from benchmark import run
    from paddle_tpu.observability import lowerings
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell_name = "qwen3_next_80b_a3b.pretrain_s4096"
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec = json.load(open(os.path.join(
        root, "benchmark", "layer_metrics", "norm.gated_kernel_ops.json")))
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "norm.gated_kernel_ops")
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["workloads"][0] == cell_name
    assert entry["layer"] == "dense_ops" and entry["moves"] == "tokens_per_s"
    assert spec["labels"] == {"impl": "pallas"}
    cell = run.load_cell(cell_name, rehearsal=False)
    assert "norm.gated_kernel_ops" in [m["name"] for m in cell["per_layer"]]
    reduce = importlib.import_module(
        f"benchmark.reducers.{spec['reducer']}").reduce
    before = reduce(spec, None) or 0.0
    dim = cell["model"]["linear_value_head_dim"]
    for program, directions, impl in (
            ("pr49_test_clone", ("forward",), "pallas"),
            ("pr49_train_step", ("forward", "backward"), "pallas"),
            ("pr49_mesh", ("forward", "backward"), "composed")):
        notes = {}
        for salt in range(3):
            for direction in directions:
                lowerings.note(notes, salt, "rms_norm_gated_lowering_total",
                               1, dict(impl=impl, direction=direction,
                                       head_dim=dim))
        lowerings.publish(notes, program)
    assert reduce(spec, None) - before == 9.0
    assert reduce(dict(spec, match="pr49_no_such_counter"), None) is None


# -- the sigmoid gate (Kimi Delta Attention's output norm) ---------------------

def sigmoid_form(x, z, w):
    unit = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    return unit * w * jax.nn.sigmoid(z.reshape(x.shape))


@pytest.mark.parametrize("wide", [True, False], ids=["wide", "as_x"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("impl", ["pallas", "composed"])
def test_the_sigmoid_gate_equals_its_closed_form_and_gradients(impl, dtype,
                                                               wide):
    """``gate_activation="sigmoid"``: ``rmsnorm(x) * scale * sigmoid(z)``,
    the norm first, then the gate (HF ``FusedRMSNormGated(activation=
    'sigmoid')``); both lowerings, forward and the three gradients of the
    registered grad op, to the silu form's tolerances."""
    x, z, w = operands(dtype, 4, 128, wide)

    def build(xv, zv):
        return layers.rms_norm(
            xv, EPS, fluid.ParamAttr(
                name="w", initializer=NumpyArrayInitializer(w)),
            gate=zv, impl=impl, gate_activation="sigmoid")
    out, grads, _, g, _ = run_with_grads(build, {"x": x, "z": z},
                                         ["x", "z", "w"])
    assert str(out.dtype) == dtype and out.shape == x.shape
    f = [jnp.asarray(a, jnp.float32) for a in (x, z, w)]
    tol, grad_tol = (1e-4, 1e-4) if dtype == "float32" else (2.0 ** -8,
                                                             2.0 ** -7)
    want = sigmoid_form(*f)
    np.testing.assert_allclose(np.asarray(out, np.float32), want, rtol=0,
                               atol=tol * np.abs(want).max())
    # not the silu form's: the two differ by the factor z
    assert np.abs(np.asarray(out, np.float32) - form(*f, False)).max() > \
        0.1 * np.abs(want).max()
    want_grads = jax.grad(lambda *v: jnp.sum(sigmoid_form(*v) * g),
                          (0, 1, 2))(*f)
    for name, got, ref in zip("xzw", grads, want_grads):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), ref, rtol=0,
            atol=grad_tol * np.abs(ref).max(), err_msg=name)


def test_the_gates_activation_is_an_attr_only_where_it_is_not_silu():
    """The default lowers and counts what it did: no ``gate_activation``
    attr on a silu-gated op (a Qwen3-Next Program is its parent's), the
    label ``activation`` on every report, and a report without it (an older
    reader's) kept as silu; anything but silu or sigmoid is refused."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        A = dict(append_batch_size=False)
        x = fluid.data("x", [32, 2, 128], "float32", **A)
        z = fluid.data("z", [32, 256], "float32", **A)
        layers.rms_norm(x, EPS, gate=z)
        layers.rms_norm(x, EPS, gate=z, gate_activation="sigmoid")
        with pytest.raises(ValueError, match="silu or sigmoid"):
            layers.rms_norm(x, EPS, gate=z, gate_activation="tanh")
    silu, sigmoid = [op for op in main.global_block().ops
                     if op.type == "rms_norm"]
    assert "gate_activation" not in silu.attrs
    assert sigmoid.attr("gate_activation") == "sigmoid"
    xs = jnp.ones((32, 2, 128))
    for salt, op in enumerate((silu, sigmoid)):
        registry.get("rms_norm").lower(
            registry.LowerCtx(dict(op.attrs), salt=salt + 1, program=main),
            {"X": [xs], "Gate": [jnp.ones((32, 256))],
             "Scale": [jnp.ones((128,))]})
    registry.LowerCtx({}, salt=9, program=main).report(
        "rms_norm_gated_lowering_total", impl="pallas", direction="forward",
        head_dim=128)
    assert lowering_reports.read(
        lowering_reports.publish(main), "rms_norm_gated_lowering_total",
        "activation") == {"silu": 2, "sigmoid": 1}
    with pytest.raises(ValueError, match="gate_activation"):
        pallas_norm.forward(xs, xs, jnp.ones((128,)), EPS, "tanh")
