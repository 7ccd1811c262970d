"""The seam between an op's lowering and the executor: every metric family
of ``observability/lowerings.py`` through ``LowerCtx.report`` and the one
publishing pass, and the one rule (``pallas_mode.lowers_kernels``) by which
the six kernel families choose their Pallas kernels or their composed
form."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import lowering_reports
import paddle_tpu as fluid
from paddle_tpu.core import registry as op_registry
from paddle_tpu.core.registry import LowerCtx
from paddle_tpu.observability import lowerings
from paddle_tpu.ops import pallas_delta, pallas_short_conv, pallas_ssd


@pytest.mark.parametrize("family", sorted(lowerings.FAMILIES))
def test_a_report_lands_in_its_family_once_an_op(family):
    """A report in, through the pass the executor runs: the registry holds
    that family alone, under the labels given (through ``str``) and the
    program's, with the amount; the same op (salt) reporting the same again
    -- the forward a grad op lowers under ``jax.vjp`` -- counts once, and
    another op adds its own."""
    kind, names, _ = lowerings.FAMILIES[family]
    labels = {name: i for i, name in enumerate(names)}
    main = fluid.Program()
    for salt in (7, 7, 8):
        LowerCtx({}, salt=salt, program=main).report(family, 3, **labels)
    registry = lowering_reports.publish(main, "p")
    assert main._lowering_notes == {}           # handed over
    assert [f.name for f in registry.collect()] == [family]
    # ``role`` is the publishing pass's to give, like ``program``
    want = (("program", "p"),) + tuple(
        (n, "" if n == "role" else str(v)) for n, v in labels.items())
    (got, child), = registry.get(family).items()
    assert sorted(got) == sorted(want) and child.value == 6
    assert type(child).__name__ == {
        lowerings.COUNT: "Counter", lowerings.GAUGE: "Gauge"}[kind]
    # a second compile of the program: a count goes on, a gauge is set anew
    LowerCtx({}, salt=7, program=main).report(family, 3, **labels)
    lowering_reports.publish(main, "p", registry)
    assert child.value == (9 if kind == lowerings.COUNT else 3)
    # no Program being lowered (a dygraph op, shape inference): nothing kept
    LowerCtx({}, salt=7).report(family, 3, **labels)


def test_an_undeclared_family_or_label_is_refused_at_trace_time():
    ctx = LowerCtx({}, salt=1, program=fluid.Program())
    with pytest.raises(KeyError, match="not declared"):
        ctx.report("no_such_lowering_total", impl="pallas")
    with pytest.raises(KeyError, match="takes the labels"):
        ctx.report("ssd_lowering_total", impl="pallas", chunk=256, heads=64)
    with pytest.raises(KeyError, match="takes the labels"):
        ctx.report("moe_row_budget", 12, layer="a")
    assert ctx.program._lowering_notes == {}


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _trace(op_type, ctx, ins, out):
    """The jaxpr, as text, of ``op_type``'s lowering under ``ctx`` over
    arrays of ``ins``' shapes, and the function traced."""
    lower = op_registry.get(op_type).lower
    slots = sorted(ins)

    def fn(*arrays):
        return lower(ctx, {s: [a] for s, a in zip(slots, arrays)})[out][0]
    return str(jax.make_jaxpr(fn)(*(ins[s][0] for s in slots))), fn


def _attention(s):
    return {"Q": [_f32(1, 2, s, 64)], "K": [_f32(1, 2, s, 64)],
            "V": [_f32(1, 2, s, 64)]}


def _short_conv(chan):
    return {"X": [_f32(32, 3 * chan)], "W": [_f32(chan, 3)]}


def _ssd(heads, p, n):
    return {"X": [_f32(1, 128, heads, p)], "Dt": [_f32(1, 128, heads)],
            "A": [_f32(heads)], "B": [_f32(1, 128, n)],
            "C": [_f32(1, 128, n)], "D": [_f32(heads)]}


def _delta(d):
    return {"Q": [_f32(1, 64, 1, d)], "K": [_f32(1, 64, 1, d)],
            "V": [_f32(1, 64, 2, d)], "G": [_f32(1, 64, 2)],
            "Beta": [_f32(1, 64, 2)]}


# op type -> (attrs, inputs its kernels take, the output to read, (family,
# the label that says which form, its value for the kernels) or None for an
# op that reports nothing, and for an op with an ``impl`` attr: inputs the
# kernels do not take and the op's sentence for them)
KERNEL_FAMILIES = {
    "fused_attention": (
        {"is_test": True}, _attention(512), "Out",
        ("attention_lowering_total", "impl", "pallas"),
        _attention(100), "requires S % 128 == 0, a .B,1,1,S. bias"),
    "rotary_embedding": (
        {}, {"X": [_f32(1, 2, 16, 64)]}, "Out",
        ("rotary_lowering_total", "form", "kernel"), None, None),
    "short_conv": (
        {"seq": 16}, _short_conv(pallas_short_conv.BLK_C), "Out",
        ("short_conv_lowering_total", "impl", "pallas"),
        _short_conv(8), "needs channels % 128 == 0, seq % 16 == 0"),
    "ssd_scan": (
        {"chunk": 128}, _ssd(pallas_ssd.HEADS, pallas_ssd.HEAD_DIM, 128), "Y",
        ("ssd_lowering_total", "impl", "pallas"),
        _ssd(2, 4, 8), "needs heads of 64, heads % 8 == 0"),
    "gated_delta_rule": (
        {"chunk": 64}, _delta(pallas_delta.HEAD_DIM), "Out",
        ("delta_lowering_total", "impl", "pallas"),
        _delta(8), "needs key and value heads of 128 and a chunk of"),
    "moe_expert_matmul": (
        {}, {"X": [_f32(16, 8)], "W": [_f32(2, 8, 8)],
             "Count": [jax.ShapeDtypeStruct((2,), jnp.int32)]}, "Out",
        None, None, None),
}


def _contexts():
    """name -> (LowerCtx keywords, whether ``impl='auto'`` lowers the
    kernels there)."""
    devices = np.array(jax.devices())
    return {
        "off_a_mesh": ({}, True),
        "one_device_mesh": ({"gspmd_mesh": Mesh(devices[:1], ("dp",)),
                             "data_axis": "dp"}, True),
        # inside a shard_map a Mosaic call is legal
        "inside_a_shard_map": ({"mesh": Mesh(devices[:4], ("dp",))}, True),
        # a jit over several devices cannot partition one
        "gspmd_mesh": ({"gspmd_mesh": Mesh(devices[:4], ("dp",)),
                        "data_axis": "dp"}, False),
        "shape_inference": ({"abstract": True}, False),
    }


@pytest.mark.parametrize("where", ["off_a_mesh", "one_device_mesh",
                                   "inside_a_shard_map", "gspmd_mesh",
                                   "shape_inference"])
@pytest.mark.parametrize("op_type", sorted(KERNEL_FAMILIES))
def test_one_rule_decides_kernels_or_composed(op_type, where, as_on_the_chip):
    """``impl='auto'`` on a TPU (the platform test patched, nothing lowered
    for one): the op's Pallas kernels off a mesh, under a one-device mesh
    and inside a ``shard_map``; its composed form under a GSPMD mesh of
    several devices, where it lowers, and under shape inference. What the op
    reports agrees with what its trace holds."""
    attrs, ins, out, report, _, _ = KERNEL_FAMILIES[op_type]
    kw, kernels = _contexts()[where]
    main = fluid.Program()
    ctx = LowerCtx(dict(attrs), salt=3, program=main, **kw)
    jaxpr, fn = _trace(op_type, ctx, ins, out)
    assert ("pallas_call" in jaxpr) == kernels
    if report is not None:
        family, label, kernel_value = report
        forms = list(lowering_reports.read(lowering_reports.publish(main),
                                           family, label))
        # (shape inference may report nothing: no compile follows it)
        assert len(forms) == 1 or where == "shape_inference"
        assert all((form == kernel_value) == kernels for form in forms)
    if where == "gspmd_mesh":
        everywhere = NamedSharding(kw["gspmd_mesh"], P())
        jax.jit(fn, in_shardings=(everywhere,) * len(ins)).lower(
            *(ins[s][0] for s in sorted(ins))).compile()


@pytest.mark.parametrize("op_type", sorted(
    t for t, case in KERNEL_FAMILIES.items() if case[4] is not None))
def test_impl_pallas_on_shapes_the_kernels_do_not_take_says_what_they_need(
        op_type):
    """``impl='pallas'`` raises the op's own sentence; under shape inference
    (where every lowering gives the same shapes) it is the composed form,
    and ``impl='auto'`` takes it in silence."""
    attrs, _, out, _, bad, sentence = KERNEL_FAMILIES[op_type]
    with pytest.raises(ValueError, match=f"{op_type} impl='pallas' {sentence}"):
        _trace(op_type, LowerCtx(dict(attrs, impl="pallas")), bad, out)
    for ctx in (LowerCtx(dict(attrs, impl="pallas"), abstract=True),
                LowerCtx(dict(attrs, impl="auto"))):
        assert "pallas_call" not in _trace(op_type, ctx, bad, out)[0]
