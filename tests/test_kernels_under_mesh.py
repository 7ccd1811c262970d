"""The Pallas kernels under a GSPMD mesh (``LowerCtx.island``,
``pallas_mode.lowers_kernels``'s ``shards``): ``auto`` is the kernels, each
device on its own batch rows inside a ``shard_map`` island over the data
axis, where the batch divides over it, and the composed form where it does
not; one device is what it was; BERT's attention at S=128 under dp4 stays
XLA's."""
import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.registry import LowerCtx
from paddle_tpu.observability import lowerings
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.ops import pallas_mode


def mesh(n=4, axis="dp"):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), (axis,))


def ctx_under(gspmd_mesh, **kw):
    return LowerCtx({}, gspmd_mesh=gspmd_mesh, data_axis="dp", **kw)


def test_lowers_kernels_by_shards():
    one = ctx_under(None)
    assert pallas_mode.lowers_kernels(one, "auto", True)
    under = ctx_under(mesh())
    assert under.data_shards(8) == 4 and under.data_shards(6) == 1
    # no island: the composed form, as before; an island: the kernels
    assert not pallas_mode.lowers_kernels(under, "auto", True)
    assert pallas_mode.lowers_kernels(under, "auto", True,
                                      shards=under.data_shards(8))
    assert not pallas_mode.lowers_kernels(under, "auto", True,
                                          shards=under.data_shards(6))
    assert not pallas_mode.lowers_kernels(under, "auto", False, shards=4)
    # a mesh of one device, and shape inference, are what they were
    assert pallas_mode.lowers_kernels(ctx_under(mesh(1)), "auto", True)
    assert not pallas_mode.lowers_kernels(
        LowerCtx({}, gspmd_mesh=mesh(), data_axis="dp", abstract=True),
        "auto", True, shards=4)


def test_inside_an_island_the_kernels_are_legal_and_no_island_opens():
    from jax.sharding import PartitionSpec as P
    under = ctx_under(mesh())
    seen = {}

    def local(x):
        seen["shards"] = under.data_shards(x.shape[0])
        seen["kernels"] = pallas_mode.lowers_kernels(under, "auto", True)
        return under.island(lambda y: y * 2, (x,), (True,))
    out = jax.jit(jax.shard_map(local, mesh=mesh(), in_specs=P("dp"),
                                out_specs=P("dp")))(np.ones((8, 4), "f4"))
    assert seen == {"shards": 1, "kernels": True}
    assert np.array_equal(np.asarray(out), np.full((8, 4), 2, "f4"))


def attention_program(batch, seq, heads=4, kv=2, d=64, window=None):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        q = fluid.data("q", [batch, heads, seq, d], "float32", **A)
        k = fluid.data("k", [batch, kv, seq, d], "float32", **A)
        v = fluid.data("v", [batch, kv, seq, d], "float32", **A)
        for t in (q, k, v):
            t.stop_gradient = False
        out = layers.fused_attention(
            layers.rotary_embedding(q, 10000.0), layers.rotary_embedding(
                k, 10000.0), v, causal=True, impl="auto", window=window)
        loss = layers.mean(layers.square(out))
        fluid.append_backward(loss)
    return main, startup, out, loss


def run_attention(batch, seq, under_mesh, window=None, rules=()):
    main, startup, out, loss = attention_program(batch, seq, window=window)
    rng = np.random.RandomState(0)
    feed = {n: rng.randn(*main.global_block().var(n).shape).astype("f4")
            for n in ("q", "k", "v")}
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    prog = main
    if under_mesh:
        prog = fluid.CompiledProgram(main).with_strategy(
            fluid.DistributedStrategy(mesh_shape={"dp": 4},
                                      data_rules=list(rules)))
    seen, real = {}, lowerings.publish

    def publish(notes, program, registry=None, role=""):
        fresh = MetricsRegistry()
        real(dict(notes), program, fresh, role)
        seen["registry"] = fresh
        return real(notes, program, registry, role)
    lowerings.publish = publish
    try:
        got = exe.run(prog, feed=feed, fetch_list=[
            out.name, "q@GRAD", "k@GRAD", "v@GRAD"], scope=scope)
    finally:
        lowerings.publish = real

    def count(family, **want):
        fam = seen["registry"].get(family)
        return sum(c.value for labels, c in (fam.items() if fam else ())
                   if set(want.items()) <= set(labels))
    return [np.asarray(g) for g in got], count


@pytest.mark.parametrize("window", [None, 128], ids=["full", "window"])
def test_attention_and_rotary_take_the_kernels_in_an_island(window):
    want, count = run_attention(4, 256, False, window)
    assert count("attention_lowering_total", impl="pallas", mesh="none") == 1
    got, count = run_attention(4, 256, True, window)
    assert count("attention_lowering_total", impl="pallas",
                 mesh="island") == 1
    assert count("attention_backward_total", stats="saved") == 1
    assert count("rotary_lowering_total", form="kernel", impl="pallas",
                 mesh="island") == 4
    for a, b in zip(want, got):
        assert np.abs(a - b).max() <= 2e-5 * np.abs(a).max()


def test_a_batch_the_data_axis_does_not_divide_stays_composed():
    # six sequences, whole on every device: no island to cut them over
    got, count = run_attention(6, 256, True, rules=[("q|k|v", ())])
    assert count("attention_lowering_total", impl="xla", mesh="none") == 1
    assert count("attention_lowering_total", impl="pallas") == 0
    assert count("rotary_lowering_total", form="composed", mesh="none") == 4
    want, _ = run_attention(6, 256, False)
    for a, b in zip(want, got):
        assert np.abs(a - b).max() <= 2e-5 * np.abs(a).max()


def test_bert_attention_at_s128_under_dp4_stays_xla():
    """``bert_base.pretrain_s128_dp4``'s lowering must not move: S=128 is
    under ``AUTO_PALLAS_MIN_S`` (and the op carries a bias and dropout,
    which no island takes)."""
    _, count = run_attention(8, 128, True)
    assert count("attention_lowering_total", impl="xla", mesh="none") == 1
    assert count("attention_lowering_total", impl="pallas") == 0
