"""What a Mamba-2 layer adds to the decoder ops, through ``layers.*`` ->
``Program`` -> ``Executor``: ``ssd_scan`` (the composed chunked form, and the
Pallas kernels in the interpreter) against the float32 recurrence position by
position, outputs and every input's gradient; the state handed across a
chunk's edge; and the short convolution in its ungated form against plain
``jax.numpy``, with LFM2's gated call bit for bit what it was."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lowering_reports
import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.registry import LowerCtx
from paddle_tpu.ops import pallas_short_conv as psc
from paddle_tpu.ops import pallas_ssd
from paddle_tpu.ops.decoder_ops import _chunk_sums
from benchmark.references import granite_pretrain as reference
from test_decoder_ops import close, rng, run_with_grads

NAMES = ["x", "dt", "a", "b", "c", "d"]


def scan_inputs(batch, seq, heads, p, n, seed=0):
    r = rng(seed)
    return {
        "x": r.randn(batch, seq, heads, p).astype("float32"),
        # after a softplus: positive, a few per cent to about one
        "dt": np.log1p(np.exp(r.randn(batch, seq, heads) - 1)).astype(
            "float32"),
        "a": -np.exp(r.uniform(0, np.log(16), heads)).astype("float32"),
        "b": (r.randn(batch, seq, n) * 0.3).astype("float32"),
        "c": (r.randn(batch, seq, n) * 0.3).astype("float32"),
        "d": r.randn(heads).astype("float32")}


def _scan_with(impl, chunk, *v):
    helper = fluid.layer_helper.LayerHelper("ssd_scan")
    out = helper.create_variable_for_type_inference(v[0].dtype)
    helper.append_op(
        "ssd_scan", inputs=dict(zip(("X", "Dt", "A", "B", "C", "D"),
                                    ([x] for x in v))),
        outputs={"Y": [out]}, attrs={"chunk": chunk, "impl": impl})
    return helper.main_program.current_block().var(out.name)


# one chunk, several chunks, a batch of two; the kernels want heads of 64 in
# blocks of 8, a state and a chunk of 128s
@pytest.mark.parametrize("impl,batch,seq,heads,p,n,chunk", [
    ("composed", 1, 8, 2, 4, 8, 8), ("composed", 1, 24, 2, 4, 8, 8),
    ("composed", 2, 16, 3, 4, 8, 4), ("auto", 2, 12, 2, 4, 8, 256),
    ("pallas", 1, 128, 8, 64, 128, 128), ("pallas", 1, 384, 8, 64, 128, 128),
    ("pallas", 2, 256, 16, 64, 128, 128), ("auto", 1, 256, 8, 64, 128, 128)])
def test_ssd_scan_equals_the_recurrence_and_its_gradient(
        impl, batch, seq, heads, p, n, chunk):
    """``pallas`` runs the kernel bodies in the interpreter
    (tests/conftest.py); ``auto`` takes them where the shapes allow and the
    composed form elsewhere. The reference is the recurrence over positions
    (``lax.scan``), not a chunked form."""
    feeds = scan_inputs(batch, seq, heads, p, n)
    out, grads, _, g, _ = run_with_grads(
        functools.partial(_scan_with, impl, chunk), feeds, NAMES)
    with jax.default_matmul_precision("highest"):
        want = reference.selective_scan(*(feeds[k] for k in NAMES))
        want_grads = jax.grad(
            lambda *v: jnp.sum(reference.selective_scan(*v) * g),
            tuple(range(6)))(*(jnp.asarray(feeds[k]) for k in NAMES))
    close(out, want, 1e-4)
    for name, got, ref in zip(NAMES, grads, want_grads):
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=5e-4 * np.abs(ref).max(), err_msg=name)


@pytest.mark.parametrize("impl,seq,heads,p,n,chunk", [
    ("composed", 16, 2, 4, 8, 8), ("pallas", 256, 8, 64, 128, 128)])
def test_the_state_crosses_a_chunks_edge_and_not_a_sequences(
        impl, seq, heads, p, n, chunk):
    """Positions after a chunk's edge see the chunk before it (another first
    chunk moves them); the second sequence of a batch sees nothing of the
    first."""
    feeds = scan_inputs(2, seq, heads, p, n, seed=3)
    feeds["dt"] = feeds["dt"] * 0.05        # a memory of some 20 positions
    other = {k: v.copy() for k, v in feeds.items()}
    other["x"][0, :chunk] = rng(4).randn(chunk, heads, p)

    def run(f):
        return run_with_grads(functools.partial(_scan_with, impl, chunk), f,
                              [])[0]
    a, b = run(feeds), run(other)
    np.testing.assert_array_equal(a[1], b[1])
    assert np.abs(a[0, chunk:chunk + 4] - b[0, chunk:chunk + 4]).max() > 0.05
    # a sequence's first position starts from a zero state
    first = (feeds["dt"][1, 0][:, None] * feeds["x"][1, 0] * np.sum(
        feeds["b"][1, 0] * feeds["c"][1, 0])
        + feeds["d"][:, None] * feeds["x"][1, 0])
    close(a[1, 0], first, 1e-5)
    # what a comparison at 1e-4 of the largest output sees: the recurrence
    # with its carried state kept in bfloat16 is several times that off
    # (without the D x term, which no state enters)
    plain = dict(feeds, d=np.zeros_like(feeds["d"]))
    with jax.default_matmul_precision("highest"):
        exact, coarse = (np.asarray(reference.selective_scan(
            *(plain[k] for k in NAMES), state_dtype=t))
            for t in (None, jnp.bfloat16))
    close(run(plain), exact, 1e-4)
    assert np.abs(coarse - exact).max() > 5e-4 * np.abs(exact).max()


def test_ssd_scan_refuses_what_it_cannot_chunk_and_counts_what_it_took():
    feeds = scan_inputs(1, 12, 2, 4, 8)
    with pytest.raises(Exception, match="must divide"):
        run_with_grads(functools.partial(_scan_with, "auto", 8), feeds, [])
    with pytest.raises(Exception, match="impl='pallas' needs"):
        run_with_grads(functools.partial(_scan_with, "pallas", 4), feeds, [])
    assert pallas_ssd.supports(4096, 64, 64, 128, 256)
    assert not pallas_ssd.supports(4096, 64, 32, 128, 256)
    assert not pallas_ssd.supports(4096, 60, 64, 128, 256)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        v = [fluid.data(k, list(a.shape), "float32", append_batch_size=False)
             for k, a in feeds.items()]
        y = layers.ssd_scan(*v, chunk=4)
    assert tuple(y.shape) == (1, 12, 2, 4)
    (op,) = [op for op in main.global_block().ops if op.type == "ssd_scan"]
    assert op.attr("chunk") == 4 and op.attr("impl") == "auto"
    for salt in (1, 2):
        LowerCtx({}, salt=salt, program=main).report(
            "ssd_lowering_total", impl="pallas", chunk=256, heads=64,
            state=128)
    LowerCtx({}, salt=3, program=main).report(
        "short_conv_lowering_total", impl="pallas", form="plain",
        activation="silu", taps=4)
    registry = lowering_reports.publish(main)
    assert registry.counter("ssd_lowering_total", program="p", impl="pallas",
                            chunk="256", heads="64", state="128").value == 2
    assert registry.counter(
        "short_conv_lowering_total", program="p", impl="pallas",
        form="plain", activation="silu", taps="4").value == 1
    assert lowering_reports.publish(fluid.Program(), "q").get(
        "ssd_lowering_total") is None


def plain_conv(x, w, b, seq, act):
    """``silu(conv(x) + b)`` position by position, each sequence by itself."""
    rows, chan = x.shape
    taps = w.shape[1]
    z = x.reshape(rows // seq, seq, chan)
    out = []
    for t in range(seq):
        acc = jnp.zeros_like(z[:, 0]) + (0.0 if b is None else b)
        for j in range(taps):
            src = t - (taps - 1) + j
            if src >= 0:
                acc = acc + w[:, j] * z[:, src]
        out.append(acc)
    out = jnp.stack(out, axis=1).reshape(rows, chan)
    return jax.nn.silu(out) if act else out


def _conv_with(seq, impl, act, x, w, b=None):
    helper = fluid.layer_helper.LayerHelper("short_conv")
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "W": [w]}
    if b is not None:
        inputs["Bias"] = [b]
    helper.append_op("short_conv", inputs=inputs, outputs={"Out": [out]},
                     attrs={"seq": seq, "impl": impl, "gated": False,
                            "activation": act})
    return helper.main_program.current_block().var(out.name)


@pytest.mark.parametrize("impl,seq,chan,taps,bias,act", [
    ("composed", 5, 8, 4, True, "silu"), ("pallas", 32, 256, 4, True, "silu"),
    ("pallas", 16, 128, 3, False, ""), ("pallas", 16, 128, 4, True, ""),
    ("auto", 16, 128, 4, False, "silu"), ("auto", 6, 8, 2, True, "silu")])
def test_ungated_conv_equals_its_plain_form_and_gradient(
        impl, seq, chan, taps, bias, act):
    feeds = {"x": rng(1).randn(3 * seq, chan).astype("float32"),
             "w": rng(2).randn(chan, taps).astype("float32")}
    if bias:
        feeds["b"] = rng(3).randn(chan).astype("float32")
    out, grads, _, g, _ = run_with_grads(
        functools.partial(_conv_with, seq, impl, act), feeds, list(feeds))
    b = feeds.get("b")
    close(out, plain_conv(feeds["x"], feeds["w"], b, seq, act))
    want = jax.grad(
        lambda *v: jnp.sum(plain_conv(v[0], v[1], v[2] if bias else None,
                                      seq, act) * g),
        tuple(range(len(feeds))))(*feeds.values())
    for got, ref in zip(grads, want):
        close(got, ref)
    assert psc.supports(4096, 4352, 4, True)
    assert not psc.supports(4096, 4352, 8, True)


def test_layer_creates_the_filter_and_the_bias_of_the_ungated_form():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        v = fluid.data("x", [32, 128], "float32", append_batch_size=False)
        y = layers.short_conv(v, 16, 4, fluid.ParamAttr(name="f"),
                              bias_attr=fluid.ParamAttr(name="fb"),
                              gated=False, activation="silu")
    block = main.global_block()
    assert tuple(block.var("f").shape) == (128, 4)
    assert tuple(block.var("fb").shape) == (128,)
    assert tuple(y.shape) == (32, 128)
    with pytest.raises(Exception, match="activation"):
        run_with_grads(functools.partial(_conv_with, 16, "auto", "gelu"),
                       {"x": np.zeros((32, 128), "float32"),
                        "w": np.zeros((128, 3), "float32")}, [])


# ---- LFM2's gated call, bit for bit what it was ---------------------------
# the kernel bodies as PR 32 wrote them (before the op was told its form)

def _old_fwd_kernel(taps, b_ref, c_ref, u_ref, w_ref, o_ref):
    z = b_ref[0].astype(jnp.float32) * u_ref[0].astype(jnp.float32)
    w = w_ref[...]
    conv = sum(psc._behind(z, taps - 1 - j) * w[j:j + 1]
               for j in range(taps))
    o_ref[0] = (c_ref[0].astype(jnp.float32) * conv).astype(o_ref.dtype)


def _old_bwd_kernel(taps, b_ref, c_ref, u_ref, w_ref, g_ref,
                    db_ref, dc_ref, du_ref, dw_ref):
    bf, cf, uf, g = (r[0].astype(jnp.float32)
                     for r in (b_ref, c_ref, u_ref, g_ref))
    w = w_ref[...]
    z = bf * uf
    past = [psc._behind(z, taps - 1 - j) for j in range(taps)]
    dc_ref[0] = (g * sum(p * w[j:j + 1] for j, p in enumerate(past))
                 ).astype(dc_ref.dtype)
    dconv = g * cf
    dz = sum(psc._ahead(dconv, taps - 1 - j) * w[j:j + 1]
             for j in range(taps))
    db_ref[0] = (dz * uf).astype(db_ref.dtype)
    du_ref[0] = (dz * bf).astype(du_ref.dtype)
    dw_ref[0] = jnp.concatenate(
        [jnp.sum(dconv * p, axis=0, keepdims=True) for p in past]
        + [jnp.zeros((8 - taps, z.shape[1]), jnp.float32)], axis=0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gated_conv_is_bit_for_bit_what_it_was(dtype, monkeypatch):
    seq, chan, taps = 32, 256, 3
    x = jnp.asarray(rng(5).randn(2 * seq, 3 * chan), dtype)
    w = jnp.asarray(rng(6).randn(chan, taps), dtype)
    g = jnp.asarray(rng(7).randn(2 * seq, chan), dtype)

    def both():
        out, vjp = jax.vjp(lambda a, b: psc.short_conv(a, b, seq, True), x, w)
        return [np.asarray(v.astype(jnp.float32)) for v in (out, *vjp(g))]
    now = both()
    monkeypatch.setattr(psc, "_fwd_kernel", lambda taps, gated, bias, act,
                        *refs: _old_fwd_kernel(taps, *refs))
    monkeypatch.setattr(psc, "_bwd_kernel", lambda taps, gated, bias, act,
                        *refs: _old_bwd_kernel(taps, *refs))
    jax.clear_caches()
    for a, b in zip(now, both()):
        np.testing.assert_array_equal(a, b)


def test_bfloat16_kernels_keep_the_decays_gradient():
    """bfloat16 x, B and C through the kernels (interpreter) against the
    float32 recurrence on the same rounded values, two chunks of 256 with
    mamba_ssm's range of dt and A: every input's gradient within a per cent.
    The gradient of ``A`` (and of ``dt`` through the decay) is a running sum
    in which nearly all of a chunk's pairs cancel; formed from operands
    rounded differently on the two sides it read 6% off here and 12-33% off
    on the chip (PERF.md section 6, PR 35)."""
    r = rng(0)
    batch, seq, heads, p, n, chunk = 1, 512, 8, 64, 128, 256
    bf, f32 = jnp.bfloat16, (lambda v: v.astype(jnp.float32))
    x = jnp.asarray(r.randn(batch, seq, heads, p) * 0.3, bf)
    bm = jnp.asarray(r.randn(batch, seq, n) * 0.3, bf)
    cm = jnp.asarray(r.randn(batch, seq, n) * 0.3, bf)
    dt = jnp.asarray(
        np.exp(r.uniform(np.log(1e-3), np.log(1e-1), (1, 1, heads)))
        * np.exp(r.randn(batch, seq, heads) * 0.3), jnp.float32)
    a = -jnp.asarray(r.uniform(1, 16, heads), jnp.float32)
    d = jnp.ones((heads,), jnp.float32)
    w = f32(jnp.asarray(r.randn(batch, seq, heads, p), bf))
    got = jax.grad(lambda *v: jnp.sum(f32(pallas_ssd.ssd_scan(
        *v, chunk, True)) * w), tuple(range(6)))(x, dt, a, bm, cm, d)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(
            lambda *v: jnp.sum(reference.selective_scan(*v) * w),
            tuple(range(6)))(f32(x), dt, a, f32(bm), f32(cm), d)
    for name, g, ref in zip(NAMES, got, want):
        g, ref = np.asarray(f32(g)), np.asarray(ref)
        assert np.linalg.norm(g - ref) <= 0.01 * np.linalg.norm(ref), name


# ---- the kernels against their form before PR 60 ---------------------------
# tools/ssd_loop_form.py holds the kernels as they were: whole [Q, Q] blocks,
# five lane sums a tile, the scalars in two padded layouts, 8 heads a step

def _kernel_feeds(dtype, batch=2, seq=512, heads=32, n=128, seed=11):
    r = rng(seed)
    x, dy = (jnp.asarray(r.randn(batch, seq, heads * 64) * 0.5, dtype)
             for _ in range(2))
    bm, cm = (jnp.asarray(r.randn(batch, seq, n) * 0.3, dtype)
              for _ in range(2))
    dt = jnp.asarray(np.log1p(np.exp(r.randn(batch, seq, heads) - 1)),
                     jnp.float32)
    a = -jnp.asarray(np.exp(r.uniform(0, np.log(16), heads)), jnp.float32)
    drow = jnp.repeat(jnp.asarray(r.randn(heads), jnp.float32), 64)[None]
    return x, dt, a, bm, cm, drow, dy


@pytest.mark.parametrize("chunk", [128, 256])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_are_bit_for_bit_what_they_were_but_for_one_sum(
        dtype, chunk, monkeypatch):
    """``y``, the states and the gradients of the kernels (the ``[Q, Q]``
    matrices as their strips on and under the diagonal, the scalars as one
    block a head a row) against the pinned form's at as many heads a step,
    two sequences of 512, two head blocks. In chunks of 128 (one strip):
    equal, not close -- except ``dcum``, whose three lane sums are now the
    one sum of the products' difference, and that to a millionth of its
    largest entry. In chunks of 256 a strip's products leave out the block
    above the diagonal: the same sums without their zero terms on the MXU
    (``tools/granite_probe.py kernels`` compares the bits on the chip), but
    XLA:CPU's dot groups a shorter contraction otherwise, so here close: a
    millionth, or an output's bfloat16 rounding."""
    from tools import ssd_loop_form as loop
    x, dt, a, bm, cm, drow, dy = _kernel_feeds(dtype)
    assert pallas_ssd.step_heads(dt.shape[2]) == pallas_ssd.HEADS == 16
    monkeypatch.setattr(loop, "HEADS", pallas_ssd.HEADS)
    jax.clear_caches()
    ops = (x, dt, _chunk_sums(dt * a, chunk), bm, cm, drow)

    def outputs(form):
        return [np.asarray(v.astype(jnp.float32)) for v in (
            form._fwd_call(*ops, chunk, True),
            form._fwd_call(*ops, chunk, True, "states"),
            *form._bwd_call(*ops, dy, chunk, True))]
    names = ["y", "states", "dx", "ddt", "dcum", "dB", "dC", "dD"]
    rounded = {"y", "dx", "dB", "dC"} if dtype == "bfloat16" else ()
    for name, now, was in zip(names, outputs(pallas_ssd), outputs(loop)):
        assert np.abs(was).max() > 0, name
        if chunk == 128 and name != "dcum":
            np.testing.assert_array_equal(now, was, err_msg=name)
        else:
            np.testing.assert_allclose(
                now, was, rtol=2.0 ** -7 if name in rounded else 0,
                atol=1e-6 * np.abs(was).max(), err_msg=name)
    jax.clear_caches()


@pytest.mark.parametrize("head", [0, 7, 16, 29])
def test_a_heads_scalar_gradients_land_in_its_column(head):
    """The scalars travel a head a row (``_by_head``: ``[B, blocks, 2 *
    step, S]``, ``dt``'s rows over ``cum``'s) and ``_heads_last`` turns ``ddt``
    / ``dcum`` back: with a cotangent on one head's lanes alone, of two head
    blocks, that head's column is the only one that is not zero."""
    chunk = 128
    x, dt, a, bm, cm, drow, dy = _kernel_feeds(jnp.float32, batch=1, seq=256)
    lanes = np.arange(dy.shape[-1]) // 64 == head
    cum = _chunk_sums(dt * a, chunk)
    _, ddt, dcum, *_ = pallas_ssd._bwd_call(
        x, dt, cum, bm, cm, drow, jnp.where(lanes, dy, 0.0), chunk, True)
    for name, got in (("ddt", ddt), ("dcum", dcum)):
        assert got.shape == dt.shape, name
        per_head = np.abs(np.asarray(got)).max(axis=(0, 1))
        assert per_head[head] > 0, name
        assert not np.delete(per_head, head).any(), name
    step = pallas_ssd.step_heads(dt.shape[2])
    by_head = pallas_ssd._by_head(dt, cum)
    assert by_head.shape == (1, 32 // step, 2 * step, 256)
    for which, v in enumerate((dt, cum)):
        np.testing.assert_array_equal(
            by_head[0, head // step, which * step + head % step],
            v[0, :, head])
        np.testing.assert_array_equal(
            pallas_ssd._heads_last(by_head, which), v)


def test_a_step_takes_whole_blocks_of_heads():
    assert [pallas_ssd.step_heads(h) for h in (8, 16, 24, 32, 40, 48, 64)
            ] == [8, 16, 8, 16, 8, 16, 16]
