"""What a Gated DeltaNet layer adds to the decoder ops, through ``layers.*``
-> ``Program`` -> ``Executor``: ``gated_delta_rule`` (the composed chunk
form, and the Pallas kernels in the interpreter) against the float32
recurrence position by position, outputs and every input's gradient; the
same result whatever the chunk; the state handed across a chunk's edge and
not a sequence's; what the op refuses and what it counts. Each in both
operand forms: q, k, v apart, and packed into the one array the kernels
read in place."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lowering_reports
import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.registry import LowerCtx
from paddle_tpu.ops import decoder_ops, pallas_delta
from benchmark.references import qwen3_next_pretrain as reference
from test_decoder_ops import close, rng, run_with_grads

NAMES = ["q", "k", "v", "g", "beta"]


def rule_inputs(batch, seq, key_heads, heads, dk, dv, seed=0):
    r = rng(seed)
    return {
        "q": r.randn(batch, seq, key_heads, dk).astype("float32"),
        "k": r.randn(batch, seq, key_heads, dk).astype("float32"),
        "v": r.randn(batch, seq, heads, dv).astype("float32"),
        # -A softplus(.): a memory of one to a thousand positions
        "g": -np.exp(r.uniform(np.log(1e-3), np.log(1.6),
                               (batch, seq, heads))).astype("float32"),
        "beta": (1 / (1 + np.exp(-r.randn(batch, seq, heads)))).astype(
            "float32")}


def rule_with(impl, chunk, form="split"):
    """The layer over the five feeds: as three operands, or (``packed``) as
    the one ``q | k | v`` array a projection writes, built by a concat the
    feeds' gradients come back through."""
    if form == "split":
        return lambda *v: layers.gated_delta_rule(*v, chunk=chunk, impl=impl)

    def packed(q, k, v, g, beta):
        b, s, n_k, d_k = (int(d) for d in q.shape)
        qkv = layers.concat([layers.reshape(x, [b, s, -1])
                             for x in (q, k, v)], axis=2)
        return layers.gated_delta_rule_packed(qkv, g, beta, n_k, d_k,
                                              chunk=chunk, impl=impl)
    return packed


def recurrence(feeds, g=None):
    with jax.default_matmul_precision("highest"):
        args = tuple(jnp.asarray(feeds[k]) for k in NAMES)
        want = reference.delta_rule(*args)
        if g is None:
            return want
        return want, jax.grad(
            lambda *v: jnp.sum(reference.delta_rule(*v) * g),
            tuple(range(5)))(*args)


# one chunk, several chunks, a batch of two, one and two value heads a key
# head; the kernels want heads of 128 and a chunk of 64 or 128
@pytest.mark.parametrize("form", ["split", "packed"])
@pytest.mark.parametrize("impl,batch,seq,key_heads,heads,dk,dv,chunk", [
    ("composed", 1, 8, 2, 4, 8, 8, 8), ("composed", 1, 24, 2, 2, 4, 8, 8),
    ("composed", 2, 16, 1, 3, 4, 8, 4), ("auto", 2, 12, 2, 4, 8, 4, 64),
    ("pallas", 1, 128, 1, 2, 128, 128, 64),
    ("pallas", 2, 256, 2, 4, 128, 128, 128),
    ("pallas", 1, 192, 2, 2, 128, 128, 64),
    ("auto", 1, 128, 1, 2, 128, 128, 128)])
def test_gated_delta_rule_equals_the_recurrence_and_its_gradient(
        impl, batch, seq, key_heads, heads, dk, dv, chunk, form):
    """``pallas`` runs the kernel bodies in the interpreter
    (tests/conftest.py); ``auto`` takes them where the shapes allow and the
    composed form elsewhere. The reference is the recurrence over positions
    (``lax.scan``), not a chunk form. ``packed``: q | k | v as one array,
    which the kernels read in place and the composed form by column
    ranges."""
    feeds = rule_inputs(batch, seq, key_heads, heads, dk, dv)
    out, grads, _, g, _ = run_with_grads(rule_with(impl, chunk, form), feeds,
                                         NAMES)
    want, want_grads = recurrence(feeds, g)
    close(out, want, 1e-4)
    for name, got, ref in zip(NAMES, grads, want_grads):
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=1e-4 * np.abs(ref).max(), err_msg=name)


@pytest.mark.parametrize("impl,dk,form", [
    ("composed", 8, "split"), ("pallas", 128, "split"),
    ("composed", 8, "packed"), ("pallas", 128, "packed")])
def test_chunks_of_64_and_128_give_the_same_result(impl, dk, form):
    feeds = rule_inputs(1, 256, 1, 2, dk, dk, seed=2)
    a, b = (run_with_grads(rule_with(impl, c, form), feeds, [])[0]
            for c in (64, 128))
    close(a, b, 2e-6)
    close(a, recurrence(feeds), 1e-4)


@pytest.mark.parametrize("impl,seq,dk,chunk,form", [
    ("composed", 16, 8, 8, "split"), ("pallas", 128, 128, 64, "split"),
    ("pallas", 128, 128, 64, "packed")])
def test_the_state_crosses_a_chunks_edge_and_not_a_sequences(
        impl, seq, dk, chunk, form):
    """Positions after a chunk's edge see the chunk before it (another first
    chunk moves them); the second sequence of a batch sees nothing of the
    first, and a sequence's first position starts from a zero state: ``o_0 =
    beta_0 (k_0 . q_0) v_0`` over the unit k and scaled unit q."""
    feeds = rule_inputs(2, seq, 1, 2, dk, dk, seed=3)
    feeds["g"] = feeds["g"] * 0.05          # a long memory
    other = {k: v.copy() for k, v in feeds.items()}
    other["v"][0, :chunk] = rng(4).randn(chunk, 2, dk)

    def run(f):
        return run_with_grads(rule_with(impl, chunk, form), f, [])[0]
    a, b = run(feeds), run(other)
    np.testing.assert_array_equal(a[1], b[1])
    assert np.abs(a[0, chunk:chunk + 4] - b[0, chunk:chunk + 4]).max() > \
        0.05 * np.abs(a[0, chunk:chunk + 4]).max()
    q, k = feeds["q"][1, 0, 0], feeds["k"][1, 0, 0]
    dot = (q / np.sqrt(np.sum(q * q) + 1e-6) / np.sqrt(dk)) @ (
        k / np.sqrt(np.sum(k * k) + 1e-6))
    close(a[1, 0], feeds["beta"][1, 0][:, None] * dot * feeds["v"][1, 0],
          1e-5)
    # what a comparison at 1e-4 of the largest output sees: the recurrence
    # with its carried state kept in bfloat16 is many times that off
    with jax.default_matmul_precision("highest"):
        coarse = np.asarray(reference.delta_rule(
            *(jnp.asarray(feeds[n]) for n in NAMES),
            state_dtype=jnp.bfloat16))
    exact = np.asarray(recurrence(feeds))
    close(a, exact, 1e-4)
    assert np.abs(coarse - exact).max() > 5e-4 * np.abs(exact).max()


def operands(feeds, form, dtype=jnp.float32):
    """What ``pallas_delta.chunked`` takes of the feeds beside the running
    sums and ``beta``: raw q, k, v, three arrays or one."""
    flat = decoder_ops._flat
    q, k, v = (flat(jnp.asarray(feeds[n], dtype)) for n in ("q", "k", "v"))
    return (q, k, v) if form == "split" else jnp.concatenate([q, k, v], -1)


def composed(q, k, v, g, beta, chunk):
    qn, kn, cum = decoder_ops._delta_operands(q, k, g, chunk, jnp.float32)
    return decoder_ops.composed_gated_delta_rule(qn, kn, v, cum, beta, chunk)


@pytest.mark.parametrize("form", ["split", "packed"])
def test_the_states_output_is_the_state_entering_each_chunk(form):
    """``States`` is what the backward kernel reads: both lowerings write
    the same, and chunk 0's is zero."""
    feeds = rule_inputs(1, 128, 1, 2, 128, 128, seed=5)
    args = [jnp.asarray(feeds[n]) for n in NAMES]
    _, want = composed(*args, 64)
    o, got = pallas_delta.chunked(
        operands(feeds, form), decoder_ops._chunk_sums(args[3], 64), args[4],
        64, True)
    assert got.shape == (1, 2, 2, 128, 128) and not np.asarray(got[:, 0]).any()
    close(got, want, 1e-5)
    assert np.abs(np.asarray(want[:, 1])).max() > 1e-3


@pytest.mark.parametrize("form", ["split", "packed"])
@pytest.mark.parametrize("batch,seq,key_heads,heads,chunk", [
    (1, 128, 1, 2, 64), (2, 256, 2, 4, 128), (1, 128, 2, 2, 64)])
def test_the_kernels_equal_the_composed_form_on_its_operands(
        form, batch, seq, key_heads, heads, chunk):
    """``pallas_delta.chunked`` on raw q and k (the norms in the kernels,
    their vjp in the backward's) against ``composed_gated_delta_rule`` on
    ``_delta_operands``, differentiated by JAX: the output, the states and
    the gradient of every input, in either operand form."""
    feeds = rule_inputs(batch, seq, key_heads, heads, 128, 128, seed=7)
    args = [jnp.asarray(feeds[n]) for n in NAMES]
    do = jnp.asarray(rng(8).randn(*feeds["v"].shape), jnp.float32)
    (want, want_states), back = jax.vjp(
        lambda *a: composed(*a, chunk), *args)
    want_grads = back((do, jnp.zeros_like(want_states)))

    def kernels(qkv, g, beta):
        return pallas_delta.chunked(qkv, decoder_ops._chunk_sums(g, chunk),
                                    beta, chunk, True)
    (o, states), back = jax.vjp(kernels, operands(feeds, form), *args[3:])
    dqkv, dg, dbeta = back((decoder_ops._flat(do), jnp.zeros_like(states)))
    close(o.reshape(want.shape), want, 1e-5)
    close(states, want_states, 1e-5)
    if form == "packed":
        keys = key_heads * 128
        dqkv = dqkv[..., :keys], dqkv[..., keys:2 * keys], dqkv[..., 2 * keys:]
    for name, got, ref in zip(NAMES, (*dqkv, dg, dbeta), want_grads):
        np.testing.assert_allclose(
            np.asarray(got).reshape(ref.shape), ref, rtol=0,
            atol=2e-5 * np.abs(ref).max(), err_msg=name)


def test_packed_and_split_operands_give_the_same_bits():
    """One kernel body under two sets of index maps: the packed array read
    in place gives bit for bit what its three parts give, forward and
    backward, in bfloat16 as on the chip."""
    feeds = rule_inputs(2, 128, 2, 4, 128, 128, seed=10)
    cum = decoder_ops._chunk_sums(jnp.asarray(feeds["g"]), 64)
    beta = jnp.asarray(feeds["beta"])
    do = jnp.asarray(rng(11).randn(2, 128, 4 * 128), jnp.bfloat16)
    got = {}
    for form in ("split", "packed"):
        qkv = operands(feeds, form, jnp.bfloat16)
        o, states = pallas_delta._fwd_call(qkv, cum, beta, 64, True)
        dqkv, dg, db = pallas_delta._bwd_call(qkv, cum, beta, states, do, 64,
                                              True)
        if form == "split":
            dqkv = jnp.concatenate(dqkv, -1)
        assert o.dtype == dqkv.dtype == jnp.bfloat16
        got[form] = [np.asarray(x, np.float32)
                     for x in (o, states, dqkv, dg, db)]
    for a, b in zip(got["split"], got["packed"]):
        assert np.abs(a).max() > 0
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("form", ["split", "packed"])
def test_the_kernels_products_read_delta_operands_unit_q_and_k(form):
    """The unit q and k a grid step forms in VMEM, rounded to bfloat16, are
    ``_delta_operands``' bit for bit: a probe kernel behind the delta
    kernels' own block specs (a step's key heads of q from lane block 0 of
    the packed array, of k from the block ``key heads`` tiles in) writes
    what the products would read."""
    import functools
    from jax.experimental import pallas as pl
    feeds = rule_inputs(2, 128, 2, 4, 128, 128, seed=12)
    bf, f32 = jnp.bfloat16, jnp.float32
    q, k = (jnp.asarray(feeds[n], bf) for n in ("q", "k"))
    want_q, want_k, _ = decoder_ops._delta_operands(
        q, k, jnp.asarray(feeds["g"]), 64, bf)
    (qa, ka, _), at, n_k, step = pallas_delta._laid_out(
        operands(feeds, form, bf), 4)
    assert (n_k, step, at) == (
        2, 2, (0, 0, 0) if form == "split" else (0, 1, 1))
    key, *_ = pallas_delta._specs(64, step, 4, lambda i: i)

    def probe(q_ref, k_ref, qn_ref, kn_ref):
        pallas_delta._to_tiles(qn_ref, pallas_delta.unit(
            pallas_delta._heads(q_ref, step).astype(f32),
            pallas_delta.QUERY_SCALE).astype(bf))
        pallas_delta._to_tiles(kn_ref, pallas_delta.unit(
            pallas_delta._heads(k_ref, step).astype(f32)).astype(bf))
    shape = jax.ShapeDtypeStruct((2, 128, 2 * 128), bf)
    qn, kn = pl.pallas_call(
        probe, grid=(2, n_k // step, 2), in_specs=[key(at[0]), key(at[1])],
        out_specs=[key(), key()],
        out_shape=[shape, shape], interpret=True)(qa, ka)
    flat = decoder_ops._flat
    for got, want in ((qn, want_q), (kn, want_k)):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(flat(want), np.float32))
    assert not np.array_equal(np.asarray(qn, np.float32),
                              np.asarray(kn, np.float32))


@pytest.mark.parametrize("form", ["split", "packed"])
def test_two_value_heads_a_key_head_each_read_their_own_scalars(form):
    """``G`` and ``beta`` go in as ``[.., rep, C]`` rows and are spread to
    columns in VMEM: with ``rep = 2`` and heads that could not differ more
    (one forgets within a few positions and steps fully, its neighbour
    keeps everything and barely steps) a layout that handed a head its
    neighbour's row, or a position another's, is far off; and the
    gradients come back head by head too."""
    feeds = rule_inputs(1, 128, 2, 4, 128, 128, seed=13)
    r = rng(14)
    fast = np.arange(4) % 2 == 0            # value heads 0, 2: the first of
    feeds["g"] = np.where(                  # each key head's two
        fast, -r.uniform(0.5, 1.5, (1, 128, 4)),
        -r.uniform(1e-4, 1e-3, (1, 128, 4))).astype("float32")
    feeds["beta"] = np.where(
        fast, r.uniform(0.9, 1.0, (1, 128, 4)),
        r.uniform(0.02, 0.1, (1, 128, 4))).astype("float32")
    out, grads, _, g, _ = run_with_grads(
        rule_with("pallas", 64, form), feeds, NAMES)
    want, want_grads = recurrence(feeds, g)
    close(out, want, 1e-4)
    for name, got, ref in zip(NAMES, grads, want_grads):
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=1e-4 * np.abs(ref).max(), err_msg=name)
    # what the comparison would see of a wrong layout: each key head's two
    # rows exchanged, the positions reversed inside a chunk
    for wrong in (lambda x: x.reshape(1, 128, 2, 2)[..., ::-1].reshape(
            1, 128, 4), lambda x: x.reshape(1, 2, 64, 4)[:, :, ::-1].reshape(
            1, 128, 4)):
        other = np.asarray(recurrence(
            dict(feeds, g=wrong(feeds["g"]), beta=wrong(feeds["beta"]))))
        assert np.abs(other - np.asarray(want)).max() > \
            0.05 * np.abs(np.asarray(want)).max()


def test_gated_delta_rule_refuses_what_it_cannot_chunk_and_counts_its_ops():
    feeds = rule_inputs(1, 12, 2, 4, 8, 8)
    with pytest.raises(Exception, match="must divide"):
        run_with_grads(rule_with("auto", 8), feeds, [])
    with pytest.raises(Exception, match="impl='pallas' needs"):
        run_with_grads(rule_with("pallas", 4), feeds, [])
    bad = dict(feeds, v=feeds["v"][:, :, :3])
    bad.update(g=feeds["g"][..., :3], beta=feeds["beta"][..., :3])
    with pytest.raises(Exception, match="multiple of the key heads"):
        run_with_grads(rule_with("auto", 4), bad, [])
    with pytest.raises(Exception, match="of 62 columns is not"):
        run_with_grads(
            lambda q, k, v, g, beta: layers.gated_delta_rule_packed(
                layers.reshape(v, [1, 12, 62]), g, beta, 2, 8, chunk=4),
            dict(feeds, v=rng().randn(1, 12, 2, 31).astype("float32")), [])
    assert pallas_delta.supports(4096, 16, 32, 128, 128, 64)
    assert pallas_delta.supports(4096, 16, 32, 128, 128, 128)
    assert not pallas_delta.supports(4096, 16, 32, 64, 128, 64)
    assert not pallas_delta.supports(4096, 16, 32, 128, 128, 256)
    assert not pallas_delta.supports(4000, 16, 32, 128, 128, 64)
    # v's first column is a whole number of a key head's value blocks
    assert pallas_delta.packs(16, 32) and pallas_delta.packs(2, 2)
    assert pallas_delta.packs(2, 8) and not pallas_delta.packs(1, 4)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        v = [fluid.data(k, list(a.shape), "float32", append_batch_size=False)
             for k, a in feeds.items()]
        y = layers.gated_delta_rule(*v, chunk=4)
    assert tuple(y.shape) == (1, 12, 4, 8)
    # one op; the states it carries: a sequence x 3 chunks x 4 heads x 8 x 8
    (op,) = [op for op in main.global_block().ops
             if op.type == "gated_delta_rule"]
    assert tuple(main.global_block().find_var_recursive(
        op.outputs["States"][0]).shape) == (1, 3, 4, 8, 8)
    big = dict(impl="pallas", chunk=64, heads=32, key_dim=128, value_dim=128)
    for salt, labels in ((1, dict(big, operands="packed")),
                         (2, dict(big, operands="packed")),
                         (3, dict(big, operands="split")),
                         (4, dict(impl="composed", chunk=4, heads=4, key_dim=8,
                                  value_dim=8, operands="split"))):
        LowerCtx({}, salt=salt, program=main).report(
            "delta_lowering_total", **labels)
    assert lowering_reports.read(
        lowering_reports.publish(main), "delta_lowering_total", "impl",
        "operands") == {("pallas", "packed"): 2, ("pallas", "split"): 1,
                        ("composed", "split"): 1}
    assert lowering_reports.read(
        lowering_reports.publish(fluid.Program(), "none"),
        "delta_lowering_total", "impl") == {}


def lowerings(**want):
    """``delta_lowering_total`` of this process over the children that carry
    the labels."""
    from paddle_tpu.observability.metrics import REGISTRY
    family = REGISTRY.get("delta_lowering_total")
    return sum(child.value for labels, child in family.items()
               if set(want.items()) <= set(labels)) if family else 0


def test_a_compiled_step_counts_the_lowering_each_op_took():
    """Through the executor: the forward op's note lands in
    ``delta_lowering_total`` once a compile, whichever lowering and operand
    form it took; a packed op's shapes are read off its outputs."""
    feeds = rule_inputs(1, 128, 1, 2, 128, 128, seed=6)
    kinds = [dict(impl="pallas", operands="split"),
             dict(impl="pallas", operands="packed"),
             dict(impl="composed", operands="split"),
             dict(impl="composed", operands="packed")]
    before = [lowerings(**k) for k in kinds]
    run_with_grads(rule_with("auto", 64), feeds, ["q"])
    run_with_grads(rule_with("auto", 64, "packed"), feeds, ["q"])
    run_with_grads(rule_with("composed", 64), feeds, [])
    run_with_grads(rule_with("composed", 64, "packed"), feeds, [])
    # the composed form cuts its operands out of a packed array: ``split``
    assert [lowerings(**k) - b
            for k, b in zip(kinds, before)] == [1, 1, 2, 0]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        v = [fluid.data(k, list(a.shape), "float32", append_batch_size=False)
             for k, a in feeds.items()]
        y = rule_with("auto", 64, "packed")(*v)
    assert tuple(y.shape) == (1, 128, 2, 128)
    (op,) = [op for op in main.global_block().ops
             if op.type == "gated_delta_rule"]
    assert "QKV" in op.inputs and tuple(main.global_block().find_var_recursive(
        op.outputs["States"][0]).shape) == (1, 2, 2, 128, 128)


def test_one_value_block_short_of_a_whole_offset_falls_back_to_three_operands():
    """Four value heads over one key head: v starts two tiles in, not a
    whole block of four, so the kernels cannot address it in the packed
    array; the op cuts three operands out of it and says ``split``."""
    feeds = rule_inputs(1, 64, 1, 4, 128, 128, seed=15)
    cut = dict(impl="pallas", operands="split", heads="4")
    before = lowerings(**cut)
    out, grads, _, g, _ = run_with_grads(
        rule_with("pallas", 64, "packed"), feeds, NAMES)
    assert lowerings(**cut) - before == 1
    want, want_grads = recurrence(feeds, g)
    close(out, want, 1e-4)
    for name, got, ref in zip(NAMES, grads, want_grads):
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=1e-4 * np.abs(ref).max(), err_msg=name)


# -- a decay a key channel (Kimi Delta Attention) ------------------------------

def channel_inputs(batch, seq, heads, d, seed=0, steep=False):
    """``rule_inputs`` with ``g [batch, seq, heads, d]``: a memory of one to
    a thousand positions a channel, or (``steep``) a fall of 0.5 to 3 a
    position and channel, under which ``exp(-G)`` overflows float32 within
    30 to 180 positions of a chunk."""
    r = rng(seed)
    feeds = rule_inputs(batch, seq, heads, heads, d, d, seed)
    feeds["g"] = (-r.uniform(0.5, 3.0, (batch, seq, heads, d)) if steep else
                  -np.exp(r.uniform(np.log(1e-3), np.log(1.6),
                                    (batch, seq, heads, d)))).astype("float32")
    return feeds


def channel_recurrence(feeds, g=None):
    from benchmark.references import kimi_linear_pretrain as kimi
    with jax.default_matmul_precision("highest"):
        args = tuple(jnp.asarray(feeds[k]) for k in NAMES)
        want = kimi.delta_rule(*args)
        if g is None:
            return want
        return want, jax.grad(
            lambda *v: jnp.sum(kimi.delta_rule(*v) * g),
            tuple(range(5)))(*args)


@pytest.mark.parametrize("form", ["split", "packed"])
@pytest.mark.parametrize("impl,batch,seq,heads,d,chunk", [
    ("composed", 1, 16, 2, 8, 8), ("composed", 2, 32, 1, 8, 16),
    ("composed", 1, 64, 2, 16, 32), ("auto", 2, 16, 2, 8, 4),
    ("pallas", 1, 128, 2, 128, 64), ("pallas", 2, 256, 1, 128, 128),
    ("auto", 1, 192, 2, 128, 64)])
def test_channel_decay_equals_the_recurrence_and_its_gradient(
        impl, batch, seq, heads, d, chunk, form):
    """``G [B, S, heads, d_k]``: the state's row c decays by ``exp(g[c])``.
    The composed chunk form and the kernels (interpreter) against the
    recurrence position by position of the Kimi Linear reference, the
    output and the gradient of q, k, v, g (a channel each) and beta."""
    feeds = channel_inputs(batch, seq, heads, d)
    out, grads, _, g, _ = run_with_grads(rule_with(impl, chunk, form), feeds,
                                         NAMES)
    want, want_grads = channel_recurrence(feeds, g)
    close(out, want, 1e-4)
    assert grads[3].shape == feeds["g"].shape
    for name, got, ref in zip(NAMES, grads, want_grads):
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=1e-4 * np.abs(ref).max(), err_msg=name)


@pytest.mark.parametrize("impl,d,form", [
    ("composed", 16, "split"), ("pallas", 128, "split"),
    ("pallas", 128, "packed")])
def test_channel_decay_chunks_of_64_and_128_agree(impl, d, form):
    feeds = channel_inputs(1, 256, 2, d, seed=2)
    a, b = (run_with_grads(rule_with(impl, c, form), feeds, [])[0]
            for c in (64, 128))
    close(a, b, 5e-6)
    close(a, channel_recurrence(feeds), 1e-4)


@pytest.mark.parametrize("impl,d,chunk", [
    ("composed", 16, 64), ("pallas", 128, 64), ("pallas", 128, 128)])
def test_decays_that_would_overflow_inside_a_chunk_stay_finite(impl, d, chunk):
    """A fall of up to 3 a position: over a chunk the running sum reaches
    -110 to -250, ``exp(-G)`` is past float32 (3e38 = exp(88.7)) within 30
    to 60 positions, and ``(k exp(G)) (k exp(-G))^T`` is ``0 x inf``. No exponent
    of a positive number is taken, so the output and every gradient are
    finite and equal the recurrence's."""
    feeds = channel_inputs(1, 256, 2, d, seed=3, steep=True)
    sums = np.cumsum(feeds["g"].reshape(1, -1, chunk, 2, d), axis=2)
    assert sums.min() < -100                # exp(-G) = inf
    out, grads, _, g, _ = run_with_grads(rule_with(impl, chunk), feeds, NAMES)
    assert np.isfinite(out).all() and all(
        np.isfinite(x).all() for x in grads)
    want, want_grads = channel_recurrence(feeds, g)
    close(out, want, 1e-4)
    for name, got, ref in zip(NAMES, grads, want_grads):
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=1e-4 * np.abs(ref).max(), err_msg=name)


@pytest.mark.parametrize("impl,d,chunk", [
    ("composed", 8, 8), ("pallas", 128, 64)])
def test_a_decay_constant_over_the_channels_is_the_scalar_rule(impl, d, chunk):
    """With ``g[c]`` the same for every channel the channel form is the
    Gated DeltaNet rule (one op, the rank of G deciding), and the gradient
    of the scalar is the sum of the channels' gradients."""
    feeds = rule_inputs(1, 128, 2, 2, d, d, seed=4)
    wide = dict(feeds, g=np.repeat(feeds["g"][..., None], d, -1))
    a, ga, _, _, _ = run_with_grads(rule_with(impl, chunk), feeds, NAMES)
    b, gb, _, _, _ = run_with_grads(rule_with(impl, chunk), wide, NAMES)
    close(a, b, 2e-5)
    close(a, recurrence(feeds), 1e-4)
    for i, name in enumerate(NAMES):
        got = gb[i].sum(-1) if name == "g" else gb[i]
        np.testing.assert_allclose(got, ga[i], rtol=0,
                                   atol=2e-4 * np.abs(ga[i]).max(),
                                   err_msg=name)


def test_channel_decay_is_refused_and_counted_by_name():
    feeds = channel_inputs(1, 16, 2, 8)
    bad = dict(feeds, g=feeds["g"][..., :4])
    with pytest.raises(Exception, match="a decay a key channel"):
        run_with_grads(rule_with("composed", 8), bad, [])
    two = rule_inputs(1, 16, 1, 2, 8, 8)            # two value heads a key head
    two["g"] = np.repeat(two["g"][..., None], 8, -1)
    with pytest.raises(Exception, match="one value head a key"):
        run_with_grads(rule_with("composed", 8), two, [])
    with pytest.raises(Exception, match="power of two"):
        run_with_grads(rule_with("composed", 12),
                       channel_inputs(1, 24, 1, 8), [])
    assert pallas_delta.supports(4096, 32, 32, 128, 128, 64, channel=True)
    assert not pallas_delta.supports(4096, 16, 32, 128, 128, 64, channel=True)
    assert pallas_delta.packs(32, 32)       # v starts 64 tiles in: whole
    kinds = [dict(impl="pallas", decay="channel", operands="packed"),
             dict(impl="composed", decay="channel"),
             dict(impl="pallas", decay="head")]
    before = [lowerings(**k) for k in kinds]
    big = channel_inputs(1, 128, 2, 128, seed=6)
    run_with_grads(rule_with("auto", 64, "packed"), big, ["g"])
    run_with_grads(rule_with("composed", 8), feeds, [])
    run_with_grads(rule_with("auto", 64),
                   rule_inputs(1, 128, 1, 2, 128, 128, seed=6), [])
    assert [lowerings(**k) - b for k, b in zip(kinds, before)] == [1, 1, 1]
    # a report without the label (an older reader's) is kept as decay=head
    main = fluid.Program()
    LowerCtx({}, salt=1, program=main).report(
        "delta_lowering_total", impl="pallas", chunk=64, heads=32,
        key_dim=128, value_dim=128, operands="packed")
    assert lowering_reports.read(
        lowering_reports.publish(main), "delta_lowering_total", "decay") == {
            "head": 1}


@pytest.mark.parametrize("impl,d,chunk", [
    ("composed", 16, 64), ("pallas", 128, 64), ("pallas", 128, 128)])
def test_keys_that_resemble_their_neighbours_keep_the_inverse_sound(impl, d,
                                                                    chunk):
    """What a KDA layer behind another's gated norm reads (chip, PR 51):
    keys nearly alike from one position to the next (cosine 0.99), steps
    near 1 and a decay that hardly applies, so ``M`` holds about 0.9 over
    the whole chunk. ``(I + M)^-1`` is tame (its entries fall off by a
    tenth a position), but the doubling ``(I - M)(I + M^2)(I + M^4)...``
    forms powers whose entries pass 1e30 at a chunk of 128 and loses every
    digit; forward substitution over sub-blocks holds 3e-4 in float32."""
    r = rng(8)
    feeds = channel_inputs(1, 256, 2, d, seed=8)
    base = r.randn(1, 1, 2, d)
    feeds["k"] = (base + 0.1 * r.randn(1, 256, 2, d)).astype("float32")
    feeds["q"] = (base + 0.5 * r.randn(1, 256, 2, d)).astype("float32")
    feeds["beta"] = np.full((1, 256, 2), 0.9, "float32")
    feeds["g"] = np.full((1, 256, 2, d), -1e-3, "float32")
    out, grads, _, g, _ = run_with_grads(rule_with(impl, chunk), feeds, NAMES)
    want, want_grads = channel_recurrence(feeds, g)
    assert np.isfinite(out).all()
    close(out, want, 3e-4)
    for name, got, ref in zip(NAMES, grads, want_grads):
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=3e-4 * np.abs(ref).max(), err_msg=name)


def chunk_values(c, d, seed, fall=None):
    """One chunk's unit-sized q and k ``[c, d]``, its running sums ``G`` (a
    memory of one to a thousand positions a channel, or every channel falling
    by ``fall`` a position) and cotangents for the two ``[c, c]`` blocks,
    masked as ``_channel_backward`` hands them over."""
    r = rng(seed)
    q, k = (r.randn(c, d).astype("float32") / np.sqrt(d) for _ in range(2))
    g = np.full((c, d), -fall, "float32") if fall else -np.exp(
        r.uniform(np.log(1e-3), np.log(1.6), (c, d))).astype("float32")
    dkk, dqk = (r.randn(c, c).astype("float32") for _ in range(2))
    return (jnp.asarray(q), jnp.asarray(k), jnp.cumsum(jnp.asarray(g), 0),
            jnp.asarray(np.tril(dkk, -1)), jnp.asarray(np.tril(dqk)))


def pairwise(x, k, g):
    """``sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c])``, pair by pair."""
    return jnp.einsum("ic,jc,ijc->ij", x, k, jnp.exp(jnp.minimum(
        g[:, None] - g[None], 0.0)), precision="highest")


@pytest.mark.parametrize("fall", [None, 2.0])
@pytest.mark.parametrize("c,d", [(4, 8), (16, 8), (64, 16), (128, 128)])
def test_the_halved_blocks_equal_the_pairwise_definition(c, d, fall):
    """``_intra``'s two blocks, one product a level, against every pair's
    own sum in float32: right under the diagonal (and on it for q), zero
    above. ``fall``: every channel falls by 2 a position, ``|G|`` reaches
    256 in a chunk of 128, and no level takes an exponent above 0."""
    q, k, g, _, _ = chunk_values(c, d, seed=20, fall=fall)
    if fall and c == 128:
        assert float(jnp.abs(g).max()) > 200
    with jax.default_matmul_precision("highest"):
        kk, qk = pallas_delta._intra(q, k, g, jnp.float32)
    want_kk, want_qk = pairwise(k, k, g), pairwise(q, k, g)
    assert np.isfinite(kk).all() and np.isfinite(qk).all()
    close(kk, jnp.tril(want_kk, -1), 1e-5)
    close(qk, jnp.tril(want_qk), 1e-5)
    assert not np.asarray(jnp.triu(kk)).any()
    assert not np.asarray(jnp.triu(qk, 1)).any()


@pytest.mark.parametrize("fall", [None, 2.0])
@pytest.mark.parametrize("c,d", [(4, 8), (32, 16), (128, 128)])
def test_intra_bwd_is_the_transpose_of_intra(c, d, fall):
    """``_intra_bwd`` (what the backward kernel runs) against ``jax.vjp`` of
    ``_intra`` in float32: dq, dk and dG, the reference rows' share of dG
    (which ``_intra_bwd`` leaves out as zero) included on JAX's side."""
    q, k, g, dkk, dqk = chunk_values(c, d, seed=21, fall=fall)
    with jax.default_matmul_precision("highest"):
        _, back = jax.vjp(
            lambda *a: pallas_delta._intra(*a, jnp.float32), q, k, g)
        want = back((dkk, dqk))
        got = pallas_delta._intra_bwd(q, k, g, dkk, dqk, jnp.float32)
    for name, a, b in zip(("dq", "dk", "dG"), got, want):
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-5 * np.abs(b).max(), err_msg=name)


@pytest.mark.parametrize("impl,d", [("composed", 16), ("pallas", 128)])
def test_a_channel_that_falls_by_two_a_position_equals_the_recurrence(impl, d):
    """The steep case as the chip read it (PR 51: ``|G|`` up to 242 inside a
    chunk of 128): every channel of one head falls by 2 a position, so the
    chunk's running sum passes 200 and ``exp(-G)`` left float32 after 45
    positions; the other head keeps a long memory. Finite, and equal to the
    recurrence with the gradient of every input, ``g``'s a channel each."""
    feeds = channel_inputs(1, 256, 2, d, seed=9)
    feeds["g"][:, :, 0] = -2.0
    sums = np.cumsum(feeds["g"].reshape(1, 2, 128, 2, d), axis=2)
    assert sums[..., 0, :].min() < -200 and sums[..., 1, :].max() <= 0
    out, grads, _, g, _ = run_with_grads(rule_with(impl, 128), feeds, NAMES)
    assert np.isfinite(out).all() and all(
        np.isfinite(x).all() for x in grads)
    want, want_grads = channel_recurrence(feeds, g)
    close(out, want, 1e-4)
    assert np.abs(grads[3]).max() > 0
    for name, got, ref in zip(NAMES, grads, want_grads):
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=1e-4 * np.abs(ref).max(), err_msg=name)


# -- several heads a grid step (the channel kernels: PR 54; the scalar: 58) --

@pytest.fixture
def heads_a_step(monkeypatch):
    """Sets ``pallas_delta.STEP_HEADS``; the kernels read it at their trace,
    behind ``jax.jit``s, so the calls' caches go with every change."""
    def clear():
        pallas_delta._fwd_call.clear_cache()
        pallas_delta._bwd_call.clear_cache()

    def set_to(limit):
        monkeypatch.setattr(pallas_delta, "STEP_HEADS", limit)
        clear()
    yield set_to
    monkeypatch.undo()
    clear()


def decay_inputs(decay, batch, seq, key_heads, rep, seed):
    """A decay a key ``channel`` (one value head a key head) or a value
    ``head`` (``rep`` of them a key head), heads of 128."""
    if decay == "channel":
        assert rep == 1
        return channel_inputs(batch, seq, key_heads, 128, seed)
    return rule_inputs(batch, seq, key_heads, key_heads * rep, 128, 128, seed)


def kernel_pass(feeds, form, chunk=64):
    """(o, the states, dq | dk | dv, dG, dbeta) of the kernels in the
    interpreter, bfloat16 operands as on the chip; ``g``'s rank says which
    pair."""
    qkv = operands(feeds, form, jnp.bfloat16)
    cum = decoder_ops._chunk_sums(jnp.asarray(feeds["g"]), chunk)
    beta = jnp.asarray(feeds["beta"])
    batch, seq, heads, dv = feeds["v"].shape
    do = jnp.asarray(rng(31).randn(batch, seq, heads * dv), jnp.bfloat16)
    o, states = pallas_delta._fwd_call(qkv, cum, beta, chunk, True)
    dqkv, dg, db = pallas_delta._bwd_call(qkv, cum, beta, states, do, chunk,
                                          True)
    if form == "split":
        dqkv = jnp.concatenate(dqkv, -1)
    return {name: np.asarray(x, np.float32) for name, x in zip(
        ("o", "states", "dqkv", "dG", "dbeta"), (o, states, dqkv, dg, db))}


@pytest.mark.parametrize("key_heads,heads,limit,took", [
    (32, 32, 8, 8), (3, 3, 8, 3), (6, 6, 4, 3), (1, 1, 8, 1),  # one a key head
    (16, 32, 8, 4), (16, 32, 16, 8), (16, 32, 2, 1), (16, 32, 1, 1),
    (3, 6, 8, 3), (6, 12, 8, 3), (3, 6, 4, 1), (5, 10, 8, 1),
    # v's offset in a packed operand, 2 key heads tiles, in whole blocks of
    # the step's value heads: two key heads of four fit the limit and not it
    (4, 16, 8, 2), (2, 8, 8, 1), (6, 24, 8, 1),
    (1, 4, 8, 1), (1, 16, 8, 1)])       # never packed; wider than the limit
def test_heads_a_step_follow_from_the_head_counts(heads_a_step, key_heads,
                                                  heads, limit, took):
    """``step_heads``: the largest divisor of the key heads whose value heads
    fit ``STEP_HEADS`` and leave a packed operand's blocks whole; one key
    head where none does (today's grid)."""
    heads_a_step(limit)
    assert pallas_delta.step_heads(key_heads, heads) == took
    if heads == key_heads:
        assert pallas_delta.step_heads(key_heads) == took
    rep = heads // key_heads
    assert key_heads % took == 0 and (took == 1 or took * rep <= limit)
    if pallas_delta.packs(key_heads, heads):
        assert 2 * key_heads % (took * rep) == 0


@pytest.mark.parametrize("form", ["split", "packed"])
@pytest.mark.parametrize("decay,heads,rep,limit,took", [
    ("channel", 4, 1, 4, 4), ("channel", 8, 1, 4, 4),   # the limit divides,
    ("channel", 8, 1, 2, 2), ("channel", 4, 1, 8, 4),   # or is past; the
    ("channel", 1, 1, 4, 1), ("channel", 3, 1, 4, 3),   # call falls to a
    ("channel", 3, 1, 2, 1),                            # divisor
    ("head", 4, 1, 4, 4), ("head", 2, 2, 4, 2), ("head", 4, 2, 8, 4),
    ("head", 4, 2, 2, 1), ("head", 3, 2, 8, 3), ("head", 6, 2, 8, 3),
    ("head", 3, 2, 4, 1), ("head", 4, 4, 8, 2), ("head", 2, 4, 8, 1)])
def test_heads_a_grid_step_give_the_bits_of_one_head_a_step(
        heads_a_step, form, decay, heads, rep, limit, took):
    """A grid step of the kernels takes ``step_heads`` key heads and all
    their value heads, each value head through ``_channel_forward`` /
    ``_channel_backward`` (``_scalar_forward`` / ``_scalar_backward``, a key
    head's sums over its value heads added in one head at a time's order) as
    one key head a step goes: ``o``, the states and the five gradients are
    the same bits, in either operand form (the packed array's lane blocks
    are then ``took`` key heads' tiles wide, k's and v's offsets whole
    blocks of them)."""
    feeds = decay_inputs(decay, 2, 128, heads, rep, seed=30 + heads)
    heads_a_step(1)
    assert pallas_delta.step_heads(heads, heads * rep) == 1
    want = kernel_pass(feeds, form)
    heads_a_step(limit)
    assert pallas_delta.step_heads(heads, heads * rep) == took
    got = kernel_pass(feeds, form)
    for name in want:
        assert np.abs(want[name]).max() > 0, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


@pytest.mark.parametrize("decay,heads,rep", [("channel", 4, 1),
                                             ("head", 2, 2)])
def test_a_heads_state_never_reaches_the_next_head_of_its_step(
        heads_a_step, decay, heads, rep):
    """Four value heads a step, v zero in the second and the fourth: their
    states stay zero through the chunks and their ``o`` is zero, whatever
    the heads before them in the step's scratch hold."""
    feeds = decay_inputs(decay, 1, 256, heads, rep, seed=40)
    feeds["v"][:, :, 1::2] = 0.0
    heads_a_step(4)
    assert pallas_delta.step_heads(heads, 4) == heads
    got = kernel_pass(feeds, "packed")
    states = got["states"]                      # [B, chunks, heads, d_k, d_v]
    assert not states[:, :, 1::2].any() and np.abs(states[:, 1:, ::2]).min(
        axis=(0, 1, 2)).max() > 0
    o = got["o"].reshape(1, 256, 4, 128)
    assert not o[:, :, 1::2].any() and np.abs(o[:, :, ::2]).max() > 0


@pytest.mark.parametrize("decay,metric,cell,key_heads,rep", [
    ("channel", "gated_delta.grouped_step_ops",
     "kimi_linear_48b_a3b.pretrain_s4096", 32, 1),
    ("head", "gated_delta.scalar_grouped_step_ops",
     "qwen3_next_80b_a3b.pretrain_s4096", 16, 2)])
def test_the_counter_says_how_many_heads_a_grid_step_took(
        decay, metric, cell, key_heads, rep):
    """``delta_lowering_total``'s ``step_heads``: the key heads
    ``step_heads`` took of an op's head counts where it lowered the kernels,
    ``1`` for the composed form and for a series without the label;
    ``metric`` reads the ops of its decay that took what the cell's head
    counts take (eight of Kimi Linear's 32 heads, four of Qwen3-Next's 16
    key heads under 32 value heads)."""
    import json
    import os
    from benchmark.reducers import registry_count
    n = pallas_delta.step_heads(key_heads, key_heads * rep)
    assert n > 1 and n * rep == pallas_delta.STEP_HEADS
    assert [pallas_delta.step_heads(h, h * rep) for h in (1, 2, 3)] == [
        1, min(n, 2), 3 if n >= 3 else 1]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert (spec["name"], spec["reducer"], spec["match"]) == (
        metric, "registry_count", "delta_lowering_total")
    assert spec["labels"] == {"impl": "pallas", "decay": decay,
                              "step_heads": str(n)}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == spec["name"]]
    assert entry["workloads"] == [cell]
    assert (entry["moves"], entry["source"], entry["layer"]) == (
        spec["moves"], spec["source"], spec["layer"]) == (
        "tokens_per_s", "program_counter", "gated_delta_rule")
    other = "head" if decay == "channel" else "channel"
    kinds = [dict(impl="pallas", decay=decay, step_heads=str(n)),
             dict(impl="pallas", decay=decay, step_heads="3"),
             dict(impl="composed", decay=decay, step_heads="1"),
             dict(impl="pallas", decay=other, step_heads="1")]
    before = [lowerings(**k) for k in kinds]
    read = registry_count.reduce(spec, None) or 0
    run_with_grads(rule_with("auto", 64, "packed"),
                   decay_inputs(decay, 1, 64, n, rep, seed=6), ["g"])
    run_with_grads(rule_with("auto", 64),
                   decay_inputs(decay, 1, 64, 3, rep, seed=0), [])
    small = (channel_inputs(1, 16, 2, 8) if decay == "channel"
             else rule_inputs(1, 16, 2, 4, 8, 8))
    run_with_grads(rule_with("composed", 8), small, [])
    run_with_grads(rule_with("auto", 64),
                   decay_inputs(other, 1, 128, 1, 1, seed=6), [])
    assert [lowerings(**k) - b for k, b in zip(kinds, before)] == [1] * 4
    assert registry_count.reduce(spec, None) - read == 1
    # a report without the label (a parent's) is kept as one head a step
    main = fluid.Program()
    LowerCtx({}, salt=1, program=main).report(
        "delta_lowering_total", impl="pallas", chunk=64, heads=32,
        key_dim=128, value_dim=128, operands="packed", decay=decay)
    assert lowering_reports.read(
        lowering_reports.publish(main), "delta_lowering_total",
        "step_heads") == {"1": 1}
