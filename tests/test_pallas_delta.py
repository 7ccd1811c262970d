"""What a Gated DeltaNet layer adds to the decoder ops, through ``layers.*``
-> ``Program`` -> ``Executor``: ``gated_delta_rule`` (the composed chunk
form, and the Pallas kernels in the interpreter) against the float32
recurrence position by position, outputs and every input's gradient; the
same result whatever the chunk; the state handed across a chunk's edge and
not a sequence's; what the op refuses and what it counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.observability import ssm as obs_ssm
from paddle_tpu.observability.metrics import MetricsRegistry
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


def rule_with(impl, chunk):
    return lambda *v: layers.gated_delta_rule(*v, chunk=chunk, impl=impl)


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
@pytest.mark.parametrize("impl,batch,seq,key_heads,heads,dk,dv,chunk", [
    ("composed", 1, 8, 2, 4, 8, 8, 8), ("composed", 1, 24, 2, 2, 4, 8, 8),
    ("composed", 2, 16, 1, 3, 4, 8, 4), ("auto", 2, 12, 2, 4, 8, 4, 64),
    ("pallas", 1, 128, 1, 2, 128, 128, 64),
    ("pallas", 2, 256, 2, 4, 128, 128, 128),
    ("pallas", 1, 192, 2, 2, 128, 128, 64),
    ("auto", 1, 128, 1, 2, 128, 128, 128)])
def test_gated_delta_rule_equals_the_recurrence_and_its_gradient(
        impl, batch, seq, key_heads, heads, dk, dv, chunk):
    """``pallas`` runs the kernel bodies in the interpreter
    (tests/conftest.py); ``auto`` takes them where the shapes allow and the
    composed form elsewhere. The reference is the recurrence over positions
    (``lax.scan``), not a chunk form."""
    feeds = rule_inputs(batch, seq, key_heads, heads, dk, dv)
    out, grads, _, g, _ = run_with_grads(rule_with(impl, chunk), feeds, NAMES)
    want, want_grads = recurrence(feeds, g)
    close(out, want, 1e-4)
    for name, got, ref in zip(NAMES, grads, want_grads):
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=1e-4 * np.abs(ref).max(), err_msg=name)


@pytest.mark.parametrize("impl,dk", [("composed", 8), ("pallas", 128)])
def test_chunks_of_64_and_128_give_the_same_result(impl, dk):
    feeds = rule_inputs(1, 256, 1, 2, dk, dk, seed=2)
    a, b = (run_with_grads(rule_with(impl, c), feeds, [])[0]
            for c in (64, 128))
    close(a, b, 2e-6)
    close(a, recurrence(feeds), 1e-4)


@pytest.mark.parametrize("impl,seq,dk,chunk", [
    ("composed", 16, 8, 8), ("pallas", 128, 128, 64)])
def test_the_state_crosses_a_chunks_edge_and_not_a_sequences(
        impl, seq, dk, chunk):
    """Positions after a chunk's edge see the chunk before it (another first
    chunk moves them); the second sequence of a batch sees nothing of the
    first, and a sequence's first position starts from a zero state: ``o_0 =
    beta_0 (k_0 . q_0) v_0`` over the unit k and scaled unit q."""
    feeds = rule_inputs(2, seq, 1, 2, dk, dk, seed=3)
    feeds["g"] = feeds["g"] * 0.05          # a long memory
    other = {k: v.copy() for k, v in feeds.items()}
    other["v"][0, :chunk] = rng(4).randn(chunk, 2, dk)

    def run(f):
        return run_with_grads(rule_with(impl, chunk), f, [])[0]
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


def test_the_states_output_is_the_state_entering_each_chunk():
    """``States`` is what the backward kernel reads: both lowerings write
    the same, and chunk 0's is zero."""
    feeds = rule_inputs(1, 128, 1, 2, 128, 128, seed=5)
    args = [jnp.asarray(feeds[n]) for n in NAMES]
    qn, kn, cum = decoder_ops._delta_operands(*args[:2], args[3], 64,
                                              jnp.float32)
    _, want = decoder_ops.composed_gated_delta_rule(qn, kn, args[2], cum,
                                                    args[4], 64)
    flat = decoder_ops._flat
    o, got = pallas_delta.chunked(flat(qn), flat(kn), flat(args[2]), cum,
                                  args[4], 64, True)
    assert got.shape == (1, 2, 2, 128, 128) and not np.asarray(got[:, 0]).any()
    close(got, want, 1e-5)
    assert np.abs(np.asarray(want[:, 1])).max() > 1e-3


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
    assert pallas_delta.supports(4096, 16, 32, 128, 128, 64)
    assert pallas_delta.supports(4096, 16, 32, 128, 128, 128)
    assert not pallas_delta.supports(4096, 16, 32, 64, 128, 64)
    assert not pallas_delta.supports(4096, 16, 32, 128, 128, 256)
    assert not pallas_delta.supports(4000, 16, 32, 128, 128, 64)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        v = [fluid.data(k, list(a.shape), "float32", append_batch_size=False)
             for k, a in feeds.items()]
        y = layers.gated_delta_rule(*v, chunk=4)
    assert tuple(y.shape) == (1, 12, 4, 8)
    registry = MetricsRegistry()
    obs_ssm.update_delta_gauges(main, "p", registry)
    for name, want in (("delta_layers", 1), ("delta_heads", 4),
                       ("delta_state_bytes", 4 * 8 * 8 * 4),
                       ("delta_chunks_per_step", 3)):
        assert registry.gauge(name, program="p").value == want, name
    obs_ssm.count_delta_lowerings(
        {1: ("pallas", 64, 32, 128, 128), 2: ("pallas", 64, 32, 128, 128),
         3: ("composed", 4, 4, 8, 8)}, "p", registry)
    assert registry.counter(
        "delta_lowering_total", program="p", impl="pallas", chunk="64",
        heads="32", key_dim="128", value_dim="128").value == 2
    obs_ssm.update_delta_gauges(fluid.Program(), "none", registry)
    assert all(("program", "none") not in labels
               for labels, _ in registry.get("delta_layers").items())


def test_a_compiled_step_counts_the_lowering_each_op_took():
    """Through the executor: the forward op's note lands in
    ``delta_lowering_total`` once a compile, whichever lowering it took."""
    from paddle_tpu.observability.metrics import REGISTRY
    feeds = rule_inputs(1, 128, 1, 2, 128, 128, seed=6)

    def count(impl):
        family = REGISTRY.get("delta_lowering_total")
        return sum(child.value for labels, child in family.items()
                   if ("impl", impl) in labels) if family else 0
    before = count("pallas"), count("composed")
    run_with_grads(rule_with("auto", 64), feeds, ["q"])
    run_with_grads(rule_with("composed", 64), feeds, [])
    assert count("pallas") - before[0] == 1
    assert count("composed") - before[1] == 1
