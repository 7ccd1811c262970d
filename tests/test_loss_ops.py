"""``softmax_with_cross_entropy`` and its grad lowering (ops/math_ops.py, PR 40).

The lean form (hard labels, last axis, no ``ignore_index``, logits narrower
than float32) against the lowering the op had, bit for bit; the row statistic
``Lse``; the registered gradient against ``jax.grad`` of a float32 reference
and between its ``written`` and ``fused`` forms; each fallback on the generic
path; and a decoder Program end to end against the path that cast its logits
to float32."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import registry
from paddle_tpu.models import decoder_lm
from paddle_tpu.ops import math_ops

T = 48
DTYPES = {"bfloat16": jnp.bfloat16, "float16": jnp.float16}
# one rounding to the dtype (relative), and the least step it can hold
ROUNDING = {"bfloat16": (2.0 ** -8, 1e-30), "float16": (2.0 ** -11, 6e-8)}


def parent_lowering(logits, label, soft_label=False, ignore_index=-100,
                    axis=-1):
    """The op's lowering as it stood before PR 40 (ops/math_ops.py at
    c105e08), which a model reached with logits cast to float32."""
    lse = jax.scipy.special.logsumexp(logits, axis=axis, keepdims=True)
    log_probs = logits - lse
    softmax_out = jnp.exp(log_probs)
    if soft_label:
        loss = -jnp.sum(label.astype(log_probs.dtype) * log_probs, axis=axis,
                        keepdims=True)
    else:
        lab = label
        if lab.ndim == logits.ndim and lab.shape[axis] == 1:
            lab = jnp.squeeze(lab, axis=axis)
        picked = jnp.take_along_axis(log_probs, lab[..., None].astype("int32"),
                                     axis=axis)
        loss = -picked
        if ignore_index >= 0:
            mask = (lab[..., None] != ignore_index)
            loss = jnp.where(mask, loss, jnp.zeros_like(loss))
    return {"Softmax": [jax.lax.stop_gradient(softmax_out)], "Loss": [loss]}


def case(dtype, V, seed=0):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.randn(T, V) * 3.0, DTYPES.get(dtype, dtype))
    label = rng.randint(0, V, (T, 1)).astype(np.int32)
    label[0, 0], label[1, 0] = 0, V - 1
    # a row whose label holds nearly all the mass: lse == x[label]
    logits = logits.at[2, label[2, 0]].set(60.0)
    return logits, jnp.asarray(label)


def lower(op_type, ins, attrs=None, notes=None, gspmd_mesh=None):
    program = None if notes is None else types.SimpleNamespace(
        _lowering_notes=notes)
    ctx = registry.LowerCtx(dict(attrs or {}), program=program,
                            gspmd_mesh=gspmd_mesh)
    return registry.get(op_type).lower(ctx, ins)


def forms(notes):
    """The ``form`` of each ``loss_backward_total`` report in ``notes``."""
    return [dict(labels)["form"] for family, _, labels in notes
            if family == "loss_backward_total"]


def forward(logits, label, **attrs):
    return lower("softmax_with_cross_entropy",
                 {"Logits": [logits], "Label": [label]}, attrs)


def backward(logits, label, g, notes, without=(), gspmd_mesh=None, **attrs):
    out = forward(logits, label, **attrs)
    slots = [s for s in ("Softmax", "Loss", "Lse") if s not in without]
    ins = {"Logits": [logits], "Label": [label], "Loss@GRAD": [g],
           **{s: out[s] for s in slots}}
    return lower("softmax_with_cross_entropy_grad", ins,
                 dict(attrs, __fwd_out_slots__=slots), notes,
                 gspmd_mesh)["Logits@GRAD"][0]


def reference_grad(logits, label, g, **attrs):
    """``jax.grad`` of the parent's expression over float32 logits."""
    def total(x):
        return jnp.sum(parent_lowering(x, label, **attrs)["Loss"][0] * g)
    return jax.grad(total)(logits.astype(jnp.float32))


@pytest.mark.parametrize("V", [512, 250])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_lean_loss_is_bit_equal_to_the_parents_lowering(dtype, V):
    logits, label = case(dtype, V)
    got = forward(logits, label)["Loss"][0]
    want = parent_lowering(logits.astype(jnp.float32), label)["Loss"][0]
    assert got.dtype == jnp.float32 and got.shape == (T, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(got[2, 0]) == 0.0      # lse - x[label], not -(x - lse)[label]


@pytest.mark.parametrize("V", [512, 250])
@pytest.mark.parametrize("dtype", sorted(DTYPES) + ["float32"])
def test_lse_is_the_rows_logsumexp_in_float32(dtype, V):
    logits, label = case(dtype, V)
    out = forward(logits, label)
    lse = out["Lse"][0]
    assert lse.dtype == jnp.float32 and lse.shape == (T, 1)
    np.testing.assert_array_equal(
        np.asarray(lse), np.asarray(jax.scipy.special.logsumexp(
            logits.astype(jnp.float32), axis=-1, keepdims=True)))
    # Softmax stays an output, in the logits' dtype
    assert out["Softmax"][0].dtype == logits.dtype
    np.testing.assert_allclose(
        np.asarray(out["Softmax"][0], np.float32).sum(-1), 1.0, atol=2e-2)


FALLBACKS = {
    "float32": (dict(), "float32"),
    "soft_label": (dict(soft_label=True), "bfloat16"),
    "axis": (dict(axis=0), "bfloat16"),
    "ignore_index": (dict(ignore_index=3), "bfloat16"),
}


def fallback_case(name):
    attrs, dtype = FALLBACKS[name]
    logits, label = case(dtype, 250)
    if name == "soft_label":
        label = jax.nn.softmax(jnp.asarray(
            np.random.RandomState(1).randn(T, 250), jnp.float32))
    if name == "axis":      # classes along axis 0: one label a column
        label = jnp.asarray(np.random.RandomState(1).randint(
            0, T, (1, 250)).astype(np.int32))
    if name == "ignore_index":
        label = label.at[5, 0].set(3)
    return logits, label, attrs


@pytest.mark.parametrize("name", sorted(FALLBACKS))
def test_every_other_input_lowers_to_the_parents_program(name):
    """Float32 logits, soft labels, another axis, an ``ignore_index``: the
    lowered text of the op is the parent's, instruction for instruction
    (``Lse`` is the ``logsumexp`` the expression already held)."""
    logits, label, attrs = fallback_case(name)

    def texts(fn):
        def step(x, lab):
            out = fn(x, lab, **attrs)
            return out["Softmax"][0], out["Loss"][0]
        return jax.jit(step).lower(logits, label).as_text()
    assert texts(forward) == texts(parent_lowering)
    got = forward(logits, label, **attrs)
    want = parent_lowering(logits, label, **attrs)
    np.testing.assert_array_equal(np.asarray(got["Loss"][0], np.float32),
                                  np.asarray(want["Loss"][0], np.float32))


@pytest.mark.parametrize("V", [512, 250])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_registered_grad_is_the_float32_gradient_rounded_once(
        dtype, V, monkeypatch):
    """``fused`` and ``written`` (the line patched down) give the same bits,
    within one rounding to the logits' dtype of ``jax.grad`` of the float32
    reference."""
    logits, label = case(dtype, V)
    g = jnp.asarray(np.random.RandomState(2).rand(T, 1) / T, jnp.float32)
    want = np.asarray(reference_grad(logits, label, g))
    got = {}
    for form, line in (("fused", 1 << 30), ("written", 1)):
        monkeypatch.setattr(math_ops, "WRITTEN_GRAD_MIN_BYTES", line)
        notes = {}
        got[form] = backward(logits, label, g, notes)
        assert forms(notes) == [form]
        assert got[form].dtype == logits.dtype
    np.testing.assert_array_equal(np.asarray(got["fused"], np.float32),
                                  np.asarray(got["written"], np.float32))
    rel, least = ROUNDING[dtype]
    err = np.abs(np.asarray(got["written"], np.float32) - want)
    assert (err <= rel * np.abs(want) + least).all()


@pytest.mark.parametrize("why", ["rows", "mesh"])
def test_written_form_needs_rows_it_can_chunk_and_no_mesh(why, monkeypatch):
    """Rows with no common factor with the chunk count, or a mesh the step
    is laid over, take ``fused`` whatever the logits' bytes."""
    monkeypatch.setattr(math_ops, "WRITTEN_GRAD_MIN_BYTES", 1)
    logits, label = case("bfloat16", 250)
    rows = T - 1 if why == "rows" else T            # 47 rows: gcd 1 with 16
    notes = {}
    backward(logits[:rows], label[:rows], jnp.ones((rows, 1), jnp.float32),
             notes, gspmd_mesh=object() if why == "mesh" else None)
    assert forms(notes) == ["fused"]


@pytest.mark.parametrize("name", sorted(FALLBACKS) + ["no_lse"])
def test_each_fallback_takes_the_generic_grad_and_is_noted_so(
        name, monkeypatch):
    monkeypatch.setattr(math_ops, "WRITTEN_GRAD_MIN_BYTES", 1)
    if name == "no_lse":        # a desc from before the op had ``Lse``
        (logits, label), attrs = case("bfloat16", 250), {}
    else:
        logits, label, attrs = fallback_case(name)
    loss = forward(logits, label, **attrs)["Loss"][0]
    g = jnp.asarray(np.random.RandomState(2).rand(*loss.shape) / T,
                    loss.dtype)
    notes = {}
    got = backward(logits, label, g, notes,
                   without=("Lse",) if name == "no_lse" else (), **attrs)
    assert forms(notes) == ["generic"]
    assert got.dtype == logits.dtype and got.shape == logits.shape
    want = np.asarray(reference_grad(logits, label,
                                     g.astype(jnp.float32), **attrs))
    rel = 2.0 ** -6 if logits.dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=rel, atol=rel * np.abs(want).max())


# -- through a Program at decoder_lm's rehearsal size -----------------------------------

MODEL = {
    "hidden_size": 64, "num_hidden_layers": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "intermediate_size": 32, "vocab_size": 512, "hidden_act": "silu",
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "norm_topk_prob": False,
    "tie_word_embeddings": False, "dtype": "bfloat16"}
BATCH, SEQ = 2, 24
LR = 0.5


def train_once(cast_logits=False, seed=5):
    """One SGD step of the tiny decoder from ``seed``: first-step loss, every
    position's loss, the parameters' change over the step and the Program. ``cast_logits``: the parent's path, the logits cast to
    float32 ahead of the loss."""
    layers = decoder_lm.layers
    loss_layer = layers.softmax_with_cross_entropy
    if cast_logits:
        layers.softmax_with_cross_entropy = lambda logits, labels: loss_layer(
            layers.cast(logits, "float32"), labels)
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    try:
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            A = dict(append_batch_size=False)
            ids = fluid.data("ids", [BATCH, SEQ], "int64", **A)
            labels = fluid.data("labels", [BATCH * SEQ, 1], "int64", **A)
            out = decoder_lm.build(MODEL, ids, labels)
            fluid.optimizer.SGD(LR).minimize(out["loss"])
    finally:
        layers.softmax_with_cross_entropy = loss_layer
    rng = np.random.RandomState(seed)
    feed = {"ids": rng.randint(0, 512, (BATCH, SEQ)).astype(np.int32),
            "labels": rng.randint(0, 512, (BATCH * SEQ, 1)).astype(np.int32)}
    exe, scope = fluid.Executor(), fluid.Scope()
    try:
        exe.run(startup, scope=scope)
        names = [p.name for p in main.global_block().all_parameters()]
        before = {n: np.asarray(scope.find_var(n), np.float32) for n in names}
        loss, each = exe.run(main, feed=feed, scope=scope,
                             fetch_list=[out["loss"], out["each"]])
        after = {n: np.asarray(scope.find_var(n), np.float32) for n in names}
    finally:
        exe.close()
    return {"loss": np.asarray(loss), "each": np.asarray(each),
            "main": main, "after": after,
            "step": {n: after[n] - before[n] for n in names}}


def loss_backward_counts():
    from paddle_tpu.observability.metrics import REGISTRY
    out = {}
    for k, c in (REGISTRY.get("loss_backward_total") or {}).items():
        out[dict(k)["form"]] = out.get(dict(k)["form"], 0) + c.value
    return out


def grown(before):
    now = loss_backward_counts()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


@pytest.fixture(scope="module")
def parent_path():
    before = loss_backward_counts()
    run = train_once(cast_logits=True)
    return run, grown(before)


@pytest.mark.parametrize("form,line", [("fused", 1 << 30), ("written", 1)])
def test_decoder_program_trains_as_the_parents_path(form, line, parent_path,
                                                    monkeypatch):
    """The decoder's Program hands the op its bfloat16 logits: the grad op is
    counted by its form, and the first step's loss and parameter updates are
    the parent path's to a bfloat16 rounding. (What the compiled step holds
    of the logits' shape is read off a compile for a described v5e, in
    test_pallas_attention_mosaic.py: XLA's CPU backend runs the head in
    float32.)"""
    monkeypatch.setattr(math_ops, "WRITTEN_GRAD_MIN_BYTES", line)
    parent, parent_counts = parent_path
    assert parent_counts == {"generic": 1}
    before = loss_backward_counts()
    run = train_once()
    assert grown(before) == {form: 1}
    types_ = [op.type for op in run["main"].global_block().ops]
    head = types_.index("softmax_with_cross_entropy")
    assert types_[head - 1] != "cast"           # the head's output, as it is
    assert "cast" in [
        op.type for op in parent["main"].global_block().ops][head - 1:head + 1]
    # (not to the bit, as the op alone is: XLA's CPU backend runs a bfloat16
    # product in float32 and, allowed excess precision, feeds each program's
    # loss fusion the unrounded logits where it can)
    np.testing.assert_allclose(run["each"], parent["each"], rtol=2.0 ** -8)
    np.testing.assert_allclose(run["loss"], parent["loss"], rtol=2.0 ** -8)
    # a parameter moves by the parent path's step to a rounding of the
    # gradient, and lands within one bfloat16 step of where it landed there
    assert np.abs(parent["step"]["lm_head_w"]).max() > 0
    for name, step in run["step"].items():
        want = parent["step"][name]
        room = (2.0 ** -7 * np.abs(parent["after"][name])
                + 2.0 ** -6 * np.abs(want).max())
        assert (np.abs(step - want) <= room).all(), name
