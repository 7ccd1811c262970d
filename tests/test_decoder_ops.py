"""The decoder-LM ops (ops/decoder_ops.py) through ``layers.*`` ->
``Program`` -> ``Executor``: each against a one-line ``jax.numpy`` form, its
gradient under ``append_backward`` against ``jax.grad`` of that form, and the
dropless expert layer against the dense form in which every expert is applied
to every token and masked by the router's choice."""
import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.observability import moe as obs_moe

HI = jax.lax.Precision.HIGHEST


def run_with_grads(build, feeds, wrt, extra=()):
    """Build ``out = build(*data vars)`` and the loss sum(out * g) for a
    fixed random ``g``; return out, the gradients of the loss with respect
    to the feeds / parameters named in ``wrt``, the named ``extra``
    variables, and ``g``."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = [fluid.data(n, list(v.shape), str(v.dtype),
                           append_batch_size=False) for n, v in feeds.items()]
        for d in data:
            d.stop_gradient = False
        # one consumer each: a leaf's gradient contributions are summed
        # only where something asks for the sum
        out = build(*[layers.scale(d, 1.0) if "float" in str(d.dtype)
                      else d for d in data])
        g = np.random.RandomState(9).randn(
            *[int(d) for d in out.shape]).astype("float32")
        gv = layers.assign(g)
        loss = layers.reduce_sum(layers.elementwise_mul(
            layers.cast(out, "float32"), gv))
        fluid.append_backward(loss)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    fetch = [out.name] + [n + "@GRAD" for n in wrt] + list(extra)
    got = exe.run(main, feed=feeds, fetch_list=fetch, scope=scope)
    exe.close()
    k = 1 + len(wrt)
    return got[0], got[1:k], got[k:], g, scope


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    np.testing.assert_allclose(a, b, atol=tol * max(1.0, np.abs(b).max()),
                               rtol=0)


def rng(seed=0):
    return np.random.RandomState(seed)


def test_rms_norm_matches_its_one_line_form_and_gradient():
    x = rng().randn(6, 16).astype("float32")

    def form(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * w

    out, (dx, dw), _, g, _ = run_with_grads(
        lambda xv: layers.rms_norm(xv, 1e-5, fluid.ParamAttr(name="w")),
        {"x": x}, ["x", "w"])
    w = np.ones(16, "float32")
    close(out, form(x, w))
    want = jax.grad(lambda x, w: jnp.sum(form(x, w) * g), (0, 1))(x, w)
    close(dx, want[0])
    close(dw, want[1])


def test_rms_norm_computes_in_float32_on_bfloat16_input():
    x = jnp.asarray(rng().randn(4, 32), jnp.bfloat16)
    out, _, _, _, _ = run_with_grads(
        lambda xv: layers.rms_norm(xv, 1e-5), {"x": np.asarray(x)}, [])
    xf = np.asarray(x, np.float32)
    want = xf / np.sqrt((xf * xf).mean(-1, keepdims=True) + 1e-5)
    assert str(out.dtype) == "bfloat16"
    # one rounding of the float32 result, nothing rounded inside
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(jnp.asarray(want).astype(jnp.bfloat16), np.float32))


@pytest.mark.parametrize("zero_centered", [False, True])
@pytest.mark.parametrize("wide", [True, False], ids=["wide", "as_x"])
def test_gated_rms_norm_matches_its_one_line_form_and_gradient(
        wide, zero_centered):
    """``rms_norm`` given a gate (X's shape, or ``[T, heads * D]`` beside
    ``X [T, heads, D]``): ``rmsnorm(x) * scale * silu(gate)`` in one op,
    forward and the three gradients, at a head size no kernel takes (the
    composed closed forms; tests/test_pallas_norm.py has the kernels)."""
    x = rng().randn(6, 3, 16).astype("float32")
    z = rng(1).randn(*((6, 48) if wide else (6, 3, 16))).astype("float32")
    w = (0.3 * rng(2).randn(16) + (0.0 if zero_centered else 1.0)).astype(
        "float32")

    def form(x, z, w):
        unit = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
        return unit * (1.0 + w if zero_centered else w) * jax.nn.silu(
            z.reshape(x.shape))
    out, grads, _, g, _ = run_with_grads(
        lambda xv, zv: layers.rms_norm(
            xv, 1e-5, fluid.ParamAttr(
                name="w", initializer=fluid.initializer.NumpyArrayInitializer(
                    w)), zero_centered=zero_centered, gate=zv),
        {"x": x, "z": z}, ["x", "z", "w"])
    assert out.shape == x.shape
    close(out, form(x, z, w))
    want = jax.grad(lambda *v: jnp.sum(form(*v) * g), (0, 1, 2))(x, z, w)
    for got, ref in zip(grads, want):
        assert got.shape == ref.shape
        close(got, ref)


def test_rotary_embedding_matches_rotate_half_and_gradient():
    x = rng().randn(2, 3, 8, 16).astype("float32")

    def form(x, theta=10000.0):
        S, D = x.shape[-2:]
        ang = jnp.arange(S)[:, None] * theta ** (-jnp.arange(0, D, 2) / D)
        cos, sin = (jnp.tile(f(ang), (1, 2)) for f in (jnp.cos, jnp.sin))
        rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
        return x * cos + rot * sin

    out, (dx,), _, g, _ = run_with_grads(
        lambda xv: layers.rotary_embedding(xv, 10000.0), {"x": x}, ["x"])
    close(out, form(x))
    close(dx, jax.grad(lambda x: jnp.sum(form(x) * g))(x))
    # position 0 is not rotated; a rotation keeps every pair's length
    np.testing.assert_allclose(out[:, :, 0], x[:, :, 0], atol=1e-6)
    pair = lambda t: t[..., :8] ** 2 + t[..., 8:] ** 2      # noqa: E731
    np.testing.assert_allclose(pair(out), pair(x), rtol=1e-4, atol=1e-5)


# the one-pass rotary lowering (PR 42): ops/pallas_rope.py's kernel (in the
# harness' interpreter here) and the composed form of the same expression

def _rotary_attrs(D, rot, scaling, **more):
    attrs = {"theta": 10000.0, "rotary_dim": 0 if rot == D else rot, **more}
    if scaling == "yarn":
        attrs.update(scaling="yarn", factor=8.0, original_max_position=16.0,
                     attention_factor=1.2)
    return attrs


def parents_rotary(x, cos, sin, rot):
    """The lowering before PR 42, on the same tables: a slice, a roll of the
    lanes by half, a concatenate for the tail."""
    D = x.shape[-1]
    xf = (x if rot == D else x[..., :rot]).astype(jnp.float32)
    out = (xf * cos + jnp.roll(xf, rot // 2, axis=-1) * sin).astype(x.dtype)
    return out if rot == D else jnp.concatenate([out, x[..., rot:]], -1)


def rotate_half_reference(x, cos, sin, rot):
    """x * cos + concat(-x2, x1) * sin in float64 over the first ``rot``
    (``sin``'s second half is the unsigned one)."""
    x = np.asarray(x, np.float64)
    cos, sin = np.asarray(cos, np.float64), np.asarray(sin, np.float64)
    head, h = x[..., :rot], rot // 2
    turned = np.concatenate([-head[..., h:], head[..., :h]], -1)
    out = head * cos + turned * np.concatenate([sin[:, h:], sin[:, h:]], -1)
    return np.concatenate([out, x[..., rot:]], -1)


def _bits(a):
    return np.asarray(jnp.asarray(a).astype(jnp.float32))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("scaling", ["", "yarn"])
@pytest.mark.parametrize("part", [1, 2, 4])
@pytest.mark.parametrize("D", [64, 128, 256])
def test_rotary_one_pass_forms_against_the_parents_expression(
        D, part, scaling, dtype, monkeypatch):
    """Forward and registered grad of both lowerings (the kernel, the
    composed form) against the rotate-half reference, the parent's
    expression and ``jax.vjp`` of it, and the generic grad lowering on the
    same ``ins``. The composed lowering, evaluated op by op as the parent's
    expression is here, equals it to the bit in either dtype; the kernel,
    which XLA's CPU backend compiles as one fusion (it may contract a
    product into the sum), to the bit on tables of 8 significant bits,
    where every product of a bfloat16 value is exact -- and to a rounding on
    the op's own."""
    from paddle_tpu.core import registry
    from paddle_tpu.ops import decoder_ops, pallas_mode, pallas_rope
    S, rot = 32, D // part
    x = jnp.asarray(rng(D + part).randn(2, 3, S, D), dtype)
    g = jnp.asarray(rng(7).randn(2, 3, S, D), dtype)
    ctx = registry.LowerCtx(_rotary_attrs(D, rot, scaling))
    cos, sin, got_rot = decoder_ops._rotary_tables(ctx, S, D)
    assert got_rot == rot and cos.shape == sin.shape == (S, rot)
    want = parents_rotary(x, cos, sin, rot)
    want_g = jax.vjp(lambda v: parents_rotary(v, cos, sin, rot), x)[1](g)[0]
    one = 2.0 ** -8 if dtype == "bfloat16" else 1e-6        # a rounding
    reference = rotate_half_reference(x, cos, sin, rot)
    scale = float(np.abs(reference).max())
    attrs = _rotary_attrs(D, rot, scaling)
    for kernel in (True, False):     # the interpreter stands in, or is off
        monkeypatch.setattr(pallas_mode, "TEST_INTERPRET", kernel)
        ins = {"X": [x], "Out@GRAD": [g]}
        out = registry.get("rotary_embedding").lower(
            registry.LowerCtx(attrs), {"X": [x]})["Out"][0]
        grad_ctx = registry.LowerCtx(dict(attrs, __fwd_out_slots__=["Out"]))
        dx = registry.get("rotary_embedding_grad").lower(
            grad_ctx, dict(ins, Out=[out]))["X@GRAD"][0]
        generic = registry._generic_grad_lower(
            registry.get("rotary_embedding"), grad_ctx,
            dict(ins, Out=[out]))["X@GRAD"][0]
        assert out.dtype == dx.dtype == generic.dtype == x.dtype
        np.testing.assert_allclose(_bits(out), reference, atol=one * scale,
                                   rtol=0)
        np.testing.assert_allclose(_bits(out), _bits(want), atol=one * scale,
                                   rtol=0)
        np.testing.assert_allclose(_bits(dx), _bits(want_g),
                                   atol=one * scale, rtol=0)
        np.testing.assert_allclose(_bits(dx), _bits(generic),
                                   atol=one * scale, rtol=0)
        # the values past rotary_dim pass through untouched, bit for bit
        np.testing.assert_array_equal(_bits(out[..., rot:]),
                                      _bits(x[..., rot:]))
        np.testing.assert_array_equal(_bits(dx[..., rot:]),
                                      _bits(g[..., rot:]))
        if not kernel:
            np.testing.assert_array_equal(_bits(out), _bits(want))
            np.testing.assert_array_equal(_bits(dx), _bits(want_g))
    if dtype == "bfloat16":
        short = lambda t: t.astype(jnp.bfloat16).astype(    # noqa: E731
            jnp.float32)
        c8, s8 = short(cos), short(sin)
        np.testing.assert_array_equal(
            _bits(pallas_rope.rotate(x, c8, s8, rot, True)),
            _bits(parents_rotary(x, c8, s8, rot)))
        np.testing.assert_array_equal(
            _bits(jax.vjp(lambda v: pallas_rope.rotate(v, c8, s8, rot, True),
                          x)[1](g)[0]),
            _bits(jax.vjp(lambda v: parents_rotary(v, c8, s8, rot),
                          x)[1](g)[0]))


def rotary_lowering_counts():
    from paddle_tpu.observability.metrics import REGISTRY
    out = {}
    for k, c in (REGISTRY.get("rotary_lowering_total") or {}).items():
        key = (dict(k)["direction"], dict(k)["form"])
        out[key] = out.get(key, 0) + c.value
    return out


@pytest.mark.parametrize("shape,interpreter,form", [
    ((2, 2, 16, 64), True, "kernel"),       # the interpreter stands in
    ((2, 2, 16, 64), False, "composed"),    # off a TPU
    ((2, 2, 6, 8), True, "composed")])      # a shape the kernel leaves
def test_rotary_lowering_total_counts_one_forward_and_one_backward(
        shape, interpreter, form, monkeypatch):
    from paddle_tpu.ops import pallas_mode
    monkeypatch.setattr(pallas_mode, "TEST_INTERPRET", interpreter)
    x = rng().randn(*shape).astype("float32")
    before = rotary_lowering_counts()
    run_with_grads(lambda xv: layers.rotary_embedding(xv), {"x": x}, ["x"])
    now = rotary_lowering_counts()
    assert {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)} == {("forward", form): 1,
                                          ("backward", form): 1}


def test_rotary_grad_op_of_a_decoder_program_lowers_no_forward():
    """The grad op a decoder Program's backward holds reads its cotangent
    alone: traced with X, Out and the cotangent as arguments, its jaxpr
    uses neither X nor Out (no second rotation of X, no residual), and it
    is one pass: a single kernel call with a single rotation in it."""
    from paddle_tpu.core import registry
    from paddle_tpu.models import decoder_lm
    cfg = {"hidden_size": 128, "num_hidden_layers": 1,
           "num_attention_heads": 2, "num_key_value_heads": 2,
           "num_experts": 4, "num_experts_per_tok": 2, "intermediate_size": 32,
           "vocab_size": 64, "hidden_act": "silu", "rms_norm_eps": 1e-5,
           "rope_theta": 10000, "norm_topk_prob": False,
           "tie_word_embeddings": False, "dtype": "bfloat16"}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        out = decoder_lm.build(cfg, fluid.data("ids", [2, 16], "int64", **A),
                               fluid.data("labels", [32, 1], "int64", **A))
        fluid.append_backward(out["loss"])
    ops = main.global_block().ops
    grads = [op for op in ops if op.type == "rotary_embedding_grad"]
    assert len(grads) == len(
        [op for op in ops if op.type == "rotary_embedding"]) == 2
    for op in grads:
        shape = [int(d) for d in main.global_block().var(
            op.input("X")[0]).shape]
        ctx = registry.LowerCtx(dict(op.attrs))
        spec = jax.ShapeDtypeStruct(tuple(shape), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(
            lambda X, Out, G: registry.get(op.type).lower(
                ctx, {"X": [X], "Out": [Out], "Out@GRAD": [G]})["X@GRAD"][0]
        )(spec, spec, spec)
        x_in, out_in, g_in = jaxpr.jaxpr.invars
        used = {v for eqn in jaxpr.jaxpr.eqns for v in eqn.invars
                if isinstance(v, jax.extend.core.Var)}
        assert x_in not in used and out_in not in used and g_in in used
        # one kernel, one rotation of the lanes: the cotangent's
        text = str(jaxpr)
        assert text.count("pallas_call[") == 1 and text.count("roll[") == 1


def test_swiglu_matches_its_one_line_form_and_gradient():
    a, b = rng(1).randn(5, 8).astype("float32"), \
        rng(2).randn(5, 8).astype("float32")
    form = lambda a, b: a * jax.nn.sigmoid(a) * b           # noqa: E731
    out, (da, db), _, g, _ = run_with_grads(
        lambda av, bv: layers.swiglu(av, bv), {"a": a, "b": b}, ["a", "b"])
    close(out, form(a, b))
    want = jax.grad(lambda a, b: jnp.sum(form(a, b) * g), (0, 1))(a, b)
    close(da, want[0])
    close(db, want[1])


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def dense_moe(x, w_router, w_gate, w_up, w_down, k):
    """Every expert on every token, masked by the top-k: no sort, no groups."""
    prob = jax.nn.softmax(jnp.dot(x, w_router, precision=HI), -1)
    top_w, top_i = jax.lax.top_k(prob, k)
    E = w_router.shape[1]
    gate = jnp.sum(jax.nn.one_hot(top_i, E) * top_w[..., None], axis=1)
    ein = lambda s, a, b: jnp.einsum(s, a, b, precision=HI)  # noqa: E731
    h = jax.nn.silu(ein("th,ehi->eti", x, w_gate)) * ein(
        "th,ehi->eti", x, w_up)
    return jnp.einsum("te,eth->th", gate, ein("eti,eih->eth", h, w_down),
                      precision=HI)


MOE_PARAMS = ["moe_router_w", "moe_gate_w", "moe_up_w", "moe_down_w"]


def moe_case(x, router_w, E=4, k=2, width=8):
    """layers.moe_ffn over ``x`` with the router's weight forced to
    ``router_w`` (None: as initialised); returns the program's output, its
    gradients, the router's variables and the dense form's."""
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.data("x", list(x.shape), "float32",
                        append_batch_size=False)
        xv.stop_gradient = False
        out, aux = layers.moe_ffn(
            layers.scale(xv, 1.0), E, k, width, name="moe",
            param_attr=fluid.ParamAttr(
                initializer=fluid.initializer.Normal(0.0, 0.5)))
        g = rng(9).randn(*x.shape).astype("float32")
        loss = layers.reduce_sum(layers.elementwise_mul(out, layers.assign(g)))
        fluid.append_backward(loss)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    if router_w is not None:
        scope.set_var("moe_router_w", jnp.asarray(router_w, jnp.float32))
    names = (["x@GRAD"] + [p + "@GRAD" for p in MOE_PARAMS]
             + [aux[n].name for n in ("index", "load", "prob", "logz")])
    got = exe.run(main, feed={"x": x}, fetch_list=[out.name] + names,
                  scope=scope)
    exe.close()
    weights = [np.asarray(scope.find_var(p)) for p in MOE_PARAMS]
    want = dense_moe(x, *weights, k)
    want_grads = jax.grad(
        lambda x, *w: jnp.sum(dense_moe(x, *w, k) * g),
        tuple(range(5)))(x, *weights)
    return {"out": got[0], "grads": got[1:6], "index": got[6],
            "load": got[7], "prob": got[8], "logz": got[9], "want": want,
            "want_grads": want_grads, "weights": weights}


def routing_cases():
    H, E = 16, 4
    x = rng(4).randn(12, H).astype("float32")
    duplicated = np.concatenate([x[:6], x[:6]])             # every row twice
    # a router that never picks expert 3 / that sends everything to expert
    # 0 first and expert 1 second, whatever the token
    never_3 = rng(5).randn(H, E).astype("float32") * 0.1
    never_3[:, 3] = 0.0
    pos = np.abs(x)                                          # logits >= 0
    one = np.zeros((H, E), "float32")
    one[:, 0], one[:, 1] = 3.0, 1.0
    return [pytest.param(x, None, None, id="random_routing"),
            pytest.param(duplicated, None, None, id="duplicated_tokens"),
            pytest.param(pos, never_3 - 5.0 * (np.arange(E) == 3), 3,
                         id="an_expert_with_no_token"),
            pytest.param(pos, one, None, id="all_tokens_on_one_expert")]


@pytest.mark.parametrize("x,router_w,empty", routing_cases())
def test_dropless_layer_equals_the_dense_masked_form(x, router_w, empty):
    r = moe_case(x, router_w)
    T, k = x.shape[0], 2
    # every one of the T x k assignments reached an expert: nothing dropped
    assert int(r["load"].sum()) == T * k
    np.testing.assert_array_equal(
        r["load"], np.bincount(r["index"].reshape(-1), minlength=4))
    if empty is not None:
        assert r["load"][empty] == 0
    if router_w is not None and empty is None:      # all on expert 0, then 1
        np.testing.assert_array_equal(r["load"], [T, T, 0, 0])
    close(r["out"], r["want"])
    for got, want in zip(r["grads"], r["want_grads"]):
        close(got, want, 5e-5)
    close(r["prob"].sum(-1), np.ones(T))
    close(r["logz"], jax.nn.logsumexp(
        jnp.dot(x, r["weights"][0], precision=HI), -1))


def test_duplicated_tokens_get_identical_outputs():
    x = rng(4).randn(6, 16).astype("float32")
    r = moe_case(np.concatenate([x, x]), None)
    np.testing.assert_allclose(r["out"][:6], r["out"][6:], atol=1e-6)
    np.testing.assert_array_equal(r["index"][:6], r["index"][6:])


def test_router_is_float32_and_its_indices_carry_no_gradient():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [8, 16], "bfloat16", append_batch_size=False)
        out, aux = layers.moe_ffn(x, 4, 2, 8, name="moe")
        assert out.dtype == "bfloat16" and tuple(out.shape) == (8, 16)
        assert aux["prob"].dtype == "float32"
        assert aux["index"].dtype == "int32" and aux["index"].stop_gradient
        assert aux["load"].dtype == "int32" and aux["load"].stop_gradient
        block = main.global_block()
        assert block.var("moe_router_w").dtype == "float32"
        assert block.var("moe_gate_w").dtype == "bfloat16"
        assert tuple(block.var("moe_gate_w").shape) == (4, 16, 8)
        assert tuple(block.var("moe_down_w").shape) == (4, 8, 16)
        fluid.append_backward(layers.reduce_sum(layers.cast(out, "float32")))
    types = [op.type for op in main.global_block().ops]
    assert types.count("moe_expert_matmul") == 3
    assert types.count("moe_expert_matmul_grad") == 3
    assert not [v for v in main.global_block().vars
                if "@GRAD" in v and ("index" in v.lower() or "count" in
                                     v.lower())]
    # one glob covers the expert layer
    moe_ops = [t for t in types if t.startswith(("moe_", "swiglu"))]
    assert {"moe_router", "moe_dispatch", "moe_expert_matmul", "swiglu",
            "moe_combine"} <= set(moe_ops)


def test_moe_ops_have_no_capacity_attribute():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [8, 16], "float32", append_batch_size=False)
        layers.moe_ffn(x, 4, 2, 8, name="moe")
    for op in main.global_block().ops:
        assert not [a for a in op.attrs if "capacity" in a.lower()
                    or "drop" in a.lower()], (op.type, op.attrs)


def test_moe_gauges_and_load_stats():
    """``moe_row_budget`` of a compiled step: without a budget, every
    assignment of every expert layer has a row (2 layers x 8 tokens x top-2),
    as the ``moe_dispatch`` lowerings report it; a program without an expert
    layer sets nothing."""
    import lowering_reports
    from paddle_tpu.observability.metrics import REGISTRY
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [8, 16], "bfloat16", append_batch_size=False)
        h, _ = layers.moe_ffn(x, 4, 2, 8, name="a")
        out, _ = layers.moe_ffn(h, 4, 2, 8, name="b")
    ops = main.global_block().ops
    assert [op.attr("num_experts") for op in ops
            if op.type == "moe_dispatch"] == [4, 4]
    label = lowering_reports.step(
        main, startup, {"x": jnp.zeros((8, 16), jnp.bfloat16)}, [out])
    plain, plain_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(plain, plain_startup):
        y = layers.scale(fluid.data("x", [8, 16], "float32",
                                    append_batch_size=False), 2.0)
    other = lowering_reports.step(
        plain, plain_startup, {"x": np.zeros((8, 16), "float32")}, [y])
    budgets = lowering_reports.read(REGISTRY, "moe_row_budget", "program")
    assert budgets[label] == 2 * 8 * 2 and other not in budgets
    stats = obs_moe.load_stats([4, 0, 8, 4])
    assert stats == {"max": 8.0, "mean": 4.0, "max_over_mean": 2.0,
                     "empty": 1}


HELD_PARAMS = ["m_router_w", "m_gate_w", "m_up_w", "m_down_w"]


def held_layer(held, budget, kernel, monkeypatch, dtype="float32", T=64,
               H=128):
    """``layers.moe_ffn`` over 8 experts (top-4, width 16) holding ``held``
    of them, under ``budget`` rows or none, one step with its backward: the
    token sums on the kernel of ops/pallas_moe_rows.py (in the interpreter)
    or -- ``kernel`` False: what every process off a TPU lowers -- the
    composed forms. Returns the output, the routed part, the gradients of x,
    the router and the three stacked weights, and the Program."""
    from paddle_tpu.ops import pallas_mode
    monkeypatch.setattr(pallas_mode, "TEST_INTERPRET", kernel)
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 5
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.data("x", [T, H], dtype, append_batch_size=False)
        xv.stop_gradient = False
        out, aux = layers.moe_ffn(
            layers.scale(xv, 1.0), 8, 4, 16, name="m", experts_held=held,
            row_budget=budget, norm_topk=True, shared_width=16,
            param_attr=fluid.ParamAttr(
                initializer=fluid.initializer.Normal(0.0, 0.3)))
        g = layers.assign(rng(9).randn(T, H).astype("float32"))
        fluid.append_backward(layers.reduce_sum(layers.elementwise_mul(
            layers.cast(out, "float32"), g)))
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    x = np.asarray(jnp.asarray(rng(1).randn(T, H), dtype))
    got = exe.run(main, feed={"x": x}, scope=scope, fetch_list=[
        out.name, aux["routed"].name, "x@GRAD"]
        + [p + "@GRAD" for p in HELD_PARAMS])
    exe.close()
    return got, main


@pytest.mark.parametrize("held,budget", [
    ((0, 2), None), ((5, 3), None), ((0, 2), 128), ((5, 3), 160),
    ((0, 2), 40), (None, None)],
    ids=["held", "held_from_5", "budget", "budget_from_5",
         "budget_that_drops", "all_held"])
def test_expert_layer_on_the_sums_kernel_equals_the_composed_lowering(
        held, budget, monkeypatch):
    """The layer's output, its routed part and every gradient (x, the
    router, the three stacked weights), float32, with the token sums of
    ``moe_combine`` and of ``moe_dispatch``'s grad op on the kernel, against
    the composed forms: equal to float32's rounding. With a part of the
    experts held the ops carry ``held`` and the sorted rows behind the held
    experts' are padding in both lowerings; with all held (OLMoE's form)
    the ops carry no ``held``."""
    import lowering_reports
    got, main = held_layer(held, budget, True, monkeypatch)
    want, plain = held_layer(held, budget, False, monkeypatch)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6,
                                   atol=1e-6 * max(1.0, np.abs(b).max()))
    assert np.abs(want[1]).max() > 0.1 and np.abs(want[2]).max() > 0.01
    for op in main.global_block().ops:
        if op.type in ("moe_dispatch", "moe_combine", "moe_dispatch_grad"):
            assert op.attr("held") == (held[1] if held else None), op.type
            # the groups' counts: Count itself where the sort starts at 0
            assert ("GroupCount" if held or op.type == "moe_combine"
                    else "Count") in (op.outputs if op.type == "moe_dispatch"
                                      else op.inputs), op.type
    # one report a lowered op, by what it took
    bound = "held" if held else "all"
    from paddle_tpu.observability.metrics import REGISTRY
    counts = lowering_reports.read(REGISTRY, "moe_rows_lowering_total",
                                   "program", "impl", "op", "bound")
    for program, impl in ((main, "pallas"), (plain, "composed")):
        label = f"{id(program)}:v{program._version}"
        assert {key[1:]: n for key, n in counts.items()
                if key[0] == label} == {(impl, "combine", bound): 1,
                                        (impl, "dispatch_grad", bound): 1}


def test_expert_layer_on_the_sums_kernel_in_bfloat16(monkeypatch):
    """bfloat16 rows: the kernel's float32 sums are rounded once, like the
    composed forms'; a sum's last bit may differ (another order of float32
    additions)."""
    got, _ = held_layer((0, 2), 128, True, monkeypatch, "bfloat16")
    want, _ = held_layer((0, 2), 128, False, monkeypatch, "bfloat16")
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=2 ** -6,
                                   atol=2 ** -6 * np.abs(b).max())


def test_the_dispatch_grad_op_sorts_nothing_and_reads_the_forwards_sort(
        monkeypatch):
    """``moe_dispatch_grad`` is a registered lowering: its jaxpr holds no
    sort (the generic grad op lowers the forward, argsort and all, again
    under ``jax.vjp``) and the token sums as one kernel call."""
    from paddle_tpu.core import registry
    from paddle_tpu.ops import pallas_mode
    monkeypatch.setattr(pallas_mode, "TEST_INTERPRET", True)
    T, k, H, E, held = 32, 2, 128, 8, 2
    index = jnp.asarray(np.argsort(-rng(2).randn(T, E), 1)[:, :k], jnp.int32)
    ins = {"X": [jnp.ones((T, H))], "Index": [index],
           "Weight": [jnp.ones((T, k))]}
    attrs = {"num_experts": E, "first_expert": 0, "held": held}
    fwd = registry.get("moe_dispatch").lower(registry.LowerCtx(attrs), ins)

    def grad(g, gw):
        return registry.get("moe_dispatch_grad").lower(
            registry.LowerCtx(dict(attrs, __fwd_out_slots__=list(fwd))),
            {**ins, **fwd, "Out@GRAD": [g], "RowWeight@GRAD": [gw]})
    g, gw = jnp.ones((T * k, H)), jnp.ones((T * k,))
    text = str(jax.make_jaxpr(grad)(g, gw))
    assert " sort[" not in text and "argsort" not in text
    assert text.count("pallas_call") == 1
    live = int(fwd["GroupCount"][0][:held].sum())
    want = (np.asarray(fwd["Slot"][0]) < live).sum(1)[:, None]
    np.testing.assert_array_equal(grad(g, gw)["X@GRAD"][0], want * np.ones(H))
    # the cotangent's padding is not read: anything there changes nothing
    dirty = g.at[live:].set(jnp.nan)
    np.testing.assert_array_equal(grad(dirty, gw)["X@GRAD"][0],
                                  want * np.ones(H))
