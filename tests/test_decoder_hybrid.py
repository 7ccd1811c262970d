"""What a hybrid decoder with a shared expert layer adds to the decoder ops,
through ``layers.*`` -> ``Program`` -> ``Executor``: the gated short
convolution, grouped-query ``fused_attention``, the sigmoid router with its
selection bias and the bias' update, and an expert layer that holds a part
of its experts -- each against its plain ``jax.numpy`` form, forward and
gradient; the four shares of a layer against the uncut layer; and the whole
program against ``benchmark/references/lfm2_pretrain.py`` (loss, every
position, every parameter's gradient, the updated bias)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lowering_reports
import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder_lm
from paddle_tpu.observability.metrics import REGISTRY
from benchmark.references import lfm2_pretrain as reference
from test_decoder_ops import close, rng, run_with_grads


def plain_short_conv(x, w, seq):
    """``c[t] = sum_j w[:, j] * (B u)[t - 2 + j]`` written position by
    position, each sequence by itself."""
    rows, wide = x.shape
    chan, taps = wide // 3, w.shape[1]
    b, c, u = x[:, :chan], x[:, chan:2 * chan], x[:, 2 * chan:]
    z = (b * u).reshape(rows // seq, seq, chan)
    out = []
    for t in range(seq):
        acc = jnp.zeros_like(z[:, 0])
        for j in range(taps):
            src = t - (taps - 1) + j
            if src >= 0:
                acc = acc + w[:, j] * z[:, src]
        out.append(acc)
    return c * jnp.stack(out, axis=1).reshape(rows, chan)


@pytest.mark.parametrize("impl,seq,chan,taps", [
    ("composed", 5, 8, 3), ("pallas", 32, 256, 3), ("pallas", 16, 128, 4),
    ("auto", 16, 128, 3), ("auto", 5, 8, 2)])
def test_short_conv_equals_its_plain_form_and_gradient(impl, seq, chan, taps):
    """``pallas`` runs the kernel bodies in the interpreter
    (tests/conftest.py), two channel blocks wide in the first case; ``auto``
    takes them where the shapes allow and the composed form elsewhere."""
    x = rng(1).randn(3 * seq, 3 * chan).astype("float32")
    w = rng(2).randn(chan, taps).astype("float32")
    out, (dx, dw), _, g, _ = run_with_grads(
        lambda xv, wv: _short_conv_with(xv, wv, seq, impl),
        {"x": x, "w": w}, ["x", "w"])
    close(out, plain_short_conv(x, w, seq))
    want = jax.grad(lambda a, b: jnp.sum(plain_short_conv(a, b, seq) * g),
                    (0, 1))(x, w)
    close(dx, want[0])
    close(dw, want[1])


def _short_conv_with(x, w, seq, impl="auto"):
    """``layers.short_conv`` creates its filter; this appends the op with
    the filter fed, so that its gradient can be fetched by name."""
    helper = fluid.layer_helper.LayerHelper("short_conv")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("short_conv", inputs={"X": [x], "W": [w]},
                     outputs={"Out": [out]}, attrs={"seq": seq, "impl": impl})
    return helper.main_program.current_block().var(out.name)


@pytest.mark.parametrize("seq,chan", [(4, 8), (16, 128)],
                         ids=["composed", "kernel"])
def test_short_conv_leaks_nothing_across_sequence_starts(seq, chan):
    """Another first sequence leaves the second one's outputs as they were,
    and a sequence's first position sees only its own tap."""
    x = rng(3).randn(2 * seq, 3 * chan).astype("float32")
    w = rng(4).randn(chan, 3).astype("float32")
    other = x.copy()
    other[:seq] = rng(5).randn(seq, 3 * chan)

    def run(feed):
        return run_with_grads(lambda xv, wv: _short_conv_with(xv, wv, seq),
                              {"x": feed, "w": w}, [])[0]
    a, b = run(x), run(other)
    np.testing.assert_array_equal(a[seq:], b[seq:])
    assert np.abs(a[:seq] - b[:seq]).max() > 0.1
    first = x[seq, chan:2 * chan] * w[:, 2] * x[seq, :chan] * x[seq, 2 * chan:]
    close(a[seq], first)
    # the layer creates a [C, taps] filter in the input's dtype
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        v = fluid.data("x", [2 * seq, 3 * chan], "float32",
                       append_batch_size=False)
        y = layers.short_conv(v, seq, 3, fluid.ParamAttr(name="f"))
    assert tuple(main.global_block().var("f").shape) == (chan, 3)
    assert tuple(y.shape) == (2 * seq, chan)
    kinds = [op.type for op in main.global_block().ops]
    assert kinds.count("short_conv") == 1 and "moe_dispatch" not in kinds


def repeated(k, group):
    return jnp.repeat(k, group, axis=1)


@pytest.mark.parametrize("impl,seq", [("composed", 16), ("pallas", 128),
                                      ("pallas", 1024)])
def test_grouped_query_attention_equals_attention_with_k_and_v_repeated(
        impl, seq):
    """Query head i reads key/value head i // group; a key/value head's
    gradient is the sum over its group. ``pallas`` runs the kernel bodies in
    the interpreter (tests/conftest.py): the forward's in-place K/V block
    index and the backward's grid over the group, whose steps read the
    forward op's ``Lse`` by (the group's head, its Q block) as they read Q
    (four Q blocks a head at S=1024; this is a Program, so the grad op is
    fused_attention_grad on the forward's statistics)."""
    from paddle_tpu.ops.pallas_attention import composed_attention
    B, H, kv, D = 2, 4, 2, 8
    q = rng(1).randn(B, H, seq, D).astype("float32")
    k = rng(2).randn(B, kv, seq, D).astype("float32")
    v = rng(3).randn(B, kv, seq, D).astype("float32")
    scale = 1.0 / np.sqrt(D)
    out, grads, _, g, _ = run_with_grads(
        lambda a, b, c: layers.fused_attention(a, b, c, causal=True,
                                               scale=scale, impl=impl),
        {"q": q, "k": k, "v": v}, ["q", "k", "v"])

    def plain(a, b, c):
        return composed_attention(a, repeated(b, H // kv),
                                  repeated(c, H // kv), None, scale, 0.0,
                                  True, None)
    close(out, plain(q, k, v), 5e-5)
    want = jax.grad(lambda a, b, c: jnp.sum(plain(a, b, c) * g),
                    (0, 1, 2))(q, k, v)
    for got, ref in zip(grads, want):
        close(got, ref, 5e-5)


def test_composed_lowering_takes_fewer_kv_heads_and_bad_counts_raise():
    from paddle_tpu.ops import pallas_attention as pa
    q = jnp.asarray(rng(1).randn(1, 4, 16, 8), jnp.float32)
    kv = q[:, :2]
    same = pa.composed_attention(q, repeated(kv, 2), repeated(kv, 2), None,
                                 0.3, 0.0, True, None)
    close(pa.composed_attention(q, kv, kv, None, 0.3, 0.0, True, None), same)
    with pytest.raises(Exception, match="multiple of the key/value heads"):
        run_with_grads(
            lambda a, b: layers.fused_attention(a, b, b, causal=True),
            {"q": np.zeros((1, 4, 16, 8), "float32"),
             "k": np.zeros((1, 3, 16, 8), "float32")}, [])


def plain_sigmoid_router(x, w, bias, k, norm, scale):
    score = jax.nn.sigmoid(jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST))
    _, index = jax.lax.top_k(score + bias, k)
    weight = jnp.take_along_axis(score, index, axis=-1)
    if norm:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    return weight * scale, index, score


def _router(x, w, bias, **attrs):
    helper = fluid.layer_helper.LayerHelper("moe_router")
    weight, prob = (helper.create_variable_for_type_inference("float32")
                    for _ in range(2))
    index = helper.create_variable_for_type_inference("int32")
    helper.append_op("moe_router",
                     inputs={"X": [x], "W": [w], "Bias": [bias]},
                     outputs={"Weight": [weight], "Index": [index],
                              "Prob": [prob]},
                     attrs=dict(attrs, scoring="sigmoid"))
    blk = helper.main_program.current_block()
    return blk.var(weight.name), blk.var(index.name)


@pytest.mark.parametrize("norm,scale", [(True, 1.0), (False, 2.5)])
def test_sigmoid_router_selects_by_biased_score_and_weighs_by_the_bare_one(
        norm, scale):
    T, H, E, k = 12, 16, 8, 3
    x = rng(1).randn(T, H).astype("float32")
    w = (rng(2).randn(H, E) * 0.3).astype("float32")
    bias = (rng(3).randn(E) * 0.5).astype("float32")   # moves the choice
    names = {}

    def build(xv, wv, bv):
        weight, index = _router(xv, wv, bv, k=k, norm_topk=norm, scale=scale)
        names["index"] = index.name
        return weight
    out, (dx, dw), _, g, scope = run_with_grads(
        build, {"x": x, "w": w, "b": bias}, ["x", "w"])
    want_w, want_i, score = plain_sigmoid_router(x, w, bias, k, norm, scale)
    close(out, want_w)
    unbiased = jax.lax.top_k(score, k)[1]
    assert (np.sort(np.asarray(want_i)) != np.sort(np.asarray(unbiased))).any()
    want = jax.grad(lambda a, b: jnp.sum(
        plain_sigmoid_router(a, b, bias, k, norm, scale)[0] * g), (0, 1))(x, w)
    close(dx, want[0])
    close(dw, want[1])


def test_bias_update_follows_the_sign_of_the_load_and_no_optimizer_owns_it():
    """One training step of an expert layer with its bias: the step's ops
    read the bias the step began with, ``moe_bias_update`` (after
    ``minimize``) moves it by rate x sign(mean load - load), the optimizer
    leaves it alone, and a clone taken before has no update in it."""
    T, H, E, k = 32, 16, 8, 2
    x = rng(1).randn(T, H).astype("float32")
    start = (rng(2).randn(E) * 0.3).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.data("x", [T, H], "float32", append_batch_size=False)
        out, aux = layers.moe_ffn(xv, E, k, 8, name="m", scoring="sigmoid",
                                  norm_topk=True, expert_bias=True)
        loss = layers.mean(out)
        test = main.clone(for_test=True)
        fluid.optimizer.AdamW(1e-2).minimize(loss)
        layers.moe_bias_update(aux["bias"], aux["load"], 1e-3)
    assert not aux["bias"].name.endswith("@GRAD")
    assert "m_router_bias" not in [p.name for p in main.all_parameters()]
    assert [op.type for op in main.global_block().ops][-1] == \
        "moe_bias_update"
    assert "moe_bias_update" not in [op.type for op in
                                     test.global_block().ops]
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    assert not np.asarray(scope.find_var("m_router_bias")).any()
    scope.set_var("m_router_bias", jnp.asarray(start))
    load, = exe.run(main, feed={"x": x}, fetch_list=[aux["load"]],
                    scope=scope)
    assert load.sum() == T * k
    want = start + 1e-3 * np.sign(load.mean() - load)
    np.testing.assert_allclose(np.asarray(scope.find_var("m_router_bias")),
                               want, rtol=0, atol=1e-7)
    exe.run(test, feed={"x": x}, fetch_list=[out], scope=scope)
    np.testing.assert_allclose(np.asarray(scope.find_var("m_router_bias")),
                               want, rtol=0, atol=1e-7)
    exe.close()


MODEL = {"num_experts": 8, "num_experts_routed": 32, "num_experts_per_tok": 4,
         "norm_topk_prob": True, "routed_scaling_factor": 1.0}


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Held ranges 0-7, 8-15, 16-23, 24-31 of one layer of 32 experts, each
    through ``layers.moe_ffn`` with its slice of the stacked weights, the
    same router and a non-zero bias: their outputs add up to what the plain
    reference gives for the whole layer, their loads are the whole layer's,
    and each share alone is the reference's for that range."""
    T, H, W, E, k = 48, 16, 8, 32, 4
    x = rng(1).randn(T, H).astype("float32")
    weights = {"router_w": (rng(2).randn(H, E) * 0.5).astype("float32"),
               "gate_w": rng(3).randn(E, H, W).astype("float32") * 0.3,
               "up_w": rng(4).randn(E, H, W).astype("float32") * 0.3,
               "down_w": rng(5).randn(E, W, H).astype("float32") * 0.3}
    bias = (rng(6).randn(E) * 0.2).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.data("x", [T, H], "float32", append_batch_size=False)
        shares = [layers.moe_ffn(
            xv, E, k, W, name=f"s{i}", experts_held=(8 * i, 8),
            scoring="sigmoid", norm_topk=True, expert_bias=True)
            for i in range(4)]
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    for i in range(4):
        scope.set_var(f"s{i}_router_w", jnp.asarray(weights["router_w"]))
        scope.set_var(f"s{i}_router_bias", jnp.asarray(bias))
        for n in ("gate_w", "up_w", "down_w"):
            assert tuple(scope.find_var(f"s{i}_{n}").shape)[0] == 8
            scope.set_var(f"s{i}_{n}",
                          jnp.asarray(weights[n][8 * i:8 * i + 8]))
    got = exe.run(main, feed={"x": x}, scope=scope, fetch_list=[
        v for out, aux in shares for v in (out, aux["load"], aux["index"])])
    exe.close()
    outs, loads, indices = got[0::3], got[1::3], got[2::3]
    args = (jnp.asarray(x), weights["router_w"], weights["gate_w"],
            weights["up_w"], weights["down_w"], bias)
    with jax.default_matmul_precision("highest"):
        whole, top_i, load = reference.expert_layer(*args, MODEL,
                                                    held=(0, E))
        for i in range(4):
            part, _, _ = reference.expert_layer(
                args[0], args[1], *(a[8 * i:8 * i + 8] for a in args[2:5]),
                bias, MODEL, held=(8 * i, 8))
            close(outs[i], part, 2e-5)
            np.testing.assert_array_equal(loads[i], load)
            np.testing.assert_array_equal(np.sort(indices[i]),
                                          np.sort(top_i))
    assert load.sum() == T * k and (load[:8].sum() != T * k // 4)
    close(sum(outs), whole, 2e-5)
    # no share is the layer: the parts differ from each other and from it
    assert np.abs(outs[0] - np.asarray(whole)).max() > 0.05


def test_gauges_say_what_a_layer_holds():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.data("x", [6, 16], "float32", append_batch_size=False)
        layers.moe_ffn(xv, 32, 4, 8, name="m", experts_held=(8, 8),
                       scoring="sigmoid", norm_topk=True)
    # what the layer holds, off the Program: 32 experts scored, the weights
    # of 8 stacked, a sorted row for each of the 6 x 4 assignments (no
    # budget), which is what the compiled step's gauge then says
    block = main.global_block()
    (sort,) = [op for op in block.ops if op.type == "moe_dispatch"]
    assert sort.attr("num_experts") == 32 and not sort.attr("rows", 0)
    assert {int(block.find_var_recursive(op.inputs["W"][0]).shape[0])
            for op in block.ops if op.type == "moe_expert_matmul"} == {8}
    label = lowering_reports.step(main, startup,
                                  {"x": np.zeros((6, 16), "float32")})
    assert lowering_reports.read(REGISTRY, "moe_row_budget",
                                 "program")[label] == 24
    with pytest.raises(Exception, match="channels % 128"):
        run_with_grads(lambda a, b: _short_conv_with(a, b, 3, "pallas"),
                       {"x": np.zeros((6, 24), "float32"),
                        "w": np.zeros((8, 3), "float32")}, [])
    with pytest.raises(ValueError, match="experts_held"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            xv = fluid.data("x", [6, 16], "float32", append_batch_size=False)
            layers.moe_ffn(xv, 32, 4, 8, experts_held=(28, 8))


SMALL = {"hidden_size": 32, "intermediate_size": 48,
         "moe_intermediate_size": 16, "num_attention_heads": 4,
         "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
         "num_hidden_layers": 5,
         "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
         "num_dense_layers": 1, "num_experts": 4, "num_experts_routed": 8,
         "first_expert_held": 4, "num_experts_per_tok": 2,
         "norm_topk_prob": True, "use_expert_bias": True,
         "routed_scaling_factor": 1, "router_scoring": "sigmoid",
         "qk_norm": "head", "norm_eps": 1e-5, "rope_theta": 1000000,
         "vocab_size": 64, "dtype": "float32"}


def test_program_matches_the_reference_in_loss_gradients_and_updated_bias():
    """float32 throughout, so that the comparison is of the mathematics: the
    loss, every position's loss, the gradient of every parameter and the
    bias after the step, with a non-zero seeded bias and the held range in
    the middle of the experts (4-7 of 8). 2e-5 of the largest entry: float32
    products summed in another order (1e-6 to 5e-6 found)."""
    B, S = 2, 16
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = fluid.data("ids", [B, S], "int64", append_batch_size=False)
        labels = fluid.data("labels", [B * S, 1], "int64",
                            append_batch_size=False)
        out = decoder_lm.build(SMALL, ids, labels)
        assert "load_balancing" not in out and len(out["expert_bias"]) == 4
        params = [p.name for p in main.global_block().all_parameters()]
        fluid.optimizer.SGD(0.0).minimize(out["loss"])
        decoder_lm.balance_experts(out, reference.BIAS_UPDATE_RATE)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    r = rng(7)
    for n in params:        # the builder's std 0.02 leaves the mixers tiny
        v = scope.find_var(n)
        if "norm" not in n and n != "tok_emb":
            scope.set_var(n, jnp.asarray(v) * 8.0)
    biases = [(r.randn(8) * 0.1).astype("float32") for _ in range(4)]
    for v, b in zip(out["expert_bias"], biases):
        scope.set_var(v.name, jnp.asarray(b))
    tokens = r.randint(0, SMALL["vocab_size"], (B, S + 1)).astype("int32")
    batch = {"ids": tokens[:, :-1].copy(),
             "labels": tokens[:, 1:].reshape(-1, 1).copy()}
    # host copies: the train step donates its state
    weights = [jnp.asarray(np.array(scope.find_var(n))) for n in params] \
        + [jnp.asarray(b) for b in biases]
    fetch = [out["loss"].name, out["each"].name] + \
        [n + "@GRAD" for n in params] + [v.name for v in out["expert_load"]]
    got = exe.run(main, feed=batch, fetch_list=fetch, scope=scope)
    with jax.default_matmul_precision("highest"):
        want = reference.forward(weights, batch, SMALL)
        grads = jax.grad(lambda w: reference.forward(
            w + weights[len(params):], batch, SMALL)["loss"])(
            weights[:len(params)])
    close(got[0], want["loss"], 2e-6)
    close(got[1].reshape(-1), want["positions"], 2e-5)
    for n, g, ref in zip(params, got[2:2 + len(params)], grads):
        assert np.abs(np.asarray(ref)).max() > 0, n
        close(g, ref, 2e-5)
    np.testing.assert_array_equal(np.stack(got[2 + len(params):]),
                                  np.asarray(want["load"]))
    new = np.stack([np.asarray(scope.find_var(v.name))
                    for v in out["expert_bias"]])
    np.testing.assert_allclose(new, np.asarray(want["new_bias"]), rtol=0,
                               atol=1e-7)
    assert np.abs(new - np.stack(biases)).max() == pytest.approx(1e-3, 1e-3)
    exe.close()


@pytest.mark.parametrize("change,match", [
    ({"layer_types": ["conv", "chunked_attention", "conv", "conv", "conv"]},
     "chunked_attention"),
    ({"conv_bias": True}, "conv_bias"),
    ({"n_shared_experts": 2}, "shared experts"),
    ({"router_scoring": "softmax"}, "sigmoid"),
    ({"qk_norm": "layer"}, "qk_norm"),
    ({"router_aux_loss_coef": 0.01}, "router losses")])
def test_what_the_builder_does_not_build_raises_by_name(change, match):
    with pytest.raises(NotImplementedError, match=match):
        decoder_lm._check(dict(SMALL, **change))
