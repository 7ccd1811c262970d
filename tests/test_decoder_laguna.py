"""What a window / full hybrid decoder with a per-head gate and a shared
expert adds to the decoder ops (``layers.*`` -> ``Program`` -> ``Executor``
on the CPU): partial and YaRN rotary against HF's formulas worked by hand,
the gate, the softmax router's renormalised and scaled weights, the expert
layer's row budget, the shared expert over the shares of a layer, and a tiny
Laguna Program against ``benchmark/references/laguna_pretrain.py`` in loss,
positions and every leaf's gradient, with each mechanism shown to matter."""
import copy
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lowering_reports
import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder_lm
from paddle_tpu.observability.metrics import REGISTRY
from benchmark.references import laguna_pretrain as reference
from tests.test_decoder_ops import close, rng, run_with_grads

YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}


def hf_yarn_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """HF ``_compute_yarn_parameters`` (truncated correction range), line by
    line in numpy."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    keep = 1 - ramp
    return interpolation * (1 - keep) + extrapolation * keep


def rotate_half(x, inv_freq, factor=1.0):
    """HF ``apply_rotary_pos_emb`` over the first ``2 len(inv_freq)`` values
    of each row of ``x [..., S, D]``; the rest pass through."""
    r = 2 * len(inv_freq)
    ang = np.arange(x.shape[-2])[:, None] * np.asarray(inv_freq)[None, :]
    emb = np.concatenate([ang, ang], -1)
    cos, sin = np.cos(emb) * factor, np.sin(emb) * factor
    rot, rest = x[..., :r], x[..., r:]
    half = np.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], -1)
    return np.concatenate([rot * cos + half * sin, rest], -1)


def test_yarn_frequencies_are_hf_s_at_the_published_numbers():
    """The full-attention layers' 64 rotated values: the fast dimensions
    (i <= 9) keep theta^(-2i/64), the slow ones (i >= 18) are divided by
    128, those between are blended; attention_factor is 0.1 ln(128) + 1."""
    from paddle_tpu.ops.decoder_ops import yarn_inv_freq
    want = hf_yarn_inv_freq(64, 500000, 128, 8192, 32, 1)
    got = yarn_inv_freq(500000, 64, 128, 8192, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(reference.yarn_inv_freq(YARN, 64), want,
                               rtol=1e-12)
    base = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:10], base[:10], rtol=1e-12)
    np.testing.assert_allclose(got[18:], base[18:] / 128, rtol=1e-12)
    assert np.all(got[10:18] < base[10:18])
    assert np.all(got[10:18] > base[10:18] / 128)
    assert YARN["attention_factor"] == pytest.approx(
        0.1 * math.log(128) + 1, rel=1e-12)


@pytest.mark.parametrize("rotary_dim,scaling", [
    (16, None), (8, None), (8, YARN), (16, dict(YARN, attention_factor=None))])
def test_partial_and_yarn_rotary_match_hf_by_hand_and_gradient(rotary_dim,
                                                               scaling):
    x = rng(3).randn(2, 3, 12, 16).astype("float32")
    theta = 500000.0 if scaling else 10000.0
    out, (dx,), _, g, _ = run_with_grads(
        lambda xv: layers.rotary_embedding(xv, theta, rotary_dim=rotary_dim,
                                           scaling=scaling),
        {"x": x}, ["x"])
    if scaling:
        inv_freq = hf_yarn_inv_freq(rotary_dim, theta, 128, 8192, 32, 1)
        factor = scaling["attention_factor"] or 0.1 * math.log(128) + 1
    else:
        inv_freq = theta ** (-np.arange(0, rotary_dim, 2) / rotary_dim)
        factor = 1.0
    close(out, rotate_half(x, inv_freq, factor))
    # the values past rotary_dim pass through untouched, bit for bit
    np.testing.assert_array_equal(out[..., rotary_dim:], x[..., rotary_dim:])
    # the rotation is linear in x: its transpose applied to g
    want = jax.grad(lambda x: jnp.sum(_rotate_jnp(
        x, inv_freq, factor) * g))(jnp.asarray(x))
    close(dx, want)


def _rotate_jnp(x, inv_freq, factor):
    r = 2 * len(inv_freq)
    ang = jnp.arange(x.shape[-2])[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    emb = jnp.concatenate([ang, ang], -1)
    rot, rest = x[..., :r], x[..., r:]
    half = jnp.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], -1)
    return jnp.concatenate(
        [rot * jnp.cos(emb) * factor + half * jnp.sin(emb) * factor, rest], -1)


def test_rotary_refuses_what_it_does_not_build():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        xv = fluid.data("x", [1, 2, 8, 16], "float32",
                        append_batch_size=False)
        with pytest.raises(NotImplementedError, match="llama3"):
            layers.rotary_embedding(xv, scaling={"rope_type": "llama3"})


def test_attention_gate_matches_its_one_line_form_and_gradient():
    x = rng(1).randn(2, 3, 5, 8).astype("float32")         # [B, heads, S, D]
    gate = rng(2).randn(10, 3).astype("float32")            # [B * S, heads]

    def form(x, gate):
        by_token = x.transpose(0, 2, 1, 3).reshape(10, 3, 8)
        gated = by_token * jax.nn.sigmoid(gate)[:, :, None]
        return gated.reshape(2, 5, 3, 8).transpose(0, 2, 1, 3)
    out, (dx, dg), _, g, _ = run_with_grads(
        layers.attention_gate, {"x": x, "gate": gate}, ["x", "gate"])
    close(out, form(x, gate))
    want = jax.grad(lambda x, gate: jnp.sum(form(x, gate) * g), (0, 1))(
        x, gate)
    close(dx, want[0])
    close(dg, want[1])


@pytest.mark.parametrize("norm,scale", [(True, 2.5), (True, 1.0),
                                        (False, 2.5)])
def test_softmax_router_renormalises_and_scales_its_top_k(norm, scale):
    T, H, E, k = 12, 16, 8, 3
    x = rng(1).randn(T, H).astype("float32")
    w = (rng(2).randn(H, E) * 0.5).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.data("x", [T, H], "float32", append_batch_size=False)
        _, aux = layers.moe_ffn(xv, E, k, 4, name="m", norm_topk=norm,
                                routed_scale=scale)
    router = next(op for op in main.global_block().ops
                  if op.type == "moe_router")
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    scope.set_var("m_router_w", jnp.asarray(w))
    weight, index = exe.run(main, feed={"x": x}, scope=scope, fetch_list=[
        router.outputs["Weight"][0], aux["index"]])
    exe.close()
    prob = np.asarray(jax.nn.softmax(jnp.asarray(x) @ w, -1))
    top = np.sort(prob, -1)[:, ::-1][:, :k]
    np.testing.assert_array_equal(np.sort(index), np.sort(
        np.argsort(-prob, -1)[:, :k]))
    want = top / top.sum(-1, keepdims=True) if norm else top
    close(weight, want * scale, 1e-6)
    if norm:            # the k weights of a token add up to the scale
        close(weight.sum(-1), np.full(T, scale), 1e-6)


def _layer(T, H, W, E, k, held, budget=None, shared=None, name="m"):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.data("x", [T, H], "float32", append_batch_size=False)
        xv.stop_gradient = False
        out, aux = layers.moe_ffn(
            layers.scale(xv, 1.0), E, k, W, name=name, experts_held=held,
            norm_topk=True, routed_scale=2.5, row_budget=budget,
            shared_width=shared)
        fluid.append_backward(layers.reduce_sum(layers.square(out)))
    return main, startup, out, aux


def _weights(H, W, E, seed=2):
    return {"router_w": (rng(seed).randn(H, E) * 0.5).astype("float32"),
            "gate_w": rng(seed + 1).randn(E, H, W).astype("float32") * 0.3,
            "up_w": rng(seed + 2).randn(E, H, W).astype("float32") * 0.3,
            "down_w": rng(seed + 3).randn(E, W, H).astype("float32") * 0.3}


def _run_layer(built, x, weights, held, name="m", extra=()):
    main, startup, out, aux = built
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    first, count = held
    scope.set_var(f"{name}_router_w", jnp.asarray(weights["router_w"]))
    for n in ("gate_w", "up_w", "down_w"):
        scope.set_var(f"{name}_{n}",
                      jnp.asarray(weights[n][first:first + count]))
    fetch = [out.name, "x@GRAD", aux["load"].name] + list(extra)
    got = exe.run(main, feed={"x": x}, scope=scope, fetch_list=fetch)
    exe.close()
    return got


def test_a_budget_of_every_assignment_is_the_layer_without_one():
    T, H, W, E, k, held = 40, 16, 8, 16, 4, (4, 4)
    x, weights = rng(1).randn(T, H).astype("float32"), _weights(H, W, E)
    plain = _run_layer(_layer(T, H, W, E, k, held), x, weights, held)
    built = _layer(T, H, W, E, k, held, budget=T * k)
    full = _run_layer(built, x, weights, held,
                      extra=[built[3]["dropped"].name])
    # the budgeted movers (the ops' ``rows`` attr) add each kept row to its
    # token: the same sums in another order, so equal to rounding
    for a, b in zip(plain, full[:3]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    assert int(np.asarray(full[3]).sum()) == 0


@pytest.mark.parametrize("budget", [8, 24, 32])
def test_a_budget_under_the_load_drops_and_counts_exactly_the_excess(budget):
    """The held experts receive ``load[first:first + count].sum()`` rows; a
    budget under that keeps the first ``budget`` of them in the sorted order
    (by expert, then by token) and drops the rest, which add nothing to
    their tokens and whose count accumulates over the steps; the kept rows'
    outputs and the gradient are those of the dense masked form with the
    dropped assignments' weights set to zero."""
    T, H, W, E, k, held = 40, 16, 8, 16, 4, (4, 4)
    first, count = held
    x, weights = rng(1).randn(T, H).astype("float32"), _weights(H, W, E)
    built = _layer(T, H, W, E, k, held, budget=budget)
    main, startup, out, aux = built
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    scope.set_var("m_router_w", jnp.asarray(weights["router_w"]))
    for n in ("gate_w", "up_w", "down_w"):
        scope.set_var(f"m_{n}", jnp.asarray(weights[n][first:first + count]))
    fetch = [out.name, "x@GRAD", aux["load"].name, aux["index"].name,
             aux["dropped"].name]
    got = [exe.run(main, feed={"x": x}, scope=scope, fetch_list=fetch)
           for _ in range(2)]
    exe.close()
    y, dx, load, index, dropped = got[0]
    held_rows = int(load[first:first + count].sum())
    assert held_rows > 32 and load.sum() == T * k
    assert int(dropped[0]) == held_rows - budget
    assert int(got[1][4][0]) == 2 * (held_rows - budget)   # summed over steps
    # the sorted order: by expert from the first held, then by token
    flat = (np.asarray(index).reshape(-1) - first) % E
    order = np.argsort(flat, kind="stable")
    kept = np.zeros(T * k, bool)
    kept[order[:budget]] = True
    kept = kept.reshape(T, k)

    def dense(x):
        prob = jax.nn.softmax(x @ weights["router_w"], -1)
        w = jnp.take_along_axis(prob, jnp.asarray(index), -1)
        w = 2.5 * w / jnp.sum(w, -1, keepdims=True) * kept
        gate = jnp.sum(jax.nn.one_hot(index, E) * w[..., None], 1)
        y = 0
        for e in range(first, first + count):
            h = jax.nn.silu(x @ weights["gate_w"][e]) * (x @ weights["up_w"][e])
            y = y + gate[:, e:e + 1] * (h @ weights["down_w"][e])
        return y
    with jax.default_matmul_precision("highest"):
        want = dense(jnp.asarray(x))
        want_dx = jax.grad(lambda x: jnp.sum(dense(x) ** 2))(jnp.asarray(x))
    close(y, want)
    close(dx, want_dx)


def test_a_budget_needs_a_held_range_and_fits_the_assignments():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        xv = fluid.data("x", [6, 16], "float32", append_batch_size=False)
        with pytest.raises(ValueError, match="row_budget"):
            layers.moe_ffn(xv, 8, 2, 8, row_budget=8)
    with pytest.raises(Exception, match="budget of 64 rows"):
        _run_layer(_layer(6, 16, 8, 8, 2, (0, 4), budget=64),
                   np.zeros((6, 16), "float32"), _weights(16, 8, 8), (0, 4))


def test_gauges_say_budget_and_shared_expert():
    """``moe_row_budget`` of a compiled step is the layer's budget and,
    without one, its 6 x 4 assignments; the shared expert is a parameter of
    the Program."""
    feed = {"x": np.zeros((6, 16), "float32")}
    main, startup = _layer(6, 16, 8, 32, 4, (8, 8), budget=12, shared=24)[:2]
    block = main.global_block()
    assert tuple(block.var("m_shared_gate_w").shape) == (16, 24)
    assert {int(block.find_var_recursive(op.inputs["W"][0]).shape[0])
            for op in block.ops if op.type == "moe_expert_matmul"} == {8}
    budgeted = lowering_reports.step(main, startup, feed)
    plain = lowering_reports.step(*_layer(6, 16, 8, 32, 4, (8, 8))[:2], feed)
    by_program = lowering_reports.read(REGISTRY, "moe_row_budget", "program")
    # no budget: every assignment
    assert (by_program[budgeted], by_program[plain]) == (12, 24)


MODEL = {
    "model_type": "laguna", "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "head_dim": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 3, "num_attention_heads_per_layer": [4, 6, 4],
    "layer_types": ["full_attention", "sliding_attention", "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse"], "mlp_only_layers": [0],
    "gating_types": ["per_head"] * 3, "gating": "per-head",
    "sliding_window": 5, "rms_norm_eps": 1e-6, "attention_bias": False,
    "num_experts": 4, "num_experts_routed": 8, "first_expert_held": 4,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5, "moe_row_budget": 48,
    "moe_router_logit_softcapping": 0, "decoder_sparse_step": 1,
    "moe_apply_router_weight_on_input": False, "tie_word_embeddings": False,
    "rope_parameters": {
        "full_attention": dict(YARN, original_max_position_embeddings=8),
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "router_scoring": "softmax", "qk_norm": "none", "vocab_size": 64,
    "dtype": "float32"}
PARAMS = {"batch": 2, "seq": 16}


def built(model, seed=5):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [PARAMS["batch"], PARAMS["seq"]], "int64", **A)
        labels = fluid.data("labels", [PARAMS["batch"] * PARAMS["seq"], 1],
                            "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        params = [p.name for p in main.global_block().all_parameters()]
        fluid.append_backward(out["loss"])
    return {"main": main, "startup": startup, "out": out, "params": params}


def batch():
    tokens = rng(7).randint(0, MODEL["vocab_size"], (
        PARAMS["batch"], PARAMS["seq"] + 1)).astype(np.int32)
    return {"ids": np.ascontiguousarray(tokens[:, :-1]),
            "labels": np.ascontiguousarray(tokens[:, 1:]).reshape(-1, 1)}


@pytest.fixture(scope="module")
def f32():
    b = built(MODEL)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(b["startup"], scope=scope)
    # q and k eight times their start: at std 0.02 and a hidden size of 32
    # every score is near zero, the softmax uniform, and neither the window
    # nor the rotary embedding would show
    for n in b["params"]:
        if n.endswith(("_attn_q_w", "_attn_k_w")):
            scope.set_var(n, scope.find_var(n) * 8.0)
    weights = [np.array(scope.find_var(n)) for n in b["params"]]
    out = b["out"]
    fetch = [out["loss"].name, out["each"].name] \
        + [n + "@GRAD" for n in b["params"]] \
        + [v.name for v in out["expert_index"] + out["expert_load"]
           + out["expert_dropped"]]
    got = exe.run(b["main"], feed=batch(), fetch_list=fetch, scope=scope)
    exe.close()
    n = len(b["params"])
    with jax.default_matmul_precision("highest"):
        want = reference.forward([jnp.asarray(w) for w in weights], batch(),
                                 MODEL)
        grads = jax.grad(lambda w: reference.forward(w, batch(), MODEL)[
            "loss"])([jnp.asarray(w) for w in weights])
    return {"b": b, "weights": weights, "loss": float(got[0].reshape(-1)[0]),
            "each": got[1].reshape(-1), "grads": dict(zip(b["params"],
                                                          got[2:2 + n])),
            "index": np.stack(got[2 + n:4 + n]),
            "load": np.stack(got[4 + n:6 + n]),
            "dropped": np.stack(got[6 + n:]), "want": want,
            "want_grads": dict(zip(b["params"], grads))}


def test_program_equals_the_reference_in_loss_positions_and_routing(f32):
    want = f32["want"]
    assert f32["loss"] == pytest.approx(float(want["loss"]), rel=2e-6)
    close(f32["each"], want["positions"], 5e-6)
    np.testing.assert_array_equal(np.sort(f32["index"], -1), want["experts"])
    np.testing.assert_array_equal(f32["load"], want["load"])
    assert f32["dropped"].sum() == 0
    kinds = [op.type for op in f32["b"]["main"].global_block().ops]
    assert kinds.count("fused_attention") == 3
    assert kinds.count("attention_gate") == 3
    assert kinds.count("moe_dispatch") == 2
    windows = [op.attr("window", 0) for op in f32["b"]["main"].global_block()
               .ops if op.type == "fused_attention"]
    assert windows == [0, 5, 0]
    rotary = [(op.attr("rotary_dim", 0), op.attr("scaling", ""))
              for op in f32["b"]["main"].global_block().ops
              if op.type == "rotary_embedding"]
    assert rotary == [(4, "yarn")] * 2 + [(0, "")] * 2 + [(4, "yarn")] * 2


LEAVES = ["tok_emb", "layer0_attn_norm_w", "layer0_attn_q_w",
          "layer0_attn_k_w", "layer0_attn_v_w", "layer0_attn_g_w",
          "layer0_attn_o_w", "layer0_ffn_norm_w", "layer0_ffn_gate_w",
          "layer0_ffn_up_w", "layer0_ffn_down_w", "layer1_attn_q_w",
          "layer1_attn_k_w", "layer1_attn_g_w", "layer1_attn_o_w",
          "layer1_moe_router_w", "layer1_moe_gate_w", "layer1_moe_up_w",
          "layer1_moe_down_w", "layer1_moe_shared_gate_w",
          "layer1_moe_shared_up_w", "layer1_moe_shared_down_w",
          "layer2_attn_q_w", "layer2_attn_g_w", "layer2_moe_router_w",
          "layer2_moe_down_w", "layer2_moe_shared_down_w", "final_norm_w",
          "lm_head_w"]


def test_the_leaves_tested_are_the_parameter_kinds_in_creation_order(f32):
    params = f32["b"]["params"]
    assert [p for p in params if p in LEAVES] == LEAVES
    assert len(params) == 41 and params[0] == "tok_emb"
    shapes = {n: tuple(w.shape) for n, w in zip(params, f32["weights"])}
    assert shapes["layer0_attn_q_w"] == (32, 4 * 8)
    assert shapes["layer1_attn_q_w"] == (32, 6 * 8)       # a head count a layer
    assert shapes["layer1_attn_g_w"] == (32, 6)
    assert shapes["layer1_attn_o_w"] == (6 * 8, 32)
    assert shapes["layer1_attn_k_w"] == (32, 2 * 8)
    assert shapes["layer1_moe_gate_w"] == (4, 32, 16)     # the held experts
    assert shapes["layer1_moe_router_w"] == (32, 8)       # all routed
    assert shapes["layer1_moe_shared_gate_w"] == (32, 16)


@pytest.mark.parametrize("name", LEAVES)
def test_float32_gradient_of_every_parameter_kind(f32, name):
    got = np.asarray(f32["grads"][name], np.float32)
    want = np.asarray(f32["want_grads"][name], np.float32)
    assert got.shape == want.shape and np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * np.abs(want).max())


def _without(mechanism):
    model = copy.deepcopy(MODEL)
    if mechanism == "window":
        model["sliding_window"] = 1 << 20
    elif mechanism == "gate":
        model["gating"] = "none"
    elif mechanism == "partial_rotary":
        model["rope_parameters"]["full_attention"]["partial_rotary_factor"] = 1
    elif mechanism == "yarn":
        model["rope_parameters"]["full_attention"]["rope_type"] = "default"
    elif mechanism == "routed_scale":
        model["moe_routed_scaling_factor"] = 1.0
    elif mechanism == "shared_expert":
        del model["shared_expert_intermediate_size"]
    return model


@pytest.mark.parametrize("mechanism", ["window", "gate", "partial_rotary",
                                       "yarn", "routed_scale",
                                       "shared_expert"])
def test_each_mechanism_matters(f32, mechanism):
    """The Program built without one mechanism, on the same weights, is off
    the reference by orders more than the Program as it is (5e-6)."""
    b = built(_without(mechanism))
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(b["startup"], scope=scope)
    for n, w in zip(f32["b"]["params"], f32["weights"]):
        scope.set_var(n, jnp.asarray(w))
    each, = exe.run(b["main"], feed=batch(), scope=scope,
                    fetch_list=[b["out"]["each"].name])
    exe.close()
    want = np.asarray(f32["want"]["positions"])
    assert np.abs(each.reshape(-1) - want).max() > 1e-3 * want.max()


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """Held ranges 0-3, 4-7, 8-11, 12-15 of one layer of 16 experts, each
    through ``layers.moe_ffn`` with its slice of the stacked weights, the
    same router and the same shared expert: every share's output is its
    held experts' part plus the shared expert's; the four routed parts and
    the shared expert counted ONCE add up to what the plain reference gives
    for the whole layer (all 16 experts held) plus its shared expert."""
    T, H, W, E, k = 48, 16, 8, 16, 4
    model = {"num_experts_per_tok": k, "num_experts_routed": E,
             "num_experts": 4, "norm_topk_prob": True,
             "moe_routed_scaling_factor": 2.5}
    x = rng(1).randn(T, H).astype("float32")
    weights = _weights(H, W, E)
    shared = {"gate_w": rng(11).randn(H, 12).astype("float32") * 0.3,
              "up_w": rng(12).randn(H, 12).astype("float32") * 0.3,
              "down_w": rng(13).randn(12, H).astype("float32") * 0.3}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.data("x", [T, H], "float32", append_batch_size=False)
        shares = [layers.moe_ffn(
            xv, E, k, W, name=f"s{i}", experts_held=(4 * i, 4),
            norm_topk=True, routed_scale=2.5, shared_width=12,
            row_budget=T * k // 2) for i in range(4)]
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    for i in range(4):
        scope.set_var(f"s{i}_router_w", jnp.asarray(weights["router_w"]))
        for n in ("gate_w", "up_w", "down_w"):
            scope.set_var(f"s{i}_{n}",
                          jnp.asarray(weights[n][4 * i:4 * i + 4]))
            scope.set_var(f"s{i}_shared_{n}", jnp.asarray(shared[n]))
    got = exe.run(main, feed={"x": x}, scope=scope, fetch_list=[
        v for out, aux in shares
        for v in (out, aux["load"], aux["dropped"])])
    exe.close()
    outs, loads, dropped = got[0::3], got[1::3], got[2::3]
    assert sum(int(d[0]) for d in dropped) == 0
    args = (jnp.asarray(x), weights["router_w"], weights["gate_w"],
            weights["up_w"], weights["down_w"])
    with jax.default_matmul_precision("highest"):
        whole, _, load = reference.expert_layer(*args, model, held=(0, E))
        once = reference._swiglu(jnp.asarray(x), shared["gate_w"],
                                 shared["up_w"], shared["down_w"])
        for i in range(4):
            part, _, _ = reference.expert_layer(
                args[0], args[1], *(a[4 * i:4 * i + 4] for a in args[2:]),
                model, held=(4 * i, 4))
            close(outs[i], part + once, 2e-5)
            np.testing.assert_array_equal(loads[i], load)
    close(sum(outs) - 3 * np.asarray(once), whole + once, 2e-5)
    assert np.abs(np.asarray(once)).max() > 0.05
    assert np.abs(outs[0] - np.asarray(whole + once)).max() > 0.05


@pytest.mark.parametrize("change,error,match", [
    ({"layer_types": ["full_attention", "chunked_attention",
                      "full_attention"]}, NotImplementedError, "chunked"),
    ({"gating": "per-channel"}, NotImplementedError, "gating"),
    ({"moe_router_logit_softcapping": 30.0}, NotImplementedError,
     "softcapping"),
    ({"moe_apply_router_weight_on_input": True}, NotImplementedError,
     "router_weight_on_input"),
    ({"n_shared_experts": 2}, NotImplementedError, "shared experts"),
    ({"use_expert_bias": True}, NotImplementedError, "sigmoid"),
    ({"sliding_window": None}, ValueError, "sliding_window"),
    ({"num_attention_heads_per_layer": [4, 6]}, ValueError, "head count"),
    ({"num_attention_heads_per_layer": [4, 5, 4]}, ValueError,
     "num_key_value_heads"),
    ({"num_experts_routed": 4}, ValueError, "moe_row_budget")])
def test_what_the_builder_does_not_build_raises_by_name(change, error, match):
    with pytest.raises(error, match=match):
        decoder_lm._check(dict(MODEL, **change))


def test_a_rope_type_the_builder_does_not_build_raises_by_name():
    model = copy.deepcopy(MODEL)
    model["rope_parameters"]["full_attention"]["rope_type"] = "longrope"
    with pytest.raises(NotImplementedError, match="longrope"):
        decoder_lm._check(model)
