"""What a DeltaNet / gated-attention hybrid decoder adds to the decoder ops
(``layers.*`` -> ``Program`` -> ``Executor`` on the CPU): the zero-centred
RMSNorm, attention's gate a token, head and channel, the shared expert's
sigmoid gate over the shares of a layer, and a tiny Qwen3-Next Program
against ``benchmark/references/qwen3_next_pretrain.py`` (the delta rule
token by token) in loss, positions, the delta rule's output, one AdamW step
and every leaf's gradient, with each mechanism shown to matter; what the
builder still refuses; and the four older decoders' Programs, op for op what
the parent commit built."""
import copy
import hashlib
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder_lm
from benchmark.references import qwen3_next_pretrain as reference
from tests.test_decoder_ops import close, rng, run_with_grads


def test_zero_centered_rms_norm_scales_by_one_plus_w_and_starts_at_zero():
    x = rng(0).randn(6, 16).astype("float32")
    w = rng(1).randn(16).astype("float32") * 0.3

    def build(xv):
        return layers.rms_norm(xv, 1e-6, fluid.ParamAttr(name="zc_w"),
                               zero_centered=True)
    out, grads, _, g, scope = run_with_grads(build, {"x": x}, ["x", "zc_w"])
    assert not np.asarray(scope.find_var("zc_w")).any()      # w starts at 0
    unit = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6)
    close(out, unit)                       # (1 + 0): the plain norm's start
    close(grads[1], np.sum(g * unit, 0))   # d/dw of unit * (1 + w)

    def form(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6
                                 ) * (1.0 + w)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.data("x", [6, 16], "float32", append_batch_size=False)
        y = build(xv)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    scope.set_var("zc_w", jnp.asarray(w))
    got, = exe.run(main, feed={"x": x}, fetch_list=[y], scope=scope)
    exe.close()
    close(got, form(jnp.asarray(x), jnp.asarray(w)))
    assert [op.attr("zero_centered", False)
            for op in main.global_block().ops] == [True]


def test_attention_gate_a_channel_matches_its_one_line_form_and_gradient():
    B, heads, S, D = 2, 3, 5, 4
    feeds = {"x": rng(0).randn(B, heads, S, D).astype("float32"),
             "gate": rng(1).randn(B * S, heads * D).astype("float32")}
    out, grads, _, g, _ = run_with_grads(layers.attention_gate, feeds,
                                         ["x", "gate"])

    def form(x, gate):
        return x * jax.nn.sigmoid(gate).reshape(B, S, heads, D).transpose(
            0, 2, 1, 3)
    close(out, form(feeds["x"], feeds["gate"]))
    want = jax.grad(lambda x, gate: jnp.sum(form(x, gate) * g), (0, 1))(
        jnp.asarray(feeds["x"]), jnp.asarray(feeds["gate"]))
    for got, ref in zip(grads, want):
        close(got, ref)
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.data("x", [B, heads, S, D], "float32",
                       append_batch_size=False)
        for width in (heads, heads * D, heads * D):
            layers.attention_gate(x, fluid.data(
                f"g{width}", [B * S, width], "float32",
                append_batch_size=False))
    # the gate's form is its width: one a token and head, or one a channel
    block = main.global_block()
    assert [int(block.find_var_recursive(op.inputs["Gate"][0]).shape[-1])
            for op in block.ops if op.type == "attention_gate"] == [
                heads, heads * D, heads * D]


MODEL = {
    "model_type": "qwen3_next", "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 24,
    "head_dim": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 3, "full_attention_interval": 3,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "delta_chunk_size": 8,
    "partial_rotary_factor": 0.5, "rope_theta": 10000000,
    "rope_scaling": None, "use_sliding_window": False,
    "mlp_only_layers": [], "decoder_sparse_step": 1, "hidden_act": "silu",
    "rms_norm_eps": 1e-6, "num_experts": 4, "num_experts_routed": 8,
    "first_expert_held": 4, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "moe_row_budget": 48, "tie_word_embeddings": False,
    "router_scoring": "softmax", "qk_norm": "head",
    "norm_form": "zero_centered", "attn_output_gate": True,
    "shared_expert_gate": True, "vocab_size": 64, "dtype": "float32"}
# what the reference reads beside the published keys
MODEL["layer_types"] = decoder_lm._layer_types(MODEL)
PARAMS = {"batch": 2, "seq": 16}


def built(model, seed=5, optimizer=None):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [PARAMS["batch"], PARAMS["seq"]], "int64", **A)
        labels = fluid.data("labels", [PARAMS["batch"] * PARAMS["seq"], 1],
                            "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        params = [p.name for p in main.global_block().all_parameters()]
        delta = [op.outputs["Out"][0] for op in main.global_block().ops
                 if op.type == "gated_delta_rule"]
        if optimizer is None:
            fluid.append_backward(out["loss"])
        else:
            optimizer.minimize(out["loss"])
    return {"main": main, "startup": startup, "out": out, "params": params,
            "delta": delta}


def batch():
    tokens = rng(7).randint(0, MODEL["vocab_size"], (
        PARAMS["batch"], PARAMS["seq"] + 1)).astype(np.int32)
    return {"ids": np.ascontiguousarray(tokens[:, :-1]),
            "labels": np.ascontiguousarray(tokens[:, 1:]).reshape(-1, 1)}


def seeded(scope, names):
    """Weights at which every mechanism shows: q and k eight times their
    start (at std 0.02 and a hidden size of 32 every score is near zero and
    the softmax uniform), the DeltaNet projections four times theirs, and
    the zero-centred norm weights and the shared gate away from zero, so
    that ``1 + w`` is not ``w`` nor 1."""
    for n in names:
        v = scope.find_var(n)
        if n.endswith(("_attn_q_w", "_attn_k_w")):
            scope.set_var(n, v * 8.0)
        elif n.endswith(("_delta_in_w", "_delta_ba_w")):
            scope.set_var(n, v * 4.0)
        elif n.endswith(("norm_w", "_shared_expert_gate_w")) and \
                not n.endswith("_gated_norm_w"):
            seed = sum(n.encode()) % 1000
            scope.set_var(n, jnp.asarray(
                rng(seed).randn(*v.shape).astype("float32") * 0.3))


@pytest.fixture(scope="module")
def f32():
    b = built(MODEL)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(b["startup"], scope=scope)
    seeded(scope, b["params"])
    weights = [np.array(scope.find_var(n)) for n in b["params"]]
    out = b["out"]
    fetch = [out["loss"].name, out["each"].name] \
        + [n + "@GRAD" for n in b["params"]] \
        + [v.name for v in out["expert_index"] + out["expert_load"]
           + out["expert_dropped"]] + b["delta"]
    got = exe.run(b["main"], feed=batch(), fetch_list=fetch, scope=scope)
    exe.close()
    n, e = len(b["params"]), len(out["expert_index"])
    with jax.default_matmul_precision("highest"):
        want = reference.forward([jnp.asarray(w) for w in weights], batch(),
                                 MODEL)
        grads = jax.grad(lambda w: reference.forward(w, batch(), MODEL)[
            "loss"])([jnp.asarray(w) for w in weights])
    return {"b": b, "weights": weights, "loss": float(got[0].reshape(-1)[0]),
            "each": got[1].reshape(-1), "grads": dict(zip(b["params"],
                                                          got[2:2 + n])),
            "index": np.stack(got[2 + n:2 + n + e]),
            "load": np.stack(got[2 + n + e:2 + n + 2 * e]),
            "dropped": np.stack(got[2 + n + 2 * e:2 + n + 3 * e]),
            "delta": got[2 + n + 3 * e:], "want": want,
            "want_grads": dict(zip(b["params"], grads))}


def test_program_equals_the_reference_in_loss_positions_and_routing(f32):
    want = f32["want"]
    assert f32["loss"] == pytest.approx(float(want["loss"]), rel=2e-6)
    close(f32["each"], want["positions"], 5e-6)
    np.testing.assert_array_equal(np.sort(f32["index"], -1), want["experts"])
    np.testing.assert_array_equal(f32["load"], want["load"])
    assert f32["dropped"].sum() == 0
    # the delta rule's output before the gated norm, a DeltaNet layer each
    norms = [np.mean(np.linalg.norm(o.reshape(32, -1), axis=-1))
             for o in f32["delta"]]
    np.testing.assert_allclose(norms, want["delta"], rtol=1e-5)
    assert len(want["each"]) == 32 + 3 + 2
    ops = f32["b"]["main"].global_block().ops
    kinds = [op.type for op in ops]
    assert decoder_lm._layer_types(MODEL) == [
        "linear_attention", "linear_attention", "full_attention"]
    assert kinds.count("gated_delta_rule") == 2
    assert kinds.count("short_conv") == 2
    assert kinds.count("fused_attention") == 1
    assert kinds.count("attention_gate") == 1
    assert kinds.count("moe_dispatch") == 3
    assert [op.attr("rotary_dim", 0) for op in ops
            if op.type == "rotary_embedding"] == [4, 4]
    # every norm but the DeltaNet mixers' gated one is zero-centred
    assert [op.attr("zero_centered", False) for op in ops
            if op.type == "rms_norm"] == [True, False, True] * 2 + [
                True, True, True, True, True]
    # each expert layer has a shared expert, under a sigmoid gate a token
    names = [p.name for p in f32["b"]["main"].global_block().all_parameters()]
    for suffix in ("_shared_gate_w", "_shared_expert_gate_w"):
        assert sum(n.endswith(suffix) for n in names) == 3


LEAVES = ["tok_emb", "layer0_delta_norm_w", "layer0_delta_in_w",
          "layer0_delta_ba_w", "layer0_delta_conv_w", "layer0_delta_dt_bias",
          "layer0_delta_A_log", "layer0_delta_gated_norm_w",
          "layer0_delta_out_w", "layer0_ffn_norm_w", "layer0_moe_router_w",
          "layer0_moe_gate_w", "layer0_moe_up_w", "layer0_moe_down_w",
          "layer0_moe_shared_gate_w", "layer0_moe_shared_up_w",
          "layer0_moe_shared_down_w", "layer0_moe_shared_expert_gate_w",
          "layer1_delta_in_w", "layer1_delta_ba_w", "layer1_delta_dt_bias",
          "layer1_delta_A_log", "layer2_attn_norm_w", "layer2_attn_q_w",
          "layer2_attn_k_w", "layer2_attn_v_w", "layer2_attn_q_norm_w",
          "layer2_attn_k_norm_w", "layer2_attn_o_w",
          "layer2_moe_shared_expert_gate_w", "final_norm_w", "lm_head_w"]


def test_the_leaves_tested_are_the_parameter_kinds_in_creation_order(f32):
    params = f32["b"]["params"]
    assert [p for p in params if p in LEAVES] == LEAVES
    assert len(params) == 53 and params[0] == "tok_emb"
    shapes = {n: tuple(w.shape) for n, w in zip(params, f32["weights"])}
    assert shapes["layer0_delta_in_w"] == (32, 2 * 16 + 2 * 32)  # q k v z
    assert shapes["layer0_delta_ba_w"] == (32, 2 * 4)
    assert shapes["layer0_delta_conv_w"] == (2 * 16 + 32, 4)
    assert shapes["layer0_delta_A_log"] == (4,)
    assert shapes["layer0_delta_gated_norm_w"] == (8,)   # shared by the heads
    assert shapes["layer0_delta_out_w"] == (32, 32)
    assert shapes["layer2_attn_q_w"] == (32, 4 * 2 * 8)  # a head's q | gate
    assert shapes["layer2_attn_q_norm_w"] == (8,)
    assert shapes["layer2_attn_k_w"] == (32, 2 * 8)
    assert shapes["layer0_moe_gate_w"] == (4, 32, 16)     # the held experts
    assert shapes["layer0_moe_router_w"] == (32, 8)       # all routed
    assert shapes["layer0_moe_shared_expert_gate_w"] == (32, 1)


@pytest.mark.parametrize("name", LEAVES)
def test_float32_gradient_of_every_parameter_kind(f32, name):
    got = np.asarray(f32["grads"][name], np.float32)
    want = np.asarray(f32["want_grads"][name], np.float32)
    assert got.shape == want.shape and np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=3e-5 * np.abs(want).max())


def test_one_adamw_step_is_the_reference_s_gradient_through_adamw(f32):
    """The first AdamW step from the seeded weights: ``p (1 - lr wd) - lr g /
    (|g| + eps)`` with the reference's gradient ``g`` (the moments start at
    zero, so the corrected first and second are ``g`` and ``g^2``). The
    decay pulls a zero-centred norm weight to 0, its scale to 1."""
    lr, wd, eps = 1e-3, 0.1, 1e-8
    b = built(MODEL, optimizer=fluid.optimizer.AdamW(
        lr, weight_decay=wd, beta1=0.9, beta2=0.95, epsilon=eps))
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(b["startup"], scope=scope)
    for n, w in zip(f32["b"]["params"], f32["weights"]):
        scope.set_var(n, jnp.asarray(w))
    loss, = exe.run(b["main"], feed=batch(), scope=scope,
                    fetch_list=[b["out"]["loss"].name])
    assert float(loss.reshape(-1)[0]) == pytest.approx(f32["loss"], rel=1e-6)
    for n, w in zip(f32["b"]["params"], f32["weights"]):
        g = np.asarray(f32["want_grads"][n], np.float64)
        big = np.abs(g) > 1e-2 * np.abs(g).max()    # sign(g) where g is sure
        want = w * (1 - lr * wd) - lr * g / (np.abs(g) + eps)
        got = np.asarray(scope.find_var(n), np.float64)
        np.testing.assert_allclose(got[big], want[big], rtol=0, atol=5e-5,
                                   err_msg=n)
    exe.close()


def _split_rule(qkv, g, beta, key_heads, key_dim, **kw):
    """The mixer's delta rule as it was built before the op took q | k | v
    whole: the conv's output cut into q, k and v, three operands."""
    batch, seq, wide = (int(d) for d in qkv.shape)
    keys, heads = key_heads * key_dim, int(g.shape[2])
    q, k, v = layers.split(qkv, [keys, keys, wide - 2 * keys], dim=-1)
    return layers.gated_delta_rule(
        layers.reshape(q, [batch, seq, key_heads, key_dim]),
        layers.reshape(k, [batch, seq, key_heads, key_dim]),
        layers.reshape(v, [batch, seq, heads, -1]), g, beta, **kw)


def test_the_delta_rule_reads_the_convs_output_whole(f32, monkeypatch):
    """No ``split`` stands between a DeltaNet layer's ``short_conv`` and its
    ``gated_delta_rule``: the op's one ``QKV`` operand is the conv's output
    (reshaped, which moves nothing) and its gradient goes back the same
    way; the first step's loss and every parameter's gradient are the split
    form's."""
    block = f32["b"]["main"].global_block()
    made_by = {name: op for op in block.ops
               for names in op.outputs.values() for name in names}
    rules = [op for op in block.ops if op.type == "gated_delta_rule"]
    assert len(rules) == 2
    for op in rules:
        assert sorted(op.inputs) == ["Beta", "G", "QKV"]
        assert (op.attr("key_heads"), op.attr("key_dim")) == (2, 8)
        reshape = made_by[op.inputs["QKV"][0]]
        assert reshape.type.startswith("reshape")
        conv = made_by[reshape.inputs["X"][0]]
        assert conv.type == "short_conv"
        readers = [o.type for o in block.ops
                   if conv.outputs["Out"][0] in sum(o.inputs.values(), [])]
        assert "split" not in readers and readers[0] == reshape.type
    grads = [op for op in block.ops if op.type == "gated_delta_rule_grad"]
    assert [sorted(k for k in op.outputs if op.outputs[k])
            for op in grads] == [["Beta@GRAD", "G@GRAD", "QKV@GRAD"]] * 2
    # q | k | v, then [qkv | z] and [b | alpha]: one split fewer a layer
    assert [op.type for op in block.ops].count("split") == 2 * 2 + 1
    monkeypatch.setattr(layers, "gated_delta_rule_packed", _split_rule)
    b = built(MODEL)
    kinds = [op.type for op in b["main"].global_block().ops]
    assert kinds.count("split") == 2 * 3 + 1
    assert all(sorted(op.inputs) == ["Beta", "G", "K", "Q", "V"]
               for op in b["main"].global_block().ops
               if op.type == "gated_delta_rule")
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(b["startup"], scope=scope)
    assert b["params"] == f32["b"]["params"]
    for n, w in zip(b["params"], f32["weights"]):
        scope.set_var(n, jnp.asarray(w))
    got = exe.run(b["main"], feed=batch(), scope=scope, fetch_list=[
        b["out"]["loss"].name] + [n + "@GRAD" for n in b["params"]])
    exe.close()
    assert float(got[0].reshape(-1)[0]) == pytest.approx(f32["loss"],
                                                         rel=2e-6)
    for n, g in zip(b["params"], got[1:]):
        want = np.asarray(f32["grads"][n], np.float32)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), want, rtol=0,
            atol=3e-5 * np.abs(want).max(), err_msg=n)


_one_op_norm = layers.rms_norm


def _two_op_norm(input, epsilon=1e-5, param_attr=None, name=None,
                 zero_centered=False, gate=None, impl="auto"):
    """The mixer's output norm as it was built before ``rms_norm`` took the
    gate: the norm over a head's values, then ``swiglu(z, .)``, two ops."""
    o = _one_op_norm(input, epsilon, param_attr, name, zero_centered)
    if gate is None:
        return o
    return layers.swiglu(gate, layers.reshape(o, [int(d) for d in gate.shape]))


def test_the_mixers_norm_and_gate_are_one_op(f32, monkeypatch):
    """A DeltaNet mixer ends ``gated_delta_rule`` -> (reshape) -> one
    ``rms_norm`` given the gate z -> the output projection: no ``swiglu``
    reads z, the grad op returns X's, Gate's and Scale's gradients, and the
    first step's loss and every parameter's gradient (``_gated_norm_w``,
    ``_in_w`` and ``_out_w`` among them) are the two-op form's, on the same
    parameters in the same creation order."""
    block = f32["b"]["main"].global_block()
    made_by = {name: op for op in block.ops
               for names in op.outputs.values() for name in names}

    def readers(name):
        return [o for o in block.ops if name in sum(o.inputs.values(), [])
                and not o.type.endswith("_grad")]
    gated = [op for op in block.ops
             if op.type == "rms_norm" and "Gate" in op.inputs]
    assert len(gated) == 2      # a DeltaNet layer each
    for i, op in enumerate(gated):
        assert not op.attr("zero_centered", False)
        reshape = made_by[op.inputs["X"][0]]
        assert reshape.type.startswith("reshape")
        assert made_by[reshape.inputs["X"][0]].type == "gated_delta_rule"
        assert op.inputs["Scale"] == [f"layer{i}_delta_gated_norm_w"]
        z = op.inputs["Gate"][0]
        assert made_by[z].type == "split"
        assert [o.type for o in readers(z)] == ["rms_norm"]
        (out,) = readers(op.outputs["Y"][0])
        assert out.type == "mul" and out.inputs["Y"] == [
            f"layer{i}_delta_out_w"]
    grads = [op for op in block.ops
             if op.type == "rms_norm_grad" and "Gate" in op.inputs]
    assert [sorted(k for k in op.outputs if op.outputs[k])
            for op in grads] == [["Gate@GRAD", "Scale@GRAD", "X@GRAD"]] * 2
    kinds = [op.type for op in block.ops]
    # the three expert layers' gated products (routed and shared) are left
    assert kinds.count("swiglu") == 2 * 3
    monkeypatch.setattr(layers, "rms_norm", _two_op_norm)
    b = built(MODEL)
    two = [op.type for op in b["main"].global_block().ops]
    assert two.count("swiglu") == 2 * 3 + 2
    assert not any("Gate" in op.inputs for op in b["main"].global_block().ops
                   if op.type == "rms_norm")
    assert two.count("rms_norm") == kinds.count("rms_norm")
    assert b["params"] == f32["b"]["params"]
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(b["startup"], scope=scope)
    for n, w in zip(b["params"], f32["weights"]):
        scope.set_var(n, jnp.asarray(w))
    got = exe.run(b["main"], feed=batch(), scope=scope, fetch_list=[
        b["out"]["loss"].name] + [n + "@GRAD" for n in b["params"]])
    exe.close()
    assert float(got[0].reshape(-1)[0]) == pytest.approx(f32["loss"],
                                                         rel=2e-6)
    for n, g in zip(b["params"], got[1:]):
        want = np.asarray(f32["grads"][n], np.float32)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), want, rtol=0,
            atol=3e-5 * np.abs(want).max(), err_msg=n)


def _without(mechanism):
    model = copy.deepcopy(MODEL)
    if mechanism == "shared_gate":
        model["shared_expert_gate"] = False
    elif mechanism == "partial_rotary":
        model["partial_rotary_factor"] = 1
    elif mechanism == "zero_centered":
        model["norm_form"] = "plain"
    elif mechanism == "qk_norm":
        model["qk_norm"] = "none"
    elif mechanism == "shared_expert":
        model["shared_expert_gate"] = False
        del model["shared_expert_intermediate_size"]
    return model


@pytest.mark.parametrize("mechanism", [
    "shared_gate", "partial_rotary", "zero_centered", "qk_norm",
    "shared_expert", "decay", "beta", "l2_norm", "attention_gate"])
def test_each_mechanism_matters(f32, mechanism):
    """The Program built without one mechanism, on the same weights, is off
    the reference by orders more than the Program as it is (5e-6); what no
    key takes out is ``tools/qwen3_next_probe.py``'s ``patched``."""
    from tools.qwen3_next_probe import patched
    exe, scope = fluid.Executor(), fluid.Scope()
    with patched(mechanism):
        b = built(_without(mechanism))
        exe.run(b["startup"], scope=scope)
        for n, w in zip(f32["b"]["params"], f32["weights"]):
            scope.set_var(n, jnp.asarray(w))
        each, = exe.run(b["main"], feed=batch(), scope=scope,
                        fetch_list=[b["out"]["each"].name])
    exe.close()
    want = np.asarray(f32["want"]["positions"])
    assert np.abs(each.reshape(-1) - want).max() > 3e-4 * want.max()


def test_the_shares_and_the_gated_shared_expert_once_add_up_to_the_layer():
    """Held ranges 0-3, 4-7, 8-11, 12-15 of one layer of 16 experts, each
    through ``layers.moe_ffn`` with its slice of the stacked weights, the
    same router, the same shared expert and the same gate on it: every
    share's output is its held experts' part plus the gated shared
    expert's; the four routed parts and the gated shared expert counted
    ONCE add up to what the plain reference gives for the whole layer (all
    16 experts held) plus ``sigmoid(x . w_s)`` times its shared expert."""
    T, H, W, E, k = 48, 16, 8, 16, 4
    model = {"num_experts_per_tok": k, "num_experts_routed": E,
             "num_experts": 4, "norm_topk_prob": True}
    x = rng(1).randn(T, H).astype("float32")
    r = rng(2)
    weights = {"router_w": r.randn(H, E).astype("float32"),
               "gate_w": r.randn(E, H, W).astype("float32") * 0.3,
               "up_w": r.randn(E, H, W).astype("float32") * 0.3,
               "down_w": r.randn(E, W, H).astype("float32") * 0.3}
    shared = {"gate_w": rng(11).randn(H, 12).astype("float32") * 0.3,
              "up_w": rng(12).randn(H, 12).astype("float32") * 0.3,
              "down_w": rng(13).randn(12, H).astype("float32") * 0.3,
              "expert_gate_w": rng(14).randn(H, 1).astype("float32")}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.data("x", [T, H], "float32", append_batch_size=False)
        shares = [layers.moe_ffn(
            xv, E, k, W, name=f"s{i}", experts_held=(4 * i, 4),
            norm_topk=True, shared_width=12, shared_gate=True,
            row_budget=T * k // 2) for i in range(4)]
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    for i in range(4):
        scope.set_var(f"s{i}_router_w", jnp.asarray(weights["router_w"]))
        for n in ("gate_w", "up_w", "down_w"):
            scope.set_var(f"s{i}_{n}",
                          jnp.asarray(weights[n][4 * i:4 * i + 4]))
        for n, w in shared.items():
            scope.set_var(f"s{i}_shared_{n}", jnp.asarray(w))
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
        plain = reference._swiglu(jnp.asarray(x), shared["gate_w"],
                                  shared["up_w"], shared["down_w"])
        once = jax.nn.sigmoid(jnp.asarray(x) @ shared["expert_gate_w"]) \
            * plain
        for i in range(4):
            part, _, _ = reference.expert_layer(
                args[0], args[1], *(a[4 * i:4 * i + 4] for a in args[2:]),
                model, held=(4 * i, 4))
            close(outs[i], part + once, 2e-5)
            np.testing.assert_array_equal(loads[i], load)
    close(sum(outs) - 3 * np.asarray(once), whole + once, 2e-5)
    assert np.abs(np.asarray(once - plain)).max() > 0.05     # the gate shows
    assert np.abs(outs[0] - np.asarray(whole + once)).max() > 0.05


@pytest.mark.parametrize("change,error,match", [
    ({"layer_types": ["linear_attention", "chunked_attention",
                      "full_attention"]}, NotImplementedError, "chunked"),
    ({"norm_form": "centered"}, NotImplementedError, "norm_form"),
    ({"gating": "per-head"}, NotImplementedError, "attn_output_gate"),
    # since PR 55 read with layer_types and max_window_layers 0; Qwen's own
    # rule (the layers from max_window_layers on slide) still raises
    ({"use_sliding_window": True, "max_window_layers": 2},
     NotImplementedError, "use_sliding_window"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, NotImplementedError,
     "rope_scaling"),
    ({"decoder_sparse_step": 2}, NotImplementedError, "decoder_sparse_step"),
    ({"linear_conv_bias": True}, NotImplementedError, "linear_conv_bias"),
    ({"linear_num_value_heads": 3}, ValueError, "linear_num_key_heads"),
    ({"shared_expert_intermediate_size": None}, ValueError,
     "shared_expert_gate"),
    ({"layer_types": ["linear_attention"]}, ValueError, "layer_types")])
def test_what_the_builder_does_not_build_raises_by_name(change, error, match):
    with pytest.raises(error, match=match):
        decoder_lm._check(dict(MODEL, **change))
    assert "delta rule" not in str(_refusal())


def _refusal():
    try:
        decoder_lm._check(dict(MODEL, layer_types=["latent"] * 3))
    except NotImplementedError as e:
        return e


def test_every_key_of_the_published_config_is_read_or_refused():
    """The catalog row's keys: each is read by ``decoder_lm`` (its name in
    the source) or is one of the three that say nothing a builder acts on
    (``model_type``, the position limit, and the dense width no layer of
    this model uses, which ``_is_dense`` layers would read)."""
    import inspect
    source = inspect.getsource(decoder_lm)
    cfg = json.load(open("benchmark/configs/qwen3_next_80b_a3b.json"))
    unread = [k for k in cfg["published"] if f'"{k}"' not in source]
    assert unread == ["max_position_embeddings", "model_type"]


def before_pr50(type, inputs, attrs):
    """An expert layer's op without what PR 50 gave it: ``moe_combine``'s
    ``GroupCount`` input and ``held``, ``moe_dispatch``'s ``held`` where it
    has no row budget (their grad ops carry the same)."""
    if type.startswith("moe_combine"):
        inputs.pop("GroupCount", None)

    def strip(a):
        if type.startswith("moe_combine") or not a.get("rows"):
            a.pop("held", None)
        for v in a.values():
            if isinstance(v, dict):
                strip(v)
    if type.startswith(("moe_combine", "moe_dispatch")):
        strip(attrs)


def program_fingerprint(program):
    rows = []
    for op in program.global_block().ops:
        inputs = {k: list(v) for k, v in sorted(op.inputs.items())}
        attrs = json.loads(json.dumps(dict(sorted(op.attrs.items())),
                                      default=str))
        before_pr50(op.type, inputs, attrs)
        rows.append([op.type, inputs,
                     {k: list(v) for k, v in sorted(op.outputs.items())},
                     attrs])
    return hashlib.sha256(
        json.dumps(rows, sort_keys=True).encode()).hexdigest()[:16], len(rows)


# (train Program, startup Program) at the cells' own sizes, as the parent
# commit of PR 41 built them (op types, slots, variable names and attrs;
# ``before_pr50`` for the expert layers' ops)
PARENTS = {
    "olmoe_1b_7b.pretrain_s4096":
        (("fa4ce639a9cdad2b", 118), ("acd9e62549696c51", 76)),
    "lfm2_8b_a1b.pretrain_s4096":
        (("11cf56eb33827853", 253), ("028c7dda63697cfe", 255)),
    "granite_4_0_h_micro.pretrain_s4096":
        (("0b888037b9e5ce8f", 733), ("84944fa49be5159f", 686)),
    "laguna_s_2_1.pretrain_s4096":
        (("73d293f8c882940b", 448), ("2c11ffe4707326e0", 350))}


@pytest.mark.parametrize("cell", sorted(PARENTS))
def test_the_older_decoders_build_the_parents_programs(cell):
    """Without the new keys every configuration lowers to the program it
    had: the builder's new branches are behind keys those files lack."""
    from benchmark import run
    loaded = run.load_cell(cell, rehearsal=False)
    b = importlib.import_module(
        f"benchmark.programs.{loaded['builder']}").build(
            loaded["model"], loaded["params"])
    assert (program_fingerprint(b["main"]),
            program_fingerprint(b["startup"])) == PARENTS[cell]
