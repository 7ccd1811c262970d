"""Kimi-Linear-48B-A3B through ``models/decoder_lm.py`` -> ``Executor`` at
small sizes on the CPU, against ``benchmark/references/kimi_linear_pretrain.
py`` (the recurrence position by position, no chunk, no kernel): the loss,
every position, the routing, every parameter kind's gradient, one AdamW step
and the bias update; what each mechanism moves; the share test (32 shares of
8 experts add up to the uncut layer); Kimi's key spellings; what the builder
refuses and what it no longer does; and that the six decoder configurations
the benchmark already had still build the parent's Programs."""
import hashlib
import importlib
import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import decoder_lm
from benchmark.references import kimi_linear_pretrain as reference
from test_decoder_ops import close, rng

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODEL = {
    "model_type": "kimi_linear", "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_hidden_layers": 4,
    "first_k_dense_replace": 1, "num_attention_heads": 3,
    "num_key_value_heads": 3, "head_dim": 10, "kv_lora_rank": 20,
    "q_lora_rank": None, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "mla_use_nope": True, "rope_theta": 10000,
    "rope_scaling": None, "rms_norm_eps": 1e-5, "hidden_act": "silu",
    "linear_attn_config": {
        "kda_layers": [1, 2, 4], "full_attn_layers": [3], "num_heads": 2,
        "head_dim": 8, "short_conv_kernel_size": 4},
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
    "topk_group": 1, "use_grouped_topk": True, "num_experts": 4,
    "num_experts_routed": 8, "first_expert_held": 4,
    "num_experts_per_token": 2, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "num_nextn_predict_layers": 0,
    "tie_word_embeddings": False, "model_max_length": 1048576,
    "delta_chunk_size": 8, "moe_row_budget": 48,
    "vocab_size": 64, "dtype": "float32"}
PARAMS = {"batch": 2, "seq": 16}
T = PARAMS["batch"] * PARAMS["seq"]


def built(model, seed=5, optimizer=None):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [PARAMS["batch"], PARAMS["seq"]], "int64", **A)
        labels = fluid.data("labels", [T, 1], "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        params = [p.name for p in main.global_block().all_parameters()]
        if optimizer is None:
            fluid.append_backward(out["loss"])
        else:
            optimizer.minimize(out["loss"])
            decoder_lm.balance_experts(out, 1e-3)
    return {"main": main, "startup": startup, "out": out, "params": params}


def batch():
    tokens = rng(7).randint(0, MODEL["vocab_size"], (
        PARAMS["batch"], PARAMS["seq"] + 1)).astype(np.int32)
    return {"ids": np.ascontiguousarray(tokens[:, :-1]),
            "labels": np.ascontiguousarray(tokens[:, 1:]).reshape(-1, 1)}


def sharpened(scope, names):
    """Weights at which every mechanism shows: the projections into q, k, v
    and the latent's up-projections eight times their start (at std 0.02
    and a hidden size of 32 every score is near zero), the low-rank pairs'
    second halves thirty times (a decay and a gate that differ by channel),
    the router's sixteen times, every norm's scale away from 1, and A_log
    and dt_bias as they start (a decay of 1e-3 to 1.6 a position)."""
    for n in names:
        v = scope.find_var(n)
        if n.endswith(("_q_w", "_kv_b_w", "_kv_a_w", "_qkv_w", "_b_w")) \
                and not n.endswith(("_f_b_w", "_g_b_w")):
            scope.set_var(n, v * 8.0)
        elif n.endswith(("_f_b_w", "_g_b_w", "_f_a_w", "_g_a_w")):
            scope.set_var(n, v * 30.0)
        elif n.endswith("_router_w"):
            scope.set_var(n, v * 16.0)
        elif n.endswith("norm_w"):
            seed = sum(n.encode()) % 1000
            scope.set_var(n, jnp.asarray(
                1.0 + rng(seed).randn(*v.shape).astype("float32") * 0.3))


@pytest.fixture(scope="module")
def f32():
    b = built(MODEL)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(b["startup"], scope=scope)
    sharpened(scope, b["params"])
    weights = [np.array(scope.find_var(n)) for n in b["params"]]
    out = b["out"]
    e = len(out["expert_index"])
    rules = [op.outputs["Out"][0] for op in b["main"].global_block().ops
             if op.type == "gated_delta_rule"]
    fetch = [out[k].name for k in ("loss", "each")] \
        + [n + "@GRAD" for n in b["params"]] \
        + [v.name for v in out["expert_index"] + out["expert_load"]
           + out["expert_dropped"] + out["expert_routed"]] + rules
    got = exe.run(b["main"], feed=batch(), fetch_list=fetch, scope=scope)
    exe.close()
    n = len(b["params"])
    with jax.default_matmul_precision("highest"):
        w = [jnp.asarray(x) for x in weights]
        want = reference.forward(w, batch(), MODEL)
        grads = jax.grad(lambda w: reference.forward(w, batch(), MODEL)[
            "loss"])(w)
    rest = got[2 + n:]
    return {"b": b, "weights": weights,
            "loss": float(got[0].reshape(-1)[0]), "each": got[1].reshape(-1),
            "grads": dict(zip(b["params"], got[2:2 + n])),
            "index": np.stack(rest[:e]), "load": np.stack(rest[e:2 * e]),
            "dropped": np.stack(rest[2 * e:3 * e]),
            "routed": rest[3 * e:4 * e], "o": rest[4 * e:],
            "want": want, "want_grads": dict(zip(b["params"], grads))}


def test_program_equals_the_reference_in_loss_positions_and_routing(f32):
    want = f32["want"]
    assert f32["loss"] == pytest.approx(float(want["loss"]), rel=2e-6)
    close(f32["each"], want["positions"], 5e-6)
    np.testing.assert_array_equal(np.sort(f32["index"], -1), want["experts"])
    np.testing.assert_array_equal(f32["load"], want["load"])
    assert f32["dropped"].sum() == 0
    norms = [np.mean(np.linalg.norm(r, axis=-1)) for r in f32["routed"]]
    np.testing.assert_allclose(norms, want["routed"], rtol=1e-5)
    # a KDA layer each: the mean norm of a head's o before the gated norm
    sizes = [np.mean(np.linalg.norm(o.reshape(-1, 8), axis=-1))
             for o in f32["o"]]
    assert len(sizes) == 3
    np.testing.assert_allclose(sizes, want["o_norm"], rtol=2e-5)
    # positions, 3 sparse layers' routed entries, 3 KDA layers' sizes
    assert len(want["each"]) == T + 3 + 3
    np.testing.assert_allclose(want["each"][-3:],
                               reference.O_SCALE * want["o_norm"], rtol=1e-6)
    ops = f32["b"]["main"].global_block().ops
    kinds = [op.type for op in ops]
    assert kinds.count("gated_delta_rule") == 3
    assert kinds.count("gated_delta_rule_grad") == 3
    assert kinds.count("short_conv") == 3
    assert kinds.count("latent_qkv") == kinds.count("latent_qkv_grad") == 1
    assert kinds.count("fused_attention") == 1
    assert kinds.count("rotary_embedding") == 0
    assert kinds.count("moe_dispatch") == 3 and kinds.count("swiglu") == 7
    (latent,) = [op for op in ops if op.type == "latent_qkv"]
    assert latent.attr("rotate") is False and latent.attr("value_dim") == 8
    assert "head_dim" not in latent.attrs           # 12 + 4: under a tile
    for op in ops:
        if op.type == "gated_delta_rule":
            assert "QKV" in op.inputs and op.attr("key_heads") == 2
            g = f32["b"]["main"].global_block().var(op.inputs["G"][0])
            assert tuple(g.shape) == (2, 16, 2, 8)  # a decay a key channel
        if op.type == "rms_norm" and "Gate" in op.inputs:
            assert op.attr("gate_activation") == "sigmoid"
    routers = [op for op in ops if op.type == "moe_router"]
    assert all(op.attr("scoring") == "sigmoid" and "Bias" in op.inputs
               and op.attr("scale") == 2.446 and op.attr("norm_topk")
               for op in routers)
    out = f32["b"]["out"]
    assert len(out["expert_bias"]) == len(out["expert_load"]) == 3


LEAVES = ["tok_emb", "layer0_kda_norm_w", "layer0_kda_qkv_w",
          "layer0_kda_conv_w", "layer0_kda_dt_bias", "layer0_kda_A_log",
          "layer0_kda_f_a_w", "layer0_kda_f_b_w", "layer0_kda_b_w",
          "layer0_kda_g_a_w", "layer0_kda_g_b_w", "layer0_kda_gated_norm_w",
          "layer0_kda_o_w", "layer0_ffn_norm_w", "layer0_ffn_gate_w",
          "layer0_ffn_up_w", "layer0_ffn_down_w", "layer1_kda_qkv_w",
          "layer1_kda_dt_bias", "layer1_kda_A_log", "layer1_kda_f_b_w",
          "layer1_moe_router_w", "layer1_moe_gate_w", "layer1_moe_up_w",
          "layer1_moe_down_w", "layer1_moe_shared_gate_w",
          "layer1_moe_shared_up_w", "layer1_moe_shared_down_w",
          "layer2_attn_norm_w", "layer2_attn_q_w", "layer2_attn_kv_a_w",
          "layer2_attn_kv_a_norm_w", "layer2_attn_kv_b_w", "layer2_attn_o_w",
          "layer2_moe_router_w", "layer3_kda_conv_w", "layer3_kda_dt_bias",
          "layer3_kda_A_log", "layer3_kda_f_a_w", "layer3_kda_b_w",
          "final_norm_w", "lm_head_w"]


def test_the_leaves_tested_are_the_parameter_kinds_in_creation_order(f32):
    params = f32["b"]["params"]
    assert [p for p in params if p in LEAVES] == LEAVES
    assert params[0] == "tok_emb" and len(params) == 1 + 12 + 4 + 2 * (
        12 + 8) + 6 + 8 + 2
    shapes = {n: tuple(w.shape) for n, w in zip(params, f32["weights"])}
    assert shapes["layer0_kda_qkv_w"] == (32, 3 * 2 * 8)
    assert shapes["layer0_kda_conv_w"] == (48, 4)
    assert shapes["layer0_kda_A_log"] == (2,)             # a head
    assert shapes["layer0_kda_dt_bias"] == (16,)          # a key channel
    assert shapes["layer0_kda_f_a_w"] == (32, 8)          # the low-rank pair
    assert shapes["layer0_kda_f_b_w"] == (8, 16)
    assert shapes["layer0_kda_gated_norm_w"] == (8,)      # shared by the heads
    assert shapes["layer2_attn_q_w"] == (32, 3 * (12 + 4))  # no query latent
    assert shapes["layer2_attn_kv_a_w"] == (32, 20 + 4)   # c_kv | k_r
    assert shapes["layer2_attn_kv_b_w"] == (20, 3 * (12 + 8))
    assert shapes["layer2_attn_o_w"] == (3 * 8, 32)       # v's width
    assert shapes["layer1_moe_gate_w"] == (4, 32, 16)     # the held experts
    assert shapes["layer1_moe_router_w"] == (32, 8)       # all routed


@pytest.mark.parametrize("name", LEAVES)
def test_float32_gradient_of_every_parameter_kind(f32, name):
    got = np.asarray(f32["grads"][name], np.float32)
    want = np.asarray(f32["want_grads"][name], np.float32)
    assert got.shape == want.shape and np.abs(want).max() > 0
    # the chunk form against the recurrence, both float32: the op's own
    # tests hold its gradients to 1e-4, and the layers above it add theirs
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=3e-4 * np.abs(want).max())


@pytest.mark.parametrize("control,entry", [
    ("no_decay", "o_norm"), ("mean_decay", "o_norm"), ("beta_one", "o_norm"),
    ("no_l2_norm", "o_norm"), ("silu_gate", "positions"),
    ("no_out_gate", "positions"), ("rotated", "positions"),
    ("scale_nope_only", "positions"), ("no_routed_scale", "routed")])
def test_each_mechanism_shows_at_sharpened_weights(f32, control, entry):
    """Every control of ``tools/kimi_linear_probe.py controls`` moves what
    the cell's check compares by far more than float32 does; the
    channel-averaged decay -- the scalar rule under this model's name --
    among them."""
    w = [jnp.asarray(x) for x in f32["weights"]]
    with jax.default_matmul_precision("highest"):
        other = reference.forward(w, batch(), MODEL, control=control)
    want = f32["want"]
    moved = np.abs(np.asarray(other[entry]) - np.asarray(want[entry])).max() \
        / np.abs(np.asarray(want[entry])).max()
    assert moved > 3e-3, (control, moved)


def test_a_bfloat16_state_moves_the_reference_by_bfloat16(f32):
    with jax.default_matmul_precision("highest"):
        other = reference.forward([jnp.asarray(x) for x in f32["weights"]],
                                  batch(), MODEL, control="bf16_state")
    moved = np.abs(np.asarray(other["o_norm"])
                   - np.asarray(f32["want"]["o_norm"])).max()
    assert 1e-6 < moved < 1e-1


def test_one_adamw_step_and_the_bias_update(f32):
    """The first AdamW step from the sharpened weights is the reference's
    gradient through AdamW (the moments start at zero), ``A_log`` and
    ``dt_bias`` among the leaves, and ``balance_experts`` moves every sparse
    layer's selection bias."""
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
        big = np.abs(g) > max(1e-2 * np.abs(g).max(), 1e-5)
        want = w * (1 - lr * wd) - lr * g / (np.abs(g) + eps)
        got = np.asarray(scope.find_var(n), np.float64)
        np.testing.assert_allclose(got[big], want[big], rtol=0, atol=5e-5,
                                   err_msg=n)
    for bias, load in zip(b["out"]["expert_bias"], f32["load"]):
        want = 1e-3 * np.sign(load.mean() - load)
        np.testing.assert_allclose(np.asarray(scope.find_var(bias.name)),
                                   want, atol=1e-7)
    exe.close()


def test_the_32_shares_of_8_experts_add_up_to_the_uncut_layer():
    """The guide's share test at the deployment's own counts: 32 shares of 8
    of 256 experts give, with the shared expert counted once, what the uncut
    reference gives for the whole layer -- the program's layer on each share
    (one Program, the held range fed as weights: share i holds experts 8 i
    to 8 i + 7) against the reference's layer over all 256."""
    H, W, E, k, tokens, held = 16, 8, 256, 8, 24, 8
    x = rng(0).randn(tokens, H).astype("float32")
    router = rng(1).randn(H, E).astype("float32")
    gate, up = (rng(s).randn(E, H, W).astype("float32") * 0.3 for s in (2, 3))
    down = rng(4).randn(E, W, H).astype("float32") * 0.3
    shared = [rng(5).randn(H, W).astype("float32") * 0.3,
              rng(6).randn(H, W).astype("float32") * 0.3,
              rng(7).randn(W, H).astype("float32") * 0.3]
    model = dict(MODEL, hidden_size=H, moe_intermediate_size=W, num_experts=E,
                 num_experts_routed=E, first_expert_held=0,
                 num_experts_per_token=k)
    with jax.default_matmul_precision("highest"):
        whole, chosen, load = reference.expert_layer(
            jnp.asarray(x), router, gate, up, down, jnp.zeros((E,)), model)
        whole = whole + reference.swiglu(jnp.asarray(x), *shared)
    assert int(load.sum()) == tokens * k
    total, shared_part, busy = np.zeros((tokens, H), np.float32), None, 0
    for share in range(E // held):
        first = share * held
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            xv = fluid.data("x", [tokens, H], "float32",
                            append_batch_size=False)
            cfg = dict(model, num_experts=held, first_expert_held=first,
                       moe_row_budget=None)
            out, aux = decoder_lm.experts(xv, cfg, "moe")
        exe, scope = fluid.Executor(), fluid.Scope()
        exe.run(startup, scope=scope)
        at = slice(first, first + held)
        for name, value in (("moe_router_w", router), ("moe_gate_w", gate[at]),
                            ("moe_up_w", up[at]), ("moe_down_w", down[at]),
                            ("moe_shared_gate_w", shared[0]),
                            ("moe_shared_up_w", shared[1]),
                            ("moe_shared_down_w", shared[2])):
            scope.set_var(name, jnp.asarray(value))
        both, routed = exe.run(main, feed={"x": x}, scope=scope,
                               fetch_list=[out.name, aux["routed"].name])
        exe.close()
        total += routed
        busy += bool(np.abs(routed).max() > 0)
        if shared_part is None:
            shared_part = both - routed
        else:       # every share computes the shared expert alike
            close(both - routed, shared_part, 1e-6)
    close(total + shared_part, whole, 1e-5)
    assert busy > 16        # 24 x 8 assignments over 32 shares


def test_kimis_key_spellings_read_as_the_repos_own():
    """``num_experts_per_token``, ``moe_renormalize``, ``moe_router_
    activation_func``, ``num_shared_experts`` with the derived width,
    ``num_expert_group`` and ``use_grouped_topk`` build op for op what
    ``num_experts_per_tok``, ``norm_topk_prob``, ``router_scoring`` +
    ``use_expert_bias``, ``shared_expert_intermediate_size`` build."""
    own = {k: v for k, v in MODEL.items()
           if k not in ("num_experts_per_token", "moe_renormalize",
                        "moe_router_activation_func", "num_shared_experts",
                        "num_expert_group", "topk_group", "use_grouped_topk",
                        "moe_layer_freq", "first_k_dense_replace")}
    own.update(num_experts_per_tok=2, norm_topk_prob=True, num_dense_layers=1,
               router_scoring="sigmoid", use_expert_bias=True,
               shared_expert_intermediate_size=16)

    def ops(model):
        return [(op.type, sorted(op.inputs), sorted(op.outputs),
                 sorted(op.attrs.items()))
                for op in built(model)["main"].global_block().ops]
    assert ops(MODEL) == ops(own)
    assert decoder_lm._layer_types(MODEL) == ["kda", "kda", "full_attention",
                                              "kda"]
    assert decoder_lm._top_k(MODEL) == 2
    assert decoder_lm._shared_width(MODEL) == 16
    assert decoder_lm._scoring(MODEL) == "sigmoid"
    assert decoder_lm._bias_chosen(MODEL)
    assert not decoder_lm._bias_chosen({"num_experts": 4})


@pytest.mark.parametrize("change,error,match", [
    ({"rope_scaling": {"type": "yarn", "factor": 40, "mscale": 1.0}},
     NotImplementedError, "rope_scaling inside latent attention"),
    ({"num_expert_group": 8, "topk_group": 4}, NotImplementedError,
     "group-limited routing"),
    ({"num_shared_experts": 2}, NotImplementedError, "shared experts"),
    ({"num_nextn_predict_layers": 2}, NotImplementedError,
     "more than one multi-token-prediction module"),
    ({"moe_layer_freq": 2}, NotImplementedError, "moe_layer_freq=2"),
    ({"moe_router_activation_func": "tanh"}, NotImplementedError,
     "moe_router_activation_func"),
    ({"hidden_act": "gelu"}, NotImplementedError, "hidden_act"),
    ({"linear_attn_config": {"kda_layers": [1, 2], "full_attn_layers": [3],
                             "num_heads": 2, "head_dim": 8,
                             "short_conv_kernel_size": 4}},
     ValueError, "must name every layer"),
    ({"linear_attn_config": {"kda_layers": [1, 2, 4],
                             "full_attn_layers": [3], "num_heads": 2}},
     ValueError, "kda layers need"),
    ({"norm_form": "layer"}, NotImplementedError, "norm_form"),
    ({"layer_types": ["kda", "kda", "sliding_attention", "kda"],
      "sliding_window": 8}, NotImplementedError,
     "full_attention layers only")])
def test_what_the_builder_still_refuses_raises_by_name(change, error, match):
    with pytest.raises(error, match=match):
        decoder_lm._check(dict(MODEL, **change))


@pytest.mark.parametrize("change", [
    {"q_lora_rank": None}, {"q_lora_rank": 24}, {"v_head_dim": 8},
    {"v_head_dim": 16}, {"mla_use_nope": False},
    {"use_grouped_topk": False, "num_expert_group": 8, "topk_group": 4}])
def test_what_the_builder_no_longer_refuses_builds(change):
    """``q_lora_rank: null``, a ``v_head_dim`` other than the q / k head's
    width and latent attention among linear layers went from ``_check_
    latent``; each builds, with or without its counterpart."""
    model = dict(MODEL, **change)
    decoder_lm._check(model)
    kinds = [op.type for op in built(model)["main"].global_block().ops]
    assert kinds.count("latent_qkv") == 1
    assert kinds.count("gated_delta_rule") == 3


def test_a_head_of_a_tile_and_a_half_is_written_two_tiles_wide():
    """The published 128 + 64: q and k leave ``latent_qkv`` 256 wide (zero
    columns behind the two parts), v 128; a head of one tile or less, or of
    whole tiles, is written as it is."""
    wide = dict(MODEL, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128, num_attention_heads=1, num_key_value_heads=1)
    ops = built(wide)["main"].global_block().ops
    (latent,) = [op for op in ops if op.type == "latent_qkv"]
    assert (latent.attr("head_dim"), latent.attr("value_dim")) == (256, 128)
    (attend,) = [op for op in ops if op.type == "fused_attention"]
    assert attend.attr("scale") == pytest.approx(192 ** -0.5)
    whole = dict(wide, qk_nope_head_dim=192, v_head_dim=256)
    (latent,) = [op for op in built(whole)["main"].global_block().ops
                 if op.type == "latent_qkv"]
    assert "head_dim" not in latent.attrs and "value_dim" not in latent.attrs


def test_every_key_of_the_published_config_is_read_or_named():
    """The catalog row's keys: each is read by ``decoder_lm`` (its name in
    the source) or is one of those that say nothing a builder acts on here
    (``model_type``, the position limit), named in the configuration
    file's ``assumed.unused_keys``."""
    source = inspect.getsource(decoder_lm)
    data = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "kimi_linear_48b_a3b.json")))
    published = data["published"]
    unread = [k for k in published if f'"{k}"' not in source]
    assert sorted(unread) == ["model_max_length", "model_type"]
    for key in unread + ["head_dim", "num_key_value_heads", "rope_theta"]:
        assert key in data["assumed"]["unused_keys"], key
    for key in published["linear_attn_config"]:
        assert f'"{key}"' in source, key


# what the six decoder cells' builders appended at the parent commit
# (f0cd89e), at the data files' rehearsal sizes: the count of ops and a hash
# over every op's type, inputs, outputs and attrs
PARENTS = {
    "olmoe_1b_7b.pretrain_s4096": (210, "8a4dd892e94c527a"),
    "lfm2_8b_a1b.pretrain_s4096": (127, "4fb955d9d6ea634a"),
    "granite_4_0_h_micro.pretrain_s4096": (157, "b8426d921d0854df"),
    "laguna_s_2_1.pretrain_s4096": (266, "a1e43852dcdd8364"),
    "qwen3_next_80b_a3b.pretrain_s4096": (241, "67f8af0631fac492"),
    "glm_4_7_flash.pretrain_s4096": (307, "ae3548962a824f1c")}


@pytest.mark.parametrize("name", sorted(PARENTS))
def test_the_existing_decoders_build_the_parents_program(name):
    """Without the new keys every configuration lowers to the Program it did
    before this mixer: same ops in the same order, same inputs, outputs and
    attrs (no ``rotate``, ``value_dim``, ``gate_activation`` appears where
    the default holds)."""
    from benchmark import run
    cell = run.load_cell(name, rehearsal=True)
    builder = importlib.import_module(f"benchmark.programs.{cell['builder']}")
    made = builder.build(cell["model"], cell["params"])
    ops = [(op.type, sorted(op.inputs.items()), sorted(op.outputs.items()),
            sorted((k, repr(v)) for k, v in op.attrs.items()))
           for op in made["main"].global_block().ops]
    assert (len(ops), hashlib.sha256(repr(ops).encode()).hexdigest()[:16]) \
        == PARENTS[name]
