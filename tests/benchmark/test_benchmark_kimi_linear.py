"""The Kimi Linear cell's pieces at small sizes on the CPU: the configuration
against its own published copy (and the catalog's row where the catalog has
one), the reference check (jobs/common.py:reference_check) passing for the
program as it is and saying no to float8 weights, what the KDA layers' and
the routed entries see, the closed forms of benchmark/needs_kimi_linear.py
against numbers worked by hand, and the cell through run.py with its
metrics. It asserts no count of cells or configurations (PERF.md 7 (z))."""
import json
import math
import os
import re

import numpy as np
import pytest

from benchmark import needs_kimi_linear as needs
from benchmark import run
from benchmark.jobs import common
from benchmark.references import kimi_linear_pretrain as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kimi_linear_48b_a3b.pretrain_s4096"
NAME = "kimi_linear_48b_a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state|proj|_dim$"
                   r"|_rank$|head_|expansion|experts_per)")
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size"]
SEED = 17


def config():
    return json.load(open(os.path.join(
        ROOT, "benchmark", "configs", NAME + ".json")))


def test_reduced_is_exactly_what_differs_from_the_published_copy():
    data = config()
    published = data["published"]
    differ = [k for k, v in published.items() if data.get(k, "?") != v]
    assert sorted(differ) == sorted(data["reduced"])
    assert data["reduced"] == REDUCED
    assert not [k for k in data["reduced"] if WIDTH.search(k)]
    for key, want in (
            ("hidden_size", 2304), ("num_attention_heads", 32),
            ("q_lora_rank", None), ("kv_lora_rank", 512),
            ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
            ("v_head_dim", 128), ("intermediate_size", 9216),
            ("moe_intermediate_size", 1024), ("num_experts_per_token", 8),
            ("num_shared_experts", 1), ("routed_scaling_factor", 2.446),
            ("first_k_dense_replace", 1), ("num_nextn_predict_layers", 0),
            ("moe_router_activation_func", "sigmoid"),
            ("moe_renormalize", True), ("num_expert_group", 1),
            ("topk_group", 1), ("use_grouped_topk", True),
            ("moe_layer_freq", 1), ("mla_use_nope", True),
            ("rms_norm_eps", 1e-5), ("rope_scaling", None),
            ("head_dim", 72), ("tie_word_embeddings", False)):
        assert data[key] == published[key] == want, key
    # inside the reduced group only the two layer lists change: no width
    lin, was = data["linear_attn_config"], published["linear_attn_config"]
    assert {k for k in was if lin[k] != was[k]} == {"kda_layers",
                                                    "full_attn_layers"}
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    # the guide's floors: the leading dense layer and four layers after it,
    # which hold one whole period at the published 3 : 1; 8 routed experts
    # held; an eighth of the vocabulary
    assert data["num_hidden_layers"] == 5 and published[
        "num_hidden_layers"] == 27
    assert (lin["kda_layers"], lin["full_attn_layers"]) == ([1, 2, 3, 5], [4])
    assert was["kda_layers"][:4] == [1, 2, 3, 5] and was[
        "full_attn_layers"][0] == 4
    assert len(was["kda_layers"]) == 20 and len(was["full_attn_layers"]) == 7
    assert (data["num_experts"], data["num_experts_routed"],
            data["first_expert_held"]) == (8, 256, 0)
    assert published["num_experts"] == 256
    assert data["vocab_size"] * 8 == published["vocab_size"] == 163840
    cell = run.load_cell(CELL, rehearsal=False)
    assert cell["params"] == {"batch": 2, "seq": 4096, "ring": 8,
                              "loss_read_every": 10}
    # 4 x what an even router sends to the held experts a layer
    assert data["moe_row_budget"] == 8192 == 4 * 8192 * 8 * 8 // 256
    assert data["flops"] is None
    for key in ("kda", "latent_attention", "column_order", "start", "router", "recipe", "traffic", "dtype",
                "delta_chunk_size", "moe_row_budget", "unused_keys"):
        assert key in data["assumed"], key
    assert (data["bias_update_rate"], data["learning_rate"]) == (1e-3, 1e-5)
    assert "32 chips" in data["deployment"] and "8 slices" in data[
        "deployment"]
    assert "pipeline" in data["reduced_detail"]
    assert "arXiv:2510.26692" in data["source_detail"]
    # every distinct width distinct in the rehearsal too
    small = dict(data, **data["rehearsal"])
    assert small["qk_nope_head_dim"] != small["qk_rope_head_dim"]
    assert small["qk_nope_head_dim"] + small["qk_rope_head_dim"] != small[
        "v_head_dim"]
    assert small["linear_attn_config"]["head_dim"] != small["v_head_dim"]


def test_configuration_holds_every_number_of_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next((r for r in rows
                if r["name"] == "Kimi-Linear-48B-A3B-Instruct"), None)
    if row is None:
        pytest.skip("the catalog on disk has no Kimi-Linear-48B-A3B row")
    data = config()
    assert data["source"] == row["source_url"]
    assert data["published"] == row["config"]
    for key, value in row["config"].items():
        if key not in data["reduced"]:
            assert data[key] == value, key


def test_benchmark_json_names_the_configuration_and_its_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REDUCED
    assert entry["source"] == config()["source"]
    cells = [w for w in bench["workloads"] if w["config"] == NAME]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1 and "32x a share" in cells[0]["why"]
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    for name in ("gated_delta_roofline.kimi_linear",
                 "gated_delta.channel_decay_ops", "kda_conv.time_share",
                 "kda_conv_roofline",
                 "flash_attention_causal_roofline.kimi_linear",
                 "moe_held_expert_matmul_roofline.kimi_linear",
                 "step.model_flops_share.kimi_linear",
                 "gated_delta.time_share", "gated_delta.pallas_ops",
                 "gated_delta.packed_ops", "norm.gated_kernel_ops",
                 "latent_qkv.time_share", "attention.time_share",
                 "attention.saved_stats_ops", "moe.time_share",
                 "moe_dispatch.time_share", "moe_bias_update.time_share",
                 "moe.row_budget_rows", "moe_rows.kernel_ops",
                 "norm_rope.time_share", "loss.time_share",
                 "optimizer_adamw.time_share", "memory.peak_forward_gb",
                 "compile.telemetry_s"):
        assert name in reported, name
    # no rotary op (mla_use_nope), the scalar rule's and the other cells'
    # closed forms are not this cell's, and swiglu is moe.time_share's (the
    # decay gate's softplus and exp leave no device event of their own: XLA
    # fuses them into their consumers, PERF.md section 6, so no share is
    # kept for them)
    for name in ("rope.time_share", "rope.one_pass_ops",
                 "gated_delta_roofline", "delta_conv_roofline",
                 "swiglu_softplus.time_share",
                 "flash_attention_causal_roofline.glm_4_7_flash"):
        assert name not in reported, name
    ends = {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert ends == {"tokens_per_s", "peak_hbm_gb", "setup_s"}
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(new) == 7 and all(m["moves"] == "tokens_per_s" for m in new)
    for m in new:
        spec = json.load(open(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".json")))
        assert "kimi_linear" in spec.get("needs", "needs_kimi_linear") or (
            spec["reducer"] in ("scope_time_share", "registry_count"))


def session():
    cell = run.load_cell(CELL, rehearsal=True)
    said = []
    s = common.Session(cell, SEED, said.append)
    batch = s.builder.batch(s.model, s.params, np.random.RandomState(SEED))
    return s, batch, said


def test_program_agrees_with_the_plain_reference_and_float8_shows():
    """The check that decides ``correct`` passes for the program as it is;
    with the program's weights rounded to float8 (e4m3) while the reference
    keeps the originals, the block means' errors are over twice what they
    were (on the chip, at the published widths, it fails the limit:
    ``READINGS``)."""
    import jax.numpy as jnp
    from tools.kimi_linear_probe import parts
    s, batch, said = session()
    try:
        assert sorted(batch) == ["ids", "labels"]
        assert common.reference_check(s, batch) is True
        assert "ok" in said[-1] and "FAILED" not in said[-1]
        worst = float(said[-1].split("positions ")[1].split(" ")[0])
        assert worst < reference.tolerance(s.model)["each"]
        as_it_is = parts(s, batch)
        kinds = [op.type for op in s.built["main"].global_block().ops]
        # KDA + dense, latent attention + experts, KDA + experts
        assert kinds.count("gated_delta_rule") == kinds.count(
            "gated_delta_rule_grad") == 2
        assert kinds.count("latent_qkv") == kinds.count("fused_attention") == 1
        assert kinds.count("moe_dispatch") == kinds.count(
            "moe_bias_update") == 2
        # the block means, two sparse layers' routed entries, two KDA
        # layers' o sizes
        assert len(s.built["check"]["each"]) == 1 + 2 + 2
        assert len(s.built["expert_dropped"]) == 2
        assert s.built["params"][-2:] == ["layer1_moe_router_bias",
                                          "layer2_moe_router_bias"]
        originals = [s.scope.find_var(n) for n in s.built["params"]]
        for n in s.built["params"]:
            v = s.scope.find_var(n)
            s.scope.set_var(n, jnp.asarray(v).astype(jnp.float8_e4m3fn)
                            .astype(v.dtype))
        real_loss = reference.loss
        reference.loss = lambda w, *a: real_loss(originals, *a)
        try:
            float8 = parts(s, batch)
            assert float8["blocks"] > 2 * as_it_is["blocks"]
        finally:
            reference.loss = real_loss
    finally:
        s.close()


@pytest.mark.parametrize("mechanism, part, low, high", [
    ("routed_scale", "held_norm", 0.35, 0.45),  # 1 / 2.446 of every norm
    ("row_budget", "held_norm", 0.05, 0.6),     # 12 rows kept of about 50
    ("decay", "o_size", 1.2, 3.0),              # a state that never fades
    ("channel_decay", "o_size", 0.5, 0.97),     # the scalar rule: other sizes
    ("beta", "o_size", 1.3, 4.0),               # every write at full step
    ("l2_norm", "o_size", 0.0, 0.5)])           # raw q and k
def test_the_checks_entries_see_the_rule_and_the_routed_scale(
        mechanism, part, low, high):
    """Beside the block means the check compares, a sparse layer each, the
    routed experts' norm over the sum of sqrt(held experts a token chose),
    and a KDA layer each the scaled mean norm of a head's ``o``: the
    program as it is reads the reference's; one without the scale 2.446,
    with an eighth of the row budget, with no decay, with the decay
    averaged over a head's channels, with beta = 1 or without the l2 norms
    reads a part or a multiple."""
    from tools.kimi_linear_probe import patched, without
    s, batch, _ = session()
    try:
        ref = np.asarray(reference.loss(
            [s.scope.find_var(n) for n in s.built["params"]], batch, s.model,
            s.params)["each"])
        blocks = (s.params["batch"] * s.params["seq"]
                  // reference.check_block(s.params["seq"]))
        at, cut = {"held_norm": (slice(1, 3), slice(blocks, blocks + 2)),
                   "o_size": (slice(3, 5), slice(blocks + 2, blocks + 4))}[
                       part]
        want = ref[cut]

        def entries(built):
            return np.concatenate([np.asarray(x).reshape(-1) for x in
                                   s.exe.run(built["test"], feed=batch,
                                             scope=s.scope, fetch_list=built[
                                                 "check"]["each"][at])])
        as_it_is = entries(s.built) / want
        np.testing.assert_allclose(as_it_is, 1.0, atol=5e-2 if part ==
                                   "held_norm" else 2e-3)
        with patched(mechanism):
            other = s.builder.build(without(s.model, mechanism), s.params)
            share = entries(other) / want
        assert (low < share).all() and (share < high).all(), share
        assert (abs(share - 1) > 3 * abs(as_it_is - 1)).all(), (share,
                                                                 as_it_is)
    finally:
        s.close()


def test_tolerance_sits_between_the_chip_readings():
    cell = run.load_cell(CELL, rehearsal=False)
    published = reference.tolerance(cell["model"])
    assert set(published) == {"loss", "each"}
    as_it_is, float8 = (reference.READINGS["as_it_is_max"],
                        reference.READINGS["float8_min"])
    assert as_it_is * 1.4 < published["each"] < float8 / 1.4
    assert published["loss"] == float("inf")
    assert 0.04 * reference.O_SCALE < 9.9       # an o size under a block's CE


def test_closed_forms_match_numbers_worked_by_hand():
    cell = run.load_cell(CELL, rehearsal=False)
    model, params = cell["model"], cell["params"]
    tokens, h, s = 8192, 2304, 4096
    assert params["batch"] * params["seq"] == tokens
    assert (needs.kda_layers(model), needs.latent_layers(model),
            needs.sparse_layers(model)) == (4, 1, 4)
    assert needs.held_assignments(model, params) == 2048
    # a chunk of c and head: M and P, the inverse's 2 (log2 c - 1) [c, c]
    # products (12 at c = 128), three products with the state, T R and P V'
    c = model["delta_chunk_size"]
    assert c == 128
    chunk = (2 * 2 * c * c * 128 + 12 * 2 * c ** 3
             + 3 * 2 * c * 128 * 128 + 2 * 2 * c * c * 128)
    assert needs.kda_rule_forward_flops(model) == 32 * chunk / c
    rule = needs.gated_delta(model, params)
    assert rule["flops"] == 4 * tokens * 3 * 32 * chunk / c
    # q, k, v, o and their gradients in bfloat16, g and dg a key channel in
    # float32, beta and its gradient a head
    assert rule["bytes"] == 4 * tokens * (8 * 4096 * 2 + 2 * 4096 * 4
                                          + 2 * 32 * 4)
    conv = needs.kda_conv(model, params)
    assert conv["bytes"] == 4 * 5 * tokens * 12288 * 2
    assert conv["flops"] == 4 * tokens * 12288 * 3 * 2 * 4
    flash = needs.flash_attention_causal(model, params)
    assert flash["flops"] == 3 * 2 * 32 * s * s * (192 + 128)
    assert flash["bytes"] == 6 * 2 * 32 * s * (192 + 128) * 2
    experts = needs.moe_held_expert_matmul(model, params)
    assert experts["flops"] == 4 * 3 * 3 * 2 * 2048 * h * 1024
    assert experts["bytes"] == 4 * 9 * (2048 * h + 8 * h * 1024
                                        + 2048 * 1024) * 2
    step = needs.train_step(model, params)
    forward = {                                         # FLOPs a step
        "kda_projections": 4 * tokens * 2 * (
            h * 12288 + 2 * (h * 128 + 128 * 4096) + h * 32 + 4096 * h),
        "kda_rule": 4 * tokens * 32 * chunk / c,
        "latent_projections": tokens * 2 * (
            h * 6144 + h * 576 + 512 * 8192 + 4096 * h),
        "scores": 2 * 32 * 2 * (s * (s + 1) // 2) * (192 + 128),
        "dense": tokens * 6 * h * 9216,
        "routers": 4 * tokens * 2 * h * 256,
        "shared": 4 * tokens * 6 * h * 1024,
        "experts": 4 * 2048 * 6 * h * 1024,
        "head": tokens * 2 * h * 20480}
    assert step["flops"] == 3 * sum(forward.values())
    assert 2.2e9 < step["per_token"] < 2.5e9            # ISSUE 51: about 2.3
    # four of five mixers are KDA: their projections and rule are nearly
    # half of the step's FLOPs, the rule alone about a tenth of that
    kda = forward["kda_projections"] + forward["kda_rule"]
    assert 0.4 < kda / sum(forward.values()) < 0.5
    assert 0.08 < forward["kda_rule"] / kda < 0.25
    assert math.isclose(needs.held_assignments(model, params) * 4,
                        model["moe_row_budget"])


def _rehearse(cell, trace):
    from test_benchmark_run import run_py
    for _ in range(3):
        r = run_py(["--workload", cell, "--seed", str(2 ** 31 + 17),
                    "--seconds", "1", "--trace", trace, "--cpu-rehearsal"])
        # the span reader refuses a capture whose host clocks jitter by over
        # 20 us (reducers/span_idle_overlap.py): this sandbox's cores do at
        # times, with every cell; that is not what this test is about
        if "the two clocks do not keep step" not in r.stderr:
            break
    return r


def test_kimi_linear_cell_rehearses_untraced():
    from test_benchmark_run import result_of
    result, _ = result_of(_rehearse(CELL, "0"))
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    assert got["tokens_per_s"]["value"] > 0 and got["setup_s"]["value"] > 0


def test_kimi_linear_cell_rehearses_with_its_metrics():
    from test_benchmark_run import result_of
    r = _rehearse(CELL, "1")
    if "the two clocks do not keep step" in r.stderr:
        pytest.skip("this machine's host clocks jitter past the span "
                    "reader's 20 us three times running (PERF.md 7 (j))")
    result, lines = result_of(r)
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    for name in ("gated_delta.time_share", "kda_conv.time_share",
                 "latent_qkv.time_share",
                 "attention.time_share", "moe.time_share",
                 "moe_dispatch.time_share", "moe_bias_update.time_share",
                 "norm_rope.time_share", "loss.time_share",
                 "optimizer_adamw.time_share", "matmul.time_share",
                 "elementwise.time_share", "embedding.time_share",
                 "compile.trace_lower_s", "memory.step_state_gb"):
        assert got[name]["value"] > 0, name
    # two expert layers at a budget of 96 rows each
    assert got["moe.row_budget_rows"]["value"] == 192
    # no chip: no kernel is lowered (the counters of impl=pallas read
    # nothing), and no peak: the roofline shares are left out, not raised
    for name in ("gated_delta_roofline.kimi_linear", "kda_conv_roofline",
                 "flash_attention_causal_roofline.kimi_linear",
                 "moe_held_expert_matmul_roofline.kimi_linear",
                 "step.model_flops_share.kimi_linear", "mfu",
                 "rope.time_share", "gated_delta_roofline"):
        assert name not in got
    shares = next(ln for ln in lines if "time_share metrics" in ln)
    together = float(shares.rsplit("together ", 1)[1].split("%")[0])
    assert together >= 99.99
    assert any("gated_delta_rule_grad" in ln for ln in lines)
