"""The GLM-4.7-Flash cell's pieces at small sizes on the CPU: the
configuration against its own published copy (and the catalog's row where
the catalog has one), the reference check (jobs/common.py:reference_check)
passing for the program as it is and saying no to float8 weights, what the
module's and the routed entries see, the closed forms of
benchmark/needs_glm_4_7_flash.py against numbers worked by hand, and the
cell through run.py with its metrics."""
import json
import os
import re

import numpy as np
import pytest

from benchmark import needs_glm_4_7_flash as needs
from benchmark import run
from benchmark.jobs import common
from benchmark.references import glm_4_7_flash_pretrain as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "glm_4_7_flash.pretrain_s4096"
NAME = "glm_4_7_flash"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state|proj|_dim$"
                   r"|_rank$|head_|expansion|experts_per)")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
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
            ("hidden_size", 2048), ("num_attention_heads", 20),
            ("q_lora_rank", 768), ("kv_lora_rank", 512),
            ("qk_nope_head_dim", 192), ("qk_rope_head_dim", 64),
            ("v_head_dim", 256), ("intermediate_size", 10240),
            ("moe_intermediate_size", 1536), ("num_experts_per_tok", 4),
            ("n_shared_experts", 1), ("routed_scaling_factor", 1.8),
            ("first_k_dense_replace", 1), ("num_nextn_predict_layers", 1),
            ("topk_method", "noaux_tc"), ("n_group", 1), ("topk_group", 1),
            ("rope_theta", 1000000), ("rms_norm_eps", 1e-5),
            ("norm_topk_prob", True), ("rope_scaling", None)):
        assert data[key] == published[key] == want, key
    assert data["tie_word_embeddings"] is False
    # the model-configs guide's floors: the leading dense layer and four
    # layers after it, at least 8 routed experts held, an eighth of the
    # vocabulary; the module whole
    assert data["num_hidden_layers"] == 5 and published[
        "num_hidden_layers"] == 47
    assert (data["n_routed_experts"], data["num_experts_routed"],
            data["first_expert_held"]) == (8, 64, 0)
    assert published["n_routed_experts"] == 64
    assert data["vocab_size"] * 8 == published["vocab_size"] == 154880
    # 4 x what an even router sends to the held experts a layer
    cell = run.load_cell(CELL, rehearsal=False)
    assert cell["params"] == {"batch": 1, "seq": 4096, "ring": 8,
                              "loss_read_every": 10}
    assert data["moe_row_budget"] == 8192 == 4 * 4096 * 4 * 8 // 64
    assert data["flops"] is None
    for key in ("rotary", "column_order", "multi_token_prediction", "router",
                "recipe", "traffic", "dtype", "moe_row_budget"):
        assert key in data["assumed"], key
    assert (data["mtp_loss_weight"], data["bias_update_rate"],
            data["learning_rate"]) == (0.3, 1e-3, 1e-5)
    assert "8 chips" in data["deployment"]
    assert "8 slices" in data["deployment"]
    assert "pipeline" in data["reduced_detail"]
    assert "arXiv:2405.04434" in data["source_detail"]
    assert "arXiv:2412.19437" in data["source_detail"]
    # every distinct width distinct in the rehearsal too
    small = dict(data, **data["rehearsal"])
    assert small["qk_nope_head_dim"] != small["qk_rope_head_dim"]
    assert small["q_lora_rank"] != small["kv_lora_rank"]
    assert small["qk_nope_head_dim"] + small["qk_rope_head_dim"] == small[
        "v_head_dim"]


def test_configuration_holds_every_number_of_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next((r for r in rows if r["name"] == "GLM-4.7-Flash"), None)
    if row is None:
        pytest.skip("the catalog on disk has no GLM-4.7-Flash row")
    data = config()
    assert data["source"] == row["source_url"]
    assert data["published"] == row["config"]
    # every number of the row under the same key, but for the reduced ones
    for key, value in row["config"].items():
        if key not in data["reduced"]:
            assert data[key] == value, key


def test_benchmark_json_names_the_configuration_and_one_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REDUCED
    cells = [w for w in bench["workloads"] if w["config"] == NAME]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1 and "8x their share" in cells[0]["why"]
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    for name in ("latent_qkv.time_share",
                 "flash_attention_causal_roofline.glm_4_7_flash",
                 "moe_held_expert_matmul_roofline.glm_4_7_flash",
                 "step.model_flops_share.glm_4_7_flash",
                 "attention.time_share", "attention.saved_stats_ops",
                 "moe.time_share", "moe_dispatch.time_share",
                 "moe_bias_update.time_share", "moe.row_budget_rows",
                 "norm_rope.time_share", "loss.time_share",
                 "optimizer_adamw.time_share", "memory.peak_forward_gb",
                 "compile.telemetry_s"):
        assert name in reported, name
    # the rotation is inside latent_qkv (no rotary_embedding op, no
    # counter of its lowering), and the slice's logits are under the
    # written form's gigabyte
    for name in ("rope.time_share", "rope.one_pass_ops",
                 "loss.written_grad_ops", "gated_delta.time_share",
                 "flash_attention_causal_roofline"):
        assert name not in reported, name
    ends = {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert ends == {"tokens_per_s", "peak_hbm_gb", "setup_s"}


def test_the_qwen3_next_cell_keeps_what_its_own_test_reads_beside_the_count():
    """``test_benchmark_qwen3_next.py::test_benchmark_json_names_the_
    configuration_and_one_cell`` asserts eleven cells, between what it reads
    of its own configuration and what it reads of its cell's metrics; this
    cell is the twelfth and a PR that adds a cell may not edit that file, so
    that test stops at its count until a ``benchmark`` PR takes the count
    out (PERF.md section 7). Held here meanwhile, on that cell's own
    entries alone: everything else it asserts."""
    cell, name = "qwen3_next_80b_a3b.pretrain_s4096", "qwen3_next_80b_a3b"
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == name)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "num_experts", "vocab_size"]
    cells = [w for w in bench["workloads"] if w["config"] == name]
    assert [w["name"] for w in cells] == [cell]
    assert cells[0]["chips"] == 1 and "16x their share" in cells[0]["why"]
    reported = {m["name"] for m in bench["per_layer"]
                if cell in m.get("workloads", [cell])}
    for metric in ("gated_delta.time_share", "gated_delta_roofline",
                   "gated_delta.pallas_ops", "delta_conv.time_share",
                   "delta_conv_roofline",
                   "flash_attention_gqa_causal_roofline.qwen3_next",
                   "moe_held_expert_matmul_roofline.qwen3_next",
                   "step.model_flops_share.qwen3_next",
                   "attention_gate.time_share", "moe.row_budget_rows",
                   "attention.saved_stats_ops", "moe_dispatch.time_share",
                   "swiglu_softplus.time_share", "memory.peak_forward_gb",
                   "compile.telemetry_s"):
        assert metric in reported, metric
    for metric in ("ssd_scan.time_share", "mamba_conv.time_share",
                   "flash_attention_gqa_causal_roofline.laguna",
                   "latent_qkv.time_share",
                   "flash_attention_causal_roofline.glm_4_7_flash"):
        assert metric not in reported, metric
    ends = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert ends == {"tokens_per_s", "peak_hbm_gb", "setup_s"}


def session():
    cell = run.load_cell(CELL, rehearsal=True)
    said = []
    s = common.Session(cell, SEED, said.append)
    batch = s.builder.batch(s.model, s.params, np.random.RandomState(SEED))
    return s, batch, said


def _worst_block(said) -> float:
    """The error of the worst entry from ``reference_check``'s line."""
    return float(said[-1].split("positions ")[1].split(" ")[0])


def test_program_agrees_with_the_plain_reference_and_float8_shows():
    """The check that decides ``correct`` passes for the program as it is;
    with the program's weights rounded to float8 (e4m3) while the reference
    keeps the originals, the cross-entropy entries' errors are over twice
    what they were (on the chip, at the published widths, it fails the
    limit: ``READINGS``)."""
    import jax.numpy as jnp
    from tools.glm_probe import parts
    s, batch, said = session()
    try:
        assert sorted(batch) == ["ids", "labels", "labels_next"]
        np.testing.assert_array_equal(
            batch["labels"].reshape(2, -1)[:, 1:],
            batch["labels_next"].reshape(2, -1)[:, :-1])
        assert common.reference_check(s, batch) is True
        assert "ok" in said[-1] and "FAILED" not in said[-1]
        assert _worst_block(said) < reference.tolerance(s.model)["each"]
        as_it_is = parts(s, batch)
        kinds = [op.type for op in s.built["main"].global_block().ops]
        # a dense block, an expert block, the module
        assert kinds.count("latent_qkv") == kinds.count(
            "latent_qkv_grad") == 3
        assert kinds.count("fused_attention") == 3
        assert kinds.count("moe_dispatch") == 2
        assert kinds.count("moe_bias_update") == 2
        assert kinds.count("softmax_with_cross_entropy") == 2
        # the trunk's blocks, the module's mean and blocks, two sparse
        # layers' routed norms
        assert len(s.built["check"]["each"]) == 3 + 2
        assert len(s.built["expert_dropped"]) == 2
        assert s.built["params"][-2:] == ["layer1_moe_router_bias",
                                          "mtp_moe_router_bias"]
        originals = [s.scope.find_var(n) for n in s.built["params"]]
        for n in s.built["params"]:
            v = s.scope.find_var(n)
            s.scope.set_var(n, jnp.asarray(v).astype(jnp.float8_e4m3fn)
                            .astype(v.dtype))
        real_loss = reference.loss
        reference.loss = lambda w, *a: real_loss(originals, *a)
        try:
            float8 = parts(s, batch)
            for part in ("trunk_blocks", "mtp_blocks"):
                assert float8[part] > 2 * as_it_is[part], part
        finally:
            reference.loss = real_loss
    finally:
        s.close()


@pytest.mark.parametrize("mechanism, at, low, high", [
    ("routed_scale", slice(3, 5), 0.5, 0.6),    # 1 / 1.8 of every norm
    ("row_budget", slice(3, 5), 0.05, 0.6),     # 12 rows kept of about 50
    ("e_norm", slice(1, 2), 1.0005, 2.0),       # the module's loss rises
    # another label: at seeded weights no easier, but another number
    ("second_label", slice(1, 2), 0.9, 1.1)])
def test_the_checks_entries_see_the_routed_scale_and_the_module(
        mechanism, at, low, high):
    """Beside the trunk's block means the check compares the module's mean
    cross-entropy and, a sparse layer each, the norm of the routed experts'
    output summed over the tokens and divided by the sum of sqrt(held
    experts a token chose): the program as it is reads the reference's (the norms
    within the few flips of 64 tokens in bfloat16); one without the scale
    1.8, with an eighth of the row budget, without the module's norm of the
    embedding, or with the module trained on the next token reads a part, a
    multiple or another number."""
    from tools.glm_probe import patched, without
    s, batch, _ = session()
    try:
        ref = reference.loss(
            [s.scope.find_var(n) for n in s.built["params"]], batch, s.model,
            s.params)
        blocks = (s.params["batch"] * s.params["seq"]
                  // reference.check_block(s.params["seq"]))
        # each: trunk blocks, the module's mean, its blocks, routed norms
        cut = {1: slice(blocks, blocks + 1),
               3: slice(2 * blocks + 1, 2 * blocks + 3)}[at.start]
        want = np.asarray(ref["each"])[cut]

        def entries(built):
            return np.concatenate([np.asarray(x).reshape(-1) for x in
                                   s.exe.run(built["test"], feed=batch,
                                             scope=s.scope, fetch_list=built[
                                                 "check"]["each"][at])])
        as_it_is = entries(s.built) / want
        np.testing.assert_allclose(as_it_is, 1.0, atol=5e-2 if at.start == 3
                                   else 3e-4)
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


def test_closed_forms_match_numbers_worked_by_hand():
    cell = run.load_cell(CELL, rehearsal=False)
    model, params = cell["model"], cell["params"]
    tokens, h, s = 4096, 2048, 4096
    assert params["batch"] * params["seq"] == tokens
    assert needs.blocks(model) == 6 and needs.sparse_layers(model) == 5
    assert needs.held_assignments(model, params) == 2048
    flash = needs.flash_attention_causal(model, params)
    assert flash["flops"] == 6 * 6 * s * s * 20 * 256
    assert flash["bytes"] == 6 * 12 * 20 * s * 256 * 2
    experts = needs.moe_held_expert_matmul(model, params)
    assert experts["flops"] == 5 * 3 * 3 * 2 * 2048 * h * 1536
    assert experts["bytes"] == 5 * 9 * (2048 * h + 8 * h * 1536
                                        + 2048 * 1536) * 2
    step = needs.train_step(model, params)
    forward = {                                         # FLOPs a step
        "latent_projections": 6 * tokens * 2 * (
            h * 768 + 768 * 5120 + h * 576 + 512 * 8960 + 5120 * h),
        "scores": 6 * 20 * 2 * (s * (s + 1) // 2) * (256 + 256),
        "dense": tokens * 6 * h * 10240,
        "routers": 5 * tokens * 2 * h * 64,
        "shared": 5 * tokens * 6 * h * 1536,
        "experts": 5 * 2048 * 6 * h * 1536,
        "w_eh": tokens * 2 * 4096 * h,
        "heads": 2 * tokens * 2 * h * 19360}
    assert step["flops"] == 3 * sum(forward.values())
    assert 2.8e9 < step["per_token"] < 2.95e9
    # all six blocks run the latent mixer: its projections and scores are
    # over half of the step's FLOPs, the two heads a sixth
    share = (forward["latent_projections"] + forward["scores"]) / sum(
        forward.values())
    assert 0.5 < share < 0.6
    assert 0.15 < forward["heads"] / sum(forward.values()) < 0.18


def _rehearse(cell):
    from test_benchmark_run import result_of, run_py
    for _ in range(3):
        r = run_py(["--workload", cell, "--seed", str(2 ** 31 + 17),
                    "--seconds", "1", "--trace", "1", "--cpu-rehearsal"])
        # the span reader refuses a capture whose host clocks jitter by over
        # 20 us (reducers/span_idle_overlap.py): this sandbox's cores do at
        # times, with every cell; that is not what this test is about
        if "the two clocks do not keep step" not in r.stderr:
            break
    return result_of(r)


def test_glm_cell_rehearses_with_its_metrics():
    result, lines = _rehearse(CELL)
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    for name in ("latent_qkv.time_share", "attention.time_share",
                 "moe.time_share", "moe_dispatch.time_share",
                 "moe_bias_update.time_share", "norm_rope.time_share",
                 "loss.time_share", "optimizer_adamw.time_share",
                 "matmul.time_share", "elementwise.time_share",
                 "embedding.time_share", "compile.trace_lower_s",
                 "memory.step_state_gb"):
        assert got[name]["value"] > 0, name
    # the rehearsal's expert block and module at a budget of 96 rows each
    assert got["moe.row_budget_rows"]["value"] == 192
    # no chip, no peak: the roofline shares are left out, not raised
    for name in ("flash_attention_causal_roofline.glm_4_7_flash",
                 "moe_held_expert_matmul_roofline.glm_4_7_flash",
                 "step.model_flops_share.glm_4_7_flash", "mfu",
                 "rope.time_share", "gated_delta.time_share"):
        assert name not in got
    shares = next(ln for ln in lines if "time_share metrics" in ln)
    together = float(shares.rsplit("together ", 1)[1].split("%")[0])
    # every op type falls under a glob (the CPU's threads run ops side by
    # side, so here the shares may pass 100; on the chip they add up)
    assert together >= 99.99
    assert any("latent_qkv_grad" in ln for ln in lines)
