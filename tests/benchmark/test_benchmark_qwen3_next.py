"""The Qwen3-Next cell's pieces at small sizes on the CPU: the configuration
against its own published copy (and the catalog's row where the catalog has
one), the reference check (jobs/common.py:reference_check) passing for the
program as it is and saying no to float8 weights, what its delta-rule and
routed entries see, the closed forms of benchmark/needs_qwen3_next.py
against numbers worked by hand, and the cell through run.py with its
metrics."""
import json
import math
import os
import re

import numpy as np
import pytest

from benchmark import needs_qwen3_next as needs
from benchmark import run
from benchmark.jobs import common
from benchmark.references import qwen3_next_pretrain as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "qwen3_next_80b_a3b.pretrain_s4096"
NAME = "qwen3_next_80b_a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state|proj|_dim$"
                   r"|_rank$|head_|expansion|experts_per)")
REDUCED = ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
SEED = 17


def config():
    return json.load(open(os.path.join(
        ROOT, "benchmark", "configs", NAME + ".json")))


def test_reduced_is_exactly_what_differs_from_the_published_copy():
    data = config()
    published = data["published"]
    # the published config carries no layer_types list: HF derives it from
    # full_attention_interval, and so does the file for its four layers
    differ = [k for k, v in published.items() if data.get(k, "?") != v]
    assert sorted(differ + ["layer_types"]) == sorted(data["reduced"])
    assert data["reduced"] == REDUCED
    assert not [k for k in data["reduced"] if WIDTH.search(k)]
    for key, want in (
            ("hidden_size", 2048), ("head_dim", 256),
            ("num_attention_heads", 16), ("num_key_value_heads", 2),
            ("linear_num_key_heads", 16), ("linear_num_value_heads", 32),
            ("linear_key_head_dim", 128), ("linear_value_head_dim", 128),
            ("linear_conv_kernel_dim", 4), ("moe_intermediate_size", 512),
            ("shared_expert_intermediate_size", 512),
            ("num_experts_per_tok", 10), ("partial_rotary_factor", 0.25),
            ("rope_theta", 10000000), ("full_attention_interval", 4),
            ("intermediate_size", 5120), ("rms_norm_eps", 1e-6),
            ("norm_topk_prob", True)):
        assert data[key] == published[key] == want, key
    assert data["tie_word_embeddings"] is False
    # one whole period at the published 3 : 1, from the interval
    from paddle_tpu.models import decoder_lm
    assert data["num_hidden_layers"] == 4
    assert data["layer_types"] == ["linear_attention"] * 3 + [
        "full_attention"]
    assert decoder_lm._layer_types(published)[:4] == data["layer_types"]
    assert decoder_lm._layer_types(published) == data["layer_types"] * 12
    assert data["mlp_only_layers"] == published["mlp_only_layers"] == []
    # the model-configs guide's floors: at least 8 routed experts held, an
    # eighth of the vocabulary, a whole period
    assert (data["num_experts"], data["num_experts_routed"],
            data["first_expert_held"]) == (32, 512, 0)
    assert published["num_experts"] == 512
    assert data["vocab_size"] * 8 == published["vocab_size"] == 151936
    # 4 x what an even router sends to the held experts a layer
    cell = run.load_cell(CELL, rehearsal=False)
    tokens = cell["params"]["batch"] * cell["params"]["seq"]
    assert tokens == 2 * 4096
    assert data["moe_row_budget"] == 20480 == 4 * tokens * 10 * 32 // 512
    assert data["flops"] is None
    for key in ("layer_equations", "column_order", "delta_start",
                "delta_chunk_size", "router_scoring",
                "multi_token_prediction", "recipe", "traffic", "dtype",
                "intermediate_size", "moe_row_budget"):
        assert key in data["assumed"], key
    assert (data["norm_form"], data["qk_norm"], data["attn_output_gate"],
            data["shared_expert_gate"]) == ("zero_centered", "head", True,
                                            True)
    assert "16 chips" in data["deployment"]
    assert "8 slices" in data["deployment"]
    assert "pipeline" in data["reduced_detail"]


def test_configuration_holds_every_number_of_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next((r for r in rows
                if r["name"] == "Qwen3-Next-80B-A3B-Instruct"), None)
    if row is None:
        pytest.skip("the catalog on disk has no Qwen3-Next-80B-A3B-Instruct "
                    "row")
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
    assert cells[0]["chips"] == 1 and "16x their share" in cells[0]["why"]
    assert len(bench["workloads"]) == 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    for name in ("gated_delta.time_share", "gated_delta_roofline",
                 "gated_delta.pallas_ops", "delta_conv.time_share",
                 "delta_conv_roofline",
                 "flash_attention_gqa_causal_roofline.qwen3_next",
                 "moe_held_expert_matmul_roofline.qwen3_next",
                 "step.model_flops_share.qwen3_next",
                 "attention_gate.time_share", "moe.row_budget_rows",
                 "attention.saved_stats_ops", "moe_dispatch.time_share",
                 "swiglu_softplus.time_share", "memory.peak_forward_gb",
                 "compile.telemetry_s"):
        assert name in reported, name
    for name in ("ssd_scan.time_share", "mamba_conv.time_share",
                 "flash_attention_gqa_causal_roofline.laguna"):
        assert name not in reported, name
    ends = {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
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
    keeps the originals, the worst entry's error is several times what it
    was (on the chip it fails the limit: ``READINGS``)."""
    import jax.numpy as jnp
    s, batch, said = session()
    try:
        assert common.reference_check(s, batch) is True
        assert "ok" in said[-1] and "FAILED" not in said[-1]
        as_it_is = _worst_block(said)
        kinds = [op.type for op in s.built["main"].global_block().ops]
        assert kinds.count("gated_delta_rule") == 1
        assert kinds.count("gated_delta_rule_grad") == 1
        assert kinds.count("short_conv") == 1
        assert kinds.count("fused_attention") == 1
        assert kinds.count("attention_gate") == 1
        assert kinds.count("moe_dispatch") == 2
        # 64 blocks of the cross-entropy, two routed norms, one delta norm
        assert len(s.built["check"]["each"]) == 1 + 2 + 1
        assert len(s.built["expert_dropped"]) == 2
        originals = [s.scope.find_var(n) for n in s.built["params"]]
        for n in s.built["params"]:
            v = s.scope.find_var(n)
            s.scope.set_var(n, jnp.asarray(v).astype(jnp.float8_e4m3fn)
                            .astype(v.dtype))
        real_loss = reference.loss
        reference.loss = lambda w, *a: real_loss(originals, *a)
        try:
            common.reference_check(s, batch)
            assert _worst_block(said) > 3 * as_it_is
        finally:
            reference.loss = real_loss
    finally:
        s.close()


@pytest.mark.parametrize("mechanism, entry, low, high", [
    ("decay", "delta", 1.05, 50.0),     # nothing forgotten: a larger state
    ("l2_norm", "delta", 1e-4, 0.5),    # raw q and k: at these widths far
                                        # shorter than unit ones
    ("row_budget", "routed", 0.05, 0.6)])   # 9 rows kept of about 64
def test_the_checks_norm_entries_see_the_delta_rule_and_dropped_rows(
        mechanism, entry, low, high):
    """Beside the cross-entropy's block means the check compares, a sparse
    layer each, the mean norm of the routed experts' output and, a DeltaNet
    layer each, that of the delta rule's output before the gated norm,
    which divides a wrong scale out again: the program as it is reads the
    reference's, one without the decay, without the l2 norm, or one that
    drops rows reads a multiple or a part of it."""
    from tools.qwen3_next_probe import patched, without
    s, batch, _ = session()
    try:
        ref = reference.loss(
            [s.scope.find_var(n) for n in s.built["params"]], batch, s.model,
            s.params)
        want = np.asarray(ref[entry])
        at = slice(1, 3) if entry == "routed" else slice(3, 4)

        def entries(built):
            return np.array(s.exe.run(
                built["test"], feed=batch, scope=s.scope,
                fetch_list=built["check"]["each"][at])).reshape(-1)
        np.testing.assert_allclose(entries(s.built), want, rtol=1e-2)
        with patched(mechanism):
            other = s.builder.build(without(s.model, mechanism), s.params)
            share = entries(other) / want
        assert (low < share).all() and (share < high).all(), share
    finally:
        s.close()


def test_tolerance_sits_between_the_chip_readings():
    cell = run.load_cell(CELL, rehearsal=False)
    published = reference.tolerance(cell["model"])
    assert set(published) == {"loss", "each"}
    as_it_is, float8 = (reference.READINGS["as_it_is_max"],
                        reference.READINGS["float8_min"])
    assert as_it_is * 1.5 < published["each"] < float8 / 1.3
    assert published["loss"] == float("inf")


def test_closed_forms_match_numbers_worked_by_hand():
    cell = run.load_cell(CELL, rehearsal=False)
    model, params = cell["model"], cell["params"]
    tokens, h, s = 8192, 2048, 4096
    assert params["batch"] * params["seq"] == tokens
    assert needs.layers_of(model, "linear_attention") == 3
    assert needs.layers_of(model, "full_attention") == 1
    assert needs.held_assignments(model, params) == 5120
    # a chunk of C positions and a value head: k k^T and q k^T shared by
    # two value heads, the inverse's 2 (log2 C - 1) products of C^3, k S,
    # q S and the update, T R and P V'
    c = model["delta_chunk_size"]
    chunk = (2 * 2 * c * c * 128 / 2 + 2 * (math.log2(c) - 1) * 2 * c ** 3
             + 3 * 2 * c * 128 * 128 + 2 * 2 * c * c * 128)
    assert needs.delta_rule_forward_flops(model) == 32 * chunk / c
    at64 = needs.delta_rule_forward_flops(dict(model, delta_chunk_size=64))
    assert at64 == 32 * (1_048_576 + 5_242_880 + 6_291_456 + 2_097_152) / 64
    delta = needs.gated_delta(model, params)
    assert delta["flops"] == 3 * tokens * 3 * 32 * chunk / c
    assert delta["bytes"] == 3 * tokens * (2 * (2 * 2048 + 2 * 4096) * 2
                                           + 4 * 32 * 4)
    conv = needs.delta_conv(model, params)
    assert conv["bytes"] == 3 * 5 * tokens * 8192 * 2
    assert conv["flops"] == 3 * tokens * 8192 * 24
    flash = needs.flash_attention_gqa_causal(model, params)
    assert flash["flops"] == 6 * 2 * s * s * 16 * 256
    assert flash["bytes"] == 6 * 2 * s * 256 * (16 + 2) * 2
    experts = needs.moe_held_expert_matmul(model, params)
    assert experts["flops"] == 4 * 3 * 3 * 2 * 5120 * h * 512
    assert experts["bytes"] == 4 * 9 * (5120 * h + 32 * h * 512
                                        + 5120 * 512) * 2
    step = needs.train_step(model, params)
    forward = {                                         # FLOPs a step
        "delta_projections": 3 * tokens * (2 * h * 12288 + 2 * h * 64
                                           + 2 * 4096 * h),
        "delta_rule": 3 * tokens * 32 * chunk / c,
        "attention_projections": tokens * (2 * h * 8192 + 2 * 2 * h * 512
                                           + 2 * 4096 * h),
        "scores": 2 * 16 * 4 * (s * (s + 1) // 2) * 256,
        "routers": 4 * tokens * (2 * h * 512 + 2 * h),
        "shared": 4 * tokens * 6 * h * 512,
        "experts": 4 * 5120 * 6 * h * 512,
        "head": tokens * 2 * h * 18992}
    assert step["flops"] == 3 * sum(forward.values())
    assert 1.2e9 < step["per_token"] < 1.5e9
    # three of four layers are DeltaNet: their projections and rule are
    # about half of the step's FLOPs
    share = (forward["delta_projections"] + forward["delta_rule"]) / sum(
        forward.values())
    assert 0.45 < share < 0.6


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


def test_qwen3_next_cell_rehearses_with_its_metrics():
    result, lines = _rehearse(CELL)
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    for name in ("gated_delta.time_share", "delta_conv.time_share",
                 "attention.time_share", "attention_gate.time_share",
                 "moe.time_share", "moe_dispatch.time_share",
                 "norm_rope.time_share", "optimizer_adamw.time_share",
                 "matmul.time_share", "elementwise.time_share",
                 "swiglu_softplus.time_share", "embedding.time_share",
                 "compile.trace_lower_s", "memory.step_state_gb"):
        assert got[name]["value"] > 0, name
    # the rehearsal's two layers at a budget of 96 rows each
    assert got["moe.row_budget_rows"]["value"] == 192
    # no chip, no peak: the roofline shares are left out, not raised; off a
    # TPU the delta rule lowers its composed form, and says so
    for name in ("gated_delta_roofline", "delta_conv_roofline",
                 "flash_attention_gqa_causal_roofline.qwen3_next",
                 "moe_held_expert_matmul_roofline.qwen3_next",
                 "step.model_flops_share.qwen3_next",
                 "gated_delta.pallas_ops", "mfu", "ssd_scan.time_share"):
        assert name not in got
    shares = next(ln for ln in lines if "time_share metrics" in ln)
    together = float(shares.rsplit("together ", 1)[1].split("%")[0])
    # every op type falls under a glob (the CPU's threads run ops side by
    # side, so here the shares may pass 100; on the chip they add up)
    assert together >= 99.99
    assert any("gated_delta_rule_grad" in ln for ln in lines)
