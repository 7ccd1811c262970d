"""The LFM2 cell's pieces at small sizes on the CPU: the configuration
against its own published copy (and the catalog's row where the catalog has
one), the reference check (jobs/common.py:reference_check) passing for the
program as it is and saying no to float8 weights, the closed forms of
benchmark/needs_lfm2.py against numbers worked by hand, and the cell through
run.py with its metrics."""
import json
import os
import re

import numpy as np
import pytest

from benchmark import needs_lfm2, run
from benchmark.jobs import common
from benchmark.references import lfm2_pretrain as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "lfm2_8b_a1b.pretrain_s4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state|proj|_dim$"
                   r"|_rank$|head_|expansion|experts_per)")
SEED = 11


def config():
    return json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "lfm2_8b_a1b.json")))


def test_reduced_is_exactly_what_differs_from_the_published_copy():
    data = config()
    published = data["published"]
    differ = [k for k, v in published.items() if data.get(k, "?") != v]
    assert sorted(differ) == sorted(data["reduced"])
    assert data["reduced"] == ["num_hidden_layers", "layer_types",
                               "num_dense_layers", "num_experts",
                               "vocab_size"]
    # no width among them: what test_config_files_resolve's pattern means
    # (its "hidden" also hits num_hidden_layers, a depth: tests/conftest.py)
    assert not [k for k in data["reduced"] if WIDTH.search(k)]
    for key, want in (("hidden_size", 2048), ("intermediate_size", 7168),
                      ("moe_intermediate_size", 1792), ("conv_L_cache", 3),
                      ("num_attention_heads", 32), ("num_key_value_heads", 8),
                      ("num_experts_per_tok", 4)):
        assert data[key] == published[key] == want
    # the router keeps its published width; the chip holds a quarter
    assert data["num_experts_routed"] == published["num_experts"] == 32
    assert data["num_experts"] * 4 == 32 and data["first_expert_held"] == 0
    assert data["vocab_size"] * 4 == published["vocab_size"]
    # a whole period: published layers 1-5, one attention to three convs
    # among the expert layers, and the floors of the model-configs guide
    assert data["layer_types"] == published["layer_types"][1:6]
    assert len(data["layer_types"]) == data["num_hidden_layers"] == 5
    assert data["num_hidden_layers"] - data["num_dense_layers"] >= 4
    assert data["num_experts"] >= 8
    assert data["vocab_size"] * 8 >= published["vocab_size"]
    assert data["flops"] is None
    for key in ("router_scoring", "bias_update", "bias_start", "untied_head",
                "optimizer", "init", "data", "dtype"):
        assert key in data["assumed"], key
    assert data["bias_update_rate"] == reference.BIAS_UPDATE_RATE


def test_configuration_holds_the_catalog_row_where_the_catalog_has_one():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next((r for r in rows if r["name"] == "LFM2-8B-A1B"), None)
    if row is None:
        pytest.skip("the catalog on disk has no LFM2-8B-A1B row")
    data = config()
    assert data["source"] == row["source_url"]
    assert data["published"] == row["config"]


def session():
    cell = run.load_cell(CELL, rehearsal=True)
    said = []
    s = common.Session(cell, SEED, said.append)
    batch = s.builder.batch(s.model, s.params, np.random.RandomState(SEED))
    return s, batch, said


def seeded_bias(s):
    import jax.numpy as jnp
    r = np.random.RandomState(SEED)
    for n in s.built["expert_bias"]:
        s.scope.set_var(n, jnp.asarray(
            (r.randn(s.model["num_experts_routed"]) * 0.05)
            .astype("float32")))


def test_program_agrees_with_the_plain_reference_on_a_non_zero_bias():
    s, batch, said = session()
    try:
        sparse = s.model["num_hidden_layers"] - s.model["num_dense_layers"]
        assert len(s.built["expert_bias"]) == sparse >= 1
        assert s.built["params"][-sparse:] == s.built["expert_bias"]
        seeded_bias(s)
        assert common.reference_check(s, batch) is True
        assert "ok" in said[-1] and "FAILED" not in said[-1]
        got = s.exe.run(s.built["test"], feed=batch,
                        fetch_list=s.built["expert_index"], scope=s.scope)
        want = reference.loss([s.scope.find_var(n) for n in
                               s.built["params"]], batch, s.model, s.params)
        # the bias moves the choice, and the program follows the reference
        plain = reference.loss(
            [s.scope.find_var(n) for n in s.built["params"][:-sparse]]
            + [np.zeros(s.model["num_experts_routed"], "float32")] * sparse,
            batch, s.model, s.params)
        assert reference.differing_share(
            np.asarray(plain["experts"]), np.asarray(want["experts"])) > 0.02
        assert reference.differing_share(
            np.stack(got), np.asarray(want["experts"])) <= 0.02
        # the test clone holds no bias update and left the bias alone
        assert "moe_bias_update" not in [
            op.type for op in s.built["test"].global_block().ops]
        assert [op.type for op in s.built["main"].global_block().ops] \
            .count("moe_bias_update") == sparse
    finally:
        s.close()


def test_float8_weights_fail_the_check():
    """The program's weights are rounded to float8 (e4m3) while the
    reference keeps the originals: the comparison that decides ``correct``
    must say no."""
    import jax.numpy as jnp
    s, batch, said = session()
    originals = [s.scope.find_var(n) for n in s.built["params"]]
    for n in s.built["params"]:
        v = s.scope.find_var(n)
        s.scope.set_var(n, jnp.asarray(v).astype(jnp.float8_e4m3fn)
                        .astype(v.dtype))
    real_loss = reference.loss
    reference.loss = lambda w, *a: real_loss(originals, *a)
    try:
        assert common.reference_check(s, batch) is False
        assert "FAILED" in said[-1]
    finally:
        reference.loss = real_loss
        s.close()


def test_tolerance_sits_between_the_chip_readings():
    cell = run.load_cell(CELL, rehearsal=False)
    published = reference.tolerance(cell["model"])
    assert set(published) == {"loss", "each"}
    # PERF.md section 6 (PR 32), the two readings on the chip: the program
    # as it is, float8 weights in its place
    as_it_is, float8 = READINGS
    assert as_it_is * 1.3 < published["each"] < float8 / 1.3
    assert published["loss"] <= 2e-4
    deeper = dict(cell["model"], num_hidden_layers=24)
    assert reference.tolerance(deeper)["each"] > published["each"]
    assert reference.check_block(4096) == 64
    assert reference.check_block(32) == 1


# largest `each` of the program as it is (22 seeds), smallest with float8
# weights at the check's own state (3 seeds): chip runs, PR 32
READINGS = (1.82e-3, 5.09e-3)


def test_closed_forms_match_numbers_worked_by_hand():
    cell = run.load_cell(CELL, rehearsal=False)
    model = cell["model"]
    params = dict(cell["params"], batch=4)
    tokens, h = 4 * 4096, 2048
    assert needs_lfm2.held_assignments(model, params) == tokens * 4 * 8 / 32
    conv = needs_lfm2.short_conv(model, params)
    # four conv layers, 11 arrays of tokens x hidden 2-byte elements each
    assert conv["bytes"] == 4 * 11 * tokens * h * 2 == 2_952_790_016
    assert conv["flops"] == 4 * 21 * tokens * h
    experts = needs_lfm2.moe_held_expert_matmul(model, params)
    assert experts["flops"] == 4 * 9 * 2 * tokens * h * 1792
    assert experts["bytes"] == 4 * 9 * 2 * (
        tokens * h + 8 * h * 1792 + tokens * 1792)
    flash = needs_lfm2.flash_attention_gqa_causal(model, params)
    # one attention layer; half the 4096 x 4096 square for 32 heads of 64
    assert flash["flops"] == 6 * 4 * 4096 * 4096 * 32 * 64
    assert flash["bytes"] == 6 * 4 * 4096 * (32 + 8) * 64 * 2
    step = needs_lfm2.train_step(model, params)
    forward = {                             # MFLOP a token, ISSUE 32
        "conv": 4 * 2 * h * 4 * h, "dense": 6 * h * 7168,
        "experts": 4 * 6 * h * 1792, "router": 4 * 2 * h * 32,
        "attention": 2 * h * 2 * h + 2 * h * 2 * 512 + 2 * 4096 * h,
        "head": 2 * h * 16384}
    assert [round(v / 1e6) for v in forward.values()] == \
        [134, 88, 88, 1, 38, 67]
    assert step["per_token"] == 3 * sum(forward.values())
    assert step["flops"] == step["per_token"] * tokens
    assert step["per_token"] == pytest.approx(1.247e9, rel=1e-3)
    # the code this PR adds does about four fifths of the step's FLOPs
    new = sum(forward[k] for k in ("conv", "dense", "experts", "attention"))
    assert 0.8 < new / sum(forward.values()) < 0.86


def test_lfm2_cell_rehearses_with_its_metrics():
    from test_benchmark_run import result_of, run_py
    for _ in range(3):
        r = run_py(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                    "--seconds", "1", "--trace", "1", "--cpu-rehearsal"])
        # the span reader refuses a capture whose host clocks jitter by over
        # 20 us (reducers/span_idle_overlap.py): this sandbox's cores do at
        # times, with every cell; that is not what this test is about
        if "the two clocks do not keep step" not in r.stderr:
            break
    result, lines = result_of(r)
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    for name in ("short_conv.time_share", "moe_bias_update.time_share",
                 "moe.time_share", "moe_dispatch.time_share",
                 "norm_rope.time_share", "optimizer_adamw.time_share",
                 "attention.time_share", "matmul.time_share",
                 "elementwise.time_share", "embedding.time_share",
                 "compile.trace_lower_s"):
        assert got[name]["value"] > 0, name
    # no chip, no peak: the roofline shares are left out, not raised
    for name in ("short_conv_roofline", "moe_held_expert_matmul_roofline",
                 "flash_attention_gqa_causal_roofline",
                 "step.model_flops_share.lfm2", "step.model_flops_share",
                 "moe_expert_matmul_roofline", "mfu"):
        assert name not in got
    shares = next(ln for ln in lines if "time_share metrics" in ln)
    together = float(shares.rsplit("together ", 1)[1].split("%")[0])
    # every op type falls under a glob (the CPU's threads run ops side by
    # side, so here the shares may pass 100; on the chip they add up)
    assert together >= 99.99
    assert any("short_conv_grad" in ln for ln in lines)
