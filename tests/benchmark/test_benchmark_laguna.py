"""The Laguna cell's pieces at small sizes on the CPU: the configuration
against its own published copy (and the catalog's row where the catalog has
one), the reference check (jobs/common.py:reference_check) passing for the
program as it is and saying no to float8 weights, the closed forms of
benchmark/needs_laguna.py against numbers worked by hand, the reader that
tells window ops from full ones, and the cell through run.py with its
metrics."""
import json
import os
import re

import numpy as np
import pytest

from benchmark import needs_laguna, run
from benchmark import trace as tr
from benchmark.jobs import common
from benchmark.reducers import needs_share_by_layer_type as by_type
from benchmark.references import laguna_pretrain as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "laguna_s_2_1.pretrain_s4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state|proj|_dim$"
                   r"|_rank$|head_|expansion|experts_per)")
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "gating_types", "num_attention_heads_per_layer", "num_experts",
           "vocab_size"]
SEED = 13


def config():
    return json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "laguna_s_2_1.json")))


def test_reduced_is_exactly_what_differs_from_the_published_copy():
    data = config()
    published = data["published"]
    differ = [k for k, v in published.items() if data.get(k, "?") != v]
    assert sorted(differ) == sorted(data["reduced"])
    assert data["reduced"] == REDUCED
    # no width among them: what test_config_files_resolve's pattern means
    # (its "hidden" also hits num_hidden_layers, a depth: tests/conftest.py)
    assert not [k for k in data["reduced"] if WIDTH.search(k)]
    for key, want in (("hidden_size", 3072), ("intermediate_size", 12288),
                      ("head_dim", 128), ("num_attention_heads", 48),
                      ("num_key_value_heads", 8),
                      ("moe_intermediate_size", 1024),
                      ("shared_expert_intermediate_size", 1024),
                      ("num_experts_per_tok", 10), ("sliding_window", 512),
                      ("moe_routed_scaling_factor", 2.5),
                      ("rms_norm_eps", 1e-6), ("gating", "per-head")):
        assert data[key] == published[key] == want, key
    assert data["rope_parameters"] == published["rope_parameters"]
    assert data["rope_parameters"]["full_attention"][
        "partial_rotary_factor"] == 0.5
    assert data["tie_word_embeddings"] is False
    # the leading dense layer and one whole period at the published 3 : 1;
    # the per-layer lists cut with the depth, their values as published
    assert data["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert data[key] == published[key][:5], key
        assert len(published[key]) == published["num_hidden_layers"] == 48
    assert data["layer_types"] == ["full_attention"] + \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert published["layer_types"] == published["layer_types"][:4] * 12
    assert data["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert data["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert data["mlp_only_layers"] == published["mlp_only_layers"] == [0]
    # the model-configs guide's floors: 8 routed experts held, an eighth of
    # the vocabulary, four layers after the leading dense one
    assert (data["num_experts"], data["num_experts_routed"],
            data["first_expert_held"]) == (8, 256, 0)
    assert published["num_experts"] == 256
    assert data["vocab_size"] * 8 == published["vocab_size"] == 100352
    # 4 x what an even router sends to the held experts a layer
    assert data["moe_row_budget"] == 5120 == 4 * 4096 * 10 * 8 // 256
    assert data["flops"] is None
    for key in ("router_scoring", "gate", "qk_norm", "shared_expert", "yarn",
                "optimizer", "init", "data", "dtype", "row_budget"):
        assert key in data["assumed"], key
    assert "32 chips" in data["deployment"]
    assert "8 slices" in data["deployment"]
    assert "pipeline" in data["deployment"]


def test_configuration_holds_every_number_of_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next((r for r in rows if r["name"] == "Laguna-S-2.1"), None)
    if row is None:
        pytest.skip("the catalog on disk has no Laguna-S-2.1 row")
    data = config()
    assert data["source"] == row["source_url"]
    assert data["published"] == row["config"]
    # every number of the row under the same key, but for the reduced ones
    for key, value in row["config"].items():
        if key not in data["reduced"]:
            assert data[key] == value, key


def test_benchmark_json_names_the_configuration_and_one_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "laguna_s_2_1")
    assert entry["reduced"] == REDUCED
    cells = [w for w in bench["workloads"] if w["config"] == "laguna_s_2_1"]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1 and "32x its share" in cells[0]["why"]
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    for name in ("flash_attention_window_roofline",
                 "flash_attention_gqa_causal_roofline.laguna",
                 "attention.window_k_tiles_visited",
                 "attention_gate.time_share",
                 "moe_held_expert_matmul_roofline.laguna",
                 "moe.row_budget_rows", "step.model_flops_share.laguna",
                 "attention.saved_stats_ops", "moe_dispatch.time_share",
                 "memory.peak_forward_gb", "compile.telemetry_s"):
        assert name in reported, name


def session():
    cell = run.load_cell(CELL, rehearsal=True)
    said = []
    s = common.Session(cell, SEED, said.append)
    batch = s.builder.batch(s.model, s.params, np.random.RandomState(SEED))
    return s, batch, said


def _worst_block(said) -> float:
    """The error of the worst block from ``reference_check``'s line."""
    return float(said[-1].split("positions ")[1].split(" ")[0])


def test_program_agrees_with_the_plain_reference_and_float8_shows():
    """The check that decides ``correct`` passes for the program as it is;
    with the program's weights rounded to float8 (e4m3) while the reference
    keeps the originals, the worst block's error is several times what it
    was (on the chip it fails the limit: ``READINGS``)."""
    import jax.numpy as jnp
    s, batch, said = session()
    try:
        assert common.reference_check(s, batch) is True
        assert "ok" in said[-1] and "FAILED" not in said[-1]
        as_it_is = _worst_block(said)
        kinds = [op.type for op in s.built["main"].global_block().ops]
        assert kinds.count("fused_attention") == 3
        assert kinds.count("fused_attention_grad") == 3
        assert kinds.count("attention_gate") == 3
        assert kinds.count("moe_dispatch") == 2
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


@pytest.mark.parametrize("mechanism, low, high", [
    ("routed_scale", 0.37, 0.43),   # every routed output 1 / 2.5 of itself
    ("row_budget", 0.05, 0.6)])     # 9 rows kept of about 64 a layer
def test_the_checks_routed_entries_see_the_scale_and_dropped_rows(
        mechanism, low, high):
    """Beside the cross-entropy's block means the check compares, a sparse
    layer each, the mean norm of the routed experts' output: the program as
    it is reads the reference's, one without the scale 2.5 or one that
    drops rows reads a part of it."""
    from tools.laguna_probe import without
    s, batch, _ = session()
    try:
        want = np.asarray(reference.loss(
            [s.scope.find_var(n) for n in s.built["params"]], batch, s.model,
            s.params)["routed"])
        names = s.built["check"]["each"][1:]
        assert len(names) == 2 == want.size

        def routed(built):
            return np.array(s.exe.run(
                built["test"], feed=batch, scope=s.scope,
                fetch_list=built["check"]["each"][1:])).reshape(-1)
        np.testing.assert_allclose(routed(s.built), want, rtol=1e-2)
        other = s.builder.build(without(s.model, mechanism), s.params)
        share = routed(other) / want
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
    # the mean's error has no upper reading (float8 weights read inside the
    # sound runs' range), so this cell's check leaves it to ``each``
    assert published["loss"] == float("inf")


def test_closed_forms_match_numbers_worked_by_hand():
    cell = run.load_cell(CELL, rehearsal=False)
    model, params = cell["model"], cell["params"]
    tokens, h, d, s = 4096, 3072, 128, 4096
    assert params["batch"] * params["seq"] == tokens
    # query i sees min(i + 1, 512) keys: the triangle, then full windows
    pairs = sum(min(i + 1, 512) for i in range(s))
    assert needs_laguna.window_pairs(s, 512) == pairs == 1_966_336
    assert needs_laguna.window_pairs(256, 512) == 256 * 257 // 2
    window = needs_laguna.flash_attention_window(model, params)
    assert window["flops"] == 3 * 12 * 72 * d * pairs
    assert window["bytes"] == 3 * 6 * s * d * (72 + 8) * 2
    full = needs_laguna.flash_attention_gqa_causal(model, params)
    assert full["flops"] == 2 * 6 * s * s * 48 * d
    assert full["bytes"] == 2 * 6 * s * d * (48 + 8) * 2
    # the window's tiles: 15 of 64 at 512 x 512 (1 + 7 x 2), 11 of 32 at
    # 512 x 1024; the program's own count agrees
    from paddle_tpu.ops import pallas_attention as pa
    for blocks, want in (((512, 512), 15), ((512, 1024), 11),
                         ((256, 256), 45)):
        assert needs_laguna.window_k_tiles(s, 512, *blocks) == want
        assert pa.k_tiles(s, *blocks, True, 512)[0] == want
    assert needs_laguna.held_assignments(model, params) == 1280
    experts = needs_laguna.moe_held_expert_matmul(model, params)
    assert experts["flops"] == 4 * 3 * 3 * 2 * 1280 * h * 1024
    assert experts["bytes"] == 4 * 9 * (1280 * h + 8 * h * 1024
                                        + 1280 * 1024) * 2
    step = needs_laguna.train_step(model, params)
    causal = s * (s + 1) // 2
    forward = {                                         # FLOPs a step
        "projections": tokens * (
            2 * (2 * h * 2 * 48 * d + 2 * h * 2 * 8 * d + 2 * h * 48)
            + 3 * (2 * h * 2 * 72 * d + 2 * h * 2 * 8 * d + 2 * h * 72)),
        "scores": 2 * 48 * 4 * causal * d + 3 * 72 * 4 * pairs * d,
        "dense": tokens * 6 * h * 12288,
        "routers": 4 * tokens * 2 * h * 256,
        "shared": 4 * tokens * 6 * h * 1024,
        "experts": 4 * 1280 * 6 * h * 1024,
        "head": tokens * 2 * h * 12544}
    assert step["flops"] == 3 * sum(forward.values())
    assert step["per_token"] == pytest.approx(3.355e9, rel=1e-3)
    # attention (projections, gate, kernels) is about 63% of the step
    share = (forward["projections"] + forward["scores"]) / sum(
        forward.values())
    assert 0.61 < share < 0.65


HLO = """
ENTRY %main {
  %a = bf16[8] custom-call(), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fused_attention#10/pallas"}
  %b = bf16[8] custom-call(), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fused_attention#30/pallas"}
  %c = bf16[8] custom-call(), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fused_attention#50/pallas"}
  %t = bf16[8] transpose(), metadata={op_name="jit(step)/fused_attention#30/transpose"}
  %gc = bf16[8] custom-call(), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fused_attention_grad#70/pallas"}
  %gb = bf16[8] custom-call(), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fused_attention_grad#90/pallas"}
  %ga = bf16[8] custom-call(), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fused_attention_grad#110/pallas"}
  %m = bf16[8] dot(), metadata={op_name="jit(step)/mul#5/dot"}
}
"""


class _Evidence:
    def __init__(self, kinds, peaks=True):
        ms = 1e6
        self.cell = {"model": {
            "layer_types": kinds, "num_attention_heads_per_layer": [4] * 3,
            "head_dim": 128, "num_key_value_heads": 2, "sliding_window": 128},
            "params": {"batch": 1, "seq": 512}, "chips": 1}
        self.hlo = tr.parse_hlo(HLO)
        # a step: the window layers' kernels 2 + 6 ms, the full one's 3 + 7,
        # a transpose inside a window op's scope that is no kernel
        events = [("%a", 0, 1 * ms), ("%b", 1 * ms, 4 * ms),
                  ("%c", 4 * ms, 5 * ms), ("%t", 5 * ms, 5.5 * ms),
                  ("%gc", 6 * ms, 9 * ms), ("%gb", 9 * ms, 16 * ms),
                  ("%ga", 16 * ms, 19 * ms), ("%m", 19 * ms, 20 * ms)]
        self.trace = tr.Trace({"/device:TPU:0": {tr.OPS_LINE: events}}, [],
                              (0.0, 20 * ms))
        self.peaks = {"bf16_flops_per_s": 1e12,
                      "hbm_bytes_per_s": 1e12} if peaks else None
        self.traced_steps, self.said = 1, []

    def say(self, line):
        self.said.append(line)


def test_window_ops_are_told_from_full_ones_by_the_layer_types():
    """The i-th forward scope and the i-th grad scope from the end are the
    i-th attention layer's; the reader hands ``needs_share`` the scopes of
    the layer type asked for, kernels only."""
    kinds = ["sliding_attention", "full_attention", "sliding_attention"]
    spec = {"name": "x_roofline", "layer_type": "sliding_attention",
            "match": ["fused_attention#*", "fused_attention_grad#*"],
            "custom_call_target": "tpu_custom_call",
            "needs": "needs_laguna:flash_attention_window",
            "over": "scope_events"}
    ev = _Evidence(kinds)
    assert by_type.scopes_of(spec, ev) == [
        "fused_attention#10", "fused_attention_grad#110",
        "fused_attention#50", "fused_attention_grad#70"]
    assert by_type.scopes_of(dict(spec, layer_type="full_attention"), ev) == [
        "fused_attention#30", "fused_attention_grad#90"]
    got = by_type.reduce(spec, ev)
    # the window layers' kernels took 1 + 3 + 1 + 3 = 8 ms of the step
    assert "8.000 ms a step" in ev.said[-1]
    need = needs_laguna.flash_attention_window(ev.cell["model"],
                                               ev.cell["params"])
    assert got == pytest.approx(100 * need["flops"] / 1e12 / 8e-3)
    full = by_type.reduce(dict(
        spec, layer_type="full_attention",
        needs="needs_laguna:flash_attention_gqa_causal"), ev)
    assert "10.000 ms a step" in ev.said[-1] and full > 0
    # no chip, another program, or a count that does not fit: nothing, and
    # no error (a parent commit runs this file too)
    assert by_type.reduce(spec, _Evidence(kinds, peaks=False)) is None
    assert by_type.reduce(spec, _Evidence(kinds[:2])) is None
    assert by_type.reduce(spec, _Evidence([])) is None
    assert by_type.reduce(dict(spec, layer_type="conv"), ev) is None


def _rehearse(cell):
    from test_benchmark_run import result_of, run_py
    for _ in range(3):
        r = run_py(["--workload", cell, "--seed", str(2 ** 31 + 13),
                    "--seconds", "1", "--trace", "1", "--cpu-rehearsal"])
        # the span reader refuses a capture whose host clocks jitter by over
        # 20 us (reducers/span_idle_overlap.py): this sandbox's cores do at
        # times, with every cell; that is not what this test is about
        if "the two clocks do not keep step" not in r.stderr:
            break
    return result_of(r)


def test_laguna_cell_rehearses_with_its_metrics():
    result, lines = _rehearse(CELL)
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    for name in ("attention.time_share", "attention_gate.time_share",
                 "moe.time_share", "moe_dispatch.time_share",
                 "norm_rope.time_share", "optimizer_adamw.time_share",
                 "matmul.time_share", "elementwise.time_share",
                 "embedding.time_share", "compile.trace_lower_s",
                 "memory.step_state_gb"):
        assert got[name]["value"] > 0, name
    # the rehearsal's two sparse layers at a budget of 96 rows each
    assert got["moe.row_budget_rows"]["value"] == 192
    # no chip, no peak: the roofline shares are left out, not raised; off a
    # TPU at S=32 the op lowers its composed form, so no tile is counted
    for name in ("flash_attention_window_roofline",
                 "flash_attention_gqa_causal_roofline.laguna",
                 "moe_held_expert_matmul_roofline.laguna",
                 "step.model_flops_share.laguna",
                 "attention.window_k_tiles_visited", "mfu",
                 "short_conv.time_share"):
        assert name not in got
    shares = next(ln for ln in lines if "time_share metrics" in ln)
    together = float(shares.rsplit("together ", 1)[1].split("%")[0])
    # every op type falls under a glob (the CPU's threads run ops side by
    # side, so here the shares may pass 100; on the chip they add up)
    assert together >= 99.99
    assert any("fused_attention_grad" in ln for ln in lines)
