"""The Ouro cell's pieces at small sizes on the CPU: the configuration
against its own published copy (and the catalog's row where the catalog has
one), the reference check (jobs/common.py:reference_check) passing for the
program as it is and saying no to float8 weights, the closed forms of
benchmark/needs_ouro.py against numbers worked by hand, the phase reader on
a hand-made trace, the cell's entries in BENCHMARK.json, and the cell
through run.py with its metrics."""
import json
import os
import re

import numpy as np
import pytest

from benchmark import needs_ouro, run
from benchmark import trace as tr
from benchmark.jobs import common
from benchmark.reducers import phase_time_share
from benchmark.references import ouro_pretrain as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ouro_2_6b.pretrain_s4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state|proj|_dim$"
                   r"|_rank$|head_|expansion|experts_per)")
SEED = 11


def config():
    return json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "ouro_2_6b.json")))


def test_reduced_is_exactly_what_differs_from_the_published_copy():
    data = config()
    published = data["published"]
    differ = [k for k, v in published.items() if data.get(k, "?") != v]
    assert sorted(differ) == sorted(data["reduced"])
    assert data["reduced"] == ["num_hidden_layers", "layer_types"]
    assert not [k for k in data["reduced"] if WIDTH.search(k)]
    for key, want in (("hidden_size", 2048), ("intermediate_size", 5632),
                      ("num_attention_heads", 16), ("head_dim", 128),
                      ("num_key_value_heads", 16), ("vocab_size", 49152),
                      ("total_ut_steps", 4), ("early_exit_threshold", 1),
                      ("rope_theta", 1000000), ("rms_norm_eps", 1e-6),
                      ("tie_word_embeddings", False)):
        assert data[key] == published[key] == want, key
    # depth only: the pattern's period is one layer, the floors ask four
    assert data["num_hidden_layers"] in (12, 8) and published[
        "num_hidden_layers"] == 48
    assert data["layer_types"] == published["layer_types"][
        :data["num_hidden_layers"]]
    assert data["flops"] is None and data["recompute"] == "layer"
    for key in ("norm_placement", "loop", "qk_norm", "exit_gate",
                "exit_entropy_coef", "early_exit_threshold", "optimizer",
                "init", "data", "recompute", "dtype"):
        assert key in data["assumed"], key
    assert "pipeline stage" in data["deployment"]
    # the rehearsal sizes name no key the model lacks
    assert set(data["rehearsal"]) <= set(data)


def test_configuration_holds_the_catalog_row_where_the_catalog_has_one():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next((r for r in rows if r["name"] == "Ouro-2.6B"), None)
    if row is None:
        pytest.skip("the catalog on disk has no Ouro-2.6B row")
    data = config()
    assert data["source"] == row["source_url"]
    assert data["published"] == row["config"]
    for key, value in row["config"].items():
        if key not in data["reduced"]:
            assert data[key] == value, key
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "ouro_2_6b")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == data["reduced"]
    assert len(entry["why"]) <= 200


def test_benchmark_json_names_the_cell_and_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == "ouro_2_6b" and cell["chips"] == 1
    assert cell["traffic"] == "pretrain_s4096" and len(cell["why"]) <= 200
    mix = json.load(open(os.path.join(ROOT, "benchmark", "workloads",
                                      CELL + ".json")))
    assert mix["job"] == "train_feed" and mix["layout"] is None
    assert mix["params"] == {"batch": 1, "seq": 4096, "ring": 8,
                             "loss_read_every": 10}
    assert mix["traced_window"] == {"steps": 6}
    reported = {m["name"]: m for kind in ("end_to_end", "per_layer")
                for m in bench[kind] if CELL in m.get("workloads", [CELL])}
    for name in ("tokens_per_s", "peak_hbm_gb", "setup_s",
                 "loop.stack_lowerings", "loop.kept_gb",
                 "recompute.time_share", "exit_loss.time_share",
                 "step.model_flops_share.ouro",
                 "flash_attention_causal_roofline.ouro",
                 "attention.time_share", "matmul.time_share",
                 "unattributed.time_share", "loss.time_share",
                 "optimizer_adamw.time_share", "memory.step_temp_gb",
                 "memory.peak_forward_gb", "memory.peak_backward_gb",
                 "attention.saved_stats_ops"):
        assert name in reported, name
    assert "mfu" not in reported
    assert reported["loop.stack_lowerings"]["moves"] == "setup_s"
    assert reported["loop.kept_gb"]["moves"] == "peak_hbm_gb"
    for name in ("recompute.time_share", "exit_loss.time_share",
                 "step.model_flops_share.ouro",
                 "flash_attention_causal_roofline.ouro"):
        assert reported[name]["moves"] == "tokens_per_s"
        assert reported[name]["workloads"] == [CELL]
    # every metric the cell lists has its file, and the file its reader
    for name, m in reported.items():
        if m in bench["end_to_end"]:
            continue
        spec = json.load(open(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json")))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "reducers", spec["reducer"] + ".py")), name


def session():
    cell = run.load_cell(CELL, rehearsal=True)
    said = []
    s = common.Session(cell, SEED, said.append)
    batch = s.builder.batch(s.model, s.params, np.random.RandomState(SEED))
    return s, batch, said


def _worst_position(said) -> float:
    return float(said[-1].split("positions ")[1].split(" ")[0])


def test_program_agrees_with_the_plain_reference_and_float8_shows():
    """The check that decides ``correct`` passes for the program as it is
    (four passes' cross-entropies and the exit probabilities, 2 x the
    positions x 4 entries); with the program's weights rounded to float8
    (e4m3) while the reference keeps the originals it fails."""
    import jax.numpy as jnp
    s, batch, said = session()
    try:
        assert s.built["params"][-2:] == ["exit_gate_w", "exit_gate_b"]
        assert common.reference_check(s, batch) is True
        assert "ok" in said[-1] and "FAILED" not in said[-1]
        tokens = s.params["batch"] * s.params["seq"]
        assert f"worst of {2 * 4 * tokens} positions" in said[-1]
        as_it_is = _worst_position(said)
        kinds = [op.type for op in s.built["main"].global_block().ops]
        assert kinds.count("scan") == kinds.count("scan_grad") == 1
        originals = [s.scope.find_var(n) for n in s.built["params"]]
        for n in s.built["params"]:
            v = s.scope.find_var(n)
            s.scope.set_var(n, jnp.asarray(v).astype(jnp.float8_e4m3fn)
                            .astype(v.dtype))
        real_loss = reference.loss
        reference.loss = lambda w, *a: real_loss(originals, *a)
        try:
            assert common.reference_check(s, batch) is False
            assert _worst_position(said) > 8 * as_it_is
        finally:
            reference.loss = real_loss
    finally:
        s.close()


# `each` on the chip at the cell's size (chip runs, PR 57; PERF.md section
# 6): the largest of the program as it is, the smallest with float8 weights
READINGS = (1.21e-2, 1.05e-1)


def test_tolerance_sits_between_the_chip_readings():
    cell = run.load_cell(CELL, rehearsal=False)
    published = reference.tolerance(cell["model"])
    assert set(published) == {"loss", "each"}
    applications = cell["model"]["num_hidden_layers"] * cell["model"][
        "total_ut_steps"]
    assert published["each"] == pytest.approx(
        reference.EACH_BASE + applications * reference.EACH_AN_APPLICATION)
    as_it_is, float8 = READINGS
    assert as_it_is * 3 < published["each"] < float8 / 2.5
    assert published["loss"] <= 2e-4
    # the error follows the layer applications: more passes, more room
    more = dict(cell["model"], total_ut_steps=8)
    assert reference.tolerance(more)["each"] > published["each"]


def test_closed_forms_match_numbers_worked_by_hand():
    cell = run.load_cell(CELL, rehearsal=False)
    model = dict(cell["model"], num_hidden_layers=12)
    params = dict(cell["params"], batch=1, seq=4096)
    tokens, h, s = 4096, 2048, 4096
    step = needs_ouro.train_step(model, params)
    # a token and layer application forward (ISSUE 57): the projections
    # 2 x 4 x 2048^2, the feed-forward 2 x 3 x 2048 x 5632, causal scores
    # 2 x 4096 x 2048
    projections, ffn, scores = 2 * 4 * h * h, 6 * h * 5632, 2 * s * h
    assert [round(v / 1e6, 2) for v in (projections, ffn, scores)] == \
        [33.55, 69.21, 16.78]
    head = 2 * h * 49152
    forward = 4 * (12 * (projections + ffn + scores) + head + 2 * h)
    assert step["per_token"] == 3 * forward
    assert step["flops"] == step["per_token"] * tokens
    # 4.93 + 0.81 + 0.81 GFLOP a token forward, 19.6 forward + backward
    assert 4 * 12 * (projections + ffn) == pytest.approx(4.93e9, rel=2e-3)
    assert 4 * 12 * scores == pytest.approx(0.81e9, rel=6e-3)
    assert 4 * head == pytest.approx(0.81e9, rel=6e-3)
    assert step["per_token"] == pytest.approx(19.6e9, rel=2e-3)
    # the heads are 12% of the forward here, 4% in the 48-layer model
    assert 0.115 < 4 * head / forward < 0.13
    deep = needs_ouro.train_step(dict(model, num_hidden_layers=48), params)
    assert 0.03 < 3 * 4 * head / deep["per_token"] < 0.04
    flash = needs_ouro.flash_attention_causal(model, params)
    # 48 applications, forward twice (recomputed once) and backward once
    assert flash["flops"] == 48 * (2 + 2 + 4) * 1 * s * s * h
    assert flash["bytes"] == 48 * 16 * 1 * s * h * 2
    olmoe = 6 * 4 * s * s * h          # needs_olmoe: one layer, batch 4
    assert flash["flops"] / 48 / 8 == olmoe / 6 / 4
    # the recomputed layers are 5.7 of 25.4 executed GFLOP a token
    executed = step["per_token"] + 4 * 12 * (projections + ffn + scores)
    assert executed == pytest.approx(25.4e9, rel=3e-3)


class _Evidence:
    def __init__(self, trace, say=print):
        self.trace, self.say = trace, say


def test_phase_reader_sums_the_recomputed_events_over_busy_time(monkeypatch):
    """``phase_time_share`` on a hand-made trace: four events of 10 us, one
    a forward op inside the loop, one the same op recomputed, one its
    backward, one an optimizer op outside any loop."""
    events = [("%fusion.1 = f32[] fusion()", 0.0, 10e3),
              ("%fusion.2 = f32[] fusion()", 10e3, 20e3),
              ("%fusion.3 = f32[] fusion()", 20e3, 30e3),
              ("%fusion.4 = f32[] fusion()", 30e3, 40e3)]
    trace = tr.Trace({"/device:TPU:0": {tr.OPS_LINE: events}}, [],
                     (0.0, 50e3))
    found = {"fusion.1": ("scan#3", "forward"),
             "fusion.2": ("scan_grad#9", "recompute"),
             "fusion.3": ("scan_grad#9", "backward"),
             "fusion.4": (None, "forward")}
    monkeypatch.setattr(phase_time_share, "phases", lambda ev: found)
    ev = _Evidence(trace)
    share = lambda **spec: phase_time_share.reduce(spec, ev)    # noqa: E731
    assert share(phase="recompute") == pytest.approx(25.0)
    assert share(phase="forward") == pytest.approx(50.0)
    assert share(phase="backward") == pytest.approx(25.0)
    # a program without the phases (a parent commit) reads nothing
    monkeypatch.setattr(phase_time_share, "phases", lambda ev: None)
    assert share(phase="recompute") is None
    assert phase_time_share.reduce({"phase": "recompute"},
                                   _Evidence(None)) is None


def _rehearse(cell):
    from test_benchmark_run import result_of, run_py
    for _ in range(3):
        r = run_py(["--workload", cell, "--seed", str(2 ** 31 + 11),
                    "--seconds", "1", "--trace", "1", "--cpu-rehearsal"])
        # the span reader refuses a capture whose host clocks jitter by over
        # 20 us (reducers/span_idle_overlap.py): this sandbox's cores do at
        # times, with every cell; that is not what this test is about
        if "the two clocks do not keep step" not in r.stderr:
            break
    return result_of(r)


def test_ouro_cell_rehearses_with_its_metrics():
    result, lines = _rehearse(CELL)
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    for name in ("attention.time_share", "matmul.time_share",
                 "elementwise.time_share", "embedding.time_share",
                 "norm_rope.time_share", "optimizer_adamw.time_share",
                 "loss.time_share", "recompute.time_share",
                 "exit_loss.time_share", "loop.kept_gb",
                 "compile.trace_lower_s", "memory.step_temp_gb"):
        assert got[name]["value"] > 0, name
    # the stack is lowered once in the train step: one lax.scan
    assert got["loop.stack_lowerings"]["value"] == 1.0
    # the recomputed forward is a part of the step, not most of it
    assert 3 < got["recompute.time_share"]["value"] < 50
    # no chip, no peak: the shares of a roofline are left out, not raised
    for name in ("flash_attention_causal_roofline.ouro",
                 "step.model_flops_share.ouro", "moe.time_share", "mfu"):
        assert name not in got
    # ops inside the loop's sub-block are read under their own types
    assert any("fused_attention" in ln and "device time by op type" in ln
               for ln in lines)
