"""The Mellum2 cell's pieces at small sizes on the CPU: the configuration
against its own published copy (and the catalog's row where the catalog has
one), ``decoder_lm`` reading every key of it, the files resolving, the
closed forms of benchmark/needs_mellum2.py against brute force, the
exchange's byte count against the gauge the program sets, and the cell
through run.py on four CPU devices. It asserts no count of cells,
configurations or four-chip cells (PERF.md 7)."""
import importlib
import json
import os
import re

import numpy as np
import pytest

from benchmark import needs_mellum2 as needs
from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mellum2_12b_a2_5b.pretrain_s4096_ep4"
NAME = "mellum2_12b_a2_5b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state|proj|_dim$"
                   r"|_rank$|head_|expansion|experts_per)")
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types"]


def config():
    return json.load(open(os.path.join(
        ROOT, "benchmark", "configs", NAME + ".json")))


def catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    for line in open(CATALOG):
        row = json.loads(line)
        if row["name"] == "Mellum2-12B-A2.5B-Instruct":
            return row
    pytest.skip("the catalog has no Mellum2 row")


def test_reduced_is_exactly_what_differs_from_the_published_copy():
    data = config()
    published = data["published"]
    differ = [k for k, v in published.items() if data.get(k, "?") != v]
    assert sorted(differ) == sorted(data["reduced"]) == sorted(REDUCED)
    assert not [k for k in data["reduced"] if WIDTH.search(k)]
    for key, want in (
            ("hidden_size", 2304), ("num_attention_heads", 32),
            ("num_key_value_heads", 4), ("head_dim", 128),
            ("num_experts", 64), ("num_experts_per_tok", 8),
            ("moe_intermediate_size", 896), ("intermediate_size", 7168),
            ("vocab_size", 98304), ("sliding_window", 1024),
            ("norm_topk_prob", True), ("use_sliding_window", True),
            ("max_window_layers", 0), ("rms_norm_eps", 1e-6),
            ("tie_word_embeddings", False), ("model_type", "mellum")):
        assert data[key] == published[key] == want, key
    assert data["rope_parameters"] == published["rope_parameters"]
    assert data["num_hidden_layers"] == 4 and published[
        "num_hidden_layers"] == 28
    assert data["layer_types"] == published["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert data["mlp_layer_types"] == ["sparse"] * 4
    # the deployment's keys, each under assumed with its reason
    assert (data["expert_axis"], data["vocab_axis"], data["qk_norm"]) == (
        "dp", "dp", "head")
    for key in ("expert_axis", "vocab_axis", "mesh_shape", "qk_norm", "mtp",
                "row_budget", "optimizer", "init"):
        assert key in data["assumed"], key
    # ISSUE 55's recipe as it was stated: a pre-training rate, every weight
    # at 0.02 (decoder_lm has no other), the startup program's mesh the
    # workload's layout
    assert data["deployment"] and data["learning_rate"] == 4e-4
    assert "attention_out_init_std" not in data
    assert data["mesh_shape"] == {"dp": 4}
    # the receive buffer: 1.25 x what an even router delivers a chip
    cell = run.load_cell(CELL, rehearsal=False)
    assert cell["chips"] == 4 and cell["job"] == "train_feed"
    assert cell["layout"] == {"mesh_shape": {"dp": 4},
                              "data_rules": [["ids|labels", ["dp"]]]}
    params = cell["params"]
    assert (params["batch"], params["seq"], params["ring"],
            params["loss_read_every"]) == (8, 4096, 8, 10)
    even = params["batch"] * params["seq"] * 8 // 4
    assert even == 65536 and data["moe_row_budget"] == even * 5 // 4 == 81920


def test_published_block_holds_every_key_of_the_catalog_row():
    row = catalog_row()
    data = config()
    assert data["source"] == row["source_url"]
    assert data["published"] == row["config"]
    for key, value in row["config"].items():
        if key not in data["reduced"]:
            assert data[key] == value, key


def test_decoder_lm_reads_the_row_and_refuses_a_window_without_layer_types():
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_lm
    row = catalog_row()
    small = dict(row["config"], **config()["rehearsal"])
    del small["moe_row_budget"]     # the catalog's row has no deployment
    small.update(num_hidden_layers=4,
                 layer_types=row["config"]["layer_types"][:4],
                 mlp_layer_types=row["config"]["mlp_layer_types"][:4],
                 qk_norm="head")

    def build(cfg):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            A = dict(append_batch_size=False)
            ids = fluid.data("ids", [2, 16], "int64", **A)
            labels = fluid.data("labels", [32, 1], "int64", **A)
            decoder_lm.build(cfg, ids, labels)
        return main
    main = build(small)
    windows = [op.attr("window") for op in main.global_block().ops
               if op.type == "fused_attention"]
    assert windows == [8, 8, 8, None] or windows == [8, 8, 8, 0]
    factors = [op.attr("attention_factor", 1.0)
               for op in main.global_block().ops
               if op.type == "rotary_embedding"]
    assert factors[-1] == pytest.approx(1.2772588722239782)
    assert len(main.global_block().all_parameters()) == 1 + 12 * 4 + 2
    bare = {k: v for k, v in small.items() if k != "layer_types"}
    with pytest.raises(NotImplementedError, match="use_sliding_window"):
        build(bare)
    with pytest.raises(NotImplementedError, match="max_window_layers"):
        build(dict(small, max_window_layers=2))


def test_files_resolve_and_every_metric_has_its_reader():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}[NAME]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    cell = run.load_cell(CELL, rehearsal=False)
    importlib.import_module(f"benchmark.programs.{cell['builder']}")
    importlib.import_module(f"benchmark.references.{cell['reference']}")
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == {
        "moe_exchange.time_share", "moe_exchange.exposed_ms_per_step",
        "moe_exchange.ici_share", "moe_exchange.rows_per_step",
        "kernels.mesh_island_ops.mellum2",
        "moe_held_expert_matmul_roofline.mellum2",
        "flash_attention_window_roofline.mellum2",
        "flash_attention_gqa_causal_roofline.mellum2",
        "attention.window_k_tiles_visited.mellum2",
        "step.model_flops_share.mellum2"}
    for m in cell["per_layer"]:
        spec = json.load(open(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".json")))
        reducer = importlib.import_module(
            f"benchmark.reducers.{spec['reducer']}")
        assert callable(reducer.reduce)
        for key in ("unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        if "needs" in spec:
            module, function = spec["needs"].split(":")
            assert callable(getattr(importlib.import_module(
                f"benchmark.{module}"), function))
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}[
        "tokens_per_s"]["workloads"]


def test_new_readers_read_nothing_where_there_is_nothing():
    """A parent commit has neither the scopes nor the label: the readers
    return None and raise nothing."""
    from benchmark import trace as tr
    from benchmark.reducers import (collective_in_scopes,
                                    registry_count_families)

    class Nothing:
        trace = None
    for what in ("exposed_ms_per_step", "wire_share"):
        assert collective_in_scopes.reduce(
            {"match": ["moe_exchange*#*"], "what": what}, Nothing()) is None
    assert registry_count_families.reduce(
        {"match": "*_no_such_family_total", "labels": {"mesh": "island"}},
        Nothing()) is None
    assert "ragged-all-to-all" in collective_in_scopes.OPCODES
    assert set(tr.COLLECTIVE_OPCODES) < set(collective_in_scopes.OPCODES)


def test_closed_forms_against_brute_force_at_a_small_size():
    model = dict(config(), hidden_size=8, head_dim=4, num_attention_heads=4,
                 num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
                 moe_intermediate_size=6, sliding_window=5, vocab_size=32)
    params = {"batch": 4, "seq": 12}
    b, s, h, d = 4, 12, 4, 4
    pairs_w = sum(1 for i in range(s) for j in range(s)
                  if j <= i and j > i - 5)
    pairs_c = sum(1 for i in range(s) for j in range(s) if j <= i)
    assert needs.window_pairs(s, 5) == pairs_w
    window = needs.flash_attention_window(model, params)
    assert window["flops"] == 3 * 3 * b * h * 2 * 2 * pairs_w * d
    full = needs.flash_attention_gqa_causal(model, params)
    assert full["flops"] == 6 * b * h * s * s * d      # half the square x 12
    assert window["bytes"] == 3 * 6 * b * s * d * (4 + 2) * 2
    rows = b * s * 2
    experts = needs.moe_expert_matmul(model, params)
    assert experts["flops"] == 4 * 3 * 3 * 2 * rows * 8 * 6
    assert experts["bytes"] == 4 * 9 * (rows * 8 + 8 * 8 * 6 + rows * 6) * 2
    # K tiles a window kernel visits, by walking the blocks
    seq, w, bq, bk = 64, 20, 8, 16
    visited = sum(len({j // bk for i in range(iq * bq, (iq + 1) * bq)
                       for j in range(max(0, i - w + 1), i + 1)})
                  for iq in range(seq // bq))
    assert needs.window_k_tiles(seq, w, bq, bk) == visited
    step = needs.train_step(model, params)
    tokens = b * s
    forward = 4 * tokens * (2 * 8 * 2 * 16 + 2 * 8 * 2 * 8)    # q o, k v
    forward += b * h * 2 * 2 * d * (3 * pairs_w + pairs_c)
    forward += 4 * tokens * 2 * 8 * 8 + 4 * rows * 3 * 2 * 8 * 6
    forward += tokens * 2 * 8 * 32
    assert step["flops"] == 3 * forward
    # the cell itself: about 27 TFLOP a chip and step (ISSUE 55)
    cell = run.load_cell(CELL, rehearsal=False)
    a_chip = needs.train_step(cell["model"], cell["params"])["flops"] / 4
    assert 24e12 < a_chip < 30e12
    wire = needs.moe_exchange(cell["model"], cell["params"])
    assert wire["rows"] == 4 * 16 * 49152
    # 3.6 GB out and 3.6 GB in a chip and step
    assert wire["bytes"] / 4 == 2 * 16 * 49152 * 2304 * 2
    assert 3.5e9 < wire["bytes"] / 4 / 2 < 3.7e9


def test_exchange_byte_count_against_the_gauge():
    """needs_mellum2.moe_exchange counts the rows' bytes; the gauge the
    program sets at the compile adds the float32 weights that cross with
    them, 4 bytes a row."""
    import paddle_tpu as fluid
    from paddle_tpu.observability.metrics import REGISTRY
    from benchmark.programs import mellum2_pretrain as program
    data = config()
    model = {k: v for k, v in data.items() if k != "rehearsal"}
    model.update(data["rehearsal"])
    params = {"batch": 4, "seq": 32}
    built = program.build(model, params)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(built["startup"], scope=scope)
    batch = program.batch(model, params, np.random.RandomState(0))
    prog = fluid.CompiledProgram(built["main"]).with_strategy(
        fluid.DistributedStrategy(mesh_shape={"dp": 4},
                                  data_rules=[("ids|labels", ("dp",))]))
    exe.run(prog, feed=batch, fetch_list=[built["loss"]], scope=scope)
    label = f"{id(built['main'])}:v{built['main']._version}"

    def gauge(name):
        return sum(child.value for labels, child in REGISTRY.get(name).items()
                   if ("program", label) in labels)
    need = needs.moe_exchange(model, params)
    rows = gauge("moe_exchange_even_rows")
    assert rows * 4 == need["rows"]             # a chip's against all four's
    assert need["bytes"] == need["rows"] * model["hidden_size"] * 2 * 2


def _rehearse(trace):
    from test_benchmark_run import run_py
    for _ in range(3):
        r = run_py(["--workload", CELL, "--seed", str(2 ** 31 + 55),
                    "--seconds", "1", "--trace", trace, "--cpu-rehearsal"],
                   devices=4)
        # the span reader refuses a capture whose host clocks jitter by over
        # 20 us (reducers/span_idle_overlap.py): this sandbox's cores do at
        # times, with every cell; that is not what this test is about
        if "the two clocks do not keep step" not in r.stderr:
            break
    return r


def test_mellum2_cell_rehearses_untraced_on_four_devices():
    from test_benchmark_run import result_of
    result, lines = result_of(_rehearse("0"))
    assert result["failed"] == 0 and result["attempted"] > 0
    checks = json.loads(next(ln for ln in lines if "checks: " in ln)
                        .split("checks: ", 1)[1])
    # the CPU backend reports no bytes in use, so that one check cannot
    # hold in a rehearsal (as for bert_base.pretrain_s128_dp4)
    assert checks.pop("bytes_grew_on_every_device") is False
    assert all(checks.values()), checks
    got = result["metrics"]
    assert got["tokens_per_s"]["value"] > 0 and got["setup_s"]["value"] > 0
    assert result["device"]["count"] == 4


def test_mellum2_cell_rehearses_with_its_metrics():
    from test_benchmark_run import result_of
    r = _rehearse("1")
    if "the two clocks do not keep step" in r.stderr:
        pytest.skip("this machine's host clocks jitter past the span "
                    "reader's 20 us three times running (PERF.md 7 (j))")
    result, lines = result_of(r)
    got = result["metrics"]
    for name in ("attention.time_share", "moe.time_share",
                 "moe_dispatch.time_share", "loss.time_share",
                 "optimizer_adamw.time_share", "matmul.time_share",
                 "elementwise.time_share", "embedding.time_share",
                 "moe_exchange.time_share", "compile.trace_lower_s",
                 "memory.step_state_gb"):
        assert got[name]["value"] > 0, name
    # four layers, a receive buffer of 256 rows a chip: 4 x 4 x 256
    assert got["moe.row_budget_rows"]["value"] == 4 * 4 * 256
    # a chip's 64 assignments x 3/4, four crossings a layer, four layers
    assert got["moe_exchange.rows_per_step"]["value"] == 16 * 48
    # no chip: no kernel is lowered and no peak is known, so the island
    # count and the shares of a roofline or a peak are left out, not raised
    for name in ("kernels.mesh_island_ops.mellum2",
                 "moe_held_expert_matmul_roofline.mellum2",
                 "flash_attention_window_roofline.mellum2",
                 "flash_attention_gqa_causal_roofline.mellum2",
                 "step.model_flops_share.mellum2", "moe_exchange.ici_share"):
        assert name not in got or name == "kernels.mesh_island_ops.mellum2"
