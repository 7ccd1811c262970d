"""BENCHMARK.json against the contract's limits, and every data file of the
benchmark resolved the way run.py resolves it."""
import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(BENCH["paths"]) <= 16 and len(BENCH["command"]) <= 32
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check with all 24 cells must fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names), names
    assert all(len(x["why"]) <= 200
               for k in ("configs", "workloads") for x in BENCH[k])
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_file_under_paths_has_a_plain_name():
    plain = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in BENCH["paths"]:
        assert plain.match(p) and len(p) <= 200
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                assert plain.match(os.path.relpath(os.path.join(d, f), ROOT))


def test_metrics_follow_the_contract():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["better"] in ("higher", "lower")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        # reported only where the metric it moves is
        moved = set(e2e[m["moves"]].get("workloads", CELLS))
        assert set(m.get("workloads", CELLS)) <= moved, m["name"]
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    def names(kind):
        return [m["name"] for m in BENCH[kind]
                if cell in m.get("workloads", [cell])]
    assert "setup_s" in names("end_to_end")
    assert len(names("end_to_end")) >= 2 and names("per_layer")


@pytest.mark.parametrize("cell", CELLS)
def test_workload_files_resolve(cell):
    from benchmark import run
    for rehearsal in (False, True):
        c = run.load_cell(cell, rehearsal)
        job = importlib.import_module(f"benchmark.jobs.{c['job']}")
        assert callable(job.setup) and callable(job.measure)
        builder = importlib.import_module(
            f"benchmark.programs.{c['builder']}")
        assert callable(builder.build) and callable(builder.batch)
        ref = importlib.import_module(f"benchmark.references.{c['reference']}")
        assert callable(ref.loss)
        assert set(ref.tolerance(c["model"])) == {"loss", "each"}
        if c["flops"]:
            from benchmark import flops
            assert getattr(flops, c["flops"])(c["model"], c["params"])[
                "per_token"] > 0
        assert set(c["traced_window"]) <= {"steps", "seconds"}
        if c["chips"] > 1:
            n = 1
            for size in c["layout"]["mesh_shape"].values():
                n *= size
            assert n == c["chips"]
        else:
            assert not c["layout"]


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_resolve(config):
    assert config["source"].startswith("http")
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    files = [c["file"] for c in BENCH["configs"]]
    assert files.count(config["file"]) == 1
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    data = json.load(open(os.path.join(ROOT, config["file"])))
    assert data["name"] == config["name"]
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    assert isinstance(data["assumed"], dict) and data["deployment"]
    banned = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$"
                        r"|head_|expansion|experts_per)")
    assert not [k for k in config["reduced"] if banned.search(k)]


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_files_resolve(metric):
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", metric["name"] + ".json")))
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == metric[key], key
    reducer = importlib.import_module(f"benchmark.reducers.{spec['reducer']}")
    assert callable(reducer.reduce) and spec["doc"]
    if "need" in spec:
        from benchmark import flops
        assert callable(getattr(flops, spec["need"]))


def test_no_layer_metric_file_is_orphaned():
    listed = {m["name"] + ".json" for m in BENCH["per_layer"]}
    assert set(os.listdir(os.path.join(ROOT, "benchmark",
                                       "layer_metrics"))) == listed
    assert {w + ".json" for w in CELLS} == set(os.listdir(
        os.path.join(ROOT, "benchmark", "workloads")))
