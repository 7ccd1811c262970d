"""benchmark/run.py as a command (subprocesses on the CPU): the refusal
without a TPU, the result line's keys, the benchmark alone in a directory,
and the data-only drill -- a new cell, configuration and per-layer metric
added as files, with no file of the harness edited."""
import filecmp
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LABEL = "[cpu-rehearsal on cpu, not a chip run] "
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run_py(args, root=ROOT, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py")] + args,
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def result_of(r):
    assert r.returncode == 3, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert all(ln.startswith(LABEL) for ln in lines)
    return json.loads(lines[-1][len(LABEL):]), lines


def test_refuses_without_a_tpu_and_prints_no_result():
    for cell in ("bert_base.pretrain_s128", "bert_base.pretrain_s128_dp4"):
        r = run_py(["--workload", cell, "--seed", "1", "--seconds", "1",
                    "--trace", "0"], devices=4)
        assert r.returncode == 2, (r.returncode, r.stderr[-800:])
        assert "'cpu'" in r.stderr and "nothing was run" in r.stderr
        assert r.stdout.strip() == ""


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: past the platform
    gate (reached here through the rehearsal flag) the benchmark needs the
    program, so it exits non-zero with no result line."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "deepfm_criteo.files_b4096", "--cpu-rehearsal"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode not in (0, 3), r.stdout[-400:]
    assert "No module named 'paddle_tpu'" in r.stderr
    assert "{" not in r.stdout


def test_end_to_end_line_has_the_contract_keys_and_no_others():
    r = run_py(["--workload", "deepfm_criteo.files_b4096", "--seed", "2",
                "--seconds", "1", "--trace", "0", "--cpu-rehearsal"])
    result, lines = result_of(r)
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == DEVICE_KEYS
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["attempted"] % 2048 == 0
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    want = {m["name"] for m in bench["end_to_end"]
            if "deepfm_criteo.files_b4096" in m.get(
                "workloads", ["deepfm_criteo.files_b4096"])}
    assert want == {"examples_per_s", "peak_hbm_gb", "setup_s"}
    assert set(result["metrics"]) == want
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
    assert any("losses (step, value)" in ln for ln in lines)
    assert any('"no_compile_in_window": true' in ln for ln in lines)


def test_data_only_drill(tmp_path):
    """A later PR adds files and entries and edits no file that is there.
    In a copy of the benchmark: a configuration of an existing builder, a
    cell of an existing job kind and a scope-share metric arrive as three
    data files plus their BENCHMARK.json entries, and run.py takes them."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b = tmp_path / "benchmark"

    config = json.load(open(b / "configs" / "bert_base.json"))
    config.update(name="bert_small", num_hidden_layers=4, hidden_size=512,
                  num_attention_heads=8, intermediate_size=2048)
    config["rehearsal"] = {"hidden_size": 32, "num_hidden_layers": 1,
                           "num_attention_heads": 2, "intermediate_size": 64,
                           "vocab_size": 256}
    json.dump(config, open(b / "configs" / "bert_small.json", "w"))
    bench["configs"].append({
        "name": "bert_small", "source": config["source"], "reduced": [],
        "file": "benchmark/configs/bert_small.json", "why": "drill"})

    mix = json.load(open(b / "workloads" / "bert_base.pretrain_s128.json"))
    mix["rehearsal"].update(batch=4, seq=8, masks_per_seq=2)
    json.dump(mix, open(b / "workloads" / "bert_small.pretrain_s8.json", "w"))
    bench["workloads"].append({
        "name": "bert_small.pretrain_s8", "config": "bert_small",
        "traffic": "pretrain_s8", "chips": 1, "why": "drill"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "bert_base.pretrain_s128" in m.get("workloads", []):
            m["workloads"].append("bert_small.pretrain_s8")

    json.dump({"name": "layer_norm.time_share", "layer": "dense_ops",
               "reducer": "scope_time_share", "unit": "%", "better": "lower",
               "source": "device_trace", "moves": "tokens_per_s",
               "match": ["layer_norm#*", "layer_norm_grad#*"],
               "doc": "drill"},
              open(b / "layer_metrics" / "layer_norm.time_share.json", "w"))
    bench["per_layer"].append({
        "name": "layer_norm.time_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "dense_ops",
        "moves": "tokens_per_s", "workloads": ["bert_small.pretrain_s8"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))

    r = run_py(["--workload", "bert_small.pretrain_s8", "--seed", "3",
                "--seconds", "1", "--trace", "1", "--cpu-rehearsal"],
               root=str(tmp_path))
    result, lines = result_of(r)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert set(result["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert result["correct"] is True
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in result["breakdown"].values())
    got = result["metrics"]
    assert got["layer_norm.time_share"]["value"] > 0          # the new one
    assert got["optimizer.time_share"]["value"] > 0           # an old one
    assert "flash_attention_roofline" not in got    # not this cell's
    assert "collective.time_share" not in got       # nothing to read
    assert "tokens_per_s" not in got                # trace 1: per-layer only
    assert any("bert_small.pretrain_s8" in ln for ln in lines[:1])
    # no file of the harness was edited: every file that was there is equal
    for d, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in d:
            continue
        for f in files:
            src = os.path.join(d, f)
            assert filecmp.cmp(src, os.path.join(
                str(tmp_path), os.path.relpath(src, ROOT)), shallow=False)
