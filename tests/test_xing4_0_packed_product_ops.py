"""The benchmark's ``hyper_connection.packed_product_ops`` metric (PR 62): its
data file against its ``BENCHMARK.json`` entry, the count it reads on the
counters the Xing4.0 cell's programs add, and the cell's rehearsal through
``benchmark/run.py`` reporting it. Kept beside the op's tests and not in
``tests/benchmark/test_benchmark_xing4_0.py``: that file is the benchmark's,
and a PR that changes the program may only add to the benchmark."""
import importlib
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "xing4_0_29b_a4b.pretrain_s4096"
NAME = "hyper_connection.packed_product_ops"
LABEL = "[cpu-rehearsal on cpu, not a chip run] "


def spec_of(name):
    return json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json")))


def test_benchmark_json_names_the_pieces_counter_for_the_one_cell():
    """A data file over the reducer the benchmark had: ``registry_count`` of
    ``hyper_connection_lowering_total{product="pieces"}``, the one cell's."""
    from benchmark import run
    from paddle_tpu.observability import lowerings
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry, = (m for m in bench["per_layer"] if m["name"] == NAME)
    assert bench["per_layer"][-1] is entry      # appended, nothing moved
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter", "layer": "hyper_connection",
                     "moves": "tokens_per_s", "workloads": [CELL]}
    spec = spec_of(NAME)
    assert spec["reducer"] == "registry_count"
    assert spec["match"] == "hyper_connection_lowering_total"
    assert spec["labels"] == {"product": "pieces"}
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    assert spec["layer"] == spec_of("hyper_connection.lowered_ops")["layer"]
    # the label is one the counter has
    assert "product" in lowerings.FAMILIES[spec["match"]][1]
    cell = run.load_cell(CELL, rehearsal=False)
    assert NAME in [m["name"] for m in cell["per_layer"]]


def test_it_reads_thirty_on_the_cells_counters_and_none_on_a_parents():
    """Over the counters the cell's two compiled programs add on the chip
    (ten read-side ops forward in the check's clone, ten forward and ten
    backward in the train step, ten write-side ops beside each) the metric
    reads 30 where ``hyper_connection.lowered_ops`` reads 60; a float32
    state's ``highest`` lowering is left out of it, and a parent's counter,
    which has no ``product`` label, reads None rather than raising."""
    from paddle_tpu.observability import lowerings
    from paddle_tpu.observability.metrics import REGISTRY
    spec, every = spec_of(NAME), spec_of("hyper_connection.lowered_ops")
    reduce = importlib.import_module(
        f"benchmark.reducers.{spec['reducer']}").reduce
    read = lambda s: reduce(s, None) or 0.0                 # noqa: E731
    before = read(spec), read(every)
    labels = {"streams": 4, "iters": 20}
    for program, directions, product in (
            ("pr62_test_clone", ("forward",), "pieces"),
            ("pr62_train_step", ("forward", "backward"), "pieces"),
            ("pr62_float32", ("forward",), "highest")):
        notes, salt = {}, 0
        for direction in directions:
            for part, how in (("pre", product), ("post", "none")):
                for _ in range(10):
                    lowerings.note(notes, salt, spec["match"], 1, dict(
                        labels, part=part, direction=direction, product=how))
                    salt += 1
        lowerings.publish(notes, program)
    assert read(spec) - before[0] == 30.0
    assert read(every) - before[1] == 60.0 + 20.0
    REGISTRY.counter("pr62_parent_hyper_connection_lowering_total",
                     program="p", part="pre", direction="forward",
                     streams="4", iters="20").inc(3)
    assert reduce(dict(spec, match="pr62_parent_hyper_connection_"
                       "lowering_total"), None) is None
    assert reduce(dict(spec, match="pr62_no_such_counter"), None) is None


def test_the_cells_rehearsal_reports_it():
    """Through ``benchmark/run.py`` at the rehearsal's sizes (two layers, a
    bfloat16 state): four read-side ops in the check's clone and four each
    way in the train step go by pieces, of the 24 hyper-connection ops."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    for _ in range(3):
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", CELL, "--seed", str(2 ** 31 + 62), "--seconds",
             "1", "--trace", "1", "--cpu-rehearsal"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
        # the span reader refuses a capture whose host clocks jitter by over
        # 20 us: this sandbox's cores do at times, with every cell
        if "the two clocks do not keep step" not in r.stderr:
            break
    assert r.returncode == 3, r.stderr[-2000:]
    result = json.loads(r.stdout.strip().splitlines()[-1][len(LABEL):])
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    assert got["hyper_connection.lowered_ops"]["value"] == 4 * 2 + 4 * 2 * 2
    assert got[NAME]["value"] == 4 + 4 * 2
