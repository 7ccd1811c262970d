"""The benchmark's ``gated_delta.packed_ops`` metric (PR 43): its data file
against its ``BENCHMARK.json`` entry, and the count it reads on the counters
the Qwen3-Next cell's programs add. Kept beside the op's tests and not in
``tests/benchmark/test_benchmark_qwen3_next.py``: that file is the
benchmark's, and a PR that changes the program may only add to the
benchmark."""
import json
import os

from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "qwen3_next_80b_a3b.pretrain_s4096"


def test_packed_ops_resolves_and_reads_six_on_the_cells_counters():
    """``gated_delta.packed_ops`` (PR 43, a data file on ``registry_count``)
    agrees with its ``BENCHMARK.json`` entry, is the cell's alone, and over
    the counters the cell's two compiled programs add on the chip (three
    DeltaNet ops each, the kernels reading the packed q | k | v) reads 6,
    as ``gated_delta.pallas_ops`` does; an op that cut three operands out
    is left out of it, and a parent's counter, which has no ``operands``
    label, reads None rather than raising."""
    import importlib
    from paddle_tpu.observability import lowerings
    from paddle_tpu.observability.metrics import REGISTRY
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

    def spec_of(name):
        return json.load(open(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json")))
    spec = spec_of("gated_delta.packed_ops")
    entry = {m["name"]: m for m in bench["per_layer"]}[
        "gated_delta.packed_ops"]
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    # Qwen3-Next's first; a later cell with a packed rule follows it
    assert entry["workloads"][0] == CELL and entry["moves"] == "tokens_per_s"
    assert spec["layer"] == spec_of("gated_delta.pallas_ops")["layer"]
    assert spec["labels"] == {"impl": "pallas", "operands": "packed"}
    cell = run.load_cell(CELL, rehearsal=False)
    assert "gated_delta.packed_ops" in [m["name"] for m in cell["per_layer"]]
    reduce = importlib.import_module(
        f"benchmark.reducers.{spec['reducer']}").reduce
    read = lambda s: reduce(s, None) or 0.0                 # noqa: E731
    model = cell["model"]
    labels = {"impl": "pallas", "chunk": model["delta_chunk_size"],
              "heads": model["linear_num_value_heads"],
              "key_dim": model["linear_key_head_dim"],
              "value_dim": model["linear_value_head_dim"]}
    before = read(spec), read(spec_of("gated_delta.pallas_ops"))
    for program, salts, operands in (("pr43_test_clone", range(3), "packed"),
                                     ("pr43_train_step", range(3), "packed"),
                                     ("pr43_split", (9,), "split")):
        notes = {}
        for salt in salts:
            lowerings.note(notes, salt, "delta_lowering_total", 1,
                           dict(labels, operands=operands))
        lowerings.publish(notes, program)
    assert read(spec) - before[0] == 6.0
    assert read(spec_of("gated_delta.pallas_ops")) - before[1] == 7.0
    # the parent's children carry no operands label
    REGISTRY.counter("pr43_parent_delta_lowering_total", program="p",
                     impl="pallas", chunk="128").inc(3)
    assert reduce(dict(spec, match="pr43_parent_delta_lowering_total"),
                  None) is None
    assert reduce(dict(spec, match="pr43_no_such_counter"), None) is None
