"""The two per-layer metrics PR 42 added as data files on readers the
benchmark has: each resolves as ``benchmark/run.py`` resolves it, agrees
with its ``BENCHMARK.json`` entry, is listed in the four decoder cells, and
``rope.one_pass_ops`` counts the ops of a compiled Program."""
import fnmatch
import importlib
import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = ["olmoe_1b_7b.pretrain_s4096", "lfm2_8b_a1b.pretrain_s4096",
         "laguna_s_2_1.pretrain_s4096", "qwen3_next_80b_a3b.pretrain_s4096",
         "mellum2_12b_a2_5b.pretrain_s4096_ep4", "ouro_2_6b.pretrain_s4096"]


def spec_of(name):
    return json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json")))


@pytest.mark.parametrize("name,reducer", [
    ("rope.time_share", "scope_time_share"),
    ("rope.one_pass_ops", "registry_count")])
def test_rope_metric_file_resolves_and_agrees_with_its_entry(name, reducer):
    spec = spec_of(name)
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["workloads"] == CELLS and entry["moves"] == "tokens_per_s"
    assert spec["reducer"] == reducer and spec["doc"]
    assert callable(importlib.import_module(
        f"benchmark.reducers.{reducer}").reduce)
    # the layer's name as the accepted metrics of the op's layer spell it
    assert spec["layer"] == spec_of("norm_rope.time_share")["layer"]


def test_rope_time_share_takes_the_op_and_its_grad_and_nothing_else():
    globs = spec_of("rope.time_share")["match"]
    hit = lambda scope: any(                                # noqa: E731
        fnmatch.fnmatchcase(scope, g) for g in globs)
    assert hit("rotary_embedding#14") and hit("rotary_embedding_grad#86")
    assert not hit("rms_norm#13") and not hit("transpose2#12")
    # a part of norm_rope.time_share, which takes the same scopes
    assert set(globs) < set(spec_of("norm_rope.time_share")["match"])


def test_rope_one_pass_ops_counts_a_programs_kernel_ops_both_directions():
    """Forward and backward of every op that took the kernel (the harness'
    interpreter stands in for the chip); an op left composed is not
    counted, and a registry without the counter reads None, as a parent
    commit's does."""
    from benchmark.reducers import registry_count
    from paddle_tpu.observability.metrics import MetricsRegistry
    spec = spec_of("rope.one_pass_ops")
    read = lambda: registry_count.reduce(spec, None) or 0.0  # noqa: E731
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [2, 2, 16, 64], "float32",
                       append_batch_size=False)
        x.stop_gradient = False
        q = layers.rotary_embedding(layers.scale(x, 1.0))
        k = layers.rotary_embedding(layers.scale(x, 2.0), rotary_dim=32)
        y = fluid.data("y", [2, 2, 6, 8], "float32", append_batch_size=False)
        y.stop_gradient = False     # a shape the kernel leaves to XLA
        left = layers.rotary_embedding(layers.scale(y, 1.0))
        fluid.append_backward(layers.elementwise_add(
            layers.reduce_sum(layers.elementwise_add(q, k)),
            layers.reduce_sum(left)))
    before = read()
    exe = fluid.Executor()
    exe.run(main, feed={"x": np.ones((2, 2, 16, 64), "float32"),
                        "y": np.ones((2, 2, 6, 8), "float32")},
            fetch_list=[q])
    exe.close()
    assert read() - before == 4.0       # two ops, forward and backward
    from paddle_tpu.observability import lowerings
    fresh, notes = MetricsRegistry(), {}
    lowerings.note(notes, 1, "rotary_lowering_total", 1,
                   {"direction": "forward", "form": "composed"})
    lowerings.note(notes, 1, "rotary_lowering_total", 1,
                   {"direction": "backward", "form": "generic"})
    lowerings.publish(notes, "p", fresh)
    forms = [dict(k)["form"] for k, _ in
             fresh.get("rotary_lowering_total").items()]
    assert sorted(forms) == ["composed", "generic"]
    assert spec["labels"]["form"] not in forms
