"""IR->HLO attribution, hlo_diff, PT060 layout-churn lint, and the bench
trajectory sentinel (ISSUE 16).

The contract under test: every op lowering runs inside
``jax.named_scope("<op_type>#<op_idx>")`` so the optimized HLO carries
Program-IR identity; the compile-miss walk buckets bytes per IR op and
category, exports ``hlo_op_bytes{program,category}`` gauges (retired with
the program), blames copy/transpose round-trips on (producer, consumer)
op pairs feeding PT060 -- and all of it costs literally zero calls when
observability is off.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis
from paddle_tpu.observability import attribution
from paddle_tpu.observability.metrics import REGISTRY, MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _simple_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [32], "float32")
        y = fluid.data("y", [1], "float32")
        h = fluid.layers.fc(x, 64, act="relu")
        p = fluid.layers.fc(h, 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(p, y))
        fluid.optimizer.SGD(0.01).minimize(loss)
    return main, startup, loss


def _simple_feed(b=16):
    rng = np.random.RandomState(0)
    return {"x": rng.rand(b, 32).astype("float32"),
            "y": rng.rand(b, 1).astype("float32")}


def _resnet_program():
    from paddle_tpu.models import resnet
    resnet._DEPTHS[8] = [1, 1, 1, 1]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [3, 32, 32], "float32")
        label = fluid.data("label", [1], "int64")
        loss, acc, _ = resnet.resnet(img, label, depth=8, num_classes=10)
        fluid.optimizer.Momentum(0.1, 0.9).minimize(loss)
    return main, startup, loss


def _resnet_feed():
    rng = np.random.RandomState(0)
    return {"img": rng.rand(4, 3, 32, 32).astype("float32"),
            "label": rng.randint(0, 10, (4, 1)).astype("int64")}


# ------------------------------------------------------- the tentpole pin --

def test_resnet_attribution_coverage_layout_and_pt060(monkeypatch):
    """Acceptance pin: on the bundled resnet program >90% of XLA
    cost_analysis bytes land on named IR ops, the copy/layout category is
    nonzero, and PT060 names the offending op pair."""
    monkeypatch.setenv("PADDLE_TPU_OBS_ATTRIB", "1")
    main, startup, loss = _resnet_program()
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=_resnet_feed(), fetch_list=[loss])
        att = attribution.lookup_program(main)
        assert att is not None, "attribution not recorded at compile miss"
        # the model's bytes agree with XLA's aggregate, and >90% of them
        # carry Program-IR identity
        assert att.cost_bytes and att.cost_bytes > 0
        assert att.attributed_bytes / att.cost_bytes > 0.90, \
            f"only {att.attributed_bytes / att.cost_bytes:.1%} of " \
            f"cost_analysis bytes attributed"
        assert att.coverage > 0.90
        # the ROOFLINE copy-done tax reproduced as attributed layout bytes
        layout = att.per_category.get("layout", {})
        assert layout.get("bytes", 0) > 0 and layout.get("instructions", 0) > 0
        assert att.copy_pairs, "no copy pairs blamed"
        # the dominant round-trips name real IR ops on at least one side
        # (weight-layout copies feeding the momentum update, conv/reduce
        # boundaries); "#" marks a resolved <op_type>#<op_idx> token
        top = att.top_copy_pairs(5)
        assert any("#" in p or "#" in c for (p, c), _ in top), top
        # per-category gauges exported under this program's label
        fam = REGISTRY.get("hlo_op_bytes")
        cats = {dict(k).get("category") for k in fam.children
                if dict(k).get("program") == att.label}
        assert "layout" in cats and "compute" in cats
        # PT060: the opt-in layout_churn pass surfaces the pairs
        diags = analysis.run_passes(main, passes=["layout_churn"])
        pt060 = [d for d in diags if d.code == "PT060"]
        assert pt060, "layout_churn produced no PT060 on resnet"
        msg = str(pt060[0])
        assert "layout round-trip" in msg and "/step" in msg
        assert "#" in msg  # names an attributed op pair
        exe.close()
        # retirement: close() dropped this program's category series
        fam = REGISTRY.get("hlo_op_bytes")
        assert not [k for k in fam.children
                    if dict(k).get("program") == att.label]


def test_named_scope_metadata_survives_to_hlo(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_OBS_ATTRIB", "1")
    main, startup, loss = _simple_program()
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=_simple_feed(), fetch_list=[loss])
        att = attribution.lookup_program(main)
        assert att is not None and att.coverage > 0.9
        # op_name metadata carries "<op_type>#<op_idx>" tokens
        text = getattr(att, "_hlo_text", "")
        assert "mul#" in text or "matmul#" in text or "fc" in text
        assert any("#" in k for k in att.per_ir), att.per_ir
        exe.close()


def test_obs_unset_hot_path_zero_attribution_work(monkeypatch):
    """The guard: with observability off the attribution walk never runs
    -- not at compile, not per step.  With PADDLE_TPU_OBS_ATTRIB=1 it
    runs exactly once, at the compile miss."""
    calls = []
    real = attribution.attribute_hlo_text

    def spy(text, label="program"):
        calls.append(label)
        return real(text, label)

    monkeypatch.setattr(attribution, "attribute_hlo_text", spy)
    monkeypatch.delenv("PADDLE_TPU_OBS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_OBS_ATTRIB", raising=False)
    assert not attribution.attribution_enabled()
    main, startup, loss = _simple_program()
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=_simple_feed(), fetch_list=[loss])
        assert calls == [], "attribution ran with obs off"
        assert attribution.lookup_program(main) is None
        exe.close()

    monkeypatch.setenv("PADDLE_TPU_OBS_ATTRIB", "1")
    main2, startup2, loss2 = _simple_program()
    exe2 = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe2.run(startup2)
        for _ in range(3):
            exe2.run(main2, feed=_simple_feed(), fetch_list=[loss2])
        main_calls = [c for c in calls
                      if c.startswith(f"{id(main2)}:")]
        assert len(main_calls) == 1, \
            f"attribution must run once per compile miss, ran {calls}"
        exe2.close()


def test_retire_program_drops_only_its_label():
    reg = MetricsRegistry()
    for label in ("7:v1", "7:v10", "8:v1"):
        reg.gauge("hlo_op_bytes", "b", program=label,
                  category="layout").set(1.0)
        reg.gauge("hlo_attributed_bytes_fraction", "f",
                  program=label).set(0.9)
    attribution.retire_program("7:v1", registry=reg)
    left = {dict(k).get("program")
            for k in reg.get("hlo_op_bytes").children}
    assert left == {"7:v10", "8:v1"}, left
    left_f = {dict(k).get("program")
              for k in reg.get("hlo_attributed_bytes_fraction").children}
    assert left_f == {"7:v10", "8:v1"}


# ---------------------------------------------------------------- hlo_diff --

_HLO_BASE = """\
HloModule base

ENTRY %main.1 (Arg_0.1: f32[64,128], Arg_1.2: f32[128,256]) -> f32[64,256] {
  %Arg_0.1 = f32[64,128]{1,0} parameter(0)
  %Arg_1.2 = f32[128,256]{1,0} parameter(1)
  %dot.3 = f32[64,256]{1,0} dot(f32[64,128]{1,0} %Arg_0.1, f32[128,256]{1,0} %Arg_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jit(main)/matmul#0/dot_general"}
  ROOT %exp.4 = f32[64,256]{1,0} exponential(f32[64,256]{1,0} %dot.3), metadata={op_name="jit(f)/jit(main)/exp#1/exp"}
}
"""

_HLO_TRANSPOSED = """\
HloModule transposed

ENTRY %main.1 (Arg_0.1: f32[64,128], Arg_1.2: f32[128,256]) -> f32[256,64] {
  %Arg_0.1 = f32[64,128]{1,0} parameter(0)
  %Arg_1.2 = f32[128,256]{1,0} parameter(1)
  %dot.3 = f32[64,256]{1,0} dot(f32[64,128]{1,0} %Arg_0.1, f32[128,256]{1,0} %Arg_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jit(main)/matmul#0/dot_general"}
  %exp.4 = f32[64,256]{1,0} exponential(f32[64,256]{1,0} %dot.3), metadata={op_name="jit(f)/jit(main)/exp#1/exp"}
  %transpose.5 = f32[256,64]{0,1} transpose(f32[64,256]{1,0} %exp.4), dimensions={1,0}, metadata={op_name="jit(f)/jit(main)/transpose2#2/transpose"}
  ROOT %copy.6 = f32[256,64]{1,0} copy(f32[256,64]{0,1} %transpose.5), metadata={op_name="jit(f)/jit(main)/transpose2#2/transpose"}
}
"""


def test_hlo_diff_synthetic_injected_transpose():
    """Two programs whose only delta is an injected transpose->copy
    round-trip: the diff isolates it in the layout category and names
    the grown op."""
    a = attribution.attribute_hlo_text(_HLO_BASE, "A")
    b = attribution.attribute_hlo_text(_HLO_TRANSPOSED, "B")
    assert "layout" not in a.per_category
    lb = b.per_category["layout"]
    # transpose + copy of a f32[64,256]: 2 instrs, 2 * 2 * 64*256*4 bytes
    assert lb["instructions"] == 2 and lb["bytes"] == 4 * 65536
    assert ("transpose2#2", "output") in b.copy_pairs
    assert ("exp#1", "transpose2#2") in b.copy_pairs
    d = attribution.diff_attributions(a, b)
    cat = {r["category"]: r for r in d["categories"]}
    assert cat["layout"]["instructions_delta"] == 2
    assert cat["layout"]["bytes_delta"] == 4 * 65536
    assert d["ops"][0]["ir"] == "transpose2#2"
    assert d["ops"][0]["status"] == "new"
    text = attribution.format_diff(d)
    assert "transpose2#2" in text and "layout" in text
    # dot FLOPs model is exact: 2 * M * N * K
    assert a.model_flops >= 2 * 64 * 256 * 128


def test_capture_artifacts_diff_end_to_end(tmp_path):
    """An armed capture writes one artifact a compile miss; two of them
    (the startup program and the train step) load and diff."""
    outdir = str(tmp_path / "hlo")
    attribution.arm_capture(outdir)
    try:
        main, startup, loss = _simple_program()
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            exe.run(main, feed=_simple_feed(), fetch_list=[loss])
            exe.run(main, feed=_simple_feed(), fetch_list=[loss])  # a hit
            exe.close()
    finally:
        attribution.arm_capture(None)
    arts = sorted(os.listdir(outdir))
    want = [f"hlo_{id(p)}_v{p._version}.json" for p in (startup, main)]
    assert arts == sorted(want), arts
    a, b = (attribution.load_artifact(os.path.join(outdir, w)) for w in want)
    assert b.label == f"{id(main)}:v{main._version}"
    d = attribution.diff_attributions(a, b)
    cat = {r["category"]: r for r in d["categories"]}
    # the train step has the products the startup program has not
    assert cat["compute"]["instructions_delta"] > 0
    assert attribution.format_diff(d)
    # artifact carries the raw HLO for external tooling
    doc = json.load(open(os.path.join(outdir, want[1])))
    assert "ENTRY" in doc["hlo"]


def test_compute_warns_not_crashes_without_as_text():
    class _NoText:
        def as_text(self):
            raise NotImplementedError("backend says no")

        def cost_analysis(self):
            return [{}]

    with pytest.warns(RuntimeWarning, match="attribution unavailable"):
        assert attribution.compute(_NoText(), "prog-no-text") is None
    # warn-once per label: a second call is silent
    assert attribution.compute(_NoText(), "prog-no-text") is None
    # on_compile never raises on the same backend
    os.environ.get("PADDLE_TPU_OBS_ATTRIB")  # doc: gated path is no-op


# ----------------------------------------------------------- serving path --

def test_predictor_signature_gauges(monkeypatch, tmp_path):
    monkeypatch.setenv("PADDLE_TPU_OBS_ATTRIB", "1")
    d = str(tmp_path / "model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [8], "float32")
        logits = fluid.layers.fc(x, 4)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [logits], exe, main)
    exe.close()
    pred = fluid.inference.Predictor(d)
    pred.run({"x": np.ones((2, 8), "float32")})
    fam = REGISTRY.get("hlo_op_bytes")
    labels = {dict(k).get("program") for k in fam.children}
    preds = sorted(l for l in labels if l and l.startswith("predict:"))
    assert preds, f"no per-signature serving gauges in {labels}"
    frac = REGISTRY.get("hlo_attributed_bytes_fraction")
    cov = [g.value for k, g in frac.children.items()
           if dict(k).get("program") in preds]
    assert cov and all(c > 0.9 for c in cov)
    for label in preds:
        attribution.retire_program(label)


# --------------------------------------------------------- bench sentinel --

def test_bench_compare_flags_r06_fused_regression():
    """Over today's checked-in BENCH_WORKLOADS_r03..r06 rounds the
    sentinel must find the -30.9% fused-transformer A/B regression, and
    the shipped baseline must suppress every current finding (CI green)."""
    from tools import bench_compare
    paths = sorted(os.path.join(REPO, f"BENCH_WORKLOADS_r0{i}.json")
                   for i in (3, 4, 5, 6))
    assert all(os.path.exists(p) for p in paths)
    res = bench_compare.compare_files(paths)
    fused = [f for f in res["findings"] if f["kind"] == "within_round"
             and f["metric"] == "transformer_nmt_tokens_per_sec_fused"]
    assert fused and fused[0]["pct"] == -30.9, res["findings"]
    # cross-round comparisons never mix device kinds (r05 TPU -> r06 cpu)
    assert not any("r05->r06" in "".join(f["key"])
                   for f in res["findings"] if f["kind"] == "cross_round")
    res2 = bench_compare.compare_files(
        paths, baseline=os.path.join(REPO, "tools",
                                     "bench_baseline.jsonl"))
    assert not res2["fresh"] and res2["suppressed"] >= 2


def test_bench_compare_direction_awareness():
    from tools import bench_compare
    assert bench_compare.direction("x_tokens_per_sec") == 1
    assert bench_compare.direction("infer_latency_ms") == -1
    assert bench_compare.direction("goodput_fraction") == 1
    assert bench_compare.direction("mystery_metric") is None


# ------------------------------------------------------------ CLI smoke --

@pytest.mark.parametrize("module", ["tools.hlo_diff", "tools.bench_compare",
                                    "paddle_tpu.observability.attribution"])
def test_cli_selftests(module):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-m", module, "--selftest"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "selftest: OK" in r.stdout
