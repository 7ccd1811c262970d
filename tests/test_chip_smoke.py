"""PR 21 (bring-up): nothing on the main path may make a CPU, interpret-mode
or fallback run look like a chip run. Fast CPU checks of the gates that
chip_smoke.py relies on; the chip itself is reached only through the
builder's tool (`python chip_smoke.py`)."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_ROOT, "chip_smoke.py")


def _run_smoke(args, cwd=_ROOT, script=_SMOKE):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, script] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_cpu_before_building_anything():
    r = _run_smoke([])
    assert r.returncode == 2, (r.returncode, r.stderr[-800:])
    assert "'cpu'" in r.stderr and "not a TPU" in r.stderr
    lines = r.stdout.strip().splitlines()
    assert len(lines) == 1 and "platform cpu" in lines[0], r.stdout
    assert not any(ln.startswith("{") for ln in lines)   # no result line


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The contract's 'script alone, without the program' leg: past the
    platform gate (reached here through the rehearsal flag) it needs the
    package, so it exits non-zero and prints no result."""
    lone = shutil.copy(_SMOKE, tmp_path / "chip_smoke.py")
    r = _run_smoke(["--cpu-rehearsal", "--phases", "dataset"],
                   cwd=str(tmp_path), script=str(lone))
    assert r.returncode not in (0, 3), r.stdout[-400:]
    assert "No module named 'paddle_tpu'" in r.stderr
    assert "{" not in r.stdout


def test_verdict_line_has_the_contract_keys_and_no_others():
    """The driver parses the LAST stdout line: exactly {ok, device{platform,
    kind, count}}. Everything else the run learned goes on the summary line
    before it. Seen here through the rehearsal, which labels each line."""
    import json
    r = _run_smoke(["--cpu-rehearsal", "--phases", "dataset"])
    assert r.returncode == 3, r.stderr[-800:]
    label = "[cpu-rehearsal on cpu, not a chip run] "
    lines = r.stdout.strip().splitlines()
    assert all(ln.startswith(label) for ln in lines)
    verdict = json.loads(lines[-1][len(label):])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is False
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    summary = lines[-2][len(label):]
    assert summary.startswith("summary: ")
    assert json.loads(summary[len("summary: "):])["phases"]["dataset"]["ok"]


def test_compile_cache_helper_places_it_once(monkeypatch):
    import jax
    from paddle_tpu.utils import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV, "/placed/from/outside")
    assert compile_cache.arm() == "/placed/from/outside"
    assert calls == []              # JAX reads the env itself: set nothing
    monkeypatch.delenv(compile_cache.ENV)
    want = os.path.join(_ROOT, ".jax_cache")
    assert compile_cache.arm() == want
    assert calls == [("jax_compilation_cache_dir", want)]


def test_only_the_helper_names_the_cache_dir():
    import glob
    files = glob.glob(os.path.join(_ROOT, "*.py"))
    for d in ("paddle_tpu", "tests", "tools", "examples"):
        files += glob.glob(os.path.join(_ROOT, d, "**", "*.py"),
                           recursive=True)
    hits = []
    for path in files:
        with open(path, encoding="utf-8") as fh:
            if "jax_compilation_cache_dir" in fh.read():
                hits.append(os.path.relpath(path, _ROOT))
    assert sorted(hits) == ["paddle_tpu/utils/compile_cache.py",
                            "tests/test_chip_smoke.py"], hits


def _attention_program(impl):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        q = fluid.data("q", [2, 2, 128, 16], "float32",
                       append_batch_size=False)
        out = fluid.layers.fused_attention(q, q, q, impl=impl)
    return main, out


def test_pallas_off_tpu_needs_the_harness_attribute(monkeypatch):
    """With TEST_INTERPRET cleared (what every non-test process sees), off
    TPU: impl='pallas' raises, 'auto' lowers the composed path, and a
    tuning candidate that needs the kernel is unmeasurable."""
    from paddle_tpu.ops import pallas_mode
    from paddle_tpu.tuning import choices
    monkeypatch.setattr(pallas_mode, "TEST_INTERPRET", False)
    assert not pallas_mode.available() and not pallas_mode.interpret()
    feed = {"q": np.random.RandomState(0).randn(2, 2, 128, 16).astype("f4")}
    exe = fluid.Executor()
    main, out = _attention_program("pallas")
    with pytest.raises(RuntimeError, match="runs only on a TPU"):
        exe.run(main, feed=feed, fetch_list=[out])
    main, out = _attention_program("auto")
    got, = exe.run(main, feed=feed, fetch_list=[out])
    assert np.isfinite(got).all()
    hlo = next(reversed(exe._cache.values())).executable.as_text()
    assert "custom_call_target=\"tpu_custom_call\"" not in hlo
    params = {"b": 2, "h": 2, "s": 2048, "d": 16, "dtype": "float32",
              "has_bias": False, "dropout": 0.0, "causal": False}
    flash = choices.get_choice("fused_attention.backend")
    assert flash.candidates(params) == ["xla"]
    assert flash.bench(params, "pallas") is None
    assert choices.get_choice("fused_attention.block_sizes").bench(
        params, (128, 2048)) is None
    convbn = choices.get_choice("conv2d_bn_fused.backend")
    cb = {"m": 896, "k": 128, "n": 128, "dtype": "float32"}
    assert convbn.candidates(cb) == ["xla"]
    assert convbn.bench(cb, "pallas") is None


def test_interpret_is_unreachable_on_tpu(monkeypatch):
    import jax
    from paddle_tpu.ops import pallas_mode
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_mode.TEST_INTERPRET        # the harness did ask...
    assert pallas_mode.available() and not pallas_mode.interpret()


def test_peaks_known_kind_and_unknown_tpu_kind():
    from paddle_tpu.utils import (device_peak_flops, device_peak_hbm_bw,
                                  device_peak_ici_bw)
    assert device_peak_flops("TPU v5 lite") == 197e12
    assert device_peak_flops("cpu") is None          # off TPU: no MFU at all
    for fn in (device_peak_flops, device_peak_hbm_bw, device_peak_ici_bw):
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            fn("TPU v9 imaginary")


def test_bench_rows_without_a_peak_or_an_interconnect(monkeypatch):
    import jax
    import bench
    assert bench._mfu_field(1e12, 0.1, None) == {}
    assert bench._mfu_field(1e12, 0.1, 100e12) == {"mfu": 0.1}
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert bench.bench_allreduce(mbytes=1) is None   # nothing to print


def test_compile_error_surfaces_and_leaves_no_entry(monkeypatch):
    """Executor.run compiles AOT and unguarded: the error is raised at the
    compile, and the half-built cache entry is dropped so a retry compiles
    again instead of dispatching through lazy jit."""
    from paddle_tpu.core.executor import Executor

    class Refused(Exception):
        pass

    class _Fn:
        def lower(self, *a, **k):
            raise Refused("Mosaic failed to compile TPU kernel")

    orig = Executor._compile

    def compile_then_refuse(self, *a, **k):
        step = orig(self, *a, **k)
        step.fn = _Fn()
        return step

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [4], "float32")
        y = fluid.layers.fc(x, 2)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        n_before = len(exe._cache)
        monkeypatch.setattr(Executor, "_compile", compile_then_refuse)
        with pytest.raises(Refused):
            exe.run(main, feed={"x": np.ones((2, 4), "f4")}, fetch_list=[y])
        assert len(exe._cache) == n_before
        monkeypatch.setattr(Executor, "_compile", orig)
        out, = exe.run(main, feed={"x": np.ones((2, 4), "f4")},
                       fetch_list=[y])
    assert out.shape == (2, 2)


def test_native_build_failure_raises_with_compiler_stderr(tmp_path):
    from paddle_tpu import native
    if shutil.which("g++") is None:
        pytest.skip("no g++ toolchain")
    bad = tmp_path / "bad.cpp"
    bad.write_text("this is not C++;\n")
    so = tmp_path / "libbad.so"
    with pytest.raises(native.NativeBuildError, match="error"):
        native._build(str(bad), str(so), str(so) + ".srchash", "0" * 64)
    assert not so.exists() and not os.path.exists(str(so) + ".srchash")
    assert native.available()       # the real one builds from its source


def test_warmstore_probe_is_not_spawned_from_a_tpu_process(
        tmp_path, monkeypatch):
    """One process for each chip: a process on the TPU holds it, so the
    probe child is never spawned, and 'not probed' is never cached as the
    build's verdict."""
    import jax
    from paddle_tpu.warmstore import probe
    monkeypatch.delenv(probe.ENV_FORCE, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    probe.reset_for_tests()
    try:
        v = probe.verdict(cache_dir=str(tmp_path))
        assert not v.tier_a and v.source == "unprobed"
        assert "holds the TPU" in v.reason
        assert probe.SPAWNS == 0
        assert os.listdir(tmp_path) == []
    finally:
        probe.reset_for_tests()
