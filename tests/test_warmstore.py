"""Warm-start store (ISSUE 20): persistent executable + decision cache
shared across restarts, resizes, and the serving pool.

Pins the contract end to end: byte-identical restores through a fresh
executor, the probe's tier-A self-disable (the serialized-executable
path is NEVER touched on a denylisted/failing build), corrupt-entry
quarantine with fall-through to a fresh compile, mesh/world keying,
serving cold-start hits, chaos coverage at the ``warmstore_write``
fault site, and the zero-overhead guard (unset env = the package never
even imports)."""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import warmstore as ws
from paddle_tpu.observability.metrics import REGISTRY
from paddle_tpu.resilience import faults
from paddle_tpu.warmstore import keys, probe
from paddle_tpu.warmstore.store import WarmStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _pristine_warmstore(monkeypatch):
    """Every test starts disarmed with a cold probe; nothing leaks into
    the rest of the suite (the singleton store and the warn-once flag
    are process-global)."""
    monkeypatch.delenv("PADDLE_TPU_WARMSTORE", raising=False)
    monkeypatch.delenv("PADDLE_TPU_WARMSTORE_PROBE", raising=False)
    faults.clear()
    yield
    faults.clear()
    ws.reset_for_tests()


def _sum_counter(name, **match):
    fam = REGISTRY.get(name)
    if fam is None:
        return 0.0
    tot = 0.0
    for lbl, child in fam.items():
        d = dict(lbl)
        if all(d.get(k) == v for k, v in match.items()):
            tot += child.value
    return tot


def _compile_count():
    fam = REGISTRY.get("executor_compile_seconds")
    if fam is None:
        return 0
    return int(sum(h.count for _, h in fam.items()))


def _eval_program(dim=6, seed=11):
    """Optimizer-free program: same feed -> bitwise-same fetch every run
    (the byte-identity oracle does not fight SGD state)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [dim], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, dim, act="tanh"))
    return main, startup, loss


def _feed(dim=6):
    rng = np.random.RandomState(3)
    return {"x": rng.randn(4, dim).astype("float32")}


def _tier_b_blob():
    import jax
    import jax.export as jexport
    import jax.numpy as jnp

    def f(x):
        return jnp.tanh(x) * 2.0 + 1.0

    aval = jax.ShapeDtypeStruct((4,), jnp.float32)
    return jexport.export(jax.jit(f))(aval).serialize()


# ---------------------------------------------------------------- smoke --

def test_cli_selftest():
    """python -m paddle_tpu.warmstore --selftest: hermetic end-to-end
    (both forced probe verdicts, quarantine, gc) exits 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("PADDLE_TPU_WARMSTORE", None)
    env.pop("PADDLE_TPU_WARMSTORE_PROBE", None)
    p = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.warmstore", "--selftest"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "PASS" in p.stdout


def test_zero_overhead_when_disarmed(tmp_path):
    """Unset PADDLE_TPU_WARMSTORE = the package never imports: a full
    train + save + Predictor run must leave paddle_tpu.warmstore out of
    sys.modules (no open, no thread, no probe subprocess)."""
    script = tmp_path / "disarmed.py"
    script.write_text(
        "import os, sys\n"
        "assert 'PADDLE_TPU_WARMSTORE' not in os.environ\n"
        "import numpy as np\n"
        "import paddle_tpu as fluid\n"
        "main, startup = fluid.Program(), fluid.Program()\n"
        "with fluid.program_guard(main, startup):\n"
        "    x = fluid.data('x', [4], 'float32')\n"
        "    y = fluid.layers.fc(x, 2)\n"
        "    loss = fluid.layers.mean(y)\n"
        "    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)\n"
        "exe = fluid.Executor()\n"
        "feed = {'x': np.ones((2, 4), 'float32')}\n"
        "with fluid.scope_guard(fluid.Scope()):\n"
        "    exe.run(startup)\n"
        "    exe.run(main, feed=feed, fetch_list=[loss])\n"
        "    exe.run(main, feed=feed, fetch_list=[loss])\n"
        "    d = os.path.join(r'%s', 'model')\n"
        "    fluid.io.save_inference_model(d, ['x'], [y], exe, main)\n"
        "pred = fluid.inference.Predictor(d)\n"
        "pred.run({'x': np.ones((2, 4), 'float32')})\n"
        "assert 'paddle_tpu.warmstore' not in sys.modules, 'imported!'\n"
        "assert not any(m.startswith('paddle_tpu.warmstore')\n"
        "               for m in sys.modules), 'submodule imported!'\n"
        "print('DISARMED-OK')\n" % tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("PADDLE_TPU_WARMSTORE", None)
    p = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "DISARMED-OK" in p.stdout


# ------------------------------------------------------------ round trip --

def test_fresh_executor_restores_byte_identical(tmp_path, monkeypatch):
    """Executor A compiles and offers; executor B (cold cache, same
    process) restores from the store -- zero new XLA compiles through
    the executor path, one tier hit, bitwise-equal fetches."""
    monkeypatch.setenv("PADDLE_TPU_WARMSTORE", str(tmp_path / "store"))
    main, startup, loss = _eval_program()
    feed = _feed()
    scope = fluid.Scope()
    exe_a = fluid.Executor()
    with fluid.scope_guard(scope):
        exe_a.run(startup)
        ref, = exe_a.run(main, feed=feed, fetch_list=[loss])
    assert ws.flush(30.0)

    compiles_before = _compile_count()
    hits_before = _sum_counter("warmstore_hits_total")
    exe_b = fluid.Executor()
    with fluid.scope_guard(scope):
        out, = exe_b.run(main, feed=feed, fetch_list=[loss])
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    assert _compile_count() == compiles_before, \
        "restore must not re-enter the executor compile path"
    assert _sum_counter("warmstore_hits_total") == hits_before + 1
    # this build is denylisted for tier A: the hit must be tier B
    assert _sum_counter("warmstore_hits_total", tier="b") >= 1


def test_consult_leaves_a_span_only_for_a_restore(tmp_path, monkeypatch):
    """The armed store is consulted under a ``warm_restore`` phase (so a
    restore reaches any profiler capture): a miss leaves the ``compile``
    span alone, a restore ``warm_restore`` alone -- goodput sums each
    under its own cause."""
    from paddle_tpu.observability import timeline
    monkeypatch.setenv("PADDLE_TPU_WARMSTORE", str(tmp_path / "store"))
    main, startup, loss = _eval_program(dim=5)
    feed = _feed(dim=5)
    scope = fluid.Scope()
    seen = {}
    for who in ("cold", "warm"):
        exe = fluid.Executor()
        with fluid.scope_guard(scope):
            if who == "cold":
                exe.run(startup)
            timeline.clear()
            exe.run(main, feed=feed, fetch_list=[loss])
        assert ws.flush(30.0)
        seen[who] = {s.name: s for s in timeline.spans()
                     if s.name in ("run", "compile", "warm_restore")}
    assert set(seen["cold"]) == {"run", "compile"}
    assert set(seen["warm"]) == {"run", "warm_restore"}
    restore, run = seen["warm"]["warm_restore"], seen["warm"]["run"]
    assert restore.parent == run.id and restore.cat == "executor"
    assert restore.args["program"] == run.args["program"]
    timeline.clear()


# -------------------------------------------------------- probe self-off --

def test_probe_self_disable_never_touches_tier_a(tmp_path, monkeypatch):
    """A failing probe disables tier A: the serialized-executable
    deserializer is never invoked (spy counts zero calls), the entry
    serves tier B, the one-time warning fires exactly once, and no
    probe subprocess ever spawns."""
    monkeypatch.setenv(probe.ENV_FORCE, "fail")
    probe.reset_for_tests()
    spy_calls = []
    from jax.experimental import serialize_executable as se
    monkeypatch.setattr(
        se, "deserialize_and_load",
        lambda *a, **k: spy_calls.append(a) or None)

    store = WarmStore(str(tmp_path / "store"))
    blob = _tier_b_blob()
    key = {"format": 1, "kind": "spy", "n": 1}
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        store.offer(key, tier_a_build=lambda: b"\x80must-never-load",
                    tier_b_build=lambda: blob)
        assert store.flush(30.0)
        hit = store.consult(key)
        assert hit is not None and hit.tier == "b"
        hit2 = store.consult(key)
        assert hit2 is not None and hit2.tier == "b"
    store.close()

    assert spy_calls == [], "tier-A deserializer was invoked"
    assert probe.SPAWNS == 0, "forced verdict must not spawn a probe"
    entry_files = os.listdir(os.path.join(
        str(tmp_path / "store"), "entries", keys.digest(key)))
    assert "tier_a.pkl" not in entry_files, \
        "failing probe must drop the tier-A builder at offer time"
    tier_a_warns = [w for w in caught if "tier A" in str(w.message)]
    assert len(tier_a_warns) == 1, \
        f"expected exactly one tier-A warning, got {len(tier_a_warns)}"


# ------------------------------------------------------------ quarantine --

def test_corrupt_payload_quarantined_and_missed(tmp_path, monkeypatch):
    """A flipped payload byte fails crc32 on consult: the entry is
    renamed ``.corrupt``, the lookup reports a miss (caller compiles
    fresh), and ``verify`` names the quarantined entry."""
    root = str(tmp_path / "store")
    store = WarmStore(root)
    key = {"format": 1, "kind": "victim", "n": 1}
    store.offer(key, tier_b_build=_tier_b_blob)
    assert store.flush(30.0)
    digest = keys.digest(key)
    payload = os.path.join(root, "entries", digest, "tier_b.bin")
    with open(payload, "r+b") as f:
        b = f.read(1)
        f.seek(0)
        f.write(bytes([b[0] ^ 0xFF]))

    q_before = _sum_counter("warmstore_quarantined_total")
    assert store.consult(key) is None
    assert _sum_counter("warmstore_quarantined_total") == q_before + 1
    assert os.path.isdir(os.path.join(root, "entries",
                                      digest + ".corrupt"))
    assert not os.path.isdir(os.path.join(root, "entries", digest))
    problems = store.verify()
    assert any("quarantined" in p for p in problems)
    # the slot is free again: a re-offer recreates a clean entry
    store.offer(key, tier_b_build=_tier_b_blob)
    assert store.flush(30.0)
    assert store.consult(key) is not None
    store.close()


def test_truncated_meta_quarantined(tmp_path):
    """Half a meta.json (torn write survived a crash) is unreadable:
    quarantine + miss, never an exception into the step path."""
    root = str(tmp_path / "store")
    store = WarmStore(root)
    key = {"format": 1, "kind": "victim", "n": 2}
    store.offer(key, tier_b_build=_tier_b_blob)
    assert store.flush(30.0)
    digest = keys.digest(key)
    meta = os.path.join(root, "entries", digest, "meta.json")
    raw = open(meta, "rb").read()
    with open(meta, "wb") as f:
        f.write(raw[:len(raw) // 2])
    assert store.consult(key) is None
    assert os.path.isdir(os.path.join(root, "entries",
                                      digest + ".corrupt"))
    store.close()


# ---------------------------------------------------------------- keying --

def test_world_change_misses_local_key_survives(monkeypatch):
    """Elastic resize 8 -> 6 devices: world-scoped keys (SPMD programs)
    change digest -- a stale plan is never served to a new mesh -- while
    local-scope keys (single-process programs) survive the resize."""
    import jax
    main, startup, _ = _eval_program(seed=23)
    kw = dict(feed_sig=(("x", (4, 6), "float32"),), fetch_names=["m"],
              seed=0, flags=None, strategy=())

    monkeypatch.setattr(jax, "process_count", lambda: 1)
    monkeypatch.setattr(jax, "device_count", lambda: 8)
    k8 = keys.build_key("train_step", main, world_dependent=True, **kw)
    l8 = keys.build_key("train_step", main, world_dependent=False, **kw)
    monkeypatch.setattr(jax, "device_count", lambda: 6)
    k6 = keys.build_key("train_step", main, world_dependent=True, **kw)
    l6 = keys.build_key("train_step", main, world_dependent=False, **kw)

    assert keys.digest(k8) != keys.digest(k6)
    assert keys.digest(l8) == keys.digest(l6)
    assert k8["topology"] == {"scope": "world", "processes": 1,
                              "devices": 8}
    assert l8["topology"] == {"scope": "local"}
    # and a different program content digest misses regardless of world
    other, _, _ = _eval_program(dim=7, seed=23)
    ko = keys.build_key("train_step", other, world_dependent=False, **kw)
    assert keys.digest(ko) != keys.digest(l8)


# --------------------------------------------------------------- serving --

def test_serving_cold_start_hits_store(tmp_path, monkeypatch):
    """A second Predictor over the same saved model restores its AOT
    executable from the store (one hit, no new signature compile) and
    serves identical outputs -- the pool's cold-start win."""
    monkeypatch.setenv("PADDLE_TPU_WARMSTORE", str(tmp_path / "store"))
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [6], "float32")
        y = fluid.layers.fc(x, 3, act="tanh")
    d = str(tmp_path / "model")
    exe = fluid.Executor()
    feed = _feed()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [y], exe, main)

    p1 = fluid.inference.Predictor(d)
    out1, = p1.run(feed)
    assert ws.flush(30.0)
    hits_before = _sum_counter("warmstore_hits_total")
    misses_before = _sum_counter("warmstore_misses_total")
    p2 = fluid.inference.Predictor(d)
    out2, = p2.run(feed)
    assert np.array_equal(np.asarray(out1), np.asarray(out2))
    assert _sum_counter("warmstore_hits_total") == hits_before + 1
    assert _sum_counter("warmstore_misses_total") == misses_before


# ----------------------------------------------------------------- chaos --

def test_chaos_corrupt_at_warmstore_write_falls_through(tmp_path,
                                                        monkeypatch):
    """Chaos at the new fault site: every committed entry is bit-flipped
    post-commit; the next process's consult catches the damage via
    crc32, quarantines, and compiles fresh -- a poisoned store can never
    fail a step, and the recomputed fetch is bitwise-identical."""
    monkeypatch.setenv("PADDLE_TPU_WARMSTORE", str(tmp_path / "store"))
    faults.install("corrupt@warmstore_write:times=0")
    main, startup, loss = _eval_program(seed=31)
    feed = _feed()
    scope = fluid.Scope()
    exe_a = fluid.Executor()
    with fluid.scope_guard(scope):
        exe_a.run(startup)
        ref, = exe_a.run(main, feed=feed, fetch_list=[loss])
    assert ws.flush(30.0)
    faults.clear()

    q_before = _sum_counter("warmstore_quarantined_total")
    compiles_before = _compile_count()
    exe_b = fluid.Executor()
    with fluid.scope_guard(scope):
        out, = exe_b.run(main, feed=feed, fetch_list=[loss])
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    assert _sum_counter("warmstore_quarantined_total") > q_before
    assert _compile_count() == compiles_before + 1, \
        "quarantined entry must fall through to one fresh compile"


# ------------------------------------------------------------------- gc --

def test_gc_and_ls_bound_the_store(tmp_path):
    """gc --max-bytes evicts oldest-first down to the cap; ls totals
    agree with what is on disk."""
    root = str(tmp_path / "store")
    store = WarmStore(root)
    blob = _tier_b_blob()
    for i in range(3):
        store.offer({"format": 1, "kind": "gc", "n": i},
                    tier_b_build=lambda b=blob: b)
    assert store.flush(30.0)
    rows = store.ls()
    assert len(rows) == 3
    per_entry = max(r["bytes"] for r in rows)
    removed = store.gc(max_bytes=per_entry)
    assert len(removed) == 2
    assert len(store.ls()) == 1
    assert store.verify() == []
    store.close()
