"""Multi-host simulation tests (VERDICT r1 #2; reference
tests/unittests/test_dist_base.py:637 _run_cluster): launch N local processes
with subprocess.Popen, each a jax.distributed participant with 4 forced CPU
devices, and assert the 2-process dp8 losses match the single-process dp8 run.

Also covers the explicit shard_map GPipe schedule (parallel/pipeline.py) and
the hierarchical (host, dp)-factored mesh helper.
"""
import functools
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_RUNNER = os.path.join(os.path.dirname(__file__), "dist_mlp_runner.py")


@functools.lru_cache(maxsize=1)
def _ranks_would_run_cpu() -> bool:
    """What backend would a spawned rank get? The rank subprocesses pop
    JAX_PLATFORMS/XLA_FLAGS so they start on the machine's default platform
    instead of the suite's 8 virtual CPU devices, so probe with the same
    env. jaxlib's CPU backend does not implement multiprocess collectives
    (XlaRuntimeError: "Multiprocess computations aren't implemented on the
    CPU backend"), so on a CPU-only machine every multi-process test is
    unrunnable.

    Under tier-1 this is harmless: the sandbox has no accelerator, the probe
    child fails or answers ``cpu``, and the marked tests skip. On a TPU host
    they could not run either -- N ranks of one host would open the same
    chips, and a chip belongs to one process (paddle_tpu/parallel/launch.py
    says what happens) -- so today they document the multi-process contract
    more than they test it; ROADMAP D5/D9 decide their fate.

    The probe timeout is deliberately short: a platform that cannot even
    initialize within 30s could not carry a multi-rank test either, so
    timeout => skip."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    try:
        r = subprocess.run(
            [sys.executable, "-c", "import jax; print(jax.default_backend())"],
            env=env, capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.SubprocessError):
        return True
    return r.returncode != 0 or r.stdout.strip() == "cpu"


# the string condition is evaluated lazily (only when a marked test is
# about to run), so plain collection / running only the unmarked tests in
# this file never pays the jax-import subprocess probe
requires_multiprocess_backend = pytest.mark.skipif(
    "_ranks_would_run_cpu()",
    reason="rank subprocesses would run on the CPU backend, which does not "
           "implement multiprocess collectives")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch(nproc, port, ckpt_dir=None, runner=_RUNNER):
    procs = []
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    extra = [str(ckpt_dir)] if ckpt_dir else []
    for r in range(nproc):
        procs.append(subprocess.Popen(
            [sys.executable, runner, str(r), str(nproc), str(port)] + extra,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env))
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, (
            f"rank process failed rc={p.returncode}:\n"
            f"{err.decode()[-2000:]}")
        outs.append(out.decode())
    return outs


def _losses(out):
    for line in out.splitlines():
        if line.startswith("LOSSES:"):
            return json.loads(line[len("LOSSES:"):])
    raise AssertionError(f"no LOSSES line in output: {out[-500:]}")


@requires_multiprocess_backend
def test_two_process_dp_matches_single_process():
    """2 hosts x 4 devices dp8 == 1 host x 8 devices dp8, same global batch."""
    single = _losses(_launch(1, _free_port())[0])
    outs = _launch(2, _free_port())
    l0, l1 = _losses(outs[0]), _losses(outs[1])
    np.testing.assert_allclose(l0, l1, rtol=1e-6)   # ranks agree
    np.testing.assert_allclose(single, l0, rtol=2e-4, atol=1e-5)


def _tagged(out, tag):
    for line in out.splitlines():
        if line.startswith(tag + ":"):
            return json.loads(line[len(tag) + 1:])
    raise AssertionError(f"no {tag} line in output: {out[-500:]}")


@requires_multiprocess_backend
def test_multihost_sharded_checkpoint_reshard(tmp_path):
    """2-host dp8+ZeRO run saves per-host shard chunks; the same processes then
    load the checkpoint into a dp4xmp2 mesh and continue -- the resumed
    trajectory must match a single-process run of the identical schedule
    (VERDICT r2 #4; reference io.py:328 _save_distributed_persistables)."""
    single_dir = tmp_path / "ck_single"
    multi_dir = tmp_path / "ck_multi"
    single = _launch(1, _free_port(), single_dir)[0]
    outs = _launch(2, _free_port(), multi_dir)
    # both ranks agree, and multi == single for both phases
    for tag in ("LOSSES", "CKPT_LOSSES"):
        ref = _tagged(single, tag)
        l0, l1 = _tagged(outs[0], tag), _tagged(outs[1], tag)
        np.testing.assert_allclose(l0, l1, rtol=1e-6)
        np.testing.assert_allclose(ref, l0, rtol=2e-4, atol=1e-5)
    # the 2-host checkpoint must contain chunks written by *both* ranks
    assert any(".r1c" in f.name for f in multi_dir.glob("*.npy")), \
        "rank 1 wrote no shard chunks -- sharded save not exercised"
    assert (multi_dir / "__manifest__.json.rank1").exists()


_CKPT_RUNNER = os.path.join(os.path.dirname(__file__),
                            "dist_ckpt_runner.py")


@requires_multiprocess_backend
def test_multihost_checkpointer_save_restore(tmp_path):
    """2-host ZeRO run under a Checkpointer: every rank writes its own
    chunk manifest, rank 0 publishes LATEST and rotates only after the
    post-save barrier, and a per-rank state digest survives the
    save -> restore round trip exactly.  The surviving tree passes the
    crc verifier (ISSUE 9 durable-checkpoint contract, multi-host)."""
    tree = tmp_path / "ck"
    outs = _launch(2, _free_port(), tree, runner=_CKPT_RUNNER)
    for out in outs:
        d = _tagged(out, "DIGESTS")
        assert d["saved"] == d["restored"], \
            f"rank {d['rank']} state changed across save/restore"
    kept = sorted(p.name for p in tree.iterdir()
                  if p.name.startswith("ckpt-"))
    assert kept == ["ckpt-1", "ckpt-2"], kept   # max_to_keep=2 rotation
    # both ranks' manifests + chunks verify clean at crc level
    import sys as _sys
    _sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools import ckpt_doctor
    rep = ckpt_doctor.verify_tree(str(tree), level="crc")
    assert rep["ok"] and rep["latest_complete_step"] == 2, rep
    assert any(s["nranks"] == 2 for s in rep["steps"]), rep


@requires_multiprocess_backend
def test_multihost_shrink_restore_2proc_to_1proc(tmp_path):
    """Elastic world shrink (ISSUE 11): the ZeRO checkpoint a 2-proc run
    wrote restores into a FRESH 1-proc world -- the restore path re-plans
    the shards for the smaller world (``reshard_plan`` journaled with the
    old/new world), and training continues with a finite loss."""
    import math
    tree = tmp_path / "ck"
    _launch(2, _free_port(), tree, runner=_CKPT_RUNNER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    p = subprocess.run(
        [sys.executable, _CKPT_RUNNER, "0", "1", "0", str(tree),
         "shrink-restore"],
        capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    d = _tagged(p.stdout, "SHRINK")
    assert d["restored"] == 2, d
    assert d["saved_world"] and d["saved_world"]["nranks"] == 2, d
    assert d["reshard_plans"] >= 1 and d["elastic_restores"] >= 1, d
    assert d["plan_actions"], d
    assert math.isfinite(d["loss"]), d


def test_pipeline_spmd_matches_serial():
    """Explicit GPipe over pp=4: outputs equal serial stage application."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from paddle_tpu.parallel import pipeline_spmd

    S, M, MB, D = 4, 6, 2, 8
    rng = np.random.RandomState(0)
    Ws = rng.randn(S, D, D).astype("float32") * 0.3
    bs = rng.randn(S, D).astype("float32") * 0.1
    x = rng.randn(M, MB, D).astype("float32")

    def stage(params, h):
        W, b = params
        return jnp.tanh(h @ W + b)

    mesh = Mesh(np.array(jax.devices()[:S]).reshape(S), ("pp",))
    out = pipeline_spmd(stage, (jnp.asarray(Ws), jnp.asarray(bs)),
                        jnp.asarray(x), mesh, axis="pp")

    ref = x.copy()
    for s in range(S):
        ref = np.tanh(ref @ Ws[s] + bs[s])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=1e-5)


def test_hierarchical_mesh_helper():
    from paddle_tpu.parallel import env as penv
    mesh = penv.global_mesh({"dp": 8}, hierarchical=False)
    assert mesh.shape == {"dp": 8}
    # hierarchical with one process: host axis of size 1
    mesh2 = penv.global_mesh({"dp": 8}, hierarchical=True)
    assert mesh2.shape["host"] == 1 and mesh2.shape["dp"] == 8


def test_shard_batch():
    from paddle_tpu.parallel.env import shard_batch
    x = np.arange(12).reshape(12, 1)
    np.testing.assert_array_equal(shard_batch(x, 1, 3), x[4:8])
    np.testing.assert_array_equal(shard_batch(x, 0, 1), x)


@requires_multiprocess_backend
def test_two_process_host_table_is_single_pserver():
    """host_embedding under multi-host dp: jax gathers callback operands to
    process 0 and runs the pull/push there alone — process 0's host RAM is
    the parameter server. Losses must match the single-process run and only
    rank 0 may apply pushes."""
    runner = os.path.join(os.path.dirname(__file__), "dist_hostemb_runner.py")
    single = _launch(1, _free_port(), runner=runner)
    multi = _launch(2, _free_port(), runner=runner)

    l1 = _tagged(single[0], "LOSSES")
    np.testing.assert_allclose(l1, _tagged(multi[0], "LOSSES"),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(l1, _tagged(multi[1], "LOSSES"),
                               rtol=1e-4, atol=1e-5)
    # the pserver is process 0: it applied every step's push, rank 1 none
    assert _tagged(multi[0], "PUSHES") == 6
    assert _tagged(multi[1], "PUSHES") == 0


@requires_multiprocess_backend
def test_two_process_row_sharded_host_table():
    """Row-sharded host tables (SCOPE gap #1 closed): each process stores
    ONLY its row range -- the table can exceed one host's RAM -- with
    per-process pull/push callbacks through the shard_map island; losses
    match the 1-process (unsharded) run and BOTH ranks act as pservers."""
    runner = os.path.join(os.path.dirname(__file__),
                          "dist_hostemb_runner.py")
    single = _launch(1, _free_port(), ckpt_dir="shard", runner=runner)
    multi = _launch(2, _free_port(), ckpt_dir="shard", runner=runner)

    l1 = _tagged(single[0], "LOSSES")
    np.testing.assert_allclose(l1, _tagged(multi[0], "LOSSES"),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(l1, _tagged(multi[1], "LOSSES"),
                               rtol=1e-4, atol=1e-5)
    # memory is actually partitioned: 32 of 64 rows per process, disjoint
    assert _tagged(single[0], "ROWS") == 64
    assert _tagged(multi[0], "ROWS") == 32
    assert _tagged(multi[1], "ROWS") == 32
    assert _tagged(multi[0], "RANGE") == [0, 32]
    assert _tagged(multi[1], "RANGE") == [32, 64]
    # every host is a pserver for its slice (vs the single-pserver topology
    # where rank 1 applies nothing)
    assert _tagged(multi[0], "PUSHES") == 6
    assert _tagged(multi[1], "PUSHES") == 6
