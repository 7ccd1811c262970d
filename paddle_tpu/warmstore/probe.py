"""Tier-A safety probe: is executable (de)serialization safe on this build?

An earlier jaxlib CPU build's compiled-executable (de)serialization
intermittently corrupted the glibc heap (PR 1). A crash like that cannot
be caught in-process -- by the time free() aborts, the damage happened
long ago -- so the verdict is decided by:

1. a **forced verdict** (``PADDLE_TPU_WARMSTORE_PROBE=pass|fail``) for
   tests and the CLI selftest;
2. a **static denylist** of builds with *known* heap corruption
   (jaxlib <= 0.4.36 on CPU; it cannot match the installed 0.9.0);
3. a **cached verdict** from a previous dynamic probe, keyed per
   (jax, jaxlib, device_kind) -- one subprocess per build, ever;
4. the **dynamic probe**: a subprocess running serialize -> deserialize
   -> execute round-trips; any crash or wrong answer fails the verdict
   without taking the parent down.

One process for each chip: the probe child needs a device of its own. A
process that runs on a TPU already holds the chip, so from there the child
is never spawned -- the verdict is "not probed", tier A stays off for this
process, nothing is written to the verdict cache, and the store serves
tier B. (Until PR 21 the child was spawned anyway and sat out its timeout
or failed, and that failure was cached as the build's verdict.)

A failing verdict self-disables tier A (the store serves tier-B
StableHLO re-compiles instead, safe everywhere) with a one-time warning.
JAX's own persistent compilation cache is not this module's business:
``paddle_tpu.utils.compile_cache`` places it.

Nothing here runs unless the warm store is armed or a caller (the CLI)
explicitly asks: disarmed processes never import this module, never stat
a verdict file, never spawn a probe subprocess.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import sys
import threading
from typing import Optional

ENV_FORCE = "PADDLE_TPU_WARMSTORE_PROBE"
_FORCE_MODES = ("auto", "pass", "fail")

#: builds whose executable (de)serialization is known to corrupt the
#: heap: (device_kind, max bad jaxlib version inclusive, reason).
#: Probabilistic corruption cannot be probed reliably -- a clean probe
#: run on a known-bad build is survivorship, not safety.
DENYLIST = (
    ("cpu", (0, 4, 36),
     "jaxlib<=0.4.36 CPU executable (de)serialization corrupts the "
     "glibc heap (PR 1: ~50% SIGABRT/SIGSEGV on test_slim with the "
     "persistent compilation cache armed)"),
)

#: probe subprocesses spawned by THIS process (the zero-overhead and
#: probe-spy tests pin this at 0/1)
SPAWNS = 0

_lock = threading.Lock()
_mem_cache: dict = {}
_warned_tier_a = False


@dataclasses.dataclass(frozen=True)
class Verdict:
    """The per-build probe outcome. ``tier_a`` gates the store's
    serialized-executable tier."""
    tier_a: bool
    reason: str
    source: str          # forced | denylist | cached | subprocess | unprobed
    jax: str = ""
    jaxlib: str = ""
    device_kind: str = ""

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _parse_ver(v: str) -> tuple:
    parts = []
    for tok in str(v).split(".")[:3]:
        num = ""
        for ch in tok:
            if not ch.isdigit():
                break
            num += ch
        parts.append(int(num or 0))
    return tuple(parts)


def build_signature() -> dict:
    from . import keys as _keys
    sig = _keys.versions()
    sig["device_kind"] = _keys.device_kind()
    return sig


def _sig_digest(sig: dict) -> str:
    blob = json.dumps(sig, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def forced_mode() -> str:
    """Parse the force env through the shared mode parser (same
    spellings as every other PADDLE_TPU gate; typos raise)."""
    from ..observability import journal as _journal
    return _journal.mode_env(ENV_FORCE, _FORCE_MODES, default="auto",
                             truthy="pass")


def _denylisted(sig: dict) -> Optional[str]:
    for kind, max_bad, reason in DENYLIST:
        if sig.get("device_kind") == kind and \
                _parse_ver(sig.get("jaxlib", "")) <= max_bad:
            return reason
    return None


def _verdict_path(cache_dir: str, sig: dict) -> str:
    return os.path.join(cache_dir, f"probe_{_sig_digest(sig)}.json")


def _load_cached(cache_dir: Optional[str], sig: dict) -> Optional[Verdict]:
    if not cache_dir:
        return None
    try:
        with open(_verdict_path(cache_dir, sig)) as f:
            doc = json.load(f)
        return Verdict(tier_a=bool(doc["tier_a"]),
                       reason=str(doc.get("reason", "")), source="cached",
                       jax=sig["jax"], jaxlib=sig["jaxlib"],
                       device_kind=sig["device_kind"])
    except (OSError, ValueError, KeyError):
        return None


def _store_cached(cache_dir: Optional[str], sig: dict, v: Verdict) -> None:
    if not cache_dir:
        return
    try:
        os.makedirs(cache_dir, exist_ok=True)
        path = _verdict_path(cache_dir, sig)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(v.to_dict(), f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # an uncacheable verdict just re-probes next process


def run_subprocess_probe(timeout: float = 180.0) -> Verdict:
    """Spawn the probe child and translate its fate into a Verdict.
    The child exercises the exact machinery tier A trusts; a crash
    (SIGSEGV/SIGABRT), timeout, or missing OK marker fails the build."""
    global SPAWNS
    import subprocess
    sig = build_signature()
    import jax
    if jax.default_backend() == "tpu":
        # build_signature() above initialised the backend: this process
        # holds the chip, and a child that needs it would fail or hang
        return Verdict(False, "not probed: this process holds the TPU and "
                              "the probe child needs a device of its own "
                              "(one process for each chip)",
                       "unprobed", **sig)
    with _lock:
        SPAWNS += 1
    env = dict(os.environ)
    env.pop(ENV_FORCE, None)
    env.pop("PADDLE_TPU_WARMSTORE", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.warmstore.probe", "--child"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        return Verdict(False, "probe subprocess timed out",
                       "subprocess", **sig)
    except OSError as e:
        return Verdict(False, f"probe subprocess unlaunchable: {e}",
                       "subprocess", **sig)
    out = (proc.stdout or b"").decode("utf-8", "replace")
    if proc.returncode == 0 and "PROBE-OK" in out:
        return Verdict(True, "serialize/deserialize/execute round-trips "
                             "clean", "subprocess", **sig)
    why = (f"probe child exited {proc.returncode}"
           + (f" (signal {-proc.returncode})" if proc.returncode and
              proc.returncode < 0 else ""))
    return Verdict(False, f"{why}: {out.strip()[-200:]}", "subprocess",
                   **sig)


def verdict(cache_dir: Optional[str] = None,
            force: Optional[str] = None) -> Verdict:
    """The tier-A verdict for this build, resolved in order: forced env
    -> in-memory cache -> denylist -> disk cache -> subprocess probe.
    The denylist outranks a cached dynamic pass: a known-bad build must
    not be resurrected by one lucky probe run."""
    mode = force if force in ("pass", "fail") else forced_mode()
    sig = build_signature()
    if mode == "pass":
        return Verdict(True, "forced by env", "forced", **sig)
    if mode == "fail":
        return Verdict(False, "forced by env", "forced", **sig)
    ck = _sig_digest(sig)
    with _lock:
        v = _mem_cache.get(ck)
    if v is not None:
        return v
    deny = _denylisted(sig)
    if deny is not None:
        v = Verdict(False, deny, "denylist", **sig)
    else:
        v = _load_cached(cache_dir, sig)
        if v is None:
            v = run_subprocess_probe()
            if v.source == "subprocess":    # "unprobed" is not a verdict
                _store_cached(cache_dir, sig, v)
    with _lock:
        _mem_cache[ck] = v
    return v


def warn_tier_a_disabled_once(v: Verdict) -> None:
    """One-time, journaled warning when a store operation wanted tier A
    and the verdict said no (the ISSUE-20 self-disable contract)."""
    global _warned_tier_a
    with _lock:
        if _warned_tier_a:
            return
        _warned_tier_a = True
    import warnings
    from ..observability import journal as _journal
    warnings.warn(
        f"paddle_tpu warmstore: tier A (serialized executables) disabled "
        f"on this build -- {v.reason} (source: {v.source}); serving "
        f"tier-B StableHLO re-compiles instead")
    _journal.emit({"event": "warmstore_probe", "tier_a": v.tier_a,
                   "reason": v.reason, "source": v.source})


def reset_for_tests() -> None:
    global _warned_tier_a, SPAWNS
    with _lock:
        _mem_cache.clear()
        _warned_tier_a = False
        SPAWNS = 0


# ---------------------------------------------------------------- child --

def _child_main() -> int:
    """The probe body, run in a throwaway subprocess: round-trip a
    conv+grad training-step-shaped program through the
    serialize_executable path tier A uses. Any heap corruption kills THIS
    process, not the trainer. The child arms no compilation cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import serialize_executable as se

    def loss_fn(params, img):
        h = jax.lax.conv_general_dilated(
            img, params["w1"], (1, 1), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        h = jax.nn.relu(h)
        h = h.reshape((h.shape[0], -1))
        return jnp.mean((h @ params["wfc"]) ** 2)

    def step(params, img):
        l, g = jax.value_and_grad(loss_fn)(params, img)
        return l, jax.tree_util.tree_map(lambda p, gg: p - 0.01 * gg,
                                         params, g)

    params = {"w1": jnp.full((8, 3, 3, 3), 0.01, jnp.float32),
              "wfc": jnp.full((8 * 12 * 12, 10), 0.01, jnp.float32)}
    img = jnp.ones((2, 3, 12, 12), jnp.float32)
    for _ in range(3):
        comp = jax.jit(step).lower(params, img).compile()
        payload, in_tree, out_tree = se.serialize(comp)
        loaded = se.deserialize_and_load(payload, in_tree, out_tree)
        l, p2 = loaded(params, img)
        if not np.isfinite(float(l)):
            print("PROBE-BAD: nonfinite loss after round-trip")
            return 1
        jax.clear_caches()
    print("PROBE-OK")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "--child":
        return _child_main()
    v = verdict()
    print(json.dumps(v.to_dict(), indent=1, sort_keys=True))
    return 0 if v.tier_a else 1


if __name__ == "__main__":
    sys.exit(main())
