"""Content-addressed keying for the warm-start store.

An entry is only reusable when EVERYTHING that shaped the executable is
identical: the program's serialized content (not its ``id()`` -- that is
what makes entries cross-process), the feed signature, the fetch list,
the seed, the XLA compiler options, the distribution strategy, the
autotuner's decision state, the jax/jaxlib build, the device kind, and
-- for world-dependent (SPMD) programs only -- the process/device
topology the mesh was built over.  The key is a flat JSON-able dict;
its canonical-JSON sha256 is the entry's directory name, the same
spec-keyed discipline ``tuning/cache.py::make_key`` uses for autotune
decisions.

World-dependence is deliberate: a single-device train step or a serving
Predictor compiles the same executable on an 8-rank and a 6-rank fleet,
so its key carries ``{"scope": "local"}`` and survives an elastic
resize; a dist-strategy step bakes the mesh into the HLO, carries the
world/device counts, and correctly misses after 8 -> 6.
"""
from __future__ import annotations

import hashlib
import json
from typing import Optional

#: bump when the key derivation changes incompatibly -- old entries
#: simply stop matching (the store is a cache, never a source of truth)
KEY_FORMAT = 1


def canonical(key: dict) -> str:
    """Deterministic byte-identical JSON for a key dict (sorted keys,
    no whitespace) -- the digest input."""
    return json.dumps(key, sort_keys=True, separators=(",", ":"))


def digest(key: dict) -> str:
    return hashlib.sha256(canonical(key).encode("utf-8")).hexdigest()[:32]


def program_digest(program) -> str:
    """sha256 of the program's serialized content, memoized per
    ``(identity, _version)`` on the Program itself so repeated compile
    misses of one program pay the JSON walk once."""
    version = getattr(program, "_version", 0)
    memo = getattr(program, "_warmstore_digest", None)
    if memo is not None and memo[0] == version:
        return memo[1]
    d = hashlib.sha256(program.to_json().encode("utf-8")).hexdigest()[:32]
    try:
        program._warmstore_digest = (version, d)
    except Exception:
        pass
    return d


def tuning_fingerprint() -> list:
    """Cross-process form of ``tuning.state_token()``: the in-process
    epoch counter means nothing to another process, so the store keys on
    (mode, digest of the decision records themselves) -- two processes
    sharing one autotune cache derive the same fingerprint."""
    from ..tuning import cache as _tc
    m = _tc.mode()
    if m == "off":
        return [m, ""]
    try:
        items = _tc.CACHE.items()
    except Exception:
        items = {}
    if not items:
        return [m, ""]
    blob = json.dumps({k: v.get("winner") for k, v in sorted(items.items())},
                      sort_keys=True, separators=(",", ":"), default=str)
    return [m, hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]]


def versions() -> dict:
    import jax
    import jaxlib
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__}


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def topology(world_dependent: bool) -> dict:
    """The world component of a key. Local (non-SPMD) programs compile
    independently of fleet size; SPMD programs bake the mesh/world in."""
    if not world_dependent:
        return {"scope": "local"}
    import jax
    return {"scope": "world", "processes": jax.process_count(),
            "devices": jax.device_count()}


def build_key(kind: str, program, *, feed_sig, fetch_names, seed,
              flags, strategy, world_dependent: bool,
              extra: Optional[dict] = None) -> dict:
    """The full entry key for one compiled artifact.  ``kind`` is
    ``train_step`` / ``predict``; ``strategy`` is the executor key's
    strategy slot (``strategy_signature()`` tuple) -- repr'd, since its
    tuples are content-based and repr-stable across processes."""
    key = {"format": KEY_FORMAT, "kind": kind,
           "program": program_digest(program),
           "feed_sig": repr(feed_sig), "fetch": list(map(str, fetch_names)),
           "seed": int(seed), "flags": repr(flags),
           "strategy": repr(strategy),
           "tuning": tuning_fingerprint(),
           "device_kind": device_kind(),
           "topology": topology(world_dependent)}
    key.update(versions())
    if extra:
        key.update(extra)
    return key
