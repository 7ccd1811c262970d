"""Where JAX's persistent compilation cache lives -- the one place that says.

Rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
code here or anywhere else sets a directory. Where it is not, an *entry
point* (``chip_smoke.py``, ``bench*.py``, ``tests/conftest.py``) calls
``arm()`` before its first compile and gets one fixed, git-ignored directory
inside the checkout. The path is part of every cache key, so it never comes
from ``tempfile``, a pid or a timestamp. ``import paddle_tpu`` does not call
this: a library import must not touch JAX config.

Everything compiled afterwards goes through the cache, including the AOT
``lower().compile()`` calls in ``Executor`` and ``Predictor``.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def arm() -> str:
    """Make the persistent compilation cache active; return its directory."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def entry_count(path: str) -> int:
    """Executables cached under ``path`` (0 for a missing directory)."""
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except OSError:
        return 0
