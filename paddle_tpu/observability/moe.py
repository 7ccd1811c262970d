"""Mixture-of-experts telemetry: the reading of a fetched expert-load vector.

The device values themselves are Program variables (``layers.moe_ffn``
returns ``load``, the assignments each expert received): fetch them beside
the loss and hand them to ``load_stats``. What a compiled step's expert
layers keep (``moe_row_budget``) is reported by the ``moe_dispatch``
lowering (observability/lowerings.py).
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def load_stats(load) -> Dict[str, float]:
    """A fetched ``[experts]`` load vector as max / mean / their ratio (1.0 is
    perfectly even; the slowest expert of an expert-parallel layout waits
    for the fullest) and the number of experts that received nothing."""
    load = np.asarray(load, np.float64).reshape(-1)
    mean = float(load.mean())
    return {"max": float(load.max()), "mean": mean,
            "max_over_mean": float(load.max() / mean) if mean else 0.0,
            "empty": int((load == 0).sum())}
