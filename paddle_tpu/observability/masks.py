"""Dropout-mask telemetry: how each mask-drawing op of a compiled program
drew its mask, as a labelled count added once per compile.

``LowerCtx.bernoulli_mask`` (the ``dropout`` op, the composed
``fused_attention``'s dropout) notes its way while the executor traces the
op, keyed by the op's salt like ``attention_lowering_total``'s notes, and
the executor hands the notes of the compile it just made to ``count_draws``.
"""
from __future__ import annotations

from collections import Counter
from typing import Optional

from .metrics import REGISTRY, MetricsRegistry


def count_draws(notes: dict, program: str,
                registry: Optional[MetricsRegistry] = None) -> None:
    """``mask_draw_total{program,draw,shards}``: the ops that drew a
    Bernoulli mask in the trace just compiled. ``draw="shard"``: each device
    of the data axis (``shards`` of them) drew its own part of the batch in
    a ``shard_map`` island; ``"global"``: one draw at the whole shape, which
    under a mesh every device repeats (``shards="1"``). ``notes`` maps each
    op's salt to its ``(draw, shards)``; the flash kernels' in-kernel dropout
    is not counted."""
    registry = registry or REGISTRY
    for (draw, shards), n in Counter(notes.values()).items():
        registry.counter(
            "mask_draw_total",
            "ops that drew a dropout mask, by how the mask was drawn",
            program=program, draw=draw, shards=str(shards)).inc(n)
