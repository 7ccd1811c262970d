"""Data-axis telemetry: how the ops of a compiled program that may lay their
work over the strategy's data axis did so, as labelled counts added once per
compile.

``LowerCtx.bernoulli_mask`` (the ``dropout`` op, the composed
``fused_attention``'s dropout) and the ``gather`` op (ops/tensor_ops.py)
note their way while the executor traces the op, keyed by the op's salt like
``attention_lowering_total``'s notes, and the executor hands the notes of
the compile it just made to ``count_data_axis``.
"""
from __future__ import annotations

from collections import Counter
from typing import Optional

from .metrics import REGISTRY, MetricsRegistry

# note kind -> (counter, help, the label that says which way)
_COUNTERS = {
    "mask_draw": ("mask_draw_total",
                  "ops that drew a dropout mask, by how the mask was drawn",
                  "draw"),
    "gather_layout": ("gather_layout_total",
                      "gather ops along axis 0, by the layout of their rows",
                      "layout"),
}


def count_data_axis(notes: dict, program: str,
                    registry: Optional[MetricsRegistry] = None) -> None:
    """``mask_draw_total{program,draw,shards}``: the ops that drew a
    Bernoulli mask in the trace just compiled. ``draw="shard"``: each device
    of the data axis (``shards`` of them) drew its own part of the batch in
    a ``shard_map`` island; ``"global"``: one draw at the whole shape, which
    under a mesh every device repeats (``shards="1"``); the flash kernels'
    in-kernel dropout is not counted.
    ``gather_layout_total{program,layout,shards}``: the ``gather`` ops along
    axis 0 likewise. ``layout="shard"``: the gathered rows left the op's
    island laid over the data axis, so each device runs the rows' consumers
    on its own ``1 / shards`` of them; ``"global"``: a plain ``take``, the
    rows as the partitioner leaves them (one device, or replicated under a
    mesh whose data axis does not divide the row and index counts).
    ``notes`` is the Program's ``_lowering_notes``: the kinds counted here
    are taken out of it, each mapping an op's salt to its ``(way, shards)``."""
    registry = registry or REGISTRY
    for kind, (name, help, label) in _COUNTERS.items():
        for (way, shards), n in Counter(notes.pop(kind, {}).values()).items():
            registry.counter(name, help, program=program, shards=str(shards),
                             **{label: way}).inc(n)
