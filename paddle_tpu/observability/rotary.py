"""Rotary telemetry: which form each ``rotary_embedding`` op of a compiled
program and each of its grad ops took, as a labelled count added once per
compile.

The lowerings note their form while the executor traces them (``ctx.note``
in ``ops/decoder_ops.py``, keyed by the op's salt), and the executor hands
the notes of the compile it just made to ``count_lowerings``, as it does
``loss_backward_total``'s (observability/loss.py).
"""
from __future__ import annotations

from collections import Counter
from typing import Optional

from .metrics import REGISTRY, MetricsRegistry


def count_lowerings(forward: dict, backward: dict, program: str,
                    registry: Optional[MetricsRegistry] = None) -> None:
    """``rotary_lowering_total{program,direction,form}``: the
    ``rotary_embedding`` ops (``direction="forward"``) and
    ``rotary_embedding_grad`` ops (``"backward"``) the trace just compiled,
    by form. ``kernel``: the one-pass Pallas kernel of
    ``ops/pallas_rope.py`` (the array read once in its own dtype, written
    once; the backward is the same pass on the cotangent with the sign of
    sin turned and lowers no forward); ``composed``: the rotation left
    to XLA (off a TPU, under a mesh, a shape the kernel does not take, the
    grad op of a head wider than 128); ``generic``: a grad op without a
    cotangent, ``jax.vjp`` over the forward. Each dict maps an op's salt to its form; nothing is added for a
    program without the op."""
    registry = registry or REGISTRY
    for direction, notes in (("forward", forward), ("backward", backward)):
        for form, n in Counter(notes.values()).items():
            registry.counter(
                "rotary_lowering_total",
                "rotary_embedding ops and grad ops compiled, by form",
                program=program, direction=direction, form=form).inc(n)
