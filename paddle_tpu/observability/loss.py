"""Loss telemetry: which form each ``softmax_with_cross_entropy_grad`` op of
a compiled program took, as a labelled count added once per compile.

The grad lowering notes its form while the executor traces it (``ctx.note``
in ``ops/math_ops.py``, keyed by the op's salt), and the executor hands the
notes of the compile it just made to ``count_backwards``, as it does
``attention_backward_total``'s (observability/attention.py).
"""
from __future__ import annotations

from collections import Counter
from typing import Optional

from .metrics import REGISTRY, MetricsRegistry


def count_backwards(notes: dict, program: str,
                    registry: Optional[MetricsRegistry] = None) -> None:
    """``loss_backward_total{program,form}``: the
    ``softmax_with_cross_entropy_grad`` ops the trace just compiled, by the
    form of the logits' gradient. ``written``: the closed form ``(exp(x -
    Lse) - onehot) * dLoss`` written once over the logits' own buffer, which
    the head's gradient products then read; ``fused``: the same expression
    handed to XLA to fuse into them (logits under
    ``math_ops.WRITTEN_GRAD_MIN_BYTES``, a mesh); ``generic``: ``jax.vjp``
    over the forward's lowering (float32 logits, soft labels, another axis,
    an ``ignore_index``, a desc without ``Lse``). ``notes`` maps each op's
    salt to its form; nothing is added for a program without the grad op (a
    test clone)."""
    registry = registry or REGISTRY
    for form, n in Counter(notes.values()).items():
        registry.counter(
            "loss_backward_total",
            "softmax_with_cross_entropy_grad ops compiled, by the form of "
            "the logits' gradient", program=program, form=form).inc(n)
