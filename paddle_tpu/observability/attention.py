"""Attention telemetry: which lowering each ``fused_attention`` op of a
compiled program took, as a labelled count added once per compile.

The op notes its choice while the executor traces it (``ctx.note`` in
``ops/pallas_attention.py``, keyed by the op's salt: the forward a grad op
lowers again under ``jax.vjp`` lands on the same key), and the executor hands
the notes of the compile it just made to ``count_lowerings``;
``autotune_decisions_total{choice,source}`` says beside it whether a default
or a persisted decision answered. The op's grad op notes where its softmax
statistics came from (``count_backwards``).
"""
from __future__ import annotations

from collections import Counter
from typing import Optional

from .metrics import REGISTRY, MetricsRegistry


def count_lowerings(notes: dict, program: str,
                    registry: Optional[MetricsRegistry] = None) -> None:
    """``attention_lowering_total{program,impl,s,block_q,block_k,kv_heads,
    window,heads,head_dim}``: the ``fused_attention`` ops the trace just
    compiled, by lowering (``pallas``, ``xla``, ``ring``, ``ulysses``),
    sequence length, the kernels' Q block and K tile (0 where no kernel ran;
    ``block_k`` = ``s`` is one tile a row), key/value heads (fewer than the
    query's under grouped-query attention), the sliding window the lowering
    applies (0: none, also for one no shorter than ``s``), query heads and
    head size (a model may give its window layers more heads than its full
    ones). ``attention_k_tiles_total{program,state,window}``: the K tiles
    the forward kernel of each such op passes over for one (batch, head)
    (``visited``) and those a causal op leaves out because they lie wholly
    above the diagonal or wholly behind the window (``skipped``), from
    static shapes (``ops/pallas_attention.py:k_tiles``). ``notes`` maps each
    op's salt to its ``(impl, s, block_q, block_k, kv_heads, visited,
    skipped, window, heads, head_dim)``; nothing is added for a program
    without the op."""
    registry = registry or REGISTRY
    for (impl, s, block_q, block_k, kv_heads, visited, skipped, window,
         heads, head_dim), n in Counter(notes.values()).items():
        registry.counter(
            "attention_lowering_total",
            "fused_attention ops compiled, by the lowering each took",
            program=program, impl=impl, s=str(s), block_q=str(block_q),
            block_k=str(block_k), kv_heads=str(kv_heads),
            window=str(window), heads=str(heads),
            head_dim=str(head_dim)).inc(n)
        if impl == "pallas":
            for state, tiles in (("visited", visited), ("skipped", skipped)):
                registry.counter(
                    "attention_k_tiles_total",
                    "K tiles a (batch, head) of the compiled flash-attention "
                    "ops' forward kernels", program=program,
                    state=state, window=str(window)).inc(n * tiles)


def update_gate_gauges(program_ir, program: str,
                       registry: Optional[MetricsRegistry] = None) -> None:
    """``attention_gate_ops{program,form}``: the ``attention_gate`` ops of
    the compiled program by the gate's form, from static shapes:
    ``per_head`` (one gate a token and head) or ``elementwise`` (one a
    token, head and channel); nothing is set for a program without the
    op."""
    registry = registry or REGISTRY
    block = program_ir.global_block()
    forms = Counter()
    for op in block.ops:
        if op.type == "attention_gate":
            x = block.find_var_recursive(op.inputs["X"][0])
            gate = block.find_var_recursive(op.inputs["Gate"][0])
            forms["per_head" if int(gate.shape[-1]) == int(x.shape[1])
                  else "elementwise"] += 1
    for form, n in forms.items():
        registry.gauge("attention_gate_ops", "attention_gate ops in the "
                       "compiled program, by the gate's form",
                       program=program, form=form).set(float(n))


def count_backwards(notes: dict, program: str,
                    registry: Optional[MetricsRegistry] = None) -> None:
    """``attention_backward_total{program,stats}``: the ``fused_attention_grad``
    ops the trace just compiled, by where the backward got the rows' softmax
    statistics. ``saved``: the backward kernel read the forward op's ``Lse``
    (no forward lowered in the grad op); ``recomputed``: the kernels, on a
    desc without ``Lse`` (the generic grad, whose vjp lowers the forward
    kernel again for them; XLA merges that call with the forward op's); ``generic``: ``jax.vjp`` over another lowering (XLA's
    composed one, a mesh's island). ``notes`` maps each op's salt to its
    label; nothing is added for a program without the grad op (a test
    clone)."""
    registry = registry or REGISTRY
    for stats, n in Counter(notes.values()).items():
        registry.counter(
            "attention_backward_total",
            "fused_attention_grad ops compiled, by where the softmax "
            "statistics came from", program=program, stats=stats).inc(n)
