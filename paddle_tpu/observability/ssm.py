"""State-space and linear-attention layer telemetry: what a compiled step's
``ssd_scan`` and ``gated_delta_rule`` ops hold, as gauges set once per
compile from the Program's static shapes (counts, not times), and which
lowering each ``ssd_scan``, ``gated_delta_rule`` and ``short_conv`` op took,
as labelled counts added once per compile (as
``observability/attention.py`` counts the attention ops': the op notes its
choice while the executor traces it, keyed by the op's salt).
"""
from __future__ import annotations

from collections import Counter
from typing import Optional

from .metrics import REGISTRY, MetricsRegistry


def update_ssm_gauges(program_ir, program: str,
                      registry: Optional[MetricsRegistry] = None) -> None:
    """``ssm_layers`` (the ``ssd_scan`` ops of the compiled program),
    ``ssm_heads``, ``ssm_state`` (N, a head's state is N x head size),
    ``ssm_chunk`` and ``ssm_chunks_per_step`` (sequences x chunks a
    sequence, all layers: the ``[chunk, chunk]`` decay blocks a head builds
    a step); nothing is set for a program without the op."""
    registry = registry or REGISTRY
    block = program_ir.global_block()
    layers = heads = state = chunk = chunks = 0
    for op in block.ops:
        if op.type != "ssd_scan":
            continue
        x = block.find_var_recursive(op.inputs["X"][0])
        b = block.find_var_recursive(op.inputs["B"][0])
        layers += 1
        heads, state = int(x.shape[2]), int(b.shape[-1])
        chunk = min(int(op.attr("chunk")), int(x.shape[1]))
        chunks += int(x.shape[0]) * (int(x.shape[1]) // chunk)
    if not layers:
        return
    for name, help, value in (
            ("ssm_layers", "state-space scans in the compiled program",
             layers),
            ("ssm_heads", "heads of a state-space scan", heads),
            ("ssm_state", "state size N of a state-space head", state),
            ("ssm_chunk", "positions a chunk of the chunked scan", chunk),
            ("ssm_chunks_per_step", "sequences x chunks a sequence, all "
             "scans (a count from static shapes)", chunks)):
        registry.gauge(name, help, program=program).set(float(value))


def update_delta_gauges(program_ir, program: str,
                        registry: Optional[MetricsRegistry] = None) -> None:
    """``delta_layers`` (the ``gated_delta_rule`` ops of the compiled
    program), ``delta_heads`` (value heads), ``delta_state_bytes`` (the
    float32 states a step carries, all layers: sequences x heads x key dim
    x value dim x 4) and ``delta_chunks_per_step`` (sequences x chunks a
    sequence, all layers: the ``[chunk, chunk]`` triangular inverses a head
    builds a step); nothing is set for a program without the op."""
    registry = registry or REGISTRY
    block = program_ir.global_block()
    layers = heads = state = chunks = 0
    for op in block.ops:
        if op.type != "gated_delta_rule":
            continue
        # from the outputs: the op reads q, k, v in either operand form
        batch, seq = (int(d) for d in block.find_var_recursive(
            op.outputs["Out"][0]).shape[:2])
        heads, dk, dv = (int(d) for d in block.find_var_recursive(
            op.outputs["States"][0]).shape[2:])
        layers += 1
        state += batch * heads * dk * dv * 4
        chunks += batch * (seq // min(int(op.attr("chunk")), seq))
    if not layers:
        return
    for name, help, value in (
            ("delta_layers", "gated delta rules in the compiled program",
             layers),
            ("delta_heads", "value heads of a gated delta rule", heads),
            ("delta_state_bytes", "bytes of the float32 states the gated "
             "delta rules carry, all layers (a count from static shapes)",
             state),
            ("delta_chunks_per_step", "sequences x chunks a sequence, all "
             "gated delta rules (a count from static shapes)", chunks)):
        registry.gauge(name, help, program=program).set(float(value))


def count_delta_lowerings(notes: dict, program: str,
                          registry: Optional[MetricsRegistry] = None) -> None:
    """``delta_lowering_total{program,impl,chunk,heads,key_dim,value_dim,
    operands}``: the ``gated_delta_rule`` ops the trace just compiled, by
    lowering (``pallas``: the kernels of ``ops/pallas_delta.py``;
    ``composed``: the chunk form in ``jax.numpy``) and by operand form
    (``packed``: the kernels read q, k and v in place in the one array the
    op was given; ``split``: three operands, given or cut out of it).
    ``notes`` maps each op's salt to its note; nothing is added for a
    program without the op."""
    registry = registry or REGISTRY
    for (impl, chunk, heads, dk, dv, form), n in Counter(
            notes.values()).items():
        registry.counter(
            "delta_lowering_total",
            "gated_delta_rule ops compiled, by the lowering and the operand "
            "form each took",
            program=program, impl=impl, chunk=str(chunk), heads=str(heads),
            key_dim=str(dk), value_dim=str(dv), operands=form).inc(n)


def count_lowerings(scans: dict, convs: dict, program: str,
                    registry: Optional[MetricsRegistry] = None) -> None:
    """``ssd_lowering_total{program,impl,chunk,heads,state}``: the
    ``ssd_scan`` ops the trace just compiled, by lowering (``pallas``: the
    kernels of ``ops/pallas_ssd.py``; ``composed``: the chunked form in
    ``jax.numpy``, off a TPU or where the shapes are not the kernels').
    ``short_conv_lowering_total{program,impl,form,activation,taps}``: the
    ``short_conv`` ops likewise, ``form`` = ``gated`` (LFM2's) or ``plain``
    (a Mamba mixer's). ``scans`` / ``convs`` map each op's salt to its
    note; nothing is added for a program without the ops."""
    registry = registry or REGISTRY
    for (impl, chunk, heads, state), n in Counter(scans.values()).items():
        registry.counter(
            "ssd_lowering_total",
            "ssd_scan ops compiled, by the lowering each took",
            program=program, impl=impl, chunk=str(chunk), heads=str(heads),
            state=str(state)).inc(n)
    for (impl, form, act, taps), n in Counter(convs.values()).items():
        registry.counter(
            "short_conv_lowering_total",
            "short_conv ops compiled, by form and the lowering each took",
            program=program, impl=impl, form=form, activation=act,
            taps=str(taps)).inc(n)
