"""Mixture-of-experts telemetry: what a compiled step's expert layers hold,
as gauges set once per compile from the Program's static shapes (counts, not
times), and the reading of a fetched expert-load vector.

The device values themselves are Program variables (``layers.moe_ffn``
returns ``load``, the assignments each expert received): fetch them beside
the loss and hand them to ``load_stats``.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from .metrics import REGISTRY, MetricsRegistry

def update_moe_gauges(program_ir, program: str,
                      registry: Optional[MetricsRegistry] = None) -> None:
    """``moe_layers``, ``moe_experts`` (the experts a layer's router scores),
    ``moe_experts_held`` (the experts whose weights a layer holds here:
    fewer under ``layers.moe_ffn``'s ``experts_held``),
    ``moe_assignments_per_step`` (tokens x top-k, all layers; a layer's
    sorted row buffer has a row for each of its assignments),
    ``moe_expert_param_bytes`` (the stacked expert weights),
    ``moe_row_budget`` (the sorted rows a step's expert layers keep, all
    layers: ``moe_assignments_per_step`` without a budget, the layers'
    ``row_budget`` summed with one), ``moe_shared_experts`` (expert layers
    with a shared expert beside the routed ones: ``layers.moe_ffn``'s
    ``shared_width``, read off its ``<name>_shared_gate_w [H, width]``),
    ``moe_shared_width`` (that expert's width), ``moe_shared_gated`` (the
    shared experts under a sigmoid gate a token: ``shared_gate``, read off
    ``<name>_shared_expert_gate_w``) and
    ``short_conv_layers`` of one compiled program; nothing is set for a
    program without an expert layer, and ``short_conv_layers`` only where
    there is such a layer."""
    from ..analysis.distributed import dtype_bytes
    registry = registry or REGISTRY
    block = program_ir.global_block()
    layers = experts = held = assignments = param_bytes = convs = 0
    budget = shared = shared_width = gated = 0
    for op in block.ops:
        if op.type == "moe_dispatch":
            layers += 1
            experts = int(op.attr("num_experts"))
            index = block.find_var_recursive(op.inputs["Index"][0])
            assignments += int(np.prod(index.shape))
            budget += int(op.attr("rows", 0)) or int(np.prod(index.shape))
        elif op.type == "moe_expert_matmul":
            w = block.find_var_recursive(op.inputs["W"][0])
            held = int(w.shape[0])
            param_bytes += int(np.prod(w.shape)) * dtype_bytes(w.dtype)
        elif op.type == "short_conv":
            convs += 1
    for param in block.all_parameters():
        if param.name.endswith("_shared_gate_w"):
            shared += 1
            shared_width = int(param.shape[1])
        elif param.name.endswith("_shared_expert_gate_w"):
            gated += 1
    if convs:
        registry.gauge("short_conv_layers", "gated short-convolution "
                       "operators in the compiled program",
                       program=program).set(float(convs))
    if not layers:
        return
    for name, help, value in (
            ("moe_layers", "expert layers in the compiled program", layers),
            ("moe_experts", "experts a layer's router scores", experts),
            ("moe_experts_held", "experts whose weights a layer holds in "
             "this program", held),
            ("moe_assignments_per_step",
             "tokens x top-k assignments routed a step, all layers (a count "
             "from static shapes)", assignments),
            ("moe_expert_param_bytes",
             "bytes of the stacked expert weights (a count from static "
             "shapes)", param_bytes),
            ("moe_row_budget",
             "sorted rows the expert layers keep a step, all layers: the "
             "assignments without a row budget, the budgets with one",
             budget),
            ("moe_shared_experts", "expert layers with a shared expert "
             "beside the routed ones", shared),
            ("moe_shared_width", "width of that shared expert",
             shared_width),
            ("moe_shared_gated", "shared experts under a sigmoid gate a "
             "token", gated)):
        registry.gauge(name, help, program=program).set(float(value))


def load_stats(load) -> Dict[str, float]:
    """A fetched ``[experts]`` load vector as max / mean / their ratio (1.0 is
    perfectly even; the slowest expert of an expert-parallel layout waits
    for the fullest) and the number of experts that received nothing."""
    load = np.asarray(load, np.float64).reshape(-1)
    mean = float(load.mean())
    return {"max": float(load.max()), "mean": mean,
            "max_over_mean": float(load.max() / mean) if mean else 0.0,
            "empty": int((load == 0).sum())}
