"""The operations and bytes the Qwen3-Next cell's algorithm needs, as closed
forms of the configuration's sizes (as ``benchmark/needs_laguna.py`` holds
Laguna's): never read from the Program under test or from the optimized HLO.
A multiply-add is 2; backward is twice forward; recomputed operations do not
count; an element is 2 bytes unless said. Read by
``reducers/needs_share.py``.

The expert layer is one chip's share: of the tokens x top-k assignments a
layer, ``num_experts`` held of ``num_experts_routed`` receive their part,
``held / routed`` of them where the router is even -- the expectation the
forms use.
"""
from __future__ import annotations

import math


def layers_of(model: dict, kind: str) -> int:
    return sum(1 for k in model["layer_types"] if k == kind)


def held_assignments(model: dict, params: dict) -> float:
    """Assignments a layer that an even router sends to the held experts."""
    tokens = params["batch"] * params["seq"]
    return (tokens * model["num_experts_per_tok"] * model["num_experts"]
            / model["num_experts_routed"])


def delta_rule_forward_flops(model: dict) -> float:
    """The chunk form's FLOPs a token and DeltaNet layer, forward, at chunks
    of C = ``delta_chunk_size`` positions (``ops/pallas_delta.py`` has the
    algebra). A chunk and value head: ``k k^T`` and ``q k^T`` (2 x 2 C^2 d_k,
    shared by the ``rep`` value heads of a key head); the triangular
    inverse as ``log2 C`` factors, two ``[C, C]`` products each after the
    first (2 (log2 C - 1) x 2 C^3); ``k S``, ``q S`` and the state's update
    (3 x 2 C d_k d_v); ``T R`` and ``P V'`` (2 x 2 C^2 d_v)."""
    c = model["delta_chunk_size"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    heads = model["linear_num_value_heads"]
    rep = heads // model["linear_num_key_heads"]
    chunk = (2 * 2 * c * c * dk / rep + 2 * (int(math.log2(c)) - 1) * 2 * c ** 3
             + 3 * 2 * c * dk * dv + 2 * 2 * c * c * dv)
    return heads * chunk / c


def gated_delta(model: dict, params: dict) -> dict:
    """The delta rule's kernels, forward and backward, every DeltaNet layer:
    three times the chunk form's forward FLOPs; q, k, v, o and their four
    gradients moved once (2-byte elements), g, beta and their gradients
    (float32)."""
    tokens = params["batch"] * params["seq"]
    keys = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    values = model["linear_num_value_heads"] * model["linear_value_head_dim"]
    n = layers_of(model, "linear_attention")
    return {"flops": n * tokens * 3 * delta_rule_forward_flops(model),
            "bytes": n * tokens * (2 * (2 * keys + 2 * values) * 2
                                   + 4 * model["linear_num_value_heads"] * 4)}


def delta_conv(model: dict, params: dict) -> dict:
    """The mixer's convolution ``silu(conv(q | k | v))``, which HBM bounds:
    the input in and the output out forward; the input and the output's
    gradient in and the input's gradient out backward: 5 x tokens x
    channels elements a layer. A tap is a multiply-add a channel, the silu
    and the backward counted as as many again."""
    tokens = params["batch"] * params["seq"]
    chan = (2 * model["linear_num_key_heads"] * model["linear_key_head_dim"]
            + model["linear_num_value_heads"] * model["linear_value_head_dim"])
    n = layers_of(model, "linear_attention")
    return {"flops": n * tokens * chan * 3 * 2 * model["linear_conv_kernel_dim"],
            "bytes": n * 5 * tokens * chan * 2}


def flash_attention_gqa_causal(model: dict, params: dict) -> dict:
    """The full-attention layers' kernels: causal grouped-query attention
    needs half the S x S square for every query head, 6 B h S^2 d a layer
    forward and backward; q in and o out forward, q, o, dO in and dq out
    backward (6 arrays of B h S d), k, v in forward, k, v in and dk, dv out
    backward (6 of B kv S d): the key/value heads are read in place."""
    b, s, d = params["batch"], params["seq"], model["head_dim"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    n = layers_of(model, "full_attention")
    return {"flops": n * 6 * b * s * s * h * d,
            "bytes": n * 6 * b * s * d * (h + kv) * 2}


def moe_held_expert_matmul(model: dict, params: dict) -> dict:
    """The three grouped products of every layer over the held experts'
    assignments, forward and backward: 3 x 2 x 3 x assignments x hidden x
    width FLOPs a layer; each of the nine products moves its rows in, its
    stacked weight (the held experts') and its rows out once."""
    h, w = model["hidden_size"], model["moe_intermediate_size"]
    a = held_assignments(model, params)
    one = a * h + model["num_experts"] * h * w + a * w
    n = model["num_hidden_layers"]
    return {"flops": n * 3 * 3 * 2 * a * h * w, "bytes": n * 3 * 3 * one * 2}


def train_step(model: dict, params: dict) -> dict:
    """The model's matmul-class FLOPs of one training step: a DeltaNet
    layer's two input projections, its output projection and the chunk form
    of its delta rule; the attention layer's doubled q, k, v and o
    projections and its QK^T and PV over half the square; every layer's
    router, shared expert with its gate, and the held experts' three
    products over the expected assignments; the output head over the held
    vocabulary; forward + 2 x backward."""
    tokens, s = params["batch"] * params["seq"], params["seq"]
    h, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    n_v = model["linear_num_value_heads"]
    keys = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    values = n_v * model["linear_value_head_dim"]
    forward = layers_of(model, "linear_attention") * tokens * (
        2 * h * (2 * keys + 2 * values) + 2 * h * 2 * n_v + 2 * values * h
        + delta_rule_forward_flops(model))
    forward += layers_of(model, "full_attention") * (
        tokens * (2 * h * 2 * heads * d + 2 * 2 * h * kv * d
                  + 2 * heads * d * h)
        + params["batch"] * heads * 2 * 2 * (s * (s + 1) // 2) * d)
    forward += model["num_hidden_layers"] * (
        tokens * (2 * h * model["num_experts_routed"] + 2 * h
                  + 3 * 2 * h * model["shared_expert_intermediate_size"])
        + held_assignments(model, params) * 3 * 2 * h
        * model["moe_intermediate_size"])
    forward += tokens * 2 * h * model["vocab_size"]
    return {"flops": 3 * forward, "per_token": 3 * forward / tokens}
