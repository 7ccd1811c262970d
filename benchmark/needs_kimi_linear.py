"""The operations and bytes the Kimi Linear cell's algorithm needs, as closed
forms of the configuration's sizes (as ``benchmark/needs_qwen3_next.py``
holds Qwen3-Next's): never read from the Program under test or from the
optimized HLO. A multiply-add is 2; backward is twice forward; recomputed
operations do not count; an element is 2 bytes unless said. Read by
``reducers/needs_share.py``.

The expert layers are one chip's share: of the tokens x top-k assignments a
layer, ``num_experts`` held of ``num_experts_routed`` receive their part,
``held / routed`` of them where the router is even -- the expectation the
forms use.
"""
from __future__ import annotations

import math


def kda_layers(model: dict) -> int:
    return len(model["linear_attn_config"]["kda_layers"])


def latent_layers(model: dict) -> int:
    return len(model["linear_attn_config"]["full_attn_layers"])


def sparse_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def held_assignments(model: dict, params: dict) -> float:
    """Assignments a layer that an even router sends to the held experts."""
    tokens = params["batch"] * params["seq"]
    return (tokens * model["num_experts_per_token"] * model["num_experts"]
            / model["num_experts_routed"])


def kda_rule_forward_flops(model: dict) -> float:
    """The channel form's chunk FLOPs a token and KDA layer, forward, at
    chunks of C = ``delta_chunk_size`` positions (``ops/pallas_delta.py``
    has the algebra). A chunk and head: the two ``[C, C]`` blocks ``M`` and
    ``P`` as contractions over the d key channels (2 x 2 C^2 d; the decay
    inside the sum is vector work and not counted); the triangular inverse
    as 2 (log2 C - 1) ``[C, C]`` products (the 8-row blocks' two doublings,
    then ``log2(C / 8)`` merges of neighbouring blocks, two products each:
    2 (log2 C - 1) x 2 C^3); ``(k exp G) S``, ``(q exp G) S`` and the
    state's update (3 x 2 C d^2); ``T R`` and ``P V'`` (2 x 2 C^2 d)."""
    c = model["delta_chunk_size"]
    lin = model["linear_attn_config"]
    d = lin["head_dim"]
    chunk = (2 * 2 * c * c * d + 2 * (int(math.log2(c)) - 1) * 2 * c ** 3
             + 3 * 2 * c * d * d + 2 * 2 * c * c * d)
    return lin["num_heads"] * chunk / c


def gated_delta(model: dict, params: dict) -> dict:
    """The rule's kernels, forward and backward, every KDA layer: three
    times the chunk form's forward FLOPs; q, k, v, o and their four
    gradients moved once (2-byte elements), g and its gradient (float32, a
    key channel: as wide as q), beta and its gradient (float32, a head)."""
    tokens = params["batch"] * params["seq"]
    lin = model["linear_attn_config"]
    wide = lin["num_heads"] * lin["head_dim"]
    n = kda_layers(model)
    return {"flops": n * tokens * 3 * kda_rule_forward_flops(model),
            "bytes": n * tokens * (8 * wide * 2 + 2 * wide * 4
                                   + 2 * lin["num_heads"] * 4)}


def kda_conv(model: dict, params: dict) -> dict:
    """The mixer's convolution ``silu(conv(q | k | v))``, which HBM bounds:
    the input in and the output out forward; the input and the output's
    gradient in and the input's gradient out backward: 5 x tokens x 12,288
    elements a layer. A tap is a multiply-add a channel, the silu and the
    backward counted as as many again."""
    tokens = params["batch"] * params["seq"]
    lin = model["linear_attn_config"]
    chan = 3 * lin["num_heads"] * lin["head_dim"]
    n = kda_layers(model)
    return {"flops": n * tokens * chan * 3 * 2 * lin["short_conv_kernel_size"],
            "bytes": n * 5 * tokens * chan * 2}


def flash_attention_causal(model: dict, params: dict) -> dict:
    """The flash kernels of the latent-attention layer, which they see as 32
    query = 32 key/value heads (group 1) of 128 + 64 = 192 for q / k and 128
    for v: causal attention needs half the S x S square, ``Q K^T`` over 192
    and ``P V`` over 128, forward and twice that backward: 3 B h S^2 (192 +
    128) a layer; q and k each in forward, in backward and their gradient
    out (6 arrays of B h S 192), v likewise and o out, in and its gradient
    in (6 of B h S 128). Counted at the published widths: the zero columns
    the kernels read behind q's and k's 192 show as a lower share."""
    b, s, h = params["batch"], params["seq"], model["num_attention_heads"]
    d = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    d_v = model["v_head_dim"]
    n = latent_layers(model)
    return {"flops": n * 3 * b * h * s * s * (d + d_v),
            "bytes": n * 6 * b * h * s * (d + d_v) * 2}


def moe_held_expert_matmul(model: dict, params: dict) -> dict:
    """The three grouped products of every sparse layer over the held
    experts' assignments, forward and backward: 3 x 2 x 3 x assignments x
    hidden x width FLOPs a layer; each of the nine products moves its rows
    in, its stacked weight (the held experts') and its rows out once."""
    h, w = model["hidden_size"], model["moe_intermediate_size"]
    a = held_assignments(model, params)
    one = a * h + model["num_experts"] * h * w + a * w
    n = sparse_layers(model)
    return {"flops": n * 3 * 3 * 2 * a * h * w, "bytes": n * 3 * 3 * one * 2}


def train_step(model: dict, params: dict) -> dict:
    """The model's matmul-class FLOPs of one training step: a KDA layer's
    projection to q | k | v, its two low-rank pairs, its beta projection,
    its output projection and the chunk form of its rule; the latent
    layer's four projections (H -> h (d_n + d_r), H -> r_kv + d_r, r_kv -> h
    (d_n + d_v), h d_v -> H) and its QK^T over 192 and PV over 128 on half
    the square; the dense layer's three products; a sparse layer's router,
    shared expert and the held experts' three products over the expected
    assignments; the head over the held vocabulary slice; forward + 2 x
    backward."""
    tokens, s = params["batch"] * params["seq"], params["seq"]
    h, heads = model["hidden_size"], model["num_attention_heads"]
    lin = model["linear_attn_config"]
    d, wide = lin["head_dim"], lin["num_heads"] * lin["head_dim"]
    d_n, d_r, d_v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    r_kv, w = model["kv_lora_rank"], model["moe_intermediate_size"]
    forward = kda_layers(model) * tokens * (
        2 * h * 3 * wide + 2 * 2 * (h * d + d * wide)
        + 2 * h * lin["num_heads"] + 2 * wide * h
        + kda_rule_forward_flops(model))
    forward += latent_layers(model) * (
        tokens * 2 * (h * heads * (d_n + d_r) + h * (r_kv + d_r)
                      + r_kv * heads * (d_n + d_v) + heads * d_v * h)
        + params["batch"] * heads * 2 * (s * (s + 1) // 2)
        * (d_n + d_r + d_v))
    forward += model["first_k_dense_replace"] * tokens * 3 * 2 * h \
        * model["intermediate_size"]
    forward += sparse_layers(model) * (
        tokens * (2 * h * model["num_experts_routed"]
                  + 3 * 2 * h * w * model["num_shared_experts"])
        + held_assignments(model, params) * 3 * 2 * h * w)
    forward += tokens * 2 * h * model["vocab_size"]
    return {"flops": 3 * forward, "per_token": 3 * forward / tokens}
