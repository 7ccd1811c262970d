"""The operations and bytes the GLM-4.7-Flash cell's algorithm needs, as
closed forms of the configuration's sizes (as ``benchmark/needs_laguna.py``
holds Laguna's): never read from the Program under test or from the
optimized HLO. A multiply-add is 2; backward is twice forward; recomputed
operations do not count; an element is 2 bytes. Read by
``reducers/needs_share.py``.

The expert layers are one chip's share: of the tokens x top-k assignments a
layer, ``n_routed_experts`` held of ``num_experts_routed`` receive their
part, ``held / routed`` of them where the router is even -- the expectation
the forms use. The prediction module is one more block of the expert kind.
"""
from __future__ import annotations


def blocks(model: dict) -> int:
    """Decoder blocks a step runs: the trunk's and the module's."""
    return model["num_hidden_layers"] + model["num_nextn_predict_layers"]


def sparse_layers(model: dict) -> int:
    return blocks(model) - model["first_k_dense_replace"]


def held_assignments(model: dict, params: dict) -> float:
    """Assignments a layer that an even router sends to the held experts."""
    tokens = params["batch"] * params["seq"]
    return (tokens * model["num_experts_per_tok"] * model["n_routed_experts"]
            / model["num_experts_routed"])


def flash_attention_causal(model: dict, params: dict) -> dict:
    """The flash kernels of every block's latent attention, which they see
    as 20 query = 20 key/value heads of 192 + 64 = 256 (group 1): causal
    attention needs half the S x S square, 6 B h S^2 d a block forward and
    backward; q, k, v in and o out forward, those, o's gradient in and dq,
    dk, dv out backward: 12 arrays of B h S d a block."""
    b, s, h = params["batch"], params["seq"], model["num_attention_heads"]
    d = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return {"flops": blocks(model) * 6 * b * s * s * h * d,
            "bytes": blocks(model) * 12 * b * h * s * d * 2}


def moe_held_expert_matmul(model: dict, params: dict) -> dict:
    """The three grouped products of every sparse layer over the held
    experts' assignments, forward and backward: 3 x 2 x 3 x assignments x
    hidden x width FLOPs a layer; each of the nine products moves its rows
    in, its stacked weight (the held experts') and its rows out once."""
    h, w = model["hidden_size"], model["moe_intermediate_size"]
    a = held_assignments(model, params)
    one = a * h + model["n_routed_experts"] * h * w + a * w
    n = sparse_layers(model)
    return {"flops": n * 3 * 3 * 2 * a * h * w, "bytes": n * 3 * 3 * one * 2}


def train_step(model: dict, params: dict) -> dict:
    """The model's matmul-class FLOPs of one training step: a block's five
    latent-attention projections (H -> r_q -> h (d_n + d_r), H -> r_kv + d_r,
    r_kv -> h (d_n + d_v), h d_v -> H) and its QK^T and PV over half the
    square; the dense layer's three products; a sparse layer's router,
    shared expert and the held experts' three products over the expected
    assignments; the module's W_eh (2H -> H); the head over the held
    vocabulary slice twice (trunk and module); forward + 2 x backward."""
    tokens, s = params["batch"] * params["seq"], params["seq"]
    h, heads = model["hidden_size"], model["num_attention_heads"]
    d_n, d_r, d_v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    r_q, r_kv = model["q_lora_rank"], model["kv_lora_rank"]
    w = model["moe_intermediate_size"]
    attention = (
        tokens * 2 * (h * r_q + r_q * heads * (d_n + d_r) + h * (r_kv + d_r)
                      + r_kv * heads * (d_n + d_v) + heads * d_v * h)
        + params["batch"] * heads * 2 * (s * (s + 1) // 2)
        * (d_n + d_r + d_v))
    forward = blocks(model) * attention
    forward += model["first_k_dense_replace"] * tokens * 3 * 2 * h \
        * model["intermediate_size"]
    forward += sparse_layers(model) * (
        tokens * (2 * h * model["num_experts_routed"]
                  + 3 * 2 * h * w * model["n_shared_experts"])
        + held_assignments(model, params) * 3 * 2 * h * w)
    forward += model["num_nextn_predict_layers"] * tokens * 2 * 2 * h * h
    forward += (1 + model["num_nextn_predict_layers"]) * tokens * 2 * h \
        * model["vocab_size"]
    return {"flops": 3 * forward, "per_token": 3 * forward / tokens}
