"""The operations and bytes the Xing4.0 cell's algorithm needs, as closed
forms of the configuration's sizes (as ``benchmark/needs_glm_4_7_flash.py``
holds GLM-4.7-Flash's): never read from the Program under test or from the
optimized HLO. A multiply-add is 2; backward is twice forward; recomputed
operations do not count; an element of an activation is 2 bytes. Read by
``reducers/needs_share.py``.

The expert layers are one chip's share: of the tokens x top-k assignments a
layer, ``n_routed_experts`` held of ``num_experts_routed`` receive their
part, ``held / routed`` of them where the router is even -- the expectation
the forms use.
"""
from __future__ import annotations


def sparse_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def sublayers(model: dict) -> int:
    """Hyper-connections a step: one around each block's attention and one
    around its feed-forward."""
    return 2 * model["num_hidden_layers"]


def held_assignments(model: dict, params: dict) -> float:
    """Assignments a layer that an even router sends to the held experts."""
    tokens = params["batch"] * params["seq"]
    return (tokens * model["num_experts_per_tok"] * model["n_routed_experts"]
            / model["num_experts_routed"])


def hyper_connection(model: dict, params: dict) -> dict:
    """The two ops of every hyper-connection and their grad ops, from sizes
    alone, each operand moved once; n = ``hc_mult``, C = ``hidden_size``, k
    = 2 n + n^2 coefficients a token (float32). Forward: the read side
    takes the state X (n C) in and gives u (C) and the coefficients out,
    the write side takes X, y (C) and the coefficients in and gives X' (n
    C) out: 3 n C + 2 C elements and 2 k floats a token. Backward: the
    write side's grad takes X, y, the coefficients and dX' in and gives dX,
    dy and the coefficients' gradient out (3 n C + 2 C, 2 k floats); the
    read side's takes X, du and the coefficients' gradient in and gives dX
    out (2 n C + C, k floats); the norm, the exponentials and the
    iterations it computes again move nothing. FLOPs: the product with Phi
    (2 n C k), the read (2 n C) and the write (2 n^2 C + 2 n C) forward,
    twice that backward; the recomputed product is not counted. Bound by
    bytes by a factor of 30."""
    tokens = params["batch"] * params["seq"]
    n, c = model["hc_mult"], model["hidden_size"]
    k = 2 * n + n * n
    elements = (3 * n * c + 2 * c) + (3 * n * c + 2 * c) + (2 * n * c + c)
    flops = 3 * (2 * n * c * k + 2 * n * c + 2 * n * n * c + 2 * n * c)
    return {"flops": sublayers(model) * tokens * flops,
            "bytes": sublayers(model) * tokens * (elements * 2 + 5 * k * 4)}


def flash_attention_causal(model: dict, params: dict) -> dict:
    """The flash kernels of every block's latent attention, which they see
    as 32 query = 32 key/value heads (group 1) of 128 + 64 = 192 for q / k
    and 128 for v, in ``needs_kimi_linear``'s form: half the S x S square,
    ``Q K^T`` over 192 and ``P V`` over 128, forward and twice that
    backward; q and k each in forward, in backward and their gradient out,
    v likewise and o out, in and its gradient in. Counted at the published
    widths: the 64 zero columns the kernels read behind q's and k's 192
    (written 256 wide, whole lane tiles) show as a lower share."""
    b, s, h = params["batch"], params["seq"], model["num_attention_heads"]
    d = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    d_v = model["v_head_dim"]
    n = model["num_hidden_layers"]
    return {"flops": n * 3 * b * h * s * s * (d + d_v),
            "bytes": n * 6 * b * h * s * (d + d_v) * 2}


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
    r_kv -> h (d_n + d_v), h d_v -> H), its QK^T over d_n + d_r and PV over
    d_v over half the square, and its two hyper-connections' products with
    Phi (n H -> 2 n + n^2); the dense layer's three products; a sparse
    layer's router, shared expert and the held experts' three products over
    the expected assignments; the head over the held vocabulary slice;
    forward + 2 x backward. The streams' reads and writes are no matrix
    products and are not counted."""
    tokens, s = params["batch"] * params["seq"], params["seq"]
    h, heads = model["hidden_size"], model["num_attention_heads"]
    d_n, d_r, d_v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    r_q, r_kv = model["q_lora_rank"], model["kv_lora_rank"]
    w, n = model["moe_intermediate_size"], model["hc_mult"]
    attention = (
        tokens * 2 * (h * r_q + r_q * heads * (d_n + d_r) + h * (r_kv + d_r)
                      + r_kv * heads * (d_n + d_v) + heads * d_v * h)
        + params["batch"] * heads * 2 * (s * (s + 1) // 2)
        * (d_n + d_r + d_v))
    forward = model["num_hidden_layers"] * attention
    forward += sublayers(model) * tokens * 2 * n * h * (2 * n + n * n)
    forward += model["first_k_dense_replace"] * tokens * 3 * 2 * h \
        * model["intermediate_size"]
    forward += sparse_layers(model) * (
        tokens * (2 * h * model["num_experts_routed"]
                  + 3 * 2 * h * w * model["n_shared_experts"])
        + held_assignments(model, params) * 3 * 2 * h * w)
    forward += tokens * 2 * h * model["vocab_size"]
    return {"flops": 3 * forward, "per_token": 3 * forward / tokens}
