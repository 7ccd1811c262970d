"""The operations and bytes the LFM2 cell's algorithm needs, as closed forms
of the configuration's sizes (as ``benchmark/needs_olmoe.py`` holds OLMoE's):
never read from the Program under test or from the optimized HLO. A
multiply-add is 2; backward is twice forward; recomputed operations do not
count; an element is 2 bytes. Read by ``reducers/needs_share.py``.

The expert layer is one chip's share: of the tokens x top-k assignments a
layer, ``num_experts`` held of ``num_experts_routed`` receive their part,
``held / routed`` of them where the router is even -- the expectation the
forms use (what a run's router sent here is a fetched ``load``, PERF.md
section 6).
"""
from __future__ import annotations


def _sizes(model: dict, params: dict):
    kinds = model["layer_types"]
    return (params["batch"] * params["seq"], model["hidden_size"],
            kinds.count("conv"), kinds.count("full_attention"),
            len(kinds) - model["num_dense_layers"])


def held_assignments(model: dict, params: dict) -> float:
    """Assignments a layer that an even router sends to the held experts."""
    tokens = params["batch"] * params["seq"]
    return (tokens * model["num_experts_per_tok"] * model["num_experts"]
            / model["num_experts_routed"])


def short_conv(model: dict, params: dict) -> dict:
    """The gated short convolution of every ``conv`` layer, forward and
    backward, over ``[tokens, 3 H]`` in and ``[tokens, H]`` out. Bytes, each
    operand moved once: forward reads the three parts and writes the output
    (4 T H elements); backward reads them and the output's gradient and
    writes the three parts' gradients (7 T H); the ``[H, taps]`` filter is
    nothing beside them. Operations an output element, forward: the gate
    ``B u`` 1, ``taps`` multiplies and ``taps - 1`` adds, the gate ``C``
    1; backward twice that. Bound by bytes on any chip."""
    tokens, h, convs, _, _ = _sizes(model, params)
    taps = model["conv_L_cache"]
    return {"flops": convs * 3 * (2 * taps + 1) * tokens * h,
            "bytes": convs * (4 + 7) * tokens * h * 2}


def moe_held_expert_matmul(model: dict, params: dict) -> dict:
    """The three grouped products of every expert layer over the held
    experts' assignments, forward and backward: 3 x 2 x 3 x assignments x
    hidden x width FLOPs a layer. Bytes as ``needs_olmoe.moe_expert_matmul``:
    each of the nine products moves its rows in, its stacked weight (the
    held experts') and its rows out once."""
    _, h, _, _, sparse = _sizes(model, params)
    a, w = held_assignments(model, params), model["moe_intermediate_size"]
    one = a * h + model["num_experts"] * h * w + a * w
    return {"flops": sparse * 3 * 3 * 2 * a * h * w,
            "bytes": sparse * 3 * 3 * one * 2}


def flash_attention_gqa_causal(model: dict, params: dict) -> dict:
    """Causal grouped-query attention needs half the S x S square for every
    query head: 2 B h S^2 d FLOPs forward and twice that backward, 6 B h S^2
    d a layer. Bytes, moved once: q in and o out forward, q, o, dO in and dq
    out backward (6 arrays of B h S d); k, v in forward, k, v in and dk, dv
    out backward (6 of B kv S d): the key/value heads are read in place, not
    once a query head."""
    b, s = params["batch"], params["seq"]
    _, h, _, layers, _ = _sizes(model, params)
    d = h // model["num_attention_heads"]
    return {"flops": layers * 6 * b * s * s * h,
            "bytes": layers * 6 * b * s * (
                h + model["num_key_value_heads"] * d) * 2}


def train_step(model: dict, params: dict) -> dict:
    """The model's matmul-class FLOPs of one training step: the conv
    operators' two projections, the attention layers' four (k and v at the
    key/value width) and their causal QK^T and PV (half the square), the
    dense layers' feed-forward, the routers, the held experts' three
    products over the expected assignments, and the output head over the
    held vocabulary; forward + 2 x backward."""
    tokens, h, convs, attns, sparse = _sizes(model, params)
    kv_width = model["num_key_value_heads"] * (
        h // model["num_attention_heads"])
    forward = (
        convs * tokens * 2 * h * 4 * h                       # W_in, W_out
        + attns * tokens * (2 * h * 2 * h + 2 * h * 2 * kv_width
                            + 2 * params["seq"] * h)         # q o, k v, scores
        + model["num_dense_layers"] * tokens * 3 * 2 * h
        * model["intermediate_size"]
        + sparse * tokens * 2 * h * model["num_experts_routed"]
        + sparse * held_assignments(model, params) * 3 * 2 * h
        * model["moe_intermediate_size"]
        + tokens * 2 * h * model["vocab_size"])
    return {"flops": 3 * forward, "per_token": 3 * forward / tokens}
