"""The operations and bytes the OLMoE cell's algorithm needs, as closed forms
of the configuration's sizes (as ``benchmark/flops.py`` holds BERT's): never
read from the Program under test or from the optimized HLO. A multiply-add
is 2; backward is twice forward; recomputed operations do not count. Read by
``reducers/needs_share.py``. They live here, not in ``flops.py``, because a
``model_config`` PR may not edit a file of the benchmark (PERF.md section 7:
a ``benchmark`` issue moves them and puts the cell into ``mfu``'s list).
"""
from __future__ import annotations


def _sizes(model: dict, params: dict):
    tokens = params["batch"] * params["seq"]
    return (tokens, tokens * model["num_experts_per_tok"],
            model["hidden_size"], model["intermediate_size"],
            model["num_experts"], model["num_hidden_layers"])


def moe_expert_matmul(model: dict, params: dict) -> dict:
    """The three grouped products of every expert layer, forward and
    backward: 3 x 2 x 3 x assignments x hidden x width FLOPs a layer. Bytes:
    each product reads its rows and its stacked weight and writes its rows
    (forward), reads the rows' gradient and the weight and writes the rows'
    gradient, reads rows and gradient and writes the weight's gradient
    (backward); each operand once, 2-byte elements."""
    _, a, h, i, e, layers = _sizes(model, params)
    one = a * h + e * h * i + a * i        # operands of one product
    return {"flops": layers * 3 * 3 * 2 * a * h * i,
            "bytes": layers * 3 * 3 * one * 2}


def flash_attention_causal(model: dict, params: dict) -> dict:
    """Causal attention needs half the S x S square: 2 B h S^2 d FLOPs
    forward (QK^T, PV below the diagonal) and twice that backward, 6 B h S^2
    d a layer. Bytes as ``flops.flash_attention``: q, k, v in and o out
    forward; q, k, v, o, dO in and dq, dk, dv out backward, B h S d 2-byte
    elements each, moved once."""
    b, s = params["batch"], params["seq"]
    h, layers = model["hidden_size"], model["num_hidden_layers"]
    return {"flops": layers * 6 * b * s * s * h,
            "bytes": layers * (4 + 8) * b * s * h * 2}


def train_step(model: dict, params: dict) -> dict:
    """The model's matmul-class FLOPs of one training step: the four
    attention projections, causal QK^T and PV (half the square), the router,
    the experts' three products over tokens x top-k assignments, and the
    output head; forward + 2 x backward."""
    tokens, a, h, i, e, layers = _sizes(model, params)
    s, v = params["seq"], model["vocab_size"]
    per_layer = (tokens * 4 * 2 * h * h         # q, k, v, o projections
                 + tokens * 2 * s * h           # causal QK^T + PV, all heads
                 + tokens * 2 * h * e           # router
                 + a * 3 * 2 * h * i)           # gate, up, down
    forward = layers * per_layer + tokens * 2 * h * v
    return {"flops": 3 * forward, "per_token": 3 * forward / tokens}
