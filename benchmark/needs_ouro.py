"""The operations and bytes the Ouro cell's algorithm needs, as closed forms
of the configuration's sizes (as ``benchmark/needs_granite.py`` holds
Granite's): never read from the Program under test or from the optimized
HLO. A multiply-add is 2; backward is twice forward; an element is 2 bytes.
Read by ``reducers/needs_share.py``.
"""
from __future__ import annotations


def _sizes(model: dict, params: dict):
    return (params["batch"], params["seq"], model["hidden_size"],
            model["num_attention_heads"] * model["head_dim"],
            model["num_hidden_layers"], model["total_ut_steps"])


def flash_attention_causal(model: dict, params: dict) -> dict:
    """What the flash kernels of the cell EXECUTE a step, not what the model
    needs once: every one of the ``total_ut_steps x num_hidden_layers``
    layer applications runs causal attention forward, forward once more
    (the cell recomputes by layer: the backward's forward runs the forward
    kernel again) and backward. Causal attention needs half the S x S
    square: 2 B h S^2 d FLOPs forward and twice that backward, so 2 + 2 + 4
    = 8 B h S^2 d an application. Bytes, each operand moved once: q, k, v
    in and o out a forward (4 arrays of B h S d, twice), q, k, v, o, dO in
    and dq, dk, dv out backward (8): 16 an application. The kernels compute
    and mask the diagonal tiles whole and visit 62.5% of the square's tiles
    at S=4096, so 80% is the ceiling at these tiles
    (``flash_attention_causal_roofline``'s note, ``needs_olmoe``)."""
    b, s, _, wide, layers, passes = _sizes(model, params)
    return {"flops": passes * layers * 8 * b * s * s * wide,
            "bytes": passes * layers * 16 * b * s * wide * 2}


def train_step(model: dict, params: dict) -> dict:
    """The model's matmul-class FLOPs of one training step: a layer
    application's four attention projections, its causal QK^T and PV (half
    the square) and its three feed-forward products, ``total_ut_steps x
    num_hidden_layers`` of them; the head over the whole vocabulary once a
    pass; the gate's H a pass. Forward + 2 x backward; the layers'
    recomputed forward is executed work, not the model's, and is not
    counted: the cell's stand-in for mfu."""
    b, s, h, wide, layers, passes = _sizes(model, params)
    tokens = b * s
    layer = (2 * h * 4 * wide                       # q, k, v, o
             + 2 * s * wide                         # scores and values, half
             + 3 * 2 * h * model["intermediate_size"])
    forward = passes * tokens * (layers * layer
                                 + 2 * h * model["vocab_size"] + 2 * h)
    return {"flops": 3 * forward, "per_token": 3 * forward / tokens}
