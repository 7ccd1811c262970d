"""The operations and bytes the Granite cell's algorithm needs, as closed
forms of the configuration's sizes (as ``benchmark/needs_lfm2.py`` holds
LFM2's): never read from the Program under test or from the optimized HLO. A
multiply-add is 2; backward is twice forward; recomputed operations do not
count; an element is 2 bytes unless said. Read by
``reducers/needs_share.py``.
"""
from __future__ import annotations


def _sizes(model: dict, params: dict):
    kinds = model["layer_types"]
    return (params["batch"] * params["seq"], model["hidden_size"],
            kinds.count("mamba"), kinds.count("attention"),
            model["mamba_n_heads"] * model["mamba_d_head"])


def ssd_scan(model: dict, params: dict) -> dict:
    """The state-space scan of every ``mamba`` layer in chunks of Q =
    ``mamba_chunk_size``, forward and backward. Operations a token,
    forward: ``C B^T`` inside the chunk 2 Q N (once for all heads: one
    group), the masked product ``(L * C B^T) (dt X)`` 2 Q x inner (the whole
    ``[Q, Q]`` block, as the chunked form computes it), the chunk's state
    out of ``B^T (dt X)`` and the entering state into ``C H`` 2 x 2 N x
    inner; backward twice that; the backward's recomputation of the states
    and of the decay blocks is not needed work. Bytes, each operand moved
    once: x, y, dy, dx at inner and B, C, dB, dC at N 2-byte elements a
    token, dt and ddt one float32 a head. The products are small and the
    decay blocks (an exp and three multiplies an element of ``[Q, Q]`` a
    head) run on the vector and transcendental units, which no peak here
    counts: at these sizes FLOPs bound the least time, 1.6 times the bytes'."""
    tokens, _, mambas, _, inner = _sizes(model, params)
    q, n = model["mamba_chunk_size"], model["mamba_d_state"]
    forward = 2 * q * n + 2 * q * inner + 2 * 2 * n * inner
    moved = (4 * inner + 4 * n) * 2 + 2 * model["mamba_n_heads"] * 4
    return {"flops": mambas * 3 * forward * tokens,
            "bytes": mambas * moved * tokens}


def mamba_conv(model: dict, params: dict) -> dict:
    """The Mamba mixer's convolution ``silu(conv(xBC) + b)`` of every
    ``mamba`` layer over ``[tokens, inner + 2 N]``, forward and backward.
    Bytes, each operand moved once: forward reads xBC and writes the output
    (2 arrays); backward reads xBC and the output's gradient and writes
    xBC's (3): 5 where the gated form moves 11. Operations an output
    element, forward: ``taps`` multiplies, ``taps - 1`` adds, the bias 1,
    the SiLU 4; backward twice that. Bound by bytes on any chip."""
    tokens, _, mambas, _, inner = _sizes(model, params)
    wide = inner + 2 * model["mamba_d_state"]
    return {"flops": mambas * 3 * (2 * model["mamba_d_conv"] + 4)
            * tokens * wide,
            "bytes": mambas * (2 + 3) * tokens * wide * 2}


def flash_attention_gqa_causal(model: dict, params: dict) -> dict:
    """Causal grouped-query attention needs half the S x S square for every
    query head: 2 B h S^2 d FLOPs forward and twice that backward, 6 B h S^2
    d a layer. Bytes, moved once: q in and o out forward, q, o, dO in and dq
    out backward (6 arrays of B h S d); k, v in forward, k, v in and dk, dv
    out backward (6 of B kv S d): the key/value heads are read in place.
    ``needs_lfm2``'s form with Granite's layer names."""
    b, s = params["batch"], params["seq"]
    _, h, _, layers, _ = _sizes(model, params)
    d = h // model["num_attention_heads"]
    return {"flops": layers * 6 * b * s * s * h,
            "bytes": layers * 6 * b * s * (
                h + model["num_key_value_heads"] * d) * 2}


def train_step(model: dict, params: dict) -> dict:
    """The model's matmul-class FLOPs of one training step: a Mamba mixer's
    two projections and its scan (``ssd_scan``'s forward), the attention
    layer's four projections (k and v at the key/value width) and its causal
    QK^T and PV (half the square), the dense feed-forward of every layer, and
    the tied head over the held vocabulary; forward + 2 x backward."""
    tokens, h, mambas, attns, inner = _sizes(model, params)
    n, heads = model["mamba_d_state"], model["mamba_n_heads"]
    kv_width = model["num_key_value_heads"] * (
        h // model["num_attention_heads"])
    scan = ssd_scan(model, params)["flops"] / 3
    forward = (
        mambas * tokens * (2 * h * (2 * inner + 2 * n + heads)  # W_in
                           + 2 * inner * h)                     # W_out
        + scan
        + attns * tokens * (2 * h * 2 * h + 2 * h * 2 * kv_width
                            + 2 * params["seq"] * h)         # q o, k v, scores
        + (mambas + attns) * tokens * 3 * 2 * h
        * model["shared_intermediate_size"]
        + tokens * 2 * h * model["vocab_size"])
    return {"flops": 3 * forward, "per_token": 3 * forward / tokens}
