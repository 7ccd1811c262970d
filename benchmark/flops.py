"""The operations and bytes the algorithm needs, as closed forms of the
configuration's sizes -- never read from the Program under test or from the
optimized HLO (which counts recomputation and fusions' extras).

Matmul-class operations only (a multiply-add is 2), as ``program_flops`` in
``paddle_tpu/utils/flops.py`` counts them; ``tests/benchmark`` holds the two
within 1% of each other on a small BERT. Backward is twice forward (one
product for the input's gradient, one for the weight's): recomputed
operations do not count.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in benchmark/"
            f"peaks.json; add the published figures with their source")
    return table[device_kind]


def bert_pretrain(model: dict, params: dict) -> dict:
    """One training step of BERT pre-training: forward and total FLOPs."""
    b, s, m = params["batch"], params["seq"], params["masks_per_seq"]
    h, i = model["hidden_size"], model["intermediate_size"]
    layers, v = model["num_hidden_layers"], model["vocab_size"]
    tokens, masked = b * s, b * m
    per_layer = tokens * (2 * h * 3 * h      # q, k, v projections
                          + 2 * h * h        # attention output
                          + 2 * 2 * h * i    # the two feed-forward products
                          + 2 * 2 * s * h)   # QK^T and PV over all heads
    heads = (masked * 2 * h * h              # masked-LM transform
             + masked * 2 * h * v            # decode through word_emb^T
             + b * 2 * h * h + b * 2 * h * 2)  # pooler, next-sentence
    forward = layers * per_layer + heads
    return {"forward": forward, "total": 3 * forward,
            "per_token": 3 * forward / tokens}


def flash_attention(model: dict, params: dict) -> dict:
    """What the attention kernels of one training step need, all layers,
    forward and backward: 4 B h S^2 d forward (QK^T, PV) and twice that
    backward (dV, dP, dQ, dK); the kernel's own recomputation of QK^T in the
    backward is not needed work. Bytes: q, k, v in and o out forward; q, k,
    v, o, dO in and dq, dk, dv out backward, each B h S d elements of the
    configuration's 2-byte type, each moved once."""
    b, s = params["batch"], params["seq"]
    h, layers = model["hidden_size"], model["num_hidden_layers"]
    qk_pv = 4 * b * s * s * h
    elems = b * s * h
    return {"flops": layers * 3 * qk_pv, "bytes": layers * (4 + 8) * elems * 2}


def roofline_seconds(need: dict, peak: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    by_flops = need["flops"] / peak["bf16_flops_per_s"]
    by_bytes = need["bytes"] / peak["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
