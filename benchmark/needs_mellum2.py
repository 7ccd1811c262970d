"""The operations and bytes the Mellum2 cell's algorithm needs, as closed
forms of the configuration's sizes (as ``benchmark/needs_laguna.py`` holds
Laguna's): never read from the Program under test or from the optimized HLO.
A multiply-add is 2; backward is twice forward; recomputed operations do not
count; an element is 2 bytes. Read by ``reducers/needs_share.py``,
``reducers/needs_share_by_layer_type.py`` and
``reducers/collective_in_scopes.py``.

Every form is the whole step's, all chips': the readers divide by the cell's
chips, because they read one device's plane and under the cell's layout each
device does its quarter. The expert layer is the deployment itself: all the
experts are held, split over the chips, and every one of the tokens x top-k
assignments reaches its expert through the exchange -- where the router is
even, ``(n - 1) / n`` of a chip's rows cross to another chip.
"""
from __future__ import annotations


def layers_of(model: dict, kind: str) -> int:
    return sum(1 for k in model["layer_types"] if k == kind)


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a head of one sequence attends over under a causal
    window: query i sees ``min(i + 1, window)`` keys."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def window_k_tiles(seq: int, window: int, block_q: int, block_k: int) -> int:
    """K tiles the Q blocks of one (batch, head) visit in the forward kernel
    of a window op (``needs_laguna.window_k_tiles``)."""
    return sum(((iq + 1) * block_q - 1) // block_k
               - max(0, iq * block_q - window + 1) // block_k + 1
               for iq in range(seq // block_q))


def assignments(model: dict, params: dict) -> int:
    """Rows a layer's experts multiply: every token's top-k, all chips'."""
    return params["batch"] * params["seq"] * model["num_experts_per_tok"]


def _moved(model: dict, params: dict) -> int:
    """Elements the two kernels of one attention layer move, each once: q in
    and o out forward, q, o, dO in and dq out backward (6 arrays of B h S
    d); k, v in forward, k, v in and dk, dv out backward (6 of B kv S d):
    the key/value heads are read in place, not once a query head."""
    return 6 * params["batch"] * params["seq"] * model["head_dim"] * (
        model["num_attention_heads"] + model["num_key_value_heads"])


def flash_attention_window(model: dict, params: dict) -> dict:
    """The sliding-window layers' kernels, forward and backward: QK^T and PV
    over the pairs inside the window, 12 h d x pairs a layer and sequence."""
    pairs = window_pairs(params["seq"], model["sliding_window"])
    n = layers_of(model, "sliding_attention")
    return {"flops": n * 12 * params["batch"] * model["num_attention_heads"]
            * model["head_dim"] * pairs,
            "bytes": n * _moved(model, params) * 2}


def flash_attention_gqa_causal(model: dict, params: dict) -> dict:
    """The full-attention layers' kernels: causal grouped-query attention
    needs half the S x S square for every query head, 6 B h S^2 d a layer."""
    b, s = params["batch"], params["seq"]
    n = layers_of(model, "full_attention")
    return {"flops": n * 6 * b * s * s * model["num_attention_heads"]
            * model["head_dim"],
            "bytes": n * _moved(model, params) * 2}


def moe_expert_matmul(model: dict, params: dict) -> dict:
    """The three grouped products of every layer over all its assignments,
    forward and backward: 3 x 2 x 3 x assignments x hidden x width FLOPs a
    layer; each of the nine products moves its rows in, the stacked weight
    (every expert, each on the chip that holds it) and its rows out once."""
    h, w = model["hidden_size"], model["moe_intermediate_size"]
    a, layers = assignments(model, params), len(model["layer_types"])
    one = a * h + model["num_experts"] * h * w + a * w
    return {"flops": layers * 3 * 3 * 2 * a * h * w,
            "bytes": layers * 3 * 3 * one * 2}


def moe_exchange(model: dict, params: dict, chips: int = 4) -> dict:
    """What the exchange puts on the wire a step under an even router: a
    crossing carries ``(chips - 1) / chips`` of the assignments' rows of
    ``hidden_size`` elements of 2 bytes (3/4 on four chips), there are four
    crossings a layer (out and back, forward and backward), and every byte
    is counted where it leaves a chip and where it arrives, as the
    published interconnect figure counts both directions
    (``peaks.json:ici_bytes_per_s``). ``rows``: the rows that leave a chip,
    all chips and crossings."""
    rows = assignments(model, params) * (chips - 1) // chips
    crossings = 4 * len(model["layer_types"])
    return {"rows": crossings * rows,
            "bytes": crossings * rows * model["hidden_size"] * 2 * 2}


def train_step(model: dict, params: dict) -> dict:
    """The model's matmul-class FLOPs of one training step, all chips': every
    attention layer's q, o (heads x head_dim) and k, v (the key/value width)
    projections and its QK^T and PV (half the square on full layers, the
    window's pairs on sliding ones), the routers, the experts' three
    products over every assignment, and the output head over the whole
    vocabulary; forward + 2 x backward."""
    tokens, s = params["batch"] * params["seq"], params["seq"]
    h, d, heads = (model["hidden_size"], model["head_dim"],
                   model["num_attention_heads"])
    kv = model["num_key_value_heads"] * d
    forward = 0
    for kind in model["layer_types"]:
        pairs = (window_pairs(s, model["sliding_window"])
                 if kind == "sliding_attention" else s * (s + 1) // 2)
        forward += tokens * (2 * h * 2 * heads * d + 2 * h * 2 * kv)
        forward += params["batch"] * heads * 2 * 2 * pairs * d
        forward += tokens * 2 * h * model["num_experts"]
        forward += assignments(model, params) * 3 * 2 * h \
            * model["moe_intermediate_size"]
    forward += tokens * 2 * h * model["vocab_size"]
    return {"flops": 3 * forward, "per_token": 3 * forward / tokens}
