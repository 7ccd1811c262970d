"""The operations and bytes the Laguna cell's algorithm needs, as closed
forms of the configuration's sizes (as ``benchmark/needs_lfm2.py`` holds
LFM2's): never read from the Program under test or from the optimized HLO. A
multiply-add is 2; backward is twice forward; recomputed operations do not
count; an element is 2 bytes. Read by ``reducers/needs_share.py`` and
``reducers/needs_share_by_layer_type.py``.

The expert layer is one chip's share: of the tokens x top-k assignments a
layer, ``num_experts`` held of ``num_experts_routed`` receive their part,
``held / routed`` of them where the router is even -- the expectation the
forms use (what a run's router sent here is a fetched ``load``, PERF.md
section 6).
"""
from __future__ import annotations

def attention_layers(model: dict, kind: str) -> list:
    """The query-head counts of the layers of type ``kind``, in order."""
    return [heads for heads, k in zip(model["num_attention_heads_per_layer"],
                                      model["layer_types"]) if k == kind]


def sparse_layers(model: dict) -> int:
    return sum(1 for k in model["mlp_layer_types"] if k == "sparse")


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a head of one sequence attends over under a causal
    window: query i sees ``min(i + 1, window)`` keys."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def window_k_tiles(seq: int, window: int, block_q: int, block_k: int) -> int:
    """K tiles the Q blocks of one (batch, head) visit in the forward kernel
    of a window op: for Q block ``iq`` the tiles from the one that holds the
    first key its first row sees, ``max(0, iq block_q - window + 1)``, to the
    one that holds its last row's diagonal, ``(iq + 1) block_q - 1``."""
    return sum(((iq + 1) * block_q - 1) // block_k
               - max(0, iq * block_q - window + 1) // block_k + 1
               for iq in range(seq // block_q))


def held_assignments(model: dict, params: dict) -> float:
    """Assignments a layer that an even router sends to the held experts."""
    tokens = params["batch"] * params["seq"]
    return (tokens * model["num_experts_per_tok"] * model["num_experts"]
            / model["num_experts_routed"])


def _moved(model: dict, params: dict, heads: int) -> int:
    """Elements the two kernels of one attention layer move, each once: q in
    and o out forward, q, o, dO in and dq out backward (6 arrays of B h S
    d); k, v in forward, k, v in and dk, dv out backward (6 of B kv S d):
    the key/value heads are read in place, not once a query head."""
    return 6 * params["batch"] * params["seq"] * model["head_dim"] * (
        heads + model["num_key_value_heads"])


def flash_attention_window(model: dict, params: dict) -> dict:
    """The sliding-window layers' kernels, forward and backward: QK^T and PV
    over the pairs inside the window, 2 x 2 x pairs x d a head forward and
    twice that backward: 12 h d x pairs a layer and sequence."""
    pairs = window_pairs(params["seq"], model["sliding_window"])
    layers = attention_layers(model, "sliding_attention")
    return {"flops": sum(12 * params["batch"] * h * model["head_dim"] * pairs
                         for h in layers),
            "bytes": sum(_moved(model, params, h) for h in layers) * 2}


def flash_attention_gqa_causal(model: dict, params: dict) -> dict:
    """The full-attention layers' kernels: causal grouped-query attention
    needs half the S x S square for every query head, 6 B h S^2 d a layer
    (``needs_lfm2.flash_attention_gqa_causal`` at this head count and
    size)."""
    b, s = params["batch"], params["seq"]
    layers = attention_layers(model, "full_attention")
    return {"flops": sum(6 * b * s * s * h * model["head_dim"]
                         for h in layers),
            "bytes": sum(_moved(model, params, h) for h in layers) * 2}


def moe_held_expert_matmul(model: dict, params: dict) -> dict:
    """The three grouped products of every sparse layer over the held
    experts' assignments, forward and backward: 3 x 2 x 3 x assignments x
    hidden x width FLOPs a layer. Bytes as ``needs_lfm2``'s: each of the
    nine products moves its rows in, its stacked weight (the held experts')
    and its rows out once."""
    h, w = model["hidden_size"], model["moe_intermediate_size"]
    a = held_assignments(model, params)
    one = a * h + model["num_experts"] * h * w + a * w
    return {"flops": sparse_layers(model) * 3 * 3 * 2 * a * h * w,
            "bytes": sparse_layers(model) * 3 * 3 * one * 2}


def train_step(model: dict, params: dict) -> dict:
    """The model's matmul-class FLOPs of one training step: every attention
    layer's q, o (at heads x head_dim), k, v (at the key/value width) and
    gate projections and its QK^T and PV (half the square on full layers,
    the window's pairs on sliding ones), the dense layers' feed-forward, the
    routers, the shared expert, the held experts' three products over the
    expected assignments, and the output head over the held vocabulary;
    forward + 2 x backward."""
    tokens, s = params["batch"] * params["seq"], params["seq"]
    h, d = model["hidden_size"], model["head_dim"]
    kv = model["num_key_value_heads"] * d
    forward = 0
    for heads, kind in zip(model["num_attention_heads_per_layer"],
                           model["layer_types"]):
        pairs = (window_pairs(s, model["sliding_window"])
                 if kind == "sliding_attention" else s * (s + 1) // 2)
        forward += tokens * (2 * h * 2 * heads * d + 2 * h * 2 * kv
                             + 2 * h * heads)
        forward += params["batch"] * heads * 2 * 2 * pairs * d
    sparse = sparse_layers(model)
    dense = len(model["mlp_layer_types"]) - sparse
    forward += dense * tokens * 3 * 2 * h * model["intermediate_size"]
    forward += sparse * tokens * (
        2 * h * model["num_experts_routed"]
        + 3 * 2 * h * model["shared_expert_intermediate_size"])
    forward += sparse * held_assignments(model, params) * 3 * 2 * h \
        * model["moe_intermediate_size"]
    forward += tokens * 2 * h * model["vocab_size"]
    return {"flops": 3 * forward, "per_token": 3 * forward / tokens}
