"""Plain reference for the Mellum2-12B-A2.5B pre-training loss, the four
layers of the cell uncut: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no framework op, no
mesh, no exchange, no sort, no grouping and no row budget -- every expert is
applied to every token by a loop over the 64 and masked by the router's
choice, so a row the program's exchange loses, a chip's experts left out or
a row that came back to the wrong token is missing from (or wrong in) its
layer's routed output, which the check compares (``tolerance``). Written
from the model's ``config.json`` (``model_type: mellum``; the catalog's row)
and, for the YaRN frequencies, from HF's ``_compute_yarn_parameters`` (Peng
et al., arXiv:2309.00071); what the config does not carry is the
configuration file's ``assumed``. Independent of
``paddle_tpu/models/decoder_lm.py`` except for the order in which parameters
are created, which is how weights are handed over.

The layer, 32 query heads of ``head_dim`` 128 over 4 key/value heads, no
biases, eps 1e-6, ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``:

1. ``a = N(x)``; ``q = a W_q [T, 32, d]``, ``k = a W_k``, ``v = a W_v [T, 4,
   d]``; each head's q and k normed, ``N(q_m; w_q)``, ``N(k_m; w_k)``, one
   scale of head size shared by the heads (``qk_norm: "head"``, assumed).
2. rotate-half rotary over the whole head of q and k by layer type:
   ``sliding_attention`` theta 500,000, plain; ``full_attention`` YaRN
   (``yarn_inv_freq``: factor 16 over 8,192 original positions), cos and sin
   times ``attention_factor``.
3. causal softmax attention at ``1 / sqrt(d)``, query head m reading
   key/value head ``m // 8``; on sliding layers key j is visible to query i
   iff ``i - sliding_window < j <= i`` -- an explicit mask, in blocks of
   query rows. ``h = x + concat(o) W_o``.
4. ``b = N(h)``; ``p = softmax(b W_r)`` over the 64; the 8 largest; ``w_e =
   p_e / (their sum)``; ``y = h + sum_e w_e W_down,e (silu(W_gate,e b) *
   (W_up,e b))``.
5. final RMSNorm, untied head over all 98,304 entries, mean next-token
   cross-entropy. The config names no router loss.

Departures from the published model, each because the program under test
makes the same choice: the RMSNorm scale multiplies in float32 before the
cast back; the router weights stay float32 in the combine; every position
has a label (the batch carries the token after the last); no
multi-token-prediction module (the config has no key for one: ``assumed``).

Memory: ``loss`` runs on the chips beside the training state and is handed
the state's own arrays (the whole model, laid over the host's chips as the
program holds it), so it computes layer by layer, a jitted call each that
turns that layer's weights to float32 and lets them go again: the 2.1 B
parameters never exist in float32 at once. Inside a layer attention runs
over blocks of ``Q_ROWS`` query rows, the head over blocks of ``HEAD_ROWS``
positions (``lax.map``) and the experts one at a time (``lax.scan``).
``forward`` is the same computation as one pure function, for the tests'
gradients at small sizes.
"""
from __future__ import annotations

import functools
import math

Q_ROWS = 256
HEAD_ROWS = 1024
LAYER_WEIGHTS = 12      # norm, q, k, v, q norm, k norm, o; norm, router, 3

def tolerance(model: dict) -> dict:
    """``each``: |program - reference| <= tol * the reference's largest
    entry, over (a) the means of every position's cross-entropy over blocks
    of ``seq // 64`` consecutive positions (64 at S=4096; single positions
    in the tests, whose sequences are shorter than 128) and (b), a layer
    each, the mean over the tokens of the norm of the routed experts'
    output. ``loss``: the same on the mean loss.

    Block means for OLMoE's reason (``references/olmoe_pretrain.py``): the
    program computes in bfloat16 with a float32 router, at seeded weights
    the 8th and 9th largest of 64 softmax probabilities lie closer than the
    bfloat16 rounding of the router's input moves them, and a flipped
    assignment swaps one expert's output for another's at weight about 1/8:
    single positions cannot carry the check, a block's mean can.

    (b) because the exchange is what this cell is there for, and no flip
    moves a layer's mean: it moves by the share of rows lost or misplaced
    (a chip's own 16 experts only: three quarters of every token's output
    gone; a receive buffer that overflowed: the rows it dropped) and by the
    router's weights (``norm_topk_prob`` off: every row times the chosen
    probabilities' sum). The reference has no budget and no exchange, so a
    run whose buffers drop rows at the seeded weights FAILS here, as it
    should: ISSUE 55's recipe (every weight at 0.02, a budget of 1.25 x the
    even share) did that on 1 seed of 10 (3.16e-3; PERF.md section 6).

    The limits, from the two readings the contract asks for (``READINGS``;
    my chip runs, PR 55: four chips, published widths, 8 x 4,096 tokens,
    seeded weights as the cell's check has them; ``tools/mellum2_probe.py
    controls`` / ``readings`` and the cell's own runs): the program as it is
    read 5.8e-4 to 8.05e-4 over nine seeds on which no row was dropped,
    float8 (e4m3) weights in the program's place -- the nearest precision
    below the configuration's bfloat16 -- 3.58e-3 to 3.86e-3 over four.
    ``EACH_LIMIT`` is 1.7e-3, their geometric mean: 2.1 times the one, 2.1
    times under the other. What else it catches is PERF.md section 2's list
    of controls, with the limit each verdict was read under.

    ``loss``: the accepted cells' 1e-4, which leaves the largest sound
    reading (2.55e-5 over the same nine seeds) 3.9 times of room; it has no
    upper reading of its own -- float8 reads 6.9e-6 to 1.05e-4, errors of
    single positions cancelling in the mean over 32,768 -- and decides
    nothing ``each`` does not."""
    return {"loss": LOSS_LIMIT, "each": EACH_LIMIT}


# The two readings (my chip runs, PR 55): the program as it is, the largest
# over the seeds run, and float8 (e4m3) weights in the program's place, the
# smallest over its seeds. The limit lies between them with room on both
# sides (PERF.md section 2).
READINGS = {"as_it_is_max": 8.05e-4, "float8_min": 3.58e-3}
EACH_LIMIT = 1.7e-3
LOSS_LIMIT = 1e-4


def check_block(seq: int) -> int:
    """Positions a block of the compared cross-entropy: 64 blocks a
    sequence (single positions under 128 tokens)."""
    return max(1, seq // 64)


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def yarn_inv_freq(rope: dict, r: int):
    """HF's ``_compute_yarn_parameters`` over the ``r`` rotated values of a
    head: ``inv_freq_i = (1 - m_i) base_i / factor + m_i base_i`` with
    ``base_i = theta^(-2i/r)``, ``m_i = 1 - clip((i - low) / (high - low), 0,
    1)``, ``low`` / ``high`` the floor / ceil of ``r ln(original / (n 2 pi))
    / (2 ln theta)`` at ``n`` = ``beta_fast`` / ``beta_slow``, held to ``[0,
    r - 1]``."""
    import numpy as np
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def correction(rotations):
        return r * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(correction(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction(rope.get("beta_slow", 1))), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0, 1)
    base = theta ** (-np.arange(0, r, 2) / r)
    return (base / factor) * ramp + base * (1 - ramp)


def _rope(x, rope: dict):
    """x [B, h, S, d]: rotate-half rotary embedding over the whole head,
    positions 0..S-1."""
    import jax.numpy as jnp
    import numpy as np
    S, d = x.shape[-2], x.shape[-1]
    if rope.get("rope_type", "default") == "yarn":
        inv_freq = yarn_inv_freq(rope, d)
        factor = rope.get("attention_factor") or (
            0.1 * math.log(rope["factor"]) + 1.0)
    else:
        inv_freq = float(rope["rope_theta"]) ** (-np.arange(0, d, 2) / d)
        factor = 1.0
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1) * factor
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1) * factor
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _block(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is at most ``target``."""
    c = max(1, min(n, target))
    while n % c:
        c -= 1
    return c


def _attention(q, k, v, window=None):
    """q [B, h, S, d] against k, v [B, kv, S, d], query head i reading
    key/value head i // (h / kv): softmax(q k^T / sqrt(d) + mask) v with the
    mask written out -- key j visible to query i iff j <= i and, under a
    ``window``, i - window < j --, in blocks of query rows so that the [S, S]
    scores never exist whole. K and V repeated: the plain form."""
    import jax
    import jax.numpy as jnp
    B, h, S, d = q.shape
    k = jnp.repeat(k, h // k.shape[1], axis=1)
    v = jnp.repeat(v, h // v.shape[1], axis=1)
    rows = _block(S, Q_ROWS)
    key_pos = jnp.arange(S)

    def one(arg):
        qb, first = arg                                  # [B, h, rows, d]
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k) / math.sqrt(d)
        q_pos = (first + jnp.arange(rows))[:, None]
        seen = key_pos[None, :] <= q_pos
        if window:
            seen = seen & (key_pos[None, :] > q_pos - window)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    blocks = q.reshape(B, h, S // rows, rows, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(one, (blocks, jnp.arange(0, S, rows)))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, h, S, d)


def router_logits(x, w_router):
    """The router's 64 logits a token, in float32 as the model computes
    them."""
    return x @ w_router


def expert_layer(x, w_router, w_gate, w_up, w_down, model: dict, chosen=None):
    """A layer's routed output for tokens ``x [T, H]`` and the chosen
    experts ``[T, k]``: every expert applied to every token, one at a time,
    and masked by the router's weight where it was chosen. ``chosen [T, k]``
    takes the choice as given (the program's own, when gradients are
    compared and an 8th / 9th expert that flips under bfloat16 must not
    stand in the way)."""
    import jax
    import jax.numpy as jnp
    k, E = model["num_experts_per_tok"], model["num_experts"]
    logits = router_logits(x, w_router)
    prob = jax.nn.softmax(logits, axis=-1)                   # [T, E]
    if chosen is None:
        _, top_i = jax.lax.top_k(jax.lax.stop_gradient(prob), k)
    else:
        top_i = chosen
    top_w = jnp.take_along_axis(prob, top_i, axis=-1)
    if model.get("norm_topk_prob"):
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # [T, E]: the router's weight where the expert was chosen
    gate = jnp.sum(jax.nn.one_hot(top_i, E) * top_w[..., None], axis=1)

    def expert(acc, w):
        g, u, dn, col = w
        return acc + col[:, None] * (
            (jax.nn.silu(x @ g) * (x @ u)) @ dn), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (w_gate, w_up, w_down, gate.T))
    return out, top_i


def layer(x, weights, model: dict, kind: str, batch: int, seq: int,
          chosen=None):
    """One decoder layer over ``x [batch * seq, H]`` float32 with its twelve
    weights in creation order; returns the layer's output, the mean norm of
    its routed output and the experts chosen (sorted a token)."""
    import jax.numpy as jnp
    (norm, wq, wk, wv, q_norm, k_norm, wo, ffn_norm, w_router, w_gate, w_up,
     w_down) = [jnp.asarray(w, jnp.float32) for w in weights]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps = model["head_dim"], model["rms_norm_eps"]
    rope = model["rope_parameters"][kind]
    a = _rms_norm(x, norm, eps)

    def heads_of(t, n, scale=None):     # [T, n d] -> [B, n, S, d]
        t = t.reshape(batch, seq, n, d)
        if scale is not None and model.get("qk_norm", "head") == "head":
            t = _rms_norm(t, scale, eps)
        return t.transpose(0, 2, 1, 3)
    attn = _attention(
        _rope(heads_of(a @ wq, heads, q_norm), rope),
        _rope(heads_of(a @ wk, kv, k_norm), rope), heads_of(a @ wv, kv),
        model["sliding_window"] if kind == "sliding_attention" else None)
    h = x + attn.transpose(0, 2, 1, 3).reshape(batch * seq, heads * d) @ wo
    moe, top_i = expert_layer(_rms_norm(h, ffn_norm, eps), w_router, w_gate,
                              w_up, w_down, model, chosen)
    return (h + moe, jnp.mean(jnp.linalg.norm(moe, axis=-1)),
            jnp.sort(top_i, axis=-1).astype(jnp.int32))


def embed(table, ids):
    import jax.numpy as jnp
    return jnp.asarray(table, jnp.float32)[ids].reshape(-1, table.shape[1])


def decode(x, final_norm, head, labels, model: dict):
    """Every position's cross-entropy under the head, in blocks of
    ``HEAD_ROWS`` positions."""
    import jax
    import jax.numpy as jnp
    xn = _rms_norm(x, jnp.asarray(final_norm, jnp.float32),
                   model["rms_norm_eps"])
    head = jnp.asarray(head, jnp.float32)
    rows = _block(x.shape[0], HEAD_ROWS)

    def one(arg):
        hb, lb = arg
        logp = jax.nn.log_softmax(hb @ head, axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=1)[:, 0]

    return jax.lax.map(one, (xn.reshape(-1, rows, x.shape[1]),
                             labels.reshape(-1, rows))).reshape(-1)


def _result(each, routed, experts, seq: int) -> dict:
    import jax.numpy as jnp
    blocks = jnp.mean(each.reshape(-1, check_block(seq)), axis=1)
    routed = jnp.stack(routed)
    return {"loss": jnp.mean(each), "positions": each, "routed": routed,
            "each": jnp.concatenate([blocks, routed]),
            "experts": jnp.stack(experts)}


def forward(weights: list, batch: dict, model: dict, chosen=None) -> dict:
    """The pure function, whole: ``weights`` in the program's creation
    order. Returns ``loss``, ``positions`` (every position's cross-entropy),
    ``routed`` (a layer each: the mean over the tokens of the norm of the
    routed experts' output), ``each`` (the cross-entropy's means over blocks
    of ``check_block(seq)``, then ``routed``) and ``experts`` ``[layers,
    tokens, k]`` sorted by expert. ``chosen [layers, tokens, k]``:
    ``expert_layer``'s, a layer each."""
    kinds = model["layer_types"]
    if len(weights) != 1 + LAYER_WEIGHTS * len(kinds) + 2:
        raise ValueError(f"{len(weights)} weights for {len(kinds)} layers")
    B, S = batch["ids"].shape
    x = embed(weights[0], batch["ids"])
    routed, experts = [], []
    for i, kind in enumerate(kinds):
        x, norm, top = layer(
            x, weights[1 + LAYER_WEIGHTS * i:1 + LAYER_WEIGHTS * (i + 1)],
            model, kind, B, S, None if chosen is None else chosen[i])
        routed.append(norm)
        experts.append(top)
    each = decode(x, weights[-2], weights[-1], batch["labels"].reshape(-1),
                  model)
    return _result(each, routed, experts, S)


def loss(weights: list, batch: dict, model: dict, params: dict) -> dict:
    """``weights``: the program's parameters in creation order, any dtype,
    wherever they lie; ``forward``'s result in float32 at matmul precision
    "highest", computed a layer a jitted call (the module docstring's
    paragraph on memory)."""
    import jax
    import numpy as np
    kinds = model["layer_types"]
    B, S = np.shape(batch["ids"])
    frozen = _freeze(model)
    with jax.default_matmul_precision("highest"):
        x = jax.jit(embed)(weights[0], batch["ids"])
        routed, experts = [], []
        for i, kind in enumerate(kinds):
            x, norm, top = _layer_call(frozen, kind, B, S)(
                x, list(weights[1 + LAYER_WEIGHTS * i:
                                1 + LAYER_WEIGHTS * (i + 1)]))
            routed.append(norm)
            experts.append(top)
        each = _decode_call(frozen)(x, weights[-2], weights[-1],
                                    np.reshape(batch["labels"], -1))
        return _result(each, routed, experts, S)


def _freeze(model: dict):
    """The model's keys the layers read, hashable (a jit's static side)."""
    import json
    return json.dumps({k: model[k] for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_parameters", "sliding_window",
        "num_experts_per_tok", "num_experts", "norm_topk_prob")
        if k in model} | {"qk_norm": model.get("qk_norm", "head")},
        sort_keys=True)


@functools.lru_cache(maxsize=None)
def _layer_call(frozen: str, kind: str, batch: int, seq: int):
    import json
    import jax
    model = json.loads(frozen)
    return jax.jit(lambda x, w: layer(x, w, model, kind, batch, seq))


@functools.lru_cache(maxsize=None)
def _decode_call(frozen: str):
    import json
    import jax
    model = json.loads(frozen)
    return jax.jit(lambda x, n, h, lb: decode(x, n, h, lb, model))
