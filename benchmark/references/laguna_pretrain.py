"""Plain reference for the Laguna-S-2.1 pre-training loss as one chip's share
of it: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no framework op, no
sort, no grouping and no row budget (a row the program drops is missing
from its layer's routed output, which the check compares: ``tolerance``).
Written from the model's ``config.json`` (``model_type: laguna``; the
catalog's row) and, for the YaRN frequencies, from HF's
``_compute_yarn_parameters`` (Peng et al., arXiv:2309.00071); what the
config does not carry is the configuration file's ``assumed``. Independent
of ``paddle_tpu/models/decoder_lm.py`` except for the order in which
parameters are created, which is how weights are handed over.

The layer, ``l`` with ``H_l = num_attention_heads_per_layer[l]`` query heads
(48 on full, 72 on sliding layers) of ``head_dim`` 128 over 8 key/value
heads, no biases, eps 1e-6:

1. ``a = rmsnorm(x)``; ``q = a W_q [T, H_l, d]``, ``k = a W_k``, ``v = a W_v
   [T, 8, d]``.
2. rotate-half rotary on the first ``r = d x partial_rotary_factor`` values
   of each head of q and k, the rest passed through: sliding layers ``r =
   128``, theta 10,000, plain; full layers ``r = 64``, theta 500,000, YaRN
   (``yarn_inv_freq``), cos and sin times ``attention_factor``.
3. causal softmax attention at ``1 / sqrt(d)``, query head h reading
   key/value head ``h // (H_l / 8)``; on sliding layers key j is visible to
   query i iff ``i - sliding_window < j <= i`` -- an explicit ``[S, S]`` mask,
   in blocks of query rows.
4. ``o_h = sigmoid(a W_g)_h * attn_h``; ``x' = x + concat(o) W_o``.
5. ``m = rmsnorm(x')``. A dense layer (``mlp_layer_types``): ``x'' = x' +
   W_down (silu(W_gate m) * (W_up m))``. A sparse one: ``p = softmax(m W_r)``
   over all ``num_experts_routed``; the ``num_experts_per_tok`` largest;
   ``w_e = moe_routed_scaling_factor p_e / (their sum)``; EVERY held expert
   applied to EVERY token and masked by the choice -- the held experts' part
   of the layer, nothing standing in for the other chips (the
   ``model-configs`` guide, section 4) -- plus the shared expert, which every
   chip computes alike, ungated.
6. final RMSNorm, untied head over the held vocabulary slice, mean
   next-token cross-entropy. The config names no router loss.

Departures from the published model, each because the program under test
makes the same choice: the RMSNorm scale multiplies in float32 before the
cast back; the router weights stay float32 in the combine; every position
has a label (the batch carries the token after the last); the vocabulary is
the held slice.

Memory: it runs on the chip beside the training state, so attention runs
over blocks of ``Q_ROWS`` query rows, the output head over blocks of
``HEAD_ROWS`` positions (``lax.map``), and the experts one at a time
(``lax.scan``).
"""
from __future__ import annotations

import math

Q_ROWS = 256
HEAD_ROWS = 1024


def tolerance(model: dict) -> dict:
    """``each``: |program - reference| <= tol * the reference's largest
    entry, over (a) the means of every position's cross-entropy over blocks
    of ``seq // 64`` consecutive positions (64 at S=4096; single positions
    in the tests, whose sequences are shorter than 128) and (b), a sparse
    layer each, the mean over the tokens of the norm of the held routed
    experts' output, before the shared expert's is added. ``loss``: the
    same on the mean loss -- left out here (no limit), see below.

    Block means for OLMoE's and LFM2's reason
    (``references/lfm2_pretrain.py``): the program computes in bfloat16 with
    a float32 router, at random initial weights the 10th and 11th largest of
    256 softmax probabilities lie closer than the bfloat16 rounding of the
    router's input moves them, and under the share a flip between an expert
    held here and one held elsewhere adds or removes a whole expert's output
    at weight 2.5 / 10. No limit on single positions separates that from a
    lower precision; over 64 positions the flips average down.

    The routed entries (b), because the cross-entropy cannot see the routed
    path on this share: 8 of 256 experts are held, 3.1% of the assignments,
    most tokens get no routed output here, and the routed scale 2.5 left
    out read 1.5e-3 / 1.8e-3 on (a) alone, twice the program's own reading.
    A layer's mean norm reads about 1.6 where the cross-entropy reads 9.4,
    so it enters the same comparison unscaled; one number a layer and not
    blocks, because 1.75% of the assignments flip and a layer has 1,280
    held rows (a flip moves a layer's mean by 0.08%, a block of 64 tokens'
    by 5%). The scale left out moves every entry of (b) by 60%, a dropped
    row by its share of the layer's held rows: the reference has no budget.

    The limit, from the two readings the contract asks for (PERF.md section
    6, PR 39; chip, published widths, 5 layers, 1 x 4096 tokens, seeded
    weights as the cell's check has them), ``READINGS``: the program as it
    is read 4.8e-4 to 1.84e-3 over (a) and (b) (22 seeds of the review
    round, and 8.2e-4 / 9.9e-4 on weights trained for 200 / 40 steps; (a)
    alone read 5.6e-4 to 1.01e-3 over 20 seeds before (b) was added: the
    flips show more in (b)), float8 (e4m3) weights in the program's place
    7.0e-3 and 8.5e-3. The limit is 3.5e-3, about their geometric mean
    (3.58e-3): 1.9 times the one, half the other. What it sees beside float8, every verdict
    ``jobs/common.py:reference_check``'s own (``tools/laguna_probe.py
    controls``, two seeds): the window ignored on the three sliding layers
    7.9e-3 and 6.2e-3, the gate left out 6.5e-2 and 2.4e-2, the rotary
    embedding over the whole head on the full layers 4.6e-2 and 4.9e-2,
    the routed scale 2.5 left out 1.17e-1 and 1.23e-1, a row budget of 512
    (rows dropped) 1.12e-1 and 1.22e-1.
    ``loss`` has no upper reading: errors of single positions cancel in the
    mean over 4,096 positions -- 5.7e-7 to 6.4e-5 as it is, float8 6.2e-6 to
    6.5e-4, inside the sound runs' range on some seeds -- so no limit on it
    separates anything ``each`` does not, and the accepted cells' 1e-4
    would leave the largest sound reading 1.6 times of room where the
    contract asks for three. It is left out of this cell's check (the
    harness takes an infinite limit; a loss that is not a number still
    fails: ``each`` is its positions' means)."""
    return {"loss": float("inf"), "each": EACH_LIMIT}


# The two readings (my chip runs, PR 39, review round: the cell's own runs
# and tools/laguna_probe.py controls / load): the program as it is, the
# largest over the seeds run, and float8 (e4m3) weights in the program's
# place, the smallest over its seeds. The limit lies between them with room
# on both sides.
READINGS = {"as_it_is_max": 1.837e-3, "float8_min": 6.977e-3}
EACH_LIMIT = 3.5e-3


def check_block(seq: int) -> int:
    """Positions a block of the compared cross-entropy: 64 blocks a
    sequence (single positions under 128 tokens)."""
    return max(1, seq // 64)


def differing_share(index, experts) -> float:
    """The share of the program's tokens x top-k assignments (``index [...,
    tokens, k]``, any order) that are not among the reference's for the same
    token (``experts``, likewise)."""
    import numpy as np
    index, experts = np.asarray(index), np.asarray(experts)
    kept = (index[..., :, None] == experts[..., None, :]).any(-1)
    return float(1.0 - kept.mean())


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
    """x [B, h, S, d]: rotate-half rotary embedding of the first ``r`` values
    of every head, positions 0..S-1; the other ``d - r`` pass through."""
    import jax.numpy as jnp
    import numpy as np
    S, d = x.shape[-2], x.shape[-1]
    r = int(d * rope.get("partial_rotary_factor", 1))
    if rope.get("rope_type", "default") == "yarn":
        inv_freq = yarn_inv_freq(rope, r)
        factor = rope.get("attention_factor") or (
            0.1 * math.log(rope["factor"]) + 1.0)
    else:
        inv_freq = float(rope["rope_theta"]) ** (-np.arange(0, r, 2) / r)
        factor = 1.0
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1) * factor
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1) * factor
    rot, rest = x[..., :r], x[..., r:]
    x1, x2 = rot[..., :r // 2], rot[..., r // 2:]
    rot = rot * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    return jnp.concatenate([rot, rest], axis=-1)


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


def _swiglu(x, w_gate, w_up, w_down):
    import jax
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def expert_layer(x, w_router, w_gate, w_up, w_down, model: dict, held=None,
                 chosen=None):
    """The held experts' part of a sparse layer's routed output for tokens
    ``x [T, H]``, the chosen experts ``[T, k]`` and the load ``[experts
    routed]``; the shared expert is not in it. ``held = (first, count)``
    (default: the model's) says which experts the stacked weights are.
    ``chosen [T, k]`` takes the choice as given (the program's own, when
    gradients are compared and a 10th / 11th expert that flips under
    bfloat16 must not stand in the way)."""
    import jax
    import jax.numpy as jnp
    k = model["num_experts_per_tok"]
    routed = model.get("num_experts_routed", model["num_experts"])
    first, count = held or (model.get("first_expert_held", 0),
                            model["num_experts"])
    prob = jax.nn.softmax(x @ w_router, axis=-1)             # [T, routed]
    if chosen is None:
        _, top_i = jax.lax.top_k(jax.lax.stop_gradient(prob), k)
    else:
        top_i = chosen
    top_w = jnp.take_along_axis(prob, top_i, axis=-1)
    if model.get("norm_topk_prob"):
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * model.get("moe_routed_scaling_factor", 1.0)
    taken = jax.nn.one_hot(top_i, routed)                    # [T, k, routed]
    # [T, routed]: the router's weight where the expert was chosen
    gate = jnp.sum(taken * top_w[..., None], axis=1)

    def expert(acc, w):
        g, u, dn, col = w
        return acc + col[:, None] * _swiglu(x, g, u, dn), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (w_gate, w_up, w_down, gate.T[first:first + count]))
    return out, top_i, jnp.sum(taken, axis=(0, 1)).astype(jnp.int32)


def _is_dense(model: dict, layer: int) -> bool:
    kinds = model.get("mlp_layer_types")
    return (kinds[layer] == "dense") if kinds else (
        layer in model.get("mlp_only_layers", ()))


def forward(weights: list, batch: dict, model: dict, chosen=None,
            held=None) -> dict:
    """The pure function: ``weights`` are float32 arrays in the program's
    creation order. Returns ``loss``, ``positions`` (every position's
    cross-entropy), ``routed`` (a sparse layer each: the mean over the
    tokens of the norm of the held routed experts' output), ``each`` (the
    cross-entropy's means over blocks of ``check_block(seq)``, then
    ``routed``),
    ``experts`` ``[expert layers, tokens, k]`` sorted by expert and ``load``
    ``[expert layers, experts routed]``. ``chosen [expert layers, tokens,
    k]`` and ``held``: ``expert_layer``'s, a layer each."""
    import jax
    import jax.numpy as jnp

    kv, d = model["num_key_value_heads"], model["head_dim"]
    kinds, eps = model["layer_types"], model["rms_norm_eps"]
    it = iter(weights)
    take = lambda n: [next(it) for _ in range(n)]           # noqa: E731
    (emb,) = take(1)
    ids = batch["ids"]
    B, S = ids.shape
    H = emb.shape[1]
    x = emb[ids].reshape(B * S, H)
    experts, loads, routed, sparse = [], [], [], 0
    for i, kind in enumerate(kinds):
        heads = model["num_attention_heads_per_layer"][i]
        rope = model["rope_parameters"][kind]
        norm, wq, wk, wv, wg, wo = take(6)
        a = _rms_norm(x, norm, eps)
        sh = lambda t, n: t.reshape(B, S, n, d).transpose(0, 2, 1, 3)  # noqa
        attn = _attention(
            _rope(sh(a @ wq, heads), rope), _rope(sh(a @ wk, kv), rope),
            sh(a @ wv, kv),
            model["sliding_window"] if kind == "sliding_attention" else None)
        attn = attn.transpose(0, 2, 1, 3).reshape(B * S, heads, d)
        if model.get("gating", "none") == "per-head":
            attn = attn * jax.nn.sigmoid(a @ wg)[:, :, None]
        h = x + attn.reshape(B * S, heads * d) @ wo
        (ffn_norm,) = take(1)
        m = _rms_norm(h, ffn_norm, eps)
        if _is_dense(model, i):
            x = h + _swiglu(m, *take(3))
            continue
        w_router, w_gate, w_up, w_down = take(4)
        moe, top_i, load = expert_layer(
            m, w_router, w_gate, w_up, w_down, model, held=held,
            chosen=None if chosen is None else chosen[sparse])
        sparse += 1
        x = h + moe + _swiglu(m, *take(3))                   # shared expert
        experts.append(jnp.sort(top_i, axis=-1))
        loads.append(load)
        routed.append(jnp.mean(jnp.linalg.norm(moe, axis=-1)))

    final_norm, head = take(2)
    xn = _rms_norm(x, final_norm, eps)
    labels = batch["labels"].reshape(-1)
    rows = _block(B * S, HEAD_ROWS)

    def decode(arg):
        hb, lb = arg
        logp = jax.nn.log_softmax(hb @ head, axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=1)[:, 0]

    each = jax.lax.map(decode, (xn.reshape(-1, rows, H),
                                labels.reshape(-1, rows))).reshape(-1)
    blocks = jnp.mean(each.reshape(-1, check_block(S)), axis=1)
    return {"loss": jnp.mean(each), "positions": each,
            "routed": jnp.stack(routed),
            "each": jnp.concatenate([blocks, jnp.stack(routed)]),
            "experts": jnp.stack(experts).astype(jnp.int32),
            "load": jnp.stack(loads)}


def loss(weights: list, batch: dict, model: dict, params: dict) -> dict:
    """``weights``: the program's parameters in creation order, any dtype;
    ``forward``'s result, computed in float32 at matmul precision
    "highest"."""
    import jax
    import jax.numpy as jnp

    def f(weights, batch):
        return forward([jnp.asarray(w, jnp.float32) for w in weights], batch,
                       model)

    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(list(weights), dict(batch))
