"""Plain reference for the LFM2-8B-A1B pre-training loss as one chip's share
of it: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no framework op, no
sort and no grouping. Written from HF ``modeling_lfm2_moe.py`` (``Lfm2Moe``:
``Lfm2MoeShortConv``, ``Lfm2MoeAttention``, ``Lfm2MoeSparseMoeBlock``) and,
for the bias rule, Wang et al., "Auxiliary-Loss-Free Load Balancing Strategy
for Mixture-of-Experts" (arXiv:2408.15664); independent of
``paddle_tpu/models/decoder_lm.py`` except for the order in which parameters
are created, which is how weights are handed over (the selection biases
follow the parameters, a layer each).

The layer: ``h = x + operator(norm(x))``, ``y = h + feed_forward(norm(h))``.
``conv``: ``B, C, u = split(W_in n, 3)``; ``c[t] = sum_j w[:, j] * (B u)[t -
2 + j]`` along the sequence, zeros before its start; ``W_out (C * c)``.
``full_attention``: 32 query and 8 key/value heads of 64, RMSNorm over each
head's q and k with one scale of 64, rotate-half rope (theta 1e6), ``softmax(Q
K^T / 8 + causal mask) V`` with query head i reading key/value head i // 4.
Dense feed-forward in the layers below ``num_dense_layers``; elsewhere the
router scores all ``num_experts_routed`` experts by sigmoid, chooses four by
score + bias, weighs them by the score over the four scores' sum + 1e-6 (all
four, held here or not), and EVERY held expert ``W_down (silu(W_gate x) *
(W_up x))`` is applied to EVERY token and masked by the choice: the result
is the held experts' part of the layer, which is what goes on (the
``model-configs`` guide, section 4; nothing stands in for the other chips).

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

# the bias' step (Wang et al. use 1e-3; LFM2's own is not published)
BIAS_UPDATE_RATE = 1e-3
Q_ROWS = 256
HEAD_ROWS = 1024


def tolerance(model: dict) -> dict:
    """``loss``: |program - reference| <= tol * |reference| on the loss.
    ``each``: the same on the means of every position's cross-entropy over
    blocks of ``seq // 64`` consecutive positions (64 at S=4096; single
    positions in the tests, whose sequences are shorter than 128), relative
    to the largest of them.

    Block means for OLMoE's reason (``references/olmoe_pretrain.py``), which
    holds more strongly here: the program computes in bfloat16 with a
    float32 router, at random initial weights the 4th and 5th largest of 32
    sigmoid scores lie a few 10^-4 apart, and the bfloat16 rounding of the
    router's input moves them by as much. A token whose 4th expert flips
    changes two experts at weight 1/4 (OLMoE: 1/64), and under the share a
    flip between an expert held here and one held elsewhere adds or removes
    a whole expert's output. No limit on single positions separates that
    from a lower precision; over 64 positions the few flips average down by
    64 and a lower precision's errors, which sit on every position, by 8.

    The limits, from the two readings the contract asks for (PERF.md section
    6, PR 32; chip, published widths, 5 layers, 4 x 4096 tokens, seeded
    weights and zero bias as the cell's check has them): the program as it
    is read 1.04e-3 to 1.82e-3 of the largest block over 22 seeds, float8
    (e4m3) weights in the program's place 5.1e-3, 5.3e-3 and 7.0e-3 (three
    seeds; 7.1e-3 and 8.0e-3 on weights trained for 24 and 100 steps):
    3.6e-3 at the cell's five layers, twice the one and 0.7 of the other,
    written as 2.6e-3 + 2e-4 a layer (each layer adds roundings to the
    residual stream, as in OLMoE's limit). The limit first stood at 2.4e-3
    (1.4e-3 + 2e-4 a layer; the builder's chip calls up to the tenth print
    it) and was moved once, to where it is, after weights trained for 100
    steps read 2.2e-3, 7% under it; the record keeps no other reason.
    What it cannot see: RMSNorm, the router and the short convolution
    lowered in bfloat16 read 1.5e-3 to 1.8e-3 (three seeds), inside the
    program's own range; no limit on block means separates those parts'
    precision (PERF.md section 7).
    ``loss``: errors of single positions cancel in the mean over thousands
    (chip: 2.3e-6 to 5.7e-5 as it is, float8 9.6e-5 to 1.9e-4: the mean
    alone would pass a lower precision in some runs, hence both)."""
    return {"loss": 1e-4, "each": 2.6e-3 + 2e-4 * model["num_hidden_layers"]}


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


def _rope(x, theta):
    """x [B, h, S, d]: rotate-half rotary embedding, positions 0..S-1."""
    import jax.numpy as jnp
    S, d = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _block(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is at most ``target``."""
    c = max(1, min(n, target))
    while n % c:
        c -= 1
    return c


def _causal_attention(q, k, v):
    """q [B, h, S, d] against k, v [B, kv, S, d], query head i reading
    key/value head i // (h / kv): softmax(q k^T / sqrt(d) + causal mask) v,
    in blocks of query rows so that the [S, S] scores never exist whole.
    The plain form of grouped-query attention: K and V repeated."""
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
        q_pos = first + jnp.arange(rows)
        s = jnp.where(key_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    blocks = q.reshape(B, h, S // rows, rows, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(one, (blocks, jnp.arange(0, S, rows)))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, h, S, d)


def short_conv(x, w_in, w, w_out, batch):
    """The gated short convolution over tokens ``x [B*S, H]``, written as the
    sum it is: one shifted copy of ``B * u`` a tap."""
    import jax.numpy as jnp
    T, H = x.shape
    taps = w.shape[1]
    gate_b, gate_c, u = jnp.split(x @ w_in, 3, axis=-1)
    z = (gate_b * u).reshape(batch, T // batch, H)
    S = z.shape[1]
    conv = jnp.zeros_like(z)
    for j in range(taps):
        shift = taps - 1 - j                    # c[t] += w[:, j] * z[t - shift]
        moved = jnp.concatenate(
            [jnp.zeros((batch, shift, H), z.dtype), z[:, :S - shift]], axis=1)
        conv = conv + w[:, j] * moved
    return (gate_c * conv.reshape(T, H)) @ w_out


def expert_layer(x, w_router, w_gate, w_up, w_down, bias, model: dict,
                 held=None, chosen=None):
    """The held experts' part of a sparse layer's output for tokens ``x [T,
    H]``, the chosen experts ``[T, k]`` and the load ``[experts routed]``.
    ``held = (first, count)`` (default: the model's) says which experts the
    stacked weights are. ``chosen [T, k]`` takes the choice as given (the
    program's own, when gradients are compared and a 4th / 5th expert that
    flips under bfloat16 must not stand in the way)."""
    import jax
    import jax.numpy as jnp
    k = model["num_experts_per_tok"]
    routed = model.get("num_experts_routed", model["num_experts"])
    first, count = held or (model.get("first_expert_held", 0),
                            model["num_experts"])
    score = jax.nn.sigmoid(x @ w_router)                     # [T, routed]
    if chosen is None:
        _, top_i = jax.lax.top_k(jax.lax.stop_gradient(score + bias), k)
    else:
        top_i = chosen
    top_w = jnp.take_along_axis(score, top_i, axis=-1)
    if model.get("norm_topk_prob"):
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-6)
    top_w = top_w * model.get("routed_scaling_factor", 1.0)
    taken = jax.nn.one_hot(top_i, routed)                    # [T, k, routed]
    # [T, routed]: the router's weight where the expert was chosen
    gate = jnp.sum(taken * top_w[..., None], axis=1)

    def expert(acc, w):
        g, u, dn, col = w
        y = (jax.nn.silu(x @ g) * (x @ u)) @ dn
        return acc + col[:, None] * y, None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (w_gate, w_up, w_down, gate.T[first:first + count]))
    return out, top_i, jnp.sum(taken, axis=(0, 1)).astype(jnp.int32)


def forward(weights: list, batch: dict, model: dict, chosen=None) -> dict:
    """The pure function: ``weights`` are float32 arrays in the program's
    creation order, then one selection bias an expert layer. Returns ``loss``,
    ``positions`` (every position's cross-entropy), ``each`` (its means over
    blocks of ``check_block(seq)``), ``experts`` ``[expert layers, tokens,
    k]`` sorted by expert, ``load`` ``[expert layers, experts routed]`` and
    ``new_bias`` (the biases after this step's update). ``chosen [expert
    layers, tokens, k]``: ``expert_layer``'s, a layer each."""
    import jax
    import jax.numpy as jnp

    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    kinds = model["layer_types"]
    dense = model.get("num_dense_layers", 0)
    eps, theta = model["norm_eps"], float(model["rope_theta"])
    n_sparse = len(kinds) - dense
    biases = weights[len(weights) - n_sparse:]
    it = iter(weights[:len(weights) - n_sparse])
    take = lambda n: [next(it) for _ in range(n)]           # noqa: E731
    (emb,) = take(1)
    ids = batch["ids"]
    B, S = ids.shape
    H = emb.shape[1]
    d = H // heads
    x = emb[ids].reshape(B * S, H)
    experts, loads, sparse = [], [], 0
    for i, kind in enumerate(kinds):
        (op_norm,) = take(1)
        xn = _rms_norm(x, op_norm, eps)
        if kind == "conv":
            w_in, w, w_out = take(3)
            h = x + short_conv(xn, w_in, w, w_out, B)
        else:
            wq, wk, wv, q_norm, k_norm, wo = take(6)
            sh = lambda t, n: t.reshape(B, S, n, d)         # noqa: E731
            q = _rms_norm(sh(xn @ wq, heads), q_norm, eps)
            kk = _rms_norm(sh(xn @ wk, kv), k_norm, eps)
            tr = lambda t: t.transpose(0, 2, 1, 3)          # noqa: E731
            a = _causal_attention(_rope(tr(q), theta), _rope(tr(kk), theta),
                                  tr(sh(xn @ wv, kv)))
            h = x + tr(a).reshape(B * S, H) @ wo
        (ffn_norm,) = take(1)
        hn = _rms_norm(h, ffn_norm, eps)
        if i < dense:
            w_gate, w_up, w_down = take(3)
            x = h + (jax.nn.silu(hn @ w_gate) * (hn @ w_up)) @ w_down
            continue
        w_router, w_gate, w_up, w_down = take(4)
        moe, top_i, load = expert_layer(
            hn, w_router, w_gate, w_up, w_down, biases[sparse], model,
            chosen=None if chosen is None else chosen[sparse])
        sparse += 1
        x = h + moe
        experts.append(jnp.sort(top_i, axis=-1))
        loads.append(load)

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
    load = jnp.stack(loads)
    loadf = load.astype(jnp.float32)
    new_bias = jnp.stack(biases) + BIAS_UPDATE_RATE * jnp.sign(
        jnp.mean(loadf, axis=1, keepdims=True) - loadf)
    return {"loss": jnp.mean(each), "positions": each,
            "each": jnp.mean(each.reshape(-1, check_block(S)), axis=1),
            "experts": jnp.stack(experts).astype(jnp.int32), "load": load,
            "new_bias": new_bias}


def loss(weights: list, batch: dict, model: dict, params: dict) -> dict:
    """``weights``: the program's parameters in creation order followed by
    its selection biases, any dtype; ``forward``'s result, computed in
    float32 at matmul precision "highest"."""
    import jax
    import jax.numpy as jnp

    def f(weights, batch):
        return forward([jnp.asarray(w, jnp.float32) for w in weights], batch,
                       model)

    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(list(weights), dict(batch))
