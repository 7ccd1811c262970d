"""Plain reference for the Kimi-Linear-48B-A3B pre-training loss as one chip's
share of it: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no framework op, no
chunks, no sort, no grouping and no row budget (a row the program drops is
missing from its layer's routed output, which the check compares:
``tolerance``). Written from the model's ``config.json`` (``model_type:
kimi_linear``; the catalog's row), HF's ``modeling_kimi.py`` of that
repository (``KimiDeltaAttention``, ``KimiMLAAttention``,
``KimiSparseMoeBlock``, ``KimiMoEGate``) and the Kimi Linear report
(arXiv:2510.26692: the KDA recurrence); what the config does not carry is
the configuration file's ``assumed``. Independent of ``paddle_tpu/`` except
for the order in which parameters are created, which is how weights are
handed over (the block sizes of the compared means and of the row blocks
are the Laguna reference's helpers, which know nothing of this model); the
selection biases, which are state and no parameters, follow the parameters
where a caller has them, zero otherwise, as at the start.

Tokens ``x [T, H]``, H = 2304, no biases, eps 1e-5, ``N(x; w) = x /
sqrt(mean(x^2) + eps) * w``; a block is ``h = x + A(N(x))``, ``y = h +
F(N(h))``. Layer i (from 0) is Kimi Delta Attention where ``i + 1`` is in
``linear_attn_config.kda_layers`` and latent attention where it is in
``full_attn_layers``.

1. Kimi Delta Attention, n = 32 heads of d = 128 for q, k and v alike, ``a =
   N(x)``: ``[q | k | v] = silu(conv4(a [W_q | W_k | W_v]))``, a causal
   depthwise filter of 4 taps without a bias over the 12,288 channels; a
   token, head j and key channel c, ``g[j, c] = -exp(A_log[j]) softplus((a
   W_fa W_fb)[j, c] + dt_bias[j, c])``; ``beta[j] = sigmoid((a W_b)[j])``;
   ``qn = q / sqrt(sum(q^2) + 1e-6) / sqrt(128)``, ``kn = k / sqrt(sum(k^2)
   + 1e-6)``; the recurrence **position by position** on a state ``S [128,
   128]`` a head, zero at a sequence's start: ``S' = diag(exp(g_t)) S`` (row
   c of S times ``exp(g_t[c])``), ``u = beta_t (v_t - S'^T kn_t)``, ``S = S'
   + kn_t u^T``, ``o_t = S^T qn_t``; out: ``W_o concat_j(o_j / sqrt(mean(
   o_j^2) + eps) * w_n * sigmoid(z_j))``, ``z = a W_ga W_gb``: the norm
   first, then the sigmoid gate.
2. Latent attention, h = 32 heads, ``d_n`` = 128, ``d_r`` = 64, ``d_v`` =
   128, ``r_kv`` = 512, no query latent: ``a W_q`` gives every head's
   ``q_n`` (the first 32 x 128 columns) and every head's ``q_r`` (the last
   32 x 64); ``[c_kv | k_r] = a W_kva`` (512 | 64); ``N(c_kv) W_kvb`` gives
   every head's ``k_n`` (the first 32 x 128 columns) and every head's ``v``
   (the last 32 x 128); ``k_r`` is ONE key head that all 32 read; NOTHING is
   rotated (``mla_use_nope``). Scores of head j: ``(q_n[j] . k_n[j] +
   q_r[j] . k_r) / sqrt(192)``, the two parts formed apart, causal softmax,
   ``o[j] = sum p v[j]``; ``[o_1 .. o_32] W_o`` (4096 -> 2304).
3. Feed-forward: layer 0 (``first_k_dense_replace`` 1) a dense SwiGLU of
   9216. Every other layer: ``s = sigmoid(m W_r)`` over the 256 routed
   experts; the 8 largest of ``s + b`` chosen (``b`` the selection bias, no
   gradient); weights ``s_i / (sum of the chosen s + 1e-20)``
   (``moe_renormalize``) times 2.446; EVERY held expert (SwiGLU of 1024)
   applied to EVERY token and masked by the choice; plus one ungated shared
   SwiGLU expert of 1024 over every token.
4. Final ``N``, untied head over the held vocabulary slice, mean
   cross-entropy against the next token.

Departures from the published model, each because the program under test
makes the same choice: the columns of ``[W_q | W_k | W_v]`` are contiguous
by part then by head, those of the latent projections by part, permutations
of HF's layouts; the RMSNorm scale multiplies in float32 before the cast
back; the router weights stay float32 in the combine; the vocabulary is the
held slice; the absent experts add nothing.

Memory: it runs on the chip beside the training state, so attention runs
over blocks of ``Q_ROWS`` query rows, the head over blocks of ``HEAD_ROWS``
positions (``lax.map``), the experts one at a time and the recurrence one
position at a time (``lax.scan``; the state is 2 MB a sequence).
"""
from __future__ import annotations

import math

from benchmark.references.laguna_pretrain import (  # noqa: F401
    _block, check_block, differing_share)

Q_ROWS = 256        # query rows a block of the attention
HEAD_ROWS = 512     # positions a block of the output head
#: a KDA layer's entry of ``each`` is the mean over tokens and heads of the
#: norm of a head's ``o`` before the gated norm, times this: at seeded
#: weights that norm reads 0.058 to 0.064 (``qn`` is a unit vector over
#: sqrt(128)), so an entry reads about 2 where ``reference_check`` divides
#: every entry's error by the largest, a block's cross-entropy near 10.7. A
#: mechanism of the rule that is off moves the size by a tenth or more (2e-2
#: of the largest entry); bfloat16 moves the deepest layer's by 2e-3 of its
#: own value, always upwards (noise adds to a norm), which at a scale of 128
#: read 1.4e-3 of the largest entry, as much as the block means (my chip
#: runs, PR 51)
O_SCALE = 32.0


def tolerance(model: dict) -> dict:
    """``each``: |program - reference| <= tol * the reference's largest
    entry, over, in this order, (a) the cross-entropy averaged over blocks
    of ``seq // 64`` consecutive positions (64 at S=4096; single positions
    in the tests), (b), a sparse layer each, GLM's routed entry (the norm of
    the held routed experts' output before the shared expert's is added,
    summed over the tokens and divided by the sum over the tokens of
    sqrt(c), c the number of a token's chosen experts that are held here:
    ``references/glm_4_7_flash_pretrain.py`` says why not the mean norm),
    and (c), a KDA layer each, ``O_SCALE`` times the mean over tokens and
    heads of the norm of a head's ``o`` BEFORE the gated norm (the norm
    after it divides a wrong scale out: a decay, a beta or an l2 norm that
    is off moves ``o``'s size and hardly the loss at seeded weights).

    Block means and a routed entry for Laguna's reasons: the program
    computes in bfloat16 with a float32 router, the 8th and 9th largest of
    256 scores + bias lie closer than bfloat16 moves them, and with 8 of 256
    held the cross-entropy barely sees the routed path. ``loss`` has no
    limit for Laguna's reason (single positions' errors cancel in the mean;
    a loss that is not a number still fails ``each``).

    The limit, from the two readings the contract asks for (PERF.md section
    2 and 6, PR 51; chip, published widths, 5 layers, 2 x 4096 tokens,
    seeded weights as the cell's check has them), ``READINGS``: the program
    as it is read 6.9e-4 to 1.163e-3 over 16 seeds by ``tools/kimi_linear_
    probe.py readings`` (by part, the largest: (a) 1.163e-3, (b) 4.4e-4, (c)
    2.6e-5; the cell's own runs 7.0e-4 to 1.16e-3), float8 (e4m3) weights in
    the program's place 6.56e-3 to 1.19e-2 over the same seeds ((a); (b)
    4e-4 to 1.8e-3, (c) 8e-4 to 2.7e-3). The limit is 2.6e-3: 2.2 times the
    largest sound reading, 2.5 times under float8's smallest. What it sees
    beside float8, every verdict ``jobs/common.py:reference_check``'s own
    (``tools/kimi_linear_probe.py controls``, one seed, the part that shows
    and its reading over the reference's largest entry): the decay left out
    4.2e-1 (c: every ``o`` size 2.1 to 2.6 times its value), the decay
    averaged over a head's channels -- the scalar rule under this model's
    name -- 7.7e-2 (c: the sizes off by 41 to 46%; 3.6e-2 in (a)), beta = 1
    1.2e-1 (c), the l2 norms of q and k left out not a number, silu for the
    norm's sigmoid gate 4.4e-2 (a), the output gate left out 1.9e-2 (a),
    the routed scale 2.446 left out 2.8e-1 (b: every entry 59% off), an
    eighth of the row budget 3.0e-1 (b). What it cannot see, as the other
    cells' checks cannot at seeded weights (scores near zero, the softmax
    near uniform, one layer of five): ``q_r`` / ``k_r`` rotated 7.8e-4 and
    the softmax scale 1 / sqrt(128) 7.7e-4, both inside the sound range
    (the CPU tests sharpen the projections and see both); nor a bfloat16
    state in the reference's recurrence (7.5e-4; the op's CPU tests hold
    the state to 1e-4 of the float32 recurrence)."""
    return {"loss": float("inf"), "each": EACH_LIMIT}


# The two readings (my chip runs, PR 51; PERF.md section 6): the program as
# it is, the largest over the seeds run, and float8 (e4m3) weights in the
# program's place, the smallest over its seeds. The limit lies between them
# with room on both sides.
READINGS = {"as_it_is_max": 1.163e-3, "float8_min": 6.56e-3}
EACH_LIMIT = 2.6e-3


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def swiglu(x, w_gate, w_up, w_down):
    import jax
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def causal_conv(x, w, seq: int):
    """``x [T, C]`` in sequences of ``seq`` rows, ``w [C, L]``: ``y[t] = sum_j
    w[:, j] x[t - (L - 1) + j]``, zeros before a sequence's start."""
    import jax.numpy as jnp
    taps = w.shape[1]
    xs = x.reshape(-1, seq, x.shape[-1])
    padded = jnp.pad(xs, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + seq] * w[:, j] for j in range(taps))
    return y.reshape(x.shape)


def delta_rule(q, k, v, g, beta, state_dtype=None, unit_norm=True):
    """Kimi Delta Attention's recurrence position by position: ``q`` / ``k``
    / ``v [B, S, heads, d]`` (q and k raw: the l2 norms and the query scale
    are applied here), ``g [B, S, heads, d]`` the decay a key channel,
    ``beta [B, S, heads]`` -> ``o`` like ``v``. ``state_dtype``: round the
    carried state to it after every position; ``unit_norm=False``: q and k
    as they come (q still over sqrt(d)): two controls."""
    import jax
    import jax.numpy as jnp
    dk = q.shape[-1]

    def unit(x):
        if not unit_norm:
            return x
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + 1e-6)
    qn, kn = unit(q) / dk ** 0.5, unit(k)

    def step(state, inp):                   # state [B, heads, d_k, d_v]
        q_t, k_t, v_t, g_t, b_t = inp
        state = state.astype(jnp.float32) * jnp.exp(g_t)[..., :, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", state, q_t)
        return state.astype(state_dtype or jnp.float32), o

    first = jnp.zeros((v.shape[0], v.shape[2], dk, v.shape[-1]),
                      state_dtype or jnp.float32)
    _, o = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(t, 1, 0) for t in (qn, kn, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def kda(a, w, model: dict, B: int, S: int, control=None):
    """The KDA mixer over normed tokens ``a [T, H]``; ``w`` = (W_qkv, filter,
    dt_bias, A_log, W_fa, W_fb, W_b, W_ga, W_gb, w_n, W_o). Returns the
    mixer's output and the mean over tokens and heads of the norm of a
    head's ``o`` before the gated norm. ``control``: one departure a check
    must see: ``"no_decay"`` (g = 0), ``"mean_decay"`` (g averaged over a
    head's channels: the scalar rule under this model's name),
    ``"beta_one"``, ``"no_l2_norm"``, ``"silu_gate"``, ``"no_out_gate"``,
    ``"bf16_state"``."""
    import jax
    import jax.numpy as jnp
    w_qkv, conv_w, dt_bias, a_log, w_fa, w_fb, w_b, w_ga, w_gb, w_n, w_o = w
    lin = model["linear_attn_config"]
    n, d = lin["num_heads"], lin["head_dim"]
    wide = n * d
    qkv = jax.nn.silu(causal_conv(a @ w_qkv, conv_w, S))
    q, k, v = (qkv[:, i * wide:(i + 1) * wide].reshape(B, S, n, d)
               for i in range(3))
    g = -jnp.exp(a_log)[None, :, None] * jax.nn.softplus(
        (a @ w_fa) @ w_fb + dt_bias).reshape(B * S, n, d)
    g = g.reshape(B, S, n, d)
    if control == "no_decay":
        g = jnp.zeros_like(g)
    elif control == "mean_decay":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(a @ w_b).reshape(B, S, n)
    if control == "beta_one":
        beta = jnp.ones_like(beta)
    o = delta_rule(q, k, v, g, beta,
                   jnp.bfloat16 if control == "bf16_state" else None,
                   unit_norm=control != "no_l2_norm").reshape(B * S, n, d)
    z = ((a @ w_ga) @ w_gb).reshape(B * S, n, d)
    gate = (1.0 if control == "no_out_gate" else
            jax.nn.silu(z) if control == "silu_gate" else jax.nn.sigmoid(z))
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + model["rms_norm_eps"]) * w_n * gate
    return (y.reshape(B * S, wide) @ w_o,
            jnp.mean(jnp.linalg.norm(o, axis=-1)))


def _rope(x, theta: float):
    """Rotate-half over the last axis of ``x [B, S, ..., r]``, positions
    along axis 1: what the model does NOT do (the control ``"rotated"``)."""
    import jax.numpy as jnp
    import numpy as np
    S, r = x.shape[1], x.shape[-1]
    inv_freq = float(theta) ** (-np.arange(0, r, 2) / r)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + (r // 2,))
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def latent_attention(a, w, model: dict, B: int, S: int, control=None):
    """Latent attention without a query latent and without positions over
    normed tokens ``a [T, H]``; ``w`` = (W_q, W_kva, w_kvnorm, W_kvb, W_o).
    ``control``: ``"rotated"`` (q_r and k_r rotated at ``rope_theta``),
    ``"scale_nope_only"`` (softmax scale 1 / sqrt(128))."""
    import jax
    import jax.numpy as jnp
    w_q, w_kva, kv_norm, w_kvb, w_o = w
    h = model["num_attention_heads"]
    d_n, d_r, d_v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    r_kv, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    q = a @ w_q
    q_n = q[:, :h * d_n].reshape(B, S, h, d_n)
    q_r = q[:, h * d_n:].reshape(B, S, h, d_r)
    ckv = a @ w_kva
    k_r = ckv[:, r_kv:].reshape(B, S, d_r)
    if control == "rotated":
        q_r, k_r = (_rope(t, model.get("rope_theta", 10000.0))
                    for t in (q_r, k_r))
    kv = rms_norm(ckv[:, :r_kv], kv_norm, eps) @ w_kvb
    k_n = kv[:, :h * d_n].reshape(B, S, h, d_n)
    v = kv[:, h * d_n:].reshape(B, S, h, d_v)
    scale = 1.0 / math.sqrt(d_n if control == "scale_nope_only"
                            else d_n + d_r)
    rows = _block(S, Q_ROWS)
    key_pos = jnp.arange(S)

    def one(arg):
        qn, qr, first = arg                     # [B, rows, h, d_n], [.., d_r]
        s = (jnp.einsum("bqhd,bkhd->bhqk", qn, k_n)
             + jnp.einsum("bqhd,bkd->bhqk", qr, k_r)) * scale
        seen = key_pos[None, :] <= (first + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def blocks(t):              # [B, S, h, d] -> [n, B, rows, h, d]
        return jnp.moveaxis(t.reshape(B, S // rows, rows, h, -1), 1, 0)
    o = jax.lax.map(one, (blocks(q_n), blocks(q_r), jnp.arange(0, S, rows)))
    return jnp.moveaxis(o, 0, 1).reshape(B * S, h * d_v) @ w_o


def expert_layer(x, w_router, w_gate, w_up, w_down, bias, model: dict,
                 held=None, chosen=None, control=None):
    """The held experts' part of a sparse layer's routed output for tokens
    ``x [T, H]``, the chosen experts ``[T, k]`` and the load ``[experts
    routed]``; the shared expert is not in it. ``held = (first, count)``
    (default: the model's) says which experts the stacked weights are.
    ``chosen [T, k]`` takes the choice as given (the program's own, when
    gradients are compared). ``control`` ``"no_routed_scale"``: without the
    2.446."""
    import jax
    import jax.numpy as jnp
    k = model["num_experts_per_token"]
    routed = model.get("num_experts_routed", model["num_experts"])
    first, count = held or (model.get("first_expert_held", 0),
                            model["num_experts"])
    score = jax.nn.sigmoid(x @ w_router)                     # [T, routed]
    if chosen is None:
        _, top_i = jax.lax.top_k(jax.lax.stop_gradient(score + bias), k)
    else:
        top_i = chosen
    top_w = jnp.take_along_axis(score, top_i, axis=-1)
    if model.get("moe_renormalize"):
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    if control != "no_routed_scale":
        top_w = top_w * model.get("routed_scaling_factor", 1.0)
    taken = jax.nn.one_hot(top_i, routed)                    # [T, k, routed]
    gate = jnp.sum(taken * top_w[..., None], axis=1)         # [T, routed]

    def expert(acc, w):
        g, u, dn, col = w
        return acc + col[:, None] * swiglu(x, g, u, dn), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (w_gate, w_up, w_down, gate.T[first:first + count]))
    return out, top_i, jnp.sum(taken, axis=(0, 1)).astype(jnp.int32)


def layer_kinds(model: dict) -> list:
    lin = model["linear_attn_config"]
    return ["kda" if i + 1 in lin["kda_layers"] else "latent"
            for i in range(model["num_hidden_layers"])]


def sparse_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model.get("first_k_dense_replace", 0)


def forward(weights: list, batch: dict, model: dict, chosen=None, held=None,
            biases=None, control=None) -> dict:
    """The pure function: ``weights`` are float32 arrays in the program's
    creation order (table; a layer each: norm, the mixer's eleven (KDA) or
    five (latent attention), norm, the feed-forward's three or the router,
    the three stacked held experts and the shared expert's three; final
    norm, head). Returns ``loss`` (= ``ce``), ``positions`` (every
    position's cross-entropy), ``held_norm`` (a sparse layer each:
    ``tolerance``'s (b)), ``routed`` (the mean norm of the same output),
    ``o_norm`` (a KDA layer each: the mean norm of a head's ``o``,
    unscaled), ``each`` (``tolerance``'s order), ``experts`` ``[sparse
    layers, tokens, k]`` sorted by expert and ``load``. ``chosen`` /
    ``held``: ``expert_layer``'s, a layer each; ``biases [sparse layers,
    experts routed]``: the selection biases (default zero); ``control``: one
    departure a check must see (``kda``'s, ``latent_attention``'s,
    ``expert_layer``'s)."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    it = iter(weights)
    take = lambda n: [next(it) for _ in range(n)]           # noqa: E731
    (emb,) = take(1)
    ids = batch["ids"]
    B, S = ids.shape
    H = emb.shape[1]
    experts, loads, routed, held_norm, o_norm = [], [], [], [], []
    routed_width = model.get("num_experts_routed", model["num_experts"])
    x = emb[ids].reshape(B * S, H)
    for i, kind in enumerate(layer_kinds(model)):
        (norm,) = take(1)
        a = rms_norm(x, norm, eps)
        if kind == "kda":
            mixed, size = kda(a, take(11), model, B, S, control)
            o_norm.append(size)
        else:
            mixed = latent_attention(a, take(5), model, B, S, control)
        h = x + mixed
        (ffn_norm,) = take(1)
        m = rms_norm(h, ffn_norm, eps)
        if i < model.get("first_k_dense_replace", 0):
            x = h + swiglu(m, *take(3))
            continue
        j = len(experts)
        w_router, w_gate, w_up, w_down = take(4)
        moe, top_i, load = expert_layer(
            m, w_router, w_gate, w_up, w_down,
            jnp.zeros((routed_width,)) if biases is None else biases[j],
            model, held=held, chosen=None if chosen is None else chosen[j],
            control=control)
        experts.append(jnp.sort(top_i, axis=-1))
        loads.append(load)
        norm = jnp.linalg.norm(moe, axis=-1)
        first, count = held or (model.get("first_expert_held", 0),
                                model["num_experts"])
        routed.append(jnp.mean(norm))
        here = jnp.sum((top_i >= first) & (top_i < first + count), axis=-1)
        held_norm.append(jnp.sum(norm) / jnp.maximum(
            jnp.sum(jnp.sqrt(here)), 1))
        x = h + moe + swiglu(m, *take(3))                   # shared expert
    final_norm, head = take(2)
    rows = _block(B * S, HEAD_ROWS)

    def decode(arg):
        hb, lb = arg
        logp = jax.nn.log_softmax(hb @ head, axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=1)[:, 0]
    each = jax.lax.map(decode, (
        rms_norm(x, final_norm, eps).reshape(-1, rows, H),
        batch["labels"].reshape(-1, rows))).reshape(-1)
    ce = jnp.mean(each)
    parts = [jnp.mean(each.reshape(-1, check_block(S)), axis=1),
             jnp.stack(held_norm), O_SCALE * jnp.stack(o_norm)]
    return {"loss": ce, "ce": ce, "positions": each,
            "routed": jnp.stack(routed), "held_norm": jnp.stack(held_norm),
            "o_norm": jnp.stack(o_norm), "each": jnp.concatenate(parts),
            "experts": jnp.stack(experts).astype(jnp.int32),
            "load": jnp.stack(loads)}


def loss(weights: list, batch: dict, model: dict, params: dict) -> dict:
    """``weights``: the program's parameters in creation order, then its
    selection biases, a sparse layer each, any dtype; ``forward``'s ``loss``
    and ``each``, computed in float32 at matmul precision "highest"."""
    import jax
    import jax.numpy as jnp

    def f(weights, batch):
        weights = [jnp.asarray(w, jnp.float32) for w in weights]
        n = sparse_layers(model)
        out = forward(weights[:-n], batch, model, biases=weights[-n:])
        return {"loss": out["loss"], "each": out["each"]}

    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(list(weights), dict(batch))
