"""Plain reference for the Xing4.0-29B-A4B pre-training loss as one chip's
share of it: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no framework op, no
sort, no grouping and no row budget. Written from the model's
``config.json`` (``model_type: xing4_0``; the catalog's row) and, for what
its keys name: manifold-constrained hyper-connections (DeepSeek-AI, mHC,
arXiv:2512.24880, over Hyper-Connections, Zhu et al., arXiv:2409.19606),
multi-head latent attention (DeepSeek-V2, arXiv:2405.04434, section 2.1)
under YaRN (Peng et al., arXiv:2309.00071, as HF's
``_compute_yarn_parameters``, ``DeepseekV3RotaryEmbedding`` and
``DeepseekV3Attention`` apply it), sigmoid scores chosen by score + bias
(DeepSeek-V3, arXiv:2412.19437, section 2.1.2). What neither the config nor
the papers give is the configuration file's ``assumed``. Independent of
``paddle_tpu/`` except for the order in which parameters are created, which
is how weights are handed over; the feed-forward parts (``rms_norm``,
``swiglu``, ``expert_layer``) and the block sizes are the GLM-4.7-Flash
reference's, which know nothing of this model.

A token's residual state is ``X [n, C]``, n = ``hc_mult`` = 4 streams of C =
3584; ``X_0`` is the token's embedding in every stream. A block is two
sub-layers, latent attention then the feed-forward, each a hyper-connection
around its branch ``F`` (the pre-norm on the C-wide input, then the
operator), with its own ``phi [n C, 2 n + n^2]``, ``b [2 n + n^2]``,
``alpha [3]`` (float32):

    xbar   = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)        (no learned scale)
    z      = xbar phi
    H_pre  = sigmoid(alpha_0 z[:n] + b[:n])
    H_post = 2 sigmoid(alpha_1 z[n:2n] + b[n:2n])
    H_res  = SK(clip(alpha_2 mat(z[2n:]) + mat(b[2n:]), -30, 30)): M = exp(.),
             20 times: M <- M / (rowsum(M) + hc_eps); M <- M / (colsum(M) +
             hc_eps)
    u      = H_pre X;  y = F(u);  X' = H_res X + H_post^T y

After the last block the streams are summed, then the final norm, the untied
head over the held vocabulary slice and the mean next-token cross-entropy.

1. Latent attention: h = 32 heads, ``d_n`` = 128, ``d_r`` = 64, ``d_v`` =
   128, ``r_q`` = 768, ``r_kv`` = 512, as the GLM reference writes it (the
   two score parts formed apart, one rotary key head), but ``q_r`` and
   ``k_r`` turn at YaRN's blended frequencies (factor 64 over 4,096
   original positions, beta 32 / 1), cos and sin times ``m(mscale) /
   m(mscale_all_dim)`` = 1, and the softmax scale is ``m(mscale_all_dim)^2
   / sqrt(192)`` with ``m(s) = 0.1 s ln 64 + 1``: 2.0048 / sqrt(192).
2. Feed-forward: block 0 a dense SwiGLU of 9,216; the others sigmoid scores
   over 64 routed experts, the 4 largest of score + bias, weights ``s / sum
   of the chosen s`` times 2, every held expert (SwiGLU of 1,024) applied to
   every token and masked by the choice, plus one shared SwiGLU of 1,024.

Departures from the published model, each because the program under test
makes the same choice: the columns of ``W_qb`` / ``W_kvb`` are contiguous by
kind; the router weights stay float32 in the combine; every position is
labelled; the vocabulary is the held slice; the absent experts add nothing;
no prediction module (``num_nextn_predict_layers`` 0: the configuration
file's ``reduced_detail``).

Memory: it runs on the chip beside the training state, so attention runs
over blocks of ``Q_ROWS`` query rows, the head over blocks of ``HEAD_ROWS``
positions and the experts one at a time (the GLM reference's).
"""
from __future__ import annotations

import math

from benchmark.references.glm_4_7_flash_pretrain import (  # noqa: F401
    HEAD_ROWS, Q_ROWS, _block, check_block, expert_layer, rms_norm, swiglu)

#: departures a check must see (``tools/xing4_0_probe.py controls``)
CONTROLS = ("hc_bfloat16", "sinkhorn_5", "post_without_2",
            "scale_without_mscale", "static_only", "no_yarn_blend",
            "no_clamp")


def tolerance(model: dict) -> dict:
    """``each``: |program - reference| <= tol * the reference's largest
    entry, over, in this order, (a) the cross-entropy averaged over blocks
    of ``seq // 64`` consecutive positions (single positions in the tests),
    (b) a sparse layer each, the held routed experts' norm as the
    GLM-4.7-Flash reference has it (``forward``'s ``held_norm``), (c) a
    block each, the root mean square of each of its output state's four
    streams (20 numbers at five blocks): H_res, H_post and the collapse show
    there and hardly in the loss. (a) reads about ln 16,384 = 9.7, (b) and
    (c) a few units, unscaled.

    The limit, from the two readings the contract asks for (my chip runs,
    PR 61; published widths, 5 layers, 1 x 4096 tokens, seeded weights with
    the static biases from N(0, 2); PERF.md sections 2 and 6), ``READINGS``:
    the program as it is read 6.0e-4 to 1.81e-3 over 9 seeds (7 of them
    within 1.27e-3), float8 (e4m3) weights in the program's place 5.78e-3;
    the limit is 3.2e-3, their geometric mean: 1.8 times over the one, 1.8
    times under the other. What it sees beside float8 (``tools/xing4_0_
    probe.py controls``, the REFERENCE with one departure): 5 Sinkhorn
    iterations for 20 5.7e-3, H_post without its factor 2 1.0e-1, the
    softmax scale without mscale^2 1.13e-2, YaRN's blend left out 1.10e-2.
    What it cannot see: the coefficients in bfloat16 (7.8e-4), the dynamic
    part ``alpha z`` left out (5.8e-4: alpha starts at 0.01) and the clamp
    (no logit reaches 30); the CPU tests see those. ``loss`` has no limit for Laguna's reason
    (single positions' errors cancel in the mean, so no limit on it
    separates anything ``each`` does not; a loss that is not a number still
    fails ``each``)."""
    return {"loss": float("inf"), "each": EACH_LIMIT}


# The two readings (my chip runs, PR 61; PERF.md section 6): the program as
# it is, the largest over the seeds run, and the reference with float8
# (e4m3) weights in the program's place, the smallest over its seeds. The
# limit lies between them with room on both sides.
READINGS = {"as_it_is_max": 1.81e-3, "float8_min": 5.78e-3}
EACH_LIMIT = 3.2e-3


def yarn_inv_freq(theta: float, dim: int, scaling: dict):
    """HF ``_compute_yarn_parameters``: the rotary head's ``dim / 2``
    frequencies, the fast dimensions kept, the slow ones divided by
    ``factor``, a linear ramp between the dimensions that turn ``beta_fast``
    and ``beta_slow`` times over the original positions; and the factor on
    cos and sin. float64 numpy."""
    import numpy as np
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def m(scale):
        return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0

    def dimension(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(dimension(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(dimension(scaling.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = float(theta) ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation = 1.0 - ramp
    inv_freq = (1.0 / (factor * pos_freqs)) * (1 - extrapolation) \
        + (1.0 / pos_freqs) * extrapolation
    if scaling.get("mscale") and scaling.get("mscale_all_dim"):
        return inv_freq, m(scaling["mscale"]) / m(scaling["mscale_all_dim"])
    return inv_freq, m(1.0)


def rope(x, inv_freq, factor: float = 1.0):
    """``x [B, S, ..., r]``: rotate-half rotary embedding of the whole last
    axis at ``inv_freq [r / 2]``, positions 0..S-1 along axis 1, cos and sin
    times ``factor``."""
    import jax.numpy as jnp
    S, r = x.shape[1], x.shape[-1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + (r // 2,))
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def latent_attention(a, w, model: dict, B: int, S: int, control=None):
    """Multi-head latent attention under YaRN over normed tokens ``a [T,
    C]``; ``w`` = (W_qa, w_qnorm, W_qb, W_kva, w_kvnorm, W_kvb, W_o).
    ``control`` ``"scale_without_mscale"``: the softmax scale 1 /
    sqrt(192); ``"no_yarn_blend"``: plain frequencies ``theta^(-2i/r)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, w_o = w
    h = model["num_attention_heads"]
    d_n, d_r, d_v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    r_kv, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    scaling = model.get("rope_scaling")
    scale = 1.0 / math.sqrt(d_n + d_r)
    inv_freq = float(model["rope_theta"]) ** (-np.arange(0, d_r, 2) / d_r)
    factor = 1.0
    if scaling is not None:
        if control != "no_yarn_blend":
            inv_freq, factor = yarn_inv_freq(model["rope_theta"], d_r,
                                             scaling)
        if scaling.get("mscale_all_dim") and control != "scale_without_mscale":
            scale *= (0.1 * scaling["mscale_all_dim"]
                      * math.log(scaling["factor"]) + 1.0) ** 2
    q = rms_norm(a @ w_qa, q_norm, eps) @ w_qb
    q_n = q[:, :h * d_n].reshape(B, S, h, d_n)
    q_r = rope(q[:, h * d_n:].reshape(B, S, h, d_r), inv_freq, factor)
    ckv = a @ w_kva
    k_r = rope(ckv[:, r_kv:].reshape(B, S, d_r), inv_freq, factor)
    kv = rms_norm(ckv[:, :r_kv], kv_norm, eps) @ w_kvb
    k_n = kv[:, :h * d_n].reshape(B, S, h, d_n)
    v = kv[:, h * d_n:].reshape(B, S, h, d_v)
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


def sinkhorn(logits, iters: int, eps: float, lo: float, hi: float):
    """``logits [..., n, n]`` -> the matrices after ``iters`` rounds of row
    then column normalisation of ``exp(clip(logits))``."""
    import jax.numpy as jnp
    m = jnp.exp(jnp.clip(logits, lo, hi))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def coefficients(X, w, model: dict, control=None):
    """``X [T, n, C]`` and ``w`` = (phi, b, alpha) -> ``H_pre [T, n]``,
    ``H_post [T, n]``, ``H_res [T, n, n]``. ``control``: ``"hc_bfloat16"``
    (everything here in bfloat16), ``"sinkhorn_5"`` (5 iterations),
    ``"post_without_2"`` (``H_post = sigmoid``), ``"static_only"`` (the
    dynamic part ``alpha z`` left out), ``"no_clamp"`` (``H_res``'s logits
    as they come)."""
    import jax
    import jax.numpy as jnp
    phi, b, alpha = w
    T, n, C = X.shape
    eps = model["hc_eps"]
    dtype = jnp.bfloat16 if control == "hc_bfloat16" else X.dtype
    x = X.reshape(T, n * C).astype(dtype)
    xbar = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + jnp.asarray(eps, dtype))
    z = xbar @ phi.astype(dtype)
    if control == "static_only":
        z = jnp.zeros_like(z)
    b, alpha = b.astype(dtype), alpha.astype(dtype)
    pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + b[:n])
    post = jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + b[n:2 * n])
    if control != "post_without_2":
        post = 2.0 * post
    res = sinkhorn(
        (alpha[2] * z[:, 2 * n:] + b[2 * n:]).reshape(T, n, n),
        5 if control == "sinkhorn_5" else model["hc_sinkhorn_iters"],
        jnp.asarray(eps, dtype),
        -jnp.inf if control == "no_clamp" else model["mhc_h_res_clamp_min"],
        jnp.inf if control == "no_clamp" else model["mhc_h_res_clamp_max"])
    return tuple(t.astype(X.dtype) for t in (pre, post, res))


def hyper_connection(X, w, branch, model: dict, control=None):
    """One sub-layer: ``H_res X + H_post^T branch(H_pre X)``; also the
    ``H_res`` it mixed with."""
    import jax.numpy as jnp
    pre, post, res = coefficients(X, w, model, control)
    y, _ = branch(jnp.einsum("tn,tnc->tc", pre, X))
    return (jnp.einsum("tij,tjc->tic", res, X)
            + post[:, :, None] * y[:, None, :]), res


def sparse_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model.get("first_k_dense_replace", 0)


def forward(weights: list, batch: dict, model: dict, chosen=None, held=None,
            biases=None, control=None) -> dict:
    """The pure function: ``weights`` are float32 arrays in the program's
    creation order (table; a block each: the attention's hyper-connection
    (phi, b, alpha), norm, the attention's seven, the feed-forward's
    hyper-connection, norm, the feed-forward's three or the router, the
    three stacked held experts and the shared expert's three; final norm,
    head). Returns ``loss`` = ``ce``, ``positions``, ``held_norm`` (a sparse
    layer each), ``stream_rms [blocks, n]``, ``res_sums`` (the largest
    distance of an ``H_res`` row or column sum from 1), ``each``
    (``tolerance``'s order), ``experts`` and ``load``. ``chosen``, ``held``,
    ``biases``: the GLM reference's; ``control``: one of ``CONTROLS``."""
    import jax
    import jax.numpy as jnp

    eps, n = model["rms_norm_eps"], model["hc_mult"]
    it = iter(weights)
    take = lambda k: [next(it) for _ in range(k)]           # noqa: E731
    (emb,) = take(1)
    ids = batch["ids"]
    B, S = ids.shape
    C = emb.shape[1]
    experts, loads, held_norm, stream_rms, res_sums = [], [], [], [], []
    routed_width = model.get("num_experts_routed", model["n_routed_experts"])

    def attention(w_norm, w):
        return lambda u: (latent_attention(
            rms_norm(u, w_norm, eps), w, model, B, S, control), None)

    def dense(w_norm, w):
        return lambda u: (swiglu(rms_norm(u, w_norm, eps), *w), None)

    def sparse(w_norm, w_routed, w_shared):
        def run(u):
            m = rms_norm(u, w_norm, eps)
            i = len(experts)
            moe, top_i, load = expert_layer(
                m, *w_routed,
                jnp.zeros((routed_width,)) if biases is None else biases[i],
                model, held=held,
                chosen=None if chosen is None else chosen[i])
            experts.append(jnp.sort(top_i, axis=-1))
            loads.append(load)
            first, count = held or (model.get("first_expert_held", 0),
                                    model["n_routed_experts"])
            here = jnp.sum((top_i >= first) & (top_i < first + count),
                           axis=-1)
            held_norm.append(jnp.sum(jnp.linalg.norm(moe, axis=-1))
                             / jnp.maximum(jnp.sum(jnp.sqrt(here)), 1))
            return moe + swiglu(m, *w_shared), None
        return run

    x = emb[ids].reshape(B * S, C)
    X = jnp.broadcast_to(x[:, None, :], (B * S, n, C))
    for i in range(model["num_hidden_layers"]):
        hc, (norm,), w = take(3), take(1), take(7)
        X, res = hyper_connection(X, hc, attention(norm, w), model, control)
        res_sums.append(res)
        hc, (norm,) = take(3), take(1)
        if i < model.get("first_k_dense_replace", 0):
            run = dense(norm, take(3))
        else:
            run = sparse(norm, take(4), take(3))
        X, res = hyper_connection(X, hc, run, model, control)
        res_sums.append(res)
        stream_rms.append(jnp.sqrt(jnp.mean(jnp.square(X), axis=(0, 2))))
    final_norm, head = take(2)
    x = jnp.sum(X, axis=1)
    rows = _block(B * S, HEAD_ROWS)

    def decode(arg):
        hb, lb = arg
        logp = jax.nn.log_softmax(hb @ head, axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=1)[:, 0]
    each = jax.lax.map(decode, (
        rms_norm(x, final_norm, eps).reshape(-1, rows, C),
        batch["labels"].reshape(-1, rows))).reshape(-1)
    res = jnp.stack(res_sums)                   # [sub-layers, T, n, n]
    off = jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, axis=-1) - 1)),
                      jnp.max(jnp.abs(jnp.sum(res, axis=-2) - 1)))
    out = {"loss": jnp.mean(each), "ce": jnp.mean(each), "positions": each,
           "stream_rms": jnp.stack(stream_rms), "res_sums": off}
    parts = [jnp.mean(each.reshape(-1, check_block(S)), axis=1)]
    if held_norm:
        out.update(held_norm=jnp.stack(held_norm),
                   experts=jnp.stack(experts).astype(jnp.int32),
                   load=jnp.stack(loads))
        parts.append(out["held_norm"])
    out["each"] = jnp.concatenate(parts + [out["stream_rms"].reshape(-1)])
    return out


def loss(weights: list, batch: dict, model: dict, params: dict) -> dict:
    """``weights``: the program's parameters in creation order, then its
    selection biases, a sparse layer each, any dtype; ``forward``'s ``loss``
    and ``each``, computed in float32 at matmul precision "highest"."""
    import jax
    import jax.numpy as jnp

    def f(weights, batch):
        weights = [jnp.asarray(w, jnp.float32) for w in weights]
        k = sparse_layers(model)
        out = forward(weights[:len(weights) - k], batch, model,
                      biases=weights[len(weights) - k:])
        return {"loss": out["loss"], "each": out["each"]}

    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(list(weights), dict(batch))
