"""Plain reference for the GLM-4.7-Flash pre-training loss as one chip's
share of it: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no framework op, no
sort, no grouping and no row budget (a row the program drops is missing from
its layer's routed output, which the check compares: ``tolerance``). Written
from the model's ``config.json`` (``model_type: glm4_moe_lite``; the
catalog's row), DeepSeek-V2 (arXiv:2405.04434, section 2.1: multi-head latent
attention), DeepSeek-V3 (arXiv:2412.19437, sections 2.1.2: sigmoid scores
chosen by score + bias, and 2.2: multi-token prediction) and HF's
``modeling_deepseek_v3.py`` / ``modeling_glm4_moe.py`` for what the papers
leave to code; what the config does not carry is the configuration file's
``assumed``. Independent of ``paddle_tpu/`` except for the order in which
parameters are created, which is how weights are handed over (the block
sizes of the compared means and of the row blocks are the Laguna
reference's two helpers, which know nothing of this model; the selection
biases, which are state and no parameters, follow them where a caller has
them; zero otherwise, as at the start).

Tokens ``x [T, H]``, H = 2048, no biases, eps 1e-5, ``N(x; w) = x /
sqrt(mean(x^2) + eps) * w``; a block is ``h = x + A(N(x))``, ``y = h +
F(N(h))``.

1. Latent attention ``A``, every block; h = 20 heads, ``d_n`` = 192, ``d_r``
   = 64, ``d_v`` = 256, ``r_q`` = 768, ``r_kv`` = 512: ``c_q = N(a W_qa)``;
   ``a W_qb`` gives every head's ``q_n`` (the first 20 x 192 columns) and
   every head's ``q_r`` (the last 20 x 64). ``[c_kv | k_r] = a W_kva`` (512
   | 64); ``c_kv = N(c_kv)``; ``c_kv W_kvb`` gives every head's ``k_n``
   (the first 20 x 192 columns) and every head's ``v`` (the last 20 x 256).
   ``q_r`` and ``k_r`` rotated (rotate-half, theta 1e6, positions from 0):
   ONE rotary key head, which all 20 heads read. Scores of head j:
   ``(q_n[j] . k_n[j] + q_r[j] . k_r) / sqrt(192 + 64)`` -- the two parts
   are formed apart here, as the paper writes them, and no k of 256 is ever
   assembled --, causal softmax, ``o[j] = sum p v[j]``; ``[o_1 .. o_20] W_o``
   (5120 -> 2048).
2. Feed-forward ``F``: layer 0 (``first_k_dense_replace`` 1) a dense SwiGLU
   ``(silu(m W_g) * (m W_u)) W_d`` of 10,240. Every other block: ``s =
   sigmoid(m W_r)`` over the 64 routed experts; the 4 largest of ``s + b``
   chosen (``b`` the selection bias, no gradient); weights ``s_i / (sum of
   the chosen s + 1e-20)`` (``norm_topk_prob``; HF's epsilon) times 1.8;
   EVERY held expert (SwiGLU of 1,536) applied to EVERY token and masked by
   the choice; plus one ungated shared SwiGLU expert of 1,536 (``n_shared_
   experts`` 1 x ``moe_intermediate_size``) over every token.
3. The trunk's loss: final ``N``, untied head over the held vocabulary
   slice, mean cross-entropy against the next token ``t_{i+1}``.
4. The multi-token-prediction module (``num_nextn_predict_layers`` 1), on
   the trunk's output ``h_i`` BEFORE the final norm: ``u_i = [N_h(h_i) |
   N_e(Emb(t_{i+1}))] W_eh`` (4096 -> 2048; the trunk's table); one more
   block of the expert kind with its own weights; its own final ``N``; the
   trunk's head; mean cross-entropy against ``t_{i+2}``.
5. Loss = the trunk's + ``mtp_loss_weight`` (0.3) x the module's.

Departures from the published model, each because the program under test
makes the same choice: the columns of ``W_qb`` / ``W_kvb`` and the halves
under ``W_eh`` are contiguous by kind, a permutation of HF's interleave by
head; the RMSNorm scale multiplies in float32 before the cast back; the
router weights stay float32 in the combine; every position has both labels;
the vocabulary is the held slice; the absent experts add nothing.

Memory: it runs on the chip beside the training state, so attention runs
over blocks of ``Q_ROWS`` query rows, the heads over blocks of ``HEAD_ROWS``
positions (``lax.map``) and the experts one at a time (``lax.scan``).
"""
from __future__ import annotations

import math

from benchmark.references.laguna_pretrain import (  # noqa: F401
    _block, check_block)

Q_ROWS = 256        # query rows a block of the attention
HEAD_ROWS = 512     # positions a block of an output head


def tolerance(model: dict) -> dict:
    """``each``: |program - reference| <= tol * the reference's largest
    entry, over, in this order, (a) the trunk's cross-entropy averaged over
    blocks of ``seq // 64`` consecutive positions (64 at S=4096; single
    positions in the tests), (b) the module's mean cross-entropy, (c) the
    module's block means, and (d), a sparse layer each (the trunk's four,
    then the module's), the norm of the held routed experts' output before
    the shared expert's is added, summed over the tokens and divided by the
    sum over the tokens of sqrt(c), c the number of a token's chosen
    experts that are held here.

    Block means and a routed entry for Laguna's reasons
    (``references/laguna_pretrain.py``): the program computes in bfloat16
    with a float32 router, the 4th and 5th largest of 64 scores + bias lie
    closer than bfloat16 moves them, a flip between an expert held here and
    one held elsewhere adds or removes a whole expert's output at weight
    1.8 / 4, and with 8 of 64 experts held the cross-entropy barely sees
    the routed path (nor its scale 1.8, nor a row its budget dropped). (b)
    and (c) because the module's loss is 0.3 of the total and its own
    block, norms and concatenation show nowhere in the trunk's. (d) is not
    Laguna's mean norm over all tokens: that moves with the count of held
    rows, which such flips change by up to 20 of a layer's 1,400 to 1,900,
    so it read up to 3.2e-3 where the block means read 1.3e-3 and would set
    the limit by routing noise. A token's norm goes as sqrt(c) (the
    experts' outputs are near orthogonal), so over the sum of sqrt(c) a
    flip moves both sums alike whatever c was: an entry of (d) is off by
    2.1e-4 in the root mean square (over the held rows, c forgotten,
    6.1e-4; over the held assignments 6.8e-4). (d) reads about 7.6 where the
    cross-entropy reads ln 19,360 = 9.87, and a dropped row is in the
    divisor and not in the sum; unscaled.

    The limit, from the two readings the contract asks for (PERF.md section
    2 and 6, PR 48 after review; chip, published widths, 5 layers + the
    module, 1 x 4096 tokens, seeded weights as the cell's check has them),
    ``READINGS``: the program as it is read 6.6e-4 to 1.49e-3 over 46 seeds
    (the first 15 set the limit and read up to 1.29e-3; by part, the largest
    of 39: (a) 1.32e-3, (b) 5.9e-5, (c) 1.29e-3, (d) 6.2e-4; the cell's seven
    runs from the committed files read 8.2e-4 to 1.49e-3), float8 (e4m3)
    weights in the program's place 3.76e-3 to 5.27e-3 over 5 seeds ((a); (d)
    9.5e-4 to 1.7e-3). The limit is 2.2e-3, the geometric mean of the first
    15 seeds' largest and float8's smallest: 1.5 times the largest of 46,
    1.7 times under the other, six standard deviations over the sound runs'
    mean (9.8e-4, 2.1e-4). What it sees beside float8, every verdict
    ``jobs/common.py:reference_check``'s own (``tools/glm_probe.py
    controls``, smallest and largest; the block means over five seeds, (d)
    over three): the routed scale 1.8 left out 3.2e-1 to 3.3e-1 (d), an
    eighth of the row budget 3.6e-1 to 4.5e-1 (d), the module trained on the
    next token in place of the one after 3.0e-2 to 4.9e-2 (c), the module's
    norm of the embedding left out 1.8e-2 to 2.9e-2 (c), the rotary key head
    left unrotated 6.8e-3 to 1.12e-2 (a), the two latent norms left out
    4.5e-3 to 5.9e-3 (a), the softmax scale 1 / sqrt(192) in place of
    1 / sqrt(256) 2.8e-3 to 3.9e-3 (a; narrowly: at seeded weights the
    scores are near zero and the softmax near uniform whatever the scale;
    the CPU tests sharpen the up-projections). What it cannot see: the two
    latent norms in bfloat16 in the reference (7.6e-4 to 1.42e-3, the sound
    runs' range). ``loss`` has no limit for Laguna's reason: single
    positions' errors cancel in the mean over 4,096 -- 7.9e-7 to 1.1e-4 as
    it is, float8 9.1e-6 to 3.4e-4 -- so no limit on it separates anything
    ``each`` does not (a loss that is not a number still fails ``each``)."""
    return {"loss": float("inf"), "each": EACH_LIMIT}


# The two readings (my chip runs, PR 48; PERF.md section 6): the program as
# it is, the largest over the seeds run, and float8 (e4m3) weights in the
# program's place, the smallest over its seeds. The limit lies between them
# with room on both sides.
READINGS = {"as_it_is_max": 1.49e-3, "float8_min": 3.76e-3}
EACH_LIMIT = 2.2e-3


def rms_norm(x, w, eps, dtype=None):
    """``x / sqrt(mean(x^2) + eps) * w``; ``dtype``: computed in it and
    returned in x's (a control: what a coarser norm would read)."""
    import jax
    import jax.numpy as jnp
    xs = x if dtype is None else x.astype(dtype)
    y = xs * jax.lax.rsqrt(jnp.mean(jnp.square(xs), axis=-1, keepdims=True)
                           + jnp.asarray(eps, xs.dtype)) * w.astype(xs.dtype)
    return y.astype(x.dtype)


def rope(x, theta: float):
    """``x [B, S, ..., r]``: rotate-half rotary embedding of the whole last
    axis, positions 0..S-1 along axis 1: with ``x = [x1 | x2]``, ``[x1 cos
    - x2 sin | x2 cos + x1 sin]`` at angle ``pos * theta^(-2i/r)``."""
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


def swiglu(x, w_gate, w_up, w_down):
    import jax
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def latent_attention(a, w, model: dict, B: int, S: int, control=None):
    """Multi-head latent attention over normed tokens ``a [T, H]``; ``w`` =
    (W_qa, w_qnorm, W_qb, W_kva, w_kvnorm, W_kvb, W_o). ``control``: a
    departure a check must see (``tools/glm_probe.py``): ``"k_r_unrotated"``,
    ``"no_latent_norms"``, ``"bf16_latent_norms"``, ``"scale_nope_only"``."""
    import jax
    import jax.numpy as jnp
    w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, w_o = w
    h = model["num_attention_heads"]
    d_n, d_r, d_v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    r_kv, eps = model["kv_lora_rank"], model["rms_norm_eps"]

    def latent_norm(x, scale):
        if control == "no_latent_norms":
            return x
        return rms_norm(x, scale, eps, jnp.bfloat16
                        if control == "bf16_latent_norms" else None)
    q = latent_norm(a @ w_qa, q_norm) @ w_qb
    q_n = q[:, :h * d_n].reshape(B, S, h, d_n)
    q_r = rope(q[:, h * d_n:].reshape(B, S, h, d_r), model["rope_theta"])
    ckv = a @ w_kva
    k_r = ckv[:, r_kv:].reshape(B, S, d_r)
    if control != "k_r_unrotated":
        k_r = rope(k_r, model["rope_theta"])
    kv = latent_norm(ckv[:, :r_kv], kv_norm) @ w_kvb
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
    gradients are compared and a 4th / 5th expert that flips under bfloat16
    must not stand in the way). ``control`` ``"no_routed_scale"``: without
    the 1.8."""
    import jax
    import jax.numpy as jnp
    k = model["num_experts_per_tok"]
    routed = model.get("num_experts_routed", model["n_routed_experts"])
    first, count = held or (model.get("first_expert_held", 0),
                            model["n_routed_experts"])
    score = jax.nn.sigmoid(x @ w_router)                     # [T, routed]
    if chosen is None:
        _, top_i = jax.lax.top_k(jax.lax.stop_gradient(score + bias), k)
    else:
        top_i = chosen
    top_w = jnp.take_along_axis(score, top_i, axis=-1)
    if model.get("norm_topk_prob"):
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    if control != "no_routed_scale":
        top_w = top_w * model.get("routed_scaling_factor", 1.0)
    taken = jax.nn.one_hot(top_i, routed)                    # [T, k, routed]
    # [T, routed]: the router's weight where the expert was chosen
    gate = jnp.sum(taken * top_w[..., None], axis=1)

    def expert(acc, w):
        g, u, dn, col = w
        return acc + col[:, None] * swiglu(x, g, u, dn), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (w_gate, w_up, w_down, gate.T[first:first + count]))
    return out, top_i, jnp.sum(taken, axis=(0, 1)).astype(jnp.int32)


def sparse_layers(model: dict) -> int:
    """The expert layers: the trunk's after the leading dense ones, and the
    prediction module's."""
    return (model["num_hidden_layers"] - model.get("first_k_dense_replace", 0)
            + model.get("num_nextn_predict_layers", 0))


def forward(weights: list, batch: dict, model: dict, chosen=None, held=None,
            biases=None, control=None) -> dict:
    """The pure function: ``weights`` are float32 arrays in the program's
    creation order (table; a block each: norm, the attention's seven, norm,
    the feed-forward's three or the router, the three stacked held experts
    and the shared expert's three; final norm, head; the module: the two
    norms, W_eh, a block, its final norm). Returns ``loss`` (the total),
    ``ce`` / ``mtp_ce`` (the two mean cross-entropies), ``positions`` /
    ``mtp_positions`` (every position's), ``routed`` (a sparse layer each,
    the module's last: the mean over the tokens of the norm of the held
    routed experts' output), ``held_norm`` (the same norms' sum over the
    sum of sqrt(c), c a token's chosen experts that are held), ``each``
    (``tolerance``'s order), ``experts``
    ``[sparse layers, tokens, k]`` sorted by expert and ``load`` ``[sparse
    layers, experts routed]``. ``chosen [sparse layers, tokens, k]`` and
    ``held``: ``expert_layer``'s, a layer each; ``biases [sparse layers,
    experts routed]``: the selection biases (default zero); ``control``: one
    departure a check must see (``latent_attention``'s, ``expert_layer``'s,
    ``"no_e_norm"``: the module's norm of the embedding left out)."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    it = iter(weights)
    take = lambda n: [next(it) for _ in range(n)]           # noqa: E731
    (emb,) = take(1)
    ids = batch["ids"]
    B, S = ids.shape
    H = emb.shape[1]
    experts, loads, routed, held_norm = [], [], [], []
    routed_width = model.get("num_experts_routed", model["n_routed_experts"])

    def block(x, dense):
        (norm,) = take(1)
        h = x + latent_attention(rms_norm(x, norm, eps), take(7), model, B,
                                 S, control)
        (ffn_norm,) = take(1)
        m = rms_norm(h, ffn_norm, eps)
        if dense:
            return h + swiglu(m, *take(3))
        i = len(experts)
        w_router, w_gate, w_up, w_down = take(4)
        moe, top_i, load = expert_layer(
            m, w_router, w_gate, w_up, w_down,
            jnp.zeros((routed_width,)) if biases is None else biases[i],
            model, held=held, chosen=None if chosen is None else chosen[i],
            control=control)
        experts.append(jnp.sort(top_i, axis=-1))
        loads.append(load)
        norm = jnp.linalg.norm(moe, axis=-1)
        first, count = held or (model.get("first_expert_held", 0),
                                model["n_routed_experts"])
        routed.append(jnp.mean(norm))
        here = jnp.sum((top_i >= first) & (top_i < first + count), axis=-1)
        held_norm.append(jnp.sum(norm) / jnp.maximum(
            jnp.sum(jnp.sqrt(here)), 1))
        return h + moe + swiglu(m, *take(3))                # shared expert

    x = emb[ids].reshape(B * S, H)
    for i in range(model["num_hidden_layers"]):
        x = block(x, dense=i < model.get("first_k_dense_replace", 0))
    final_norm, head = take(2)
    rows = _block(B * S, HEAD_ROWS)

    def cross_entropy(x, labels):
        def decode(arg):
            hb, lb = arg
            logp = jax.nn.log_softmax(hb @ head, axis=-1)
            return -jnp.take_along_axis(logp, lb[:, None], axis=1)[:, 0]
        return jax.lax.map(decode, (x.reshape(-1, rows, H),
                                    labels.reshape(-1, rows))).reshape(-1)

    labels = batch["labels"].reshape(-1)
    each = cross_entropy(rms_norm(x, final_norm, eps), labels)
    out = {"ce": jnp.mean(each), "positions": each}
    parts = [jnp.mean(each.reshape(-1, check_block(S)), axis=1)]
    out["loss"] = out["ce"]
    if model.get("num_nextn_predict_layers"):
        h_norm, e_norm, w_eh = take(3)
        e = emb[labels]
        if control != "no_e_norm":
            e = rms_norm(e, e_norm, eps)
        u = jnp.concatenate([rms_norm(x, h_norm, eps), e], axis=-1) @ w_eh
        u = block(u, dense=False)
        (mtp_norm,) = take(1)
        mtp_each = cross_entropy(rms_norm(u, mtp_norm, eps),
                                 batch["labels_next"].reshape(-1))
        out.update(mtp_ce=jnp.mean(mtp_each), mtp_positions=mtp_each)
        out["loss"] = out["ce"] + model.get("mtp_loss_weight", 0.3) \
            * out["mtp_ce"]
        parts += [out["mtp_ce"][None],
                  jnp.mean(mtp_each.reshape(-1, check_block(S)), axis=1)]
    out.update(routed=jnp.stack(routed), held_norm=jnp.stack(held_norm),
               each=jnp.concatenate(parts + [jnp.stack(held_norm)]),
               experts=jnp.stack(experts).astype(jnp.int32),
               load=jnp.stack(loads))
    return out


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
