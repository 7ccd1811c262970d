"""Plain reference for the OLMoE pre-training loss: straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
no kernels, no framework op, no sort and no grouping. Written from
Muennighoff et al., "OLMoE: Open Mixture-of-Experts Language Models"
(arXiv:2409.02060) and HF ``modeling_olmoe.py``; independent of
``paddle_tpu/models/decoder_lm.py`` except for the order in which parameters
are created, which is how weights are handed over.

The layer: ``h = x + W_o attn(norm(x))``, ``y = h + moe(norm(h))``; q and k
are RMS-normalised over the whole 2048-wide projection before the split into
heads, then rotated (rotate-half, theta 10000, positions 0..S-1); attention
is ``softmax(Q K^T / sqrt(d) + causal mask) V``; the router is a softmax over
the expert logits whose 8 largest values weigh the experts as they are;
EVERY expert ``W_down (silu(W_gate x) * (W_up x))`` is applied to EVERY token
and masked by the top-8.

Departures from the published model, each because the program under test
makes the same choice (a reference that differed there would measure the
choice, not the precision):

- the RMSNorm scale multiplies in float32, before the cast back (HF casts
  first); the router weights stay float32 in the combine (HF casts them to
  the activations' dtype);
- the load-balancing loss counts an expert's share of the tokens x 8
  assignments (HF's sums the eight choices' shares, 8 times this; the
  paper's f_i); it and the z-loss (paper eq. 3, not in HF's file) are
  computed a layer and averaged over the layers;
- every position has a label (the batch carries the token after the last).

Memory: it runs on the chip beside the training state, so it never holds a
float32 copy of a stacked expert weight or of the logits: the experts are
cast and applied one at a time (``lax.scan``), attention runs over blocks of
``Q_ROWS`` query rows and the output head over blocks of ``HEAD_ROWS``
positions (``lax.map``).
"""
from __future__ import annotations

import math


def tolerance(model: dict) -> dict:
    """``loss``: |program - reference| <= tol * |reference| on the total
    loss. ``each``: the same on the means of every position's cross-entropy
    over blocks of ``seq // 64`` consecutive positions (64 at S=4096; single
    positions in the tests, whose sequences are shorter than 64), relative
    to the largest of them.

    Why block means and not single positions. The program computes in
    bfloat16 (one rounding is 2^-9) with float32 accumulation, norms, router
    and softmaxes. At random initial weights the router's probabilities are
    nearly uniform, the k-th and (k+1)-th largest lie a few 10^-4 apart, and
    the bfloat16 rounding of the router's input moves them by as much: for
    about 4 tokens in a hundred the program's 8th expert is the reference's
    9th (0.45-0.52% of the assignments differ, ``differing_share``; chip,
    PR 26). Such a token's layer output differs by two experts' outputs at
    weight about 1/64 against a residual stream that is small at
    initialisation, and its loss by up to 3.8-6.0% of the largest position's
    (chip, one layer, five seeds; 1.1-1.6% at two layers, where the stream
    is larger) -- while float8 weights move the worst position by 6.1-7.6%.
    Nothing rounds a flip away, a float32 program on another machine would
    flip other tokens, and no limit on single positions separates the two.
    Over a block of 64 positions the few flips average down by 64 and a
    lower precision's errors, which sit on every position, by 8. Measured on
    the chip at the published widths, one layer, four seeds (PERF.md
    section 6, PR 26): the program as it is 1.40e-3 to 1.83e-3 of the
    largest block; float8 (e4m3) weights 4.3e-3 to 6.7e-3. The limit is
    2.6e-3 plus 2e-4 a layer of depth (every layer adds roundings to the
    residual stream; BERT's twelve add 1.6e-4 each on the chip): 2.8e-3 at
    the cell's one layer.

    What it cannot tell apart, said plainly: a bfloat16 router behind a
    bfloat16 input (1.6e-3 to 1.9e-3, 0.51-0.59% of assignments: the
    input's rounding already decides the ties) and one dropped assignment
    (one position in 16,384 moves by less than a flip). Those are held by
    the tests at the tests' widths, where no token flips for the seed and
    the limit (3.0e-3 at two layers, on single positions) is tight:
    tests/benchmark/test_benchmark_olmoe.py shows float8 weights, a
    bfloat16 router and one dropped assignment failing this same check.
    ``loss``: errors of single positions cancel in the mean over thousands
    (chip: 2e-6 to 2.5e-5; float8 1.7e-5 to 1.5e-4, so the mean alone would
    pass a lower precision in some runs -- hence both); half of BERT's."""
    return {"loss": 1e-4,
            "each": 2.6e-3 + 2e-4 * model["num_hidden_layers"]}


def differing_share(index, experts) -> float:
    """The share of the program's tokens x top-k assignments (``index [...,
    tokens, k]``, any order) that are not among the reference's for the same
    token (``experts``, likewise)."""
    import numpy as np
    index, experts = np.asarray(index), np.asarray(experts)
    kept = (index[..., :, None] == experts[..., None, :]).any(-1)
    return float(1.0 - kept.mean())


def flipped_share(model: dict) -> float:
    """The largest ``differing_share``, over all layers, a bfloat16 program
    with a float32 router may show (see ``tolerance``): 1% a layer of depth
    (a later layer sees the earlier ones' errors; chip: 0.45-0.52% at one
    layer, 0.53% / 0.77% at two; float8 weights 6.3-6.6%). Read by the
    tests and by the builder's chip run; ``reference_check`` compares
    losses."""
    return 0.01 * model["num_hidden_layers"]


Q_ROWS = 512
HEAD_ROWS = 1024


def check_block(seq: int) -> int:
    """Positions a block of the compared cross-entropy: 64 blocks a
    sequence (single positions under 128 tokens)."""
    return max(1, seq // 64)


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
    """softmax(q k^T / sqrt(d) + causal mask) v over [B, h, S, d], in blocks
    of query rows so that the [S, S] scores never exist whole."""
    import jax
    import jax.numpy as jnp
    B, h, S, d = q.shape
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


def loss(weights: list, batch: dict, model: dict, params: dict):
    """``weights``: the program's parameters in creation order, any dtype.
    Returns ``{"loss": the total, "positions": every position's
    cross-entropy, "each": its means over blocks of ``seq // 64``
    consecutive positions (what ``reference_check`` compares, see
    ``tolerance``), "experts": the chosen experts [layers, tokens, k] sorted
    by expert}`` in float32 / int32."""
    import jax
    import jax.numpy as jnp

    heads = model["num_attention_heads"]
    n_layers = model["num_hidden_layers"]
    E, k = model["num_experts"], model["num_experts_per_tok"]
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    f32 = lambda a: jnp.asarray(a, jnp.float32)             # noqa: E731

    def f(weights, batch):
        it = iter(weights)
        take = lambda n=1: [next(it) for _ in range(n)]     # noqa: E731
        (emb,) = take()
        layers = [take(12) for _ in range(n_layers)]
        final_norm, head = take(2)
        ids = batch["ids"]
        B, S = ids.shape
        H = emb.shape[1]
        d = H // heads
        x = f32(emb)[ids].reshape(B * S, H)
        balance, z, chosen = [], [], []
        for (attn_norm, wq, q_norm, wk, k_norm, wv, wo, ffn_norm, w_router,
             w_gate, w_up, w_down) in layers:
            xn = _rms_norm(x, f32(attn_norm), eps)
            q = _rms_norm(xn @ f32(wq), f32(q_norm), eps)
            kk = _rms_norm(xn @ f32(wk), f32(k_norm), eps)
            sh = lambda t: t.reshape(B, S, heads, d) \
                .transpose(0, 2, 1, 3)                      # noqa: E731
            a = _causal_attention(_rope(sh(q), theta), _rope(sh(kk), theta),
                                  sh(xn @ f32(wv)))
            h = x + a.transpose(0, 2, 1, 3).reshape(B * S, H) @ f32(wo)

            hn = _rms_norm(h, f32(ffn_norm), eps)
            logits = hn @ f32(w_router)                      # [T, E]
            prob = jax.nn.softmax(logits, axis=-1)
            top_w, top_i = jax.lax.top_k(prob, k)
            # [T, E]: the router's weight where the expert was chosen
            gate = jnp.sum(jax.nn.one_hot(top_i, E) * top_w[..., None],
                           axis=1)

            def expert(acc, w):
                g, u, dn, col = w
                y = (jax.nn.silu(hn @ f32(g)) * (hn @ f32(u))) @ f32(dn)
                return acc + col[:, None] * y, None

            moe, _ = jax.lax.scan(expert, jnp.zeros_like(hn),
                                  (w_gate, w_up, w_down, gate.T))
            x = h + moe
            share = jnp.sum(jax.nn.one_hot(top_i, E), axis=(0, 1)) \
                / (B * S * k)
            balance.append(E * jnp.sum(share * jnp.mean(prob, axis=0)))
            z.append(jnp.mean(jnp.square(
                jax.nn.logsumexp(logits, axis=-1))))
            chosen.append(jnp.sort(top_i, axis=-1))

        xn = _rms_norm(x, f32(final_norm), eps)
        labels = batch["labels"].reshape(-1)
        rows = _block(B * S, HEAD_ROWS)

        def decode(arg):
            hb, lb = arg
            logp = jax.nn.log_softmax(hb @ f32(head), axis=-1)
            return -jnp.take_along_axis(logp, lb[:, None], axis=1)[:, 0]

        each = jax.lax.map(decode, (xn.reshape(-1, rows, H),
                                    labels.reshape(-1, rows))).reshape(-1)
        total = (jnp.mean(each)
                 + model["router_aux_loss_coef"] * sum(balance) / n_layers
                 + model["router_z_loss_coef"] * sum(z) / n_layers)
        return {"loss": total, "positions": each,
                "each": jnp.mean(each.reshape(-1, check_block(S)), axis=1),
                "experts": jnp.stack(chosen).astype(jnp.int32)}

    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(list(weights), dict(batch))
