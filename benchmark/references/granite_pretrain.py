"""Plain reference for the granite-4.0-h-micro pre-training loss over one
chip's slice of the vocabulary: straightforward ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``, no kernels, no framework
op. Written from HF ``modeling_granitemoehybrid.py``
(``GraniteMoeHybridMambaLayer``, which is HF's Mamba-2 mixer;
``GraniteMoeHybridAttention``; ``GraniteMoeHybridDecoderLayer``) and, for the
scan, Dao & Gu, "Transformers are SSMs" (arXiv:2405.21060), section 2's
recurrence; independent of ``paddle_tpu/models/decoder_lm.py`` except for the
order in which parameters are created, which is how weights are handed over.

Model: ``x0 = embedding_multiplier x tok_emb[ids]``; blocks ``h = x + r
mixer(norm(x))``, ``y = h + r W_down (silu(W_gate n) * (W_up n))`` with ``n =
norm(h)`` and ``r = residual_multiplier``; final RMSNorm; ``logits = (x
tok_emb^T) / logits_scaling`` from the one table; next-token cross-entropy.
``attention``: q, k, v projections, no norm and no rotary on q or k,
``softmax(q k^T x attention_multiplier + causal mask) v`` with query head i
reading key/value head i // 4. ``mamba``, per token t: ``[z | xBC | dt] = W_in
u``; ``xBC = silu(conv(xBC) + b)``, a causal depthwise filter, zeros before
the sequence's start; ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``;
``A = -exp(A_log)``; per head with state ``h [N, P]``: ``h_t = exp(dt_t A)
h_{t-1} + B_t (x) dt_t x_t``, ``y_t = C_t h_t + D x_t`` -- **the recurrence,
position by position** (``lax.scan``), not the chunked form the program's
kernels compute; ``W_out rmsnorm(y * silu(z))``.

Departures from the published model, each because the program under test
makes the same choice: the RMSNorm scale multiplies in float32 before the
cast back; every position has a label (the batch carries the token after the
last); the vocabulary is the held slice; gate and up are two matrices (HF
holds them as one ``input_linear`` and halves its output: the same product).

Memory: it runs on the chip beside the training state, so attention runs over
blocks of ``Q_ROWS`` query rows and the output head over blocks of
``HEAD_ROWS`` positions (``lax.map``); the recurrence runs in blocks of
``SCAN_ROWS`` positions under ``jax.checkpoint``, so that its own backward
(``tools/granite_probe.py grads``) keeps a state a block and not a position.
"""
from __future__ import annotations

Q_ROWS = 256
HEAD_ROWS = 1024
SCAN_ROWS = 64


def tolerance(model: dict) -> dict:
    """``loss``: |program - reference| <= tol * |reference| on the mean loss.
    ``each``: the same on every position's cross-entropy, relative to the
    largest. Single positions can carry the check here, as in the BERT
    cells: no router chooses, so no position's loss jumps when a rounding
    flips a choice, and a lower precision's error sits on every position.

    The limits, from the two readings the contract asks for (PERF.md section
    6, PR 35; chip, published widths, ten layers, 1 x 4096 tokens, seeded
    weights): the program as it is read ``each`` 6.5e-4 to 8.9e-4 (the six
    seeds whose line was kept; 19 seeds passed), float8 (e4m3) weights in
    the program's place 7.2e-3 and 7.8e-3: 3.0e-3 at the cell's ten layers,
    3.4 times the one and 0.4 of the other, written as 1.0e-3 +
    2e-4 a layer (each layer adds its roundings to the residual stream, as
    in the other decoders' limits). ``loss``: errors of single positions
    cancel in the mean over 4,096 (1.0e-6 to 2.9e-6 as it is, float8 1.6e-5
    and 4.5e-5): the harness's accepted cells' 1e-4 passes both, so ``each``
    decides, and the mean guards against an error of one sign.
    What it cannot see: the scan's carried state kept in bfloat16 reads
    7.7e-4 where the program as it is reads 7.5e-4 (``selective_scan``'s
    ``state_dtype``; ``tools/granite_probe.py controls``): at the seeded
    weights the scan's part of a mixer's output lies under ``D x`` and every
    activation is rounded to bfloat16 anyway, so no limit on the loss
    separates it. The op's own tests do (tests/test_ssd_scan.py: kernels
    against the float32 recurrence at 1e-4; a bfloat16 state is several
    times that off)."""
    return {"loss": LOSS, "each": EACH_BASE + EACH_A_LAYER
            * model["num_hidden_layers"]}


LOSS = 1e-4
EACH_BASE = 1.0e-3
EACH_A_LAYER = 2.0e-4
EACH_AT_TEN_LAYERS = EACH_BASE + 10 * EACH_A_LAYER


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def _block(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is at most ``target``."""
    c = max(1, min(n, target))
    while n % c:
        c -= 1
    return c


def causal_attention(q, k, v, scale):
    """q [B, h, S, d] against k, v [B, kv, S, d], query head i reading
    key/value head i // (h / kv): softmax(q k^T scale + causal mask) v, in
    blocks of query rows so that the [S, S] scores never exist whole. The
    plain form of grouped-query attention: K and V repeated."""
    import jax
    import jax.numpy as jnp
    B, h, S, d = q.shape
    k = jnp.repeat(k, h // k.shape[1], axis=1)
    v = jnp.repeat(v, h // v.shape[1], axis=1)
    rows = _block(S, Q_ROWS)
    key_pos = jnp.arange(S)

    def one(arg):
        qb, first = arg                                  # [B, h, rows, d]
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k) * scale
        q_pos = first + jnp.arange(rows)
        s = jnp.where(key_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    blocks = q.reshape(B, h, S // rows, rows, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(one, (blocks, jnp.arange(0, S, rows)))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, h, S, d)


def causal_conv(x, w, b):
    """``x [B, S, C]``, one filter ``w [C, taps]`` a channel, written as the
    sum it is: ``out[t] = b + sum_j w[:, j] x[t - (taps - 1) + j]``, zeros
    before the sequence's start."""
    import jax.numpy as jnp
    B, S, C = x.shape
    taps = w.shape[1]
    out = jnp.zeros_like(x) + b
    for j in range(taps):
        shift = taps - 1 - j
        moved = jnp.concatenate(
            [jnp.zeros((B, shift, C), x.dtype), x[:, :S - shift]], axis=1)
        out = out + w[:, j] * moved
    return out


def selective_scan(x, dt, a, bm, cm, d, state_dtype=None):
    """The recurrence of the state-space layer, position by position: ``x
    [B, S, heads, P]``, ``dt [B, S, heads]``, ``a [heads]``, ``bm`` / ``cm
    [B, S, N]``, ``d [heads]`` -> ``y`` like ``x``. ``state_dtype``: a
    control that keeps the carried state in a lower precision."""
    import jax
    import jax.numpy as jnp
    B, S, heads, P = x.shape
    N = bm.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp            # [B, heads, P] [B, heads] [B, N] x2
        h = (jnp.exp(dtt * a)[..., None, None] * h
             + bt[:, None, :, None] * (dtt[..., None] * xt)[:, :, None, :])
        if state_dtype is not None:     # a cast pair would be optimised away
            bits = jnp.finfo(state_dtype)
            h = jax.lax.reduce_precision(h, bits.nexp, bits.nmant)
        return h, jnp.einsum("bn,bhnp->bhp", ct, h) + d[:, None] * xt

    rows = _block(S, SCAN_ROWS)

    @jax.checkpoint
    def block(h, inp):
        return jax.lax.scan(step, h, inp)

    def blocks(t):                      # [B, S, ...] -> [S / rows, rows, B, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape(S // rows, rows, *t.shape[1:])

    _, y = jax.lax.scan(block, jnp.zeros((B, heads, N, P), jnp.float32),
                        tuple(blocks(t) for t in (x, dt, bm, cm)))
    return jnp.moveaxis(y.reshape(S, B, heads, P), 0, 1)


def mamba(x, weights, B, model, state_dtype=None):
    """The Mamba-2 mixer over tokens ``x [B*S, H]``."""
    import jax
    import jax.numpy as jnp
    w_in, conv_w, conv_b, dt_bias, a_log, d, norm_w, w_out = weights
    heads, P, N = (model["mamba_n_heads"], model["mamba_d_head"],
                   model["mamba_d_state"])
    inner = heads * P
    T = x.shape[0]
    S = T // B
    proj = x @ w_in
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * N],
                  proj[:, 2 * inner + 2 * N:])
    xbc = jax.nn.silu(causal_conv(xbc.reshape(B, S, -1), conv_w, conv_b))
    xs, bm, cm = (xbc[..., :inner], xbc[..., inner:inner + N],
                  xbc[..., inner + N:])
    dt = jax.nn.softplus(dt.reshape(B, S, heads) + dt_bias)
    y = selective_scan(xs.reshape(B, S, heads, P), dt, -jnp.exp(a_log), bm,
                       cm, d, state_dtype)
    y = _rms_norm(y.reshape(T, inner) * jax.nn.silu(z), norm_w,
                  model["rms_norm_eps"])
    return y @ w_out


N_MAMBA, N_ATTENTION = 8, 4         # weights of a mixer, in creation order


def forward(weights: list, batch: dict, model: dict,
            state_dtype=None) -> dict:
    """The pure function: ``weights`` are float32 arrays in the program's
    creation order. Returns ``loss`` and ``each`` (every position's
    cross-entropy)."""
    import jax
    import jax.numpy as jnp

    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    eps, r = model["rms_norm_eps"], model["residual_multiplier"]
    it = iter(weights)
    take = lambda n: [next(it) for _ in range(n)]           # noqa: E731
    (emb,) = take(1)
    ids = batch["ids"]
    B, S = ids.shape
    H = emb.shape[1]
    d = H // heads
    x = (model["embedding_multiplier"] * emb[ids]).reshape(B * S, H)
    for kind in model["layer_types"]:
        (op_norm,) = take(1)
        xn = _rms_norm(x, op_norm, eps)
        if kind == "mamba":
            mixed = mamba(xn, take(N_MAMBA), B, model, state_dtype)
        else:
            wq, wk, wv, wo = take(N_ATTENTION)
            sh = lambda t, n: t.reshape(B, S, n, d).transpose(  # noqa: E731
                0, 2, 1, 3)
            a = causal_attention(sh(xn @ wq, heads), sh(xn @ wk, kv),
                                 sh(xn @ wv, kv),
                                 model["attention_multiplier"])
            mixed = a.transpose(0, 2, 1, 3).reshape(B * S, H) @ wo
        h = x + r * mixed
        ffn_norm, w_gate, w_up, w_down = take(4)
        hn = _rms_norm(h, ffn_norm, eps)
        x = h + r * ((jax.nn.silu(hn @ w_gate) * (hn @ w_up)) @ w_down)
    (final_norm,) = take(1)
    assert next(it, None) is None, "weights left over: the head is tied"
    xn = _rms_norm(x, final_norm, eps)
    labels = batch["labels"].reshape(-1)
    rows = _block(B * S, HEAD_ROWS)

    def decode(arg):
        hb, lb = arg
        logp = jax.nn.log_softmax(
            (hb @ emb.T) / model["logits_scaling"], axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=1)[:, 0]

    each = jax.lax.map(decode, (xn.reshape(-1, rows, H),
                                labels.reshape(-1, rows))).reshape(-1)
    return {"loss": jnp.mean(each), "each": each}


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
