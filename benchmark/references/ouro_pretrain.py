"""Plain reference for the Ouro (LoopLM) pre-training loss: straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``, a
Python loop over the passes and the layers, no kernels, no framework op, no
loop op and no recomputation. Written from the description below (Zhu et
al., "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741; HF ``modeling_ouro.py`` of ByteDance/Ouro-2.6B for the
norms' placement); independent of ``paddle_tpu/models/decoder_lm.py`` except
for the order in which parameters are created, which is how weights are
handed over.

With ``R = total_ut_steps``, ``N = num_hidden_layers``, tokens ``t_1..t_S``,
every weight shared by the passes:

- ``h^(0) = tok_emb[t]``. Pass ``r = 1..R``: ``x = h^(r-1)``; layer ``l``:
  ``a = x + Norm_2(Attn(Norm_1(x)))``, ``x' = a + Norm_4(FFN(Norm_3(a)))``
  (sandwich: HF's ``input_layernorm``, ``input_layernorm_2``,
  ``post_attention_layernorm``, ``post_attention_layernorm_2``);
  ``h^(r) = Norm_f(x_N)``, and that normed state is what pass ``r + 1``
  starts from.
- ``Attn``: q, k, v, o without biases, ``num_attention_heads`` heads of
  ``head_dim`` for q, k and v, rotate-half rotary at ``rope_theta`` over the
  whole head with positions 0..S-1 in every pass, causal softmax at
  1/sqrt(head_dim), no q / k norm. ``FFN``: ``W_down (silu(W_gate n) *
  (W_up n))``. RMSNorm with ``rms_norm_eps``, ``* w``.
- After pass ``r``: ``logits^(r) = W_head h^(r)`` (one head, untied),
  ``ce^(r)_i`` the next-token cross-entropy of position ``i``, and the exit
  gate ``lambda^(r)_i = sigmoid(w_g . h^(r)_i + b_g)`` (one ``Linear(H, 1)``
  shared by the passes).
- Exit distribution of a position: ``p^(1) = lambda^(1)``; ``p^(r) =
  lambda^(r) prod_{j<r} (1 - lambda^(j))`` for ``r < R``; ``p^(R) =
  prod_{j<R} (1 - lambda^(j))``. Loss (the report's stage-I objective):
  ``mean_i [sum_r p^(r)_i ce^(r)_i - beta H(p_i)]``, ``H`` the entropy of
  ``p_i``, ``beta = exit_entropy_coef``.

Departures, each because the program under test makes the same choice: the
RMSNorm scale multiplies in float32 before the cast back (HF casts first);
every position has a label (the batch carries the token after the last).

Memory: it runs on the chip beside the training state, so attention runs
over blocks of ``Q_ROWS`` query rows and the head over blocks of
``HEAD_ROWS`` positions (``lax.map``): neither the ``[S, S]`` scores nor a
pass's float32 logits exist whole.
"""
from __future__ import annotations

import math

Q_ROWS = 512
HEAD_ROWS = 1024

LOSS = 1e-4
EACH_BASE = 1.0e-3
EACH_AN_APPLICATION = 8.0e-4


def tolerance(model: dict) -> dict:
    """``loss``: |program - reference| <= tol * |reference| on the loss (the
    expected cross-entropy less beta x the exit entropy). ``each``: the
    same on the vector ``reference_check`` compares -- every pass's
    cross-entropy of every position, then every position's exit
    probabilities -- relative to its largest entry (a cross-entropy, about
    ln V = 10.8 at seeded weights). Single positions carry the check, as in
    the Granite cell: no router chooses, so no position's loss jumps on a
    rounding, and a lower precision's error sits on every position.

    The limit, from the two readings the contract asks for (PERF.md section
    6, PR 57; chip, published widths, twelve layers run four times, 1 x 4096
    tokens, seeded weights; the cell's own check and ``tools/ouro_probe.py
    controls``): the program as it is read ``each`` 5.7e-3 to 1.21e-2 over
    the eleven seeds whose line was kept (26 seeds passed), float8 (e4m3)
    weights in the program's place 1.05e-1 and 1.39e-1. The limit is 1.0e-3
    + 8e-4 a layer APPLICATION (``total_ut_steps x num_hidden_layers``):
    3.94e-2 at the cell's 48, 3.3 times the largest reading and 0.38 of
    float8's smaller, near their geometric mean. It counts
    applications and not the stack's depth because that is what the error
    follows: a sandwich norm rescales a branch's small output to unit size,
    so every application passes its input's rounding on undiminished and
    adds its own (``granite``'s ten layers read 8.9e-4, these 48
    applications ten times that; the builder's first guess, 3.4e-3 by
    ``granite``'s rule over twelve layers, failed every run). That the
    size is the configuration's stated precision and no fault: the float32
    reference itself moves by 7.05e-3 and 5.06e-3 when only its norms'
    inputs and outputs, its logits, gate and exit probabilities are rounded
    through bfloat16 (``controls``, the same two seeds: the program 7.68e-3
    and 1.03e-2). ``loss``: the harness's accepted cells' 1e-4; the errors
    of single positions cancel in the mean (2e-6 to 2.1e-5 as it is, float8
    1.1e-4 and 4.0e-4).

    The review round's readings, each through ``common.reference_check``
    itself (``tools/ouro_probe.py controls``, seeds 2147484011 / ...012, my
    chip runs, PR 57): as it is 8.82e-3 / 5.80e-3; float8 weights in the
    program's place 1.205e-1 / 1.445e-1, FAILED; the gate's weights seeded
    at std 0.02 8.82e-3 / 5.80e-3 (the same entries: the worst is a
    cross-entropy, the exit probabilities are off by less); the reference
    with its float32-stated parts rounded through bfloat16, against itself:
    6.33e-3 / 4.34e-3, UNDER the program's own error. So this limit does
    NOT do what ISSUE 57 asked of it -- fail bfloat16 in the norms, the
    softmax, the gate or the exit distribution --, and no limit on a maximum
    over positions can, behind bfloat16 activations: it takes the rms or
    quantile comparison of PERF.md 7 (c), a ``benchmark`` issue's.

    What the check cannot see, said plainly. (1) The exit gate starts from
    zero weights, so every ``lambda`` is 1/2 and the exit probabilities are
    (1/2, 1/4, 1/8, 1/8) in any precision. (2) With bfloat16 activations the
    parts stated in float32 (the norms' arithmetic, the softmax, the gate,
    the exit distribution) could be lowered in bfloat16 and stay inside the
    program's own range, as in ``lfm2`` (PERF.md 7 (c)). (3) It runs the
    test clone: no ``keep``, no pullback, no recomputation, no sum over a
    shared weight's four uses. (1) and (2) are held by the tests alone, at
    float32 and a tiny size (tests/test_decoder_ouro.py: a float32 program
    agrees with this file to 1e-5 on seeded non-zero gates, and rounding
    those parts through bfloat16 moves it hundreds of times that); (3) by
    the tests and, at the published widths, by ``tools/ouro_probe.py
    grads`` (every leaf's gradient of the train step itself against
    ``jax.grad`` of this file; the gate's gradient is not zero at the
    start: it reads the passes' losses)."""
    return {"loss": LOSS, "each": EACH_BASE + EACH_AN_APPLICATION
            * model["total_ut_steps"] * model["num_hidden_layers"]}


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


def forward(weights: list, batch: dict, model: dict, cast=None,
            remat: bool = False) -> dict:
    """The equations of the module's docstring over ``weights`` (the
    program's parameters in creation order); traceable, so ``jax.grad`` of
    its ``loss`` gives the reference's gradients. ``cast``: a dtype every
    norm, the softmaxes' inputs, the gate and the exit distribution are
    rounded through (the tests' and the probe's lower-precision control);
    None: float32 throughout. ``remat``: each layer application under
    ``jax.checkpoint``, so that ``jax.grad`` at the published widths fits
    the chip (the same numbers). Returns ``loss``, ``ce`` (the expected
    cross-entropy), ``passes [R, T]`` (every pass's per-position
    cross-entropy), ``exit_p [R, T]`` and ``each`` (both, flattened
    pass-major, ``passes`` first: the order of the program's
    ``check.each``)."""
    import jax
    import jax.numpy as jnp

    heads, d = model["num_attention_heads"], model["head_dim"]
    n_layers, passes = model["num_hidden_layers"], model["total_ut_steps"]
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    beta = float(model.get("exit_entropy_coef", 0.0))
    f32 = lambda a: jnp.asarray(a, jnp.float32)             # noqa: E731
    low = (lambda a: a) if cast is None else (
        lambda a: a.astype(cast).astype(jnp.float32))       # noqa: E731

    def norm(x, w):
        return low(_rms_norm(low(x), f32(w), eps))

    it = iter(weights)
    take = lambda n=1: [next(it) for _ in range(n)]         # noqa: E731
    (emb,) = take()
    layers = [take(11) for _ in range(n_layers)]
    final_norm, head, gate_w, gate_b = take(4)
    ids = batch["ids"]
    B, S = ids.shape
    H = emb.shape[1]
    labels = batch["labels"].reshape(-1)
    rows = _block(B * S, HEAD_ROWS)
    sh = lambda t: t.reshape(B, S, heads, d).transpose(0, 2, 1, 3)  # noqa

    def decode(arg):
        hb, lb = arg
        logp = jax.nn.log_softmax(low(hb @ f32(head)), axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=1)[:, 0]

    def layer(x, w):
        n1, wq, wk, wv, wo, n2, n3, w_gate, w_up, w_down, n4 = w
        xn = norm(x, n1)
        attn = _causal_attention(_rope(sh(xn @ f32(wq)), theta),
                                 _rope(sh(xn @ f32(wk)), theta),
                                 sh(xn @ f32(wv)))
        attn = attn.transpose(0, 2, 1, 3).reshape(B * S, heads * d)
        a = x + norm(attn @ f32(wo), n2)
        an = norm(a, n3)
        ffn = (jax.nn.silu(an @ f32(w_gate)) * (an @ f32(w_up))) \
            @ f32(w_down)
        return a + norm(ffn, n4)

    if remat:
        layer = jax.checkpoint(layer)
    h = f32(emb)[ids].reshape(B * S, H)
    ces, lams = [], []
    for _ in range(passes):
        x = h
        for w in layers:
            x = layer(x, w)
        h = norm(x, final_norm)
        ces.append(jax.lax.map(decode, (h.reshape(-1, rows, H),
                                        labels.reshape(-1, rows)))
                   .reshape(-1))
        lams.append(low(jax.nn.sigmoid(low(
            jnp.sum(h * f32(gate_w).reshape(1, -1), axis=-1)
            + f32(gate_b).reshape(())))))
    ce, lam = jnp.stack(ces), jnp.stack(lams)               # [R, T]
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    p = low(jnp.concatenate([lam[:-1] * before[:-1], before[-1:]], axis=0))
    expected = jnp.sum(p * ce, axis=0)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(
        jnp.where(p > 0, p, 1.0)), 0.0), axis=0)
    return {"loss": jnp.mean(expected - beta * entropy),
            "ce": jnp.mean(expected), "passes": ce, "exit_p": p,
            "each": jnp.concatenate([ce.reshape(-1), p.reshape(-1)])}


def loss(weights: list, batch: dict, model: dict, params: dict):
    """``weights``: the program's parameters in creation order, any dtype.
    ``forward``'s result (``loss``, ``each``, ...) in float32."""
    import jax
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, b: forward(w, b, model))(
            list(weights), dict(batch))
