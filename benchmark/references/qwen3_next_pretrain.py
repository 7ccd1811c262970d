"""Plain reference for the Qwen3-Next-80B-A3B pre-training loss as one chip's
share of it: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no framework op, no
chunk form (the delta rule runs **token by token**), no sort, no grouping
and no row budget (a row the program drops is missing from its layer's
routed output, which the check compares: ``tolerance``). Written from the
model's ``config.json`` (``model_type: qwen3_next``; the catalog's row) and
HF's ``modeling_qwen3_next.py`` (``torch_recurrent_gated_delta_rule``,
``Qwen3NextGatedDeltaNet``, ``Qwen3NextAttention``,
``Qwen3NextSparseMoeBlock``, ``Qwen3NextRMSNorm`` / ``RMSNormGated``); what
the config does not carry is the configuration file's ``assumed``.
Independent of ``paddle_tpu/`` except for the order in which parameters are
created, which is how weights are handed over. The causal softmax attention
in blocks of query rows, the rotate-half rotary embedding over a head's
leading share, and the held experts' dense loop are the Laguna reference's
plain functions (``references/laguna_pretrain.py``), which know nothing of
this model.

Tokens ``x [T, 2048]``, no biases, eps 1e-6, ``N(x; w) = x / sqrt(mean(x^2)
+ eps) * (1 + w)``. Layer i is ``full_attention`` where ``(i + 1) % 4 ==
0``, else ``linear_attention``; ``h = x + mixer(N(x))``, ``y = h +
moe(N(h))``.

1. Gated DeltaNet, 16 key and 32 value heads of 128, ``a = N(x)``: ``[q | k |
   v | z] = a W_in`` (2048, 2048, 4096, 4096), ``[b | alpha] = a W_ba`` (32,
   32); ``[q | k | v] <- silu(conv4([q | k | v]))``, causal and depthwise;
   per value head j (key head ``j // 2``): ``beta = sigmoid(b)``, ``g =
   -exp(A_log) softplus(alpha + dt_bias)``, ``qn = q / sqrt(sum(q^2) + 1e-6)
   / sqrt(128)``, ``kn = k / sqrt(sum(k^2) + 1e-6)``; the recurrence from a
   zero state: ``S' = exp(g_t) S``, ``u = beta_t (v_t - S'^T kn_t)``, ``S = S'
   + kn_t u^T``, ``o_t = S^T qn_t`` (``delta_rule``); ``W_out concat_j(o_j /
   sqrt(mean(o_j^2) + eps) * w_n * silu(z_j))``.
2. Gated attention, 16 query over 2 key/value heads of 256: ``[q | gate]`` a
   head from ``a W_q`` (a head's first 256, then its last 256); ``N`` over
   each head of q and k; rotary on a head's first 64 values at theta 1e7;
   causal softmax at 1/16; ``W_o (attn * sigmoid(gate))``.
3. Experts: ``p = softmax(m W_r)`` over all 512; the 10 largest over their
   sum; EVERY held expert applied to EVERY token and masked by the choice,
   plus ``sigmoid(m . w_s)`` times the shared expert.
4. Final ``N``, untied head over the held vocabulary slice, mean next-token
   cross-entropy. The config names no router loss.

Departures from the published model, each because the program under test
makes the same choice: the columns of ``W_in`` / ``W_ba`` are contiguous
(``q | k | v | z``, ``b | alpha``), a permutation of HF's interleave by key
head; the RMSNorm scale multiplies in float32 before the cast back; the
router weights stay float32 in the combine; every position has a label; the
vocabulary is the held slice.

Memory: it runs on the chip beside the training state, so attention runs
over blocks of query rows, the output head over blocks of ``HEAD_ROWS``
positions (``lax.map``), the experts one at a time (``lax.scan``), and the
recurrence carries ``[B, 32, 128, 128]`` float32 over the positions.
"""
from __future__ import annotations

from benchmark.references.laguna_pretrain import (  # noqa: F401
    HEAD_ROWS, _attention, _block, _rope, _swiglu, check_block,
    differing_share, expert_layer)


def tolerance(model: dict) -> dict:
    """``each``: |program - reference| <= tol * the reference's largest
    entry, over (a) the means of every position's cross-entropy over blocks
    of ``seq // 64`` consecutive positions (64 at S=4096; single positions
    in the tests), (b), a sparse layer each, the mean over the tokens of the
    norm of the held routed experts' output before the shared expert's is
    added, and (c), a DeltaNet layer each, the mean over the tokens of the
    norm of the delta rule's output ``o`` before the gated norm.

    (a) in block means and (b) for Laguna's reasons
    (``references/laguna_pretrain.py``): the program computes in bfloat16
    with a float32 router, the 10th and 11th largest of 512 probabilities
    lie closer than bfloat16 moves them, and 32 of 512 experts are held,
    so the cross-entropy barely sees the routed path. (c) because the norm
    after the scan divides by ``o``'s own size: a wrong query scale, a
    decay or a step left out change ``o`` by a factor that the gated norm
    removes again, and the cross-entropy at random weights sees little of
    what is left. ``o``'s mean norm reads about 0.5-1 and a layer's routed
    norm somewhat less where the cross-entropy reads 9.9, so (b) and (c)
    enter the same comparison unscaled, as Laguna's (b) does: an error of
    a tenth of ``o`` reads 5e-3 to 1e-2.

    The limit, from the two readings the contract asks for (PERF.md section
    2 and 6, PR 41; chip, published widths, 4 layers, 2 x 4096 tokens, seeded
    weights as the cell's check has them), ``READINGS``: the program as it
    is read 5.74e-4 to 9.01e-4 over 19 seeds (and 7.2e-4 on weights trained
    for 200 steps), float8 (e4m3) weights in the program's place 1.02e-2
    and 1.13e-2. The limit is 2.2e-3: 2.4 times the one, under a quarter
    of the other (their geometric mean is 3.0e-3; the lower limit also
    says no to the attention gate left out, below). What it sees beside
    float8, every verdict ``jobs/common.py:reference_check``'s own
    (``tools/qwen3_next_probe.py controls``, two seeds): the decay left out
    (``g = 0``) 7.2e-2 and 7.5e-2, ``beta = 1`` 2.4e-2 and 2.2e-2, the l2
    norm of q and k left out not a number (the triangular inverse of raw
    keys overflows), the shared expert's gate out 1.2e-2 and 1.1e-2, a
    tenth of the row budget (rows dropped) 3.3e-2 and 2.9e-2, the
    attention gate out 2.5e-3 and 2.9e-3 (narrowly: one of four layers, a
    factor of a half at seeded weights). What it cannot see: the rotary
    embedding over the whole head (1.5e-3 and 1.6e-3: at seeded weights the
    scores are near zero and the softmax near uniform whatever the
    positions; the CPU tests sharpen q and k and see it) and a bfloat16
    state in the reference's recurrence (7.6e-4 and 7.1e-4, inside the
    sound runs' range; the op's CPU tests see it at 1e-4 of the float32
    recurrence). ``loss`` has no limit here for Laguna's reason (single
    positions' errors cancel in the mean: 1.9e-6 to 4.1e-5 as it is, float8
    1.5e-4 and 4.4e-4; a loss that is not a number still fails ``each``)."""
    return {"loss": float("inf"), "each": EACH_LIMIT}


# The two readings (my chip runs, PR 41; PERF.md section 6): the program as
# it is, the largest over the seeds run, and float8 (e4m3) weights in the
# program's place, the smallest over its seeds. The limit lies between them
# with room on both sides.
READINGS = {"as_it_is_max": 9.011e-4, "float8_min": 1.016e-2}
EACH_LIMIT = 2.2e-3


def _norm(x, w, eps):
    """``Qwen3NextRMSNorm``: the scale is ``1 + w``."""
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def causal_conv(x, w, seq: int):
    """``x [T, C]`` in sequences of ``seq`` rows, ``w [C, L]``: ``y[t] = sum_j
    w[:, j] x[t - (L - 1) + j]``, zeros before a sequence's start."""
    import jax.numpy as jnp
    taps = w.shape[1]
    xs = x.reshape(-1, seq, x.shape[-1])
    padded = jnp.pad(xs, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + seq] * w[:, j] for j in range(taps))
    return y.reshape(x.shape)


def delta_rule(q, k, v, g, beta, state_dtype=None):
    """The gated delta rule position by position (HF's
    ``torch_recurrent_gated_delta_rule``): ``q`` / ``k [B, S, key heads,
    d_k]`` (raw: the l2 norms and the query scale are applied here), ``v [B,
    S, heads, d_v]``, ``g`` / ``beta [B, S, heads]`` -> ``o`` like ``v``.
    ``state_dtype``: round the carried state to it after every position (a
    control: what a coarser state would read)."""
    import jax
    import jax.numpy as jnp
    heads, dk = v.shape[2], q.shape[-1]
    rep = heads // q.shape[2]

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + 1e-6)
    qn = jnp.repeat(unit(q) / dk ** 0.5, rep, axis=2)
    kn = jnp.repeat(unit(k), rep, axis=2)

    def step(state, inp):                   # state [B, heads, d_k, d_v]
        q_t, k_t, v_t, g_t, b_t = inp
        state = state.astype(jnp.float32) * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", state, q_t)
        return state.astype(state_dtype or jnp.float32), o

    first = jnp.zeros((v.shape[0], heads, dk, v.shape[-1]),
                      state_dtype or jnp.float32)
    _, o = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(t, 1, 0) for t in (qn, kn, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def delta_net(a, w, model: dict, B: int, S: int):
    """The DeltaNet mixer over normed tokens ``a [T, H]``; ``w`` = (W_in,
    W_ba, filter, dt_bias, A_log, w_n, W_out). Returns the mixer's output
    and the delta rule's ``o [T, heads * d_v]`` before the gated norm."""
    import jax
    import jax.numpy as jnp
    w_in, w_ba, conv_w, dt_bias, a_log, w_n, w_out = w
    n_k, d_k = model["linear_num_key_heads"], model["linear_key_head_dim"]
    n_v, d_v = model["linear_num_value_heads"], model["linear_value_head_dim"]
    keys, values = n_k * d_k, n_v * d_v
    qkvz, ba = a @ w_in, a @ w_ba
    qkv = jax.nn.silu(causal_conv(qkvz[:, :2 * keys + values], conv_w, S))
    z = qkvz[:, 2 * keys + values:].reshape(B * S, n_v, d_v)
    q = qkv[:, :keys].reshape(B, S, n_k, d_k)
    k = qkv[:, keys:2 * keys].reshape(B, S, n_k, d_k)
    v = qkv[:, 2 * keys:].reshape(B, S, n_v, d_v)
    beta = jax.nn.sigmoid(ba[:, :n_v]).reshape(B, S, n_v)
    g = (-jnp.exp(a_log) * jax.nn.softplus(ba[:, n_v:] + dt_bias)).reshape(
        B, S, n_v)
    o = delta_rule(q, k, v, g, beta).reshape(B * S, n_v, d_v)
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + model["rms_norm_eps"]) * w_n * jax.nn.silu(z)
    return y.reshape(B * S, values) @ w_out, o.reshape(B * S, values)


def gated_attention(a, w, model: dict, B: int, S: int):
    """``Qwen3NextAttention`` over normed tokens ``a``; ``w`` = (W_q, W_k,
    W_v, w_q, w_k, W_o)."""
    import jax
    wq, wk, wv, q_norm, k_norm, wo = w
    heads, kv, d = (model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    eps = model["rms_norm_eps"]
    rope = {"rope_theta": model["rope_theta"],
            "partial_rotary_factor": model.get("partial_rotary_factor", 1)}
    qg = (a @ wq).reshape(B, S, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(B * S, heads * d)
    k = (a @ wk).reshape(B, S, kv, d)
    v = (a @ wv).reshape(B, S, kv, d)
    sh = lambda t: t.transpose(0, 2, 1, 3)                  # noqa: E731
    attn = _attention(_rope(sh(_norm(q, q_norm, eps)), rope),
                      _rope(sh(_norm(k, k_norm, eps)), rope), sh(v))
    attn = attn.transpose(0, 2, 1, 3).reshape(B * S, heads * d)
    return (attn * jax.nn.sigmoid(gate)) @ wo


def forward(weights: list, batch: dict, model: dict, chosen=None,
            held=None) -> dict:
    """The pure function: ``weights`` are float32 arrays in the program's
    creation order. Returns ``loss``, ``positions`` (every position's
    cross-entropy), ``routed`` (a sparse layer each: the mean over the
    tokens of the norm of the held routed experts' output), ``delta`` (a
    DeltaNet layer each: the mean over the tokens of the norm of the delta
    rule's output), ``each`` (the cross-entropy's means over blocks of
    ``check_block(seq)``, then ``routed``, then ``delta``), ``experts``
    ``[layers, tokens, k]`` sorted by expert and ``load`` ``[layers, experts
    routed]``. ``chosen [layers, tokens, k]`` and ``held``: Laguna's
    ``expert_layer``'s, a layer each."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    it = iter(weights)
    take = lambda n: [next(it) for _ in range(n)]           # noqa: E731
    (emb,) = take(1)
    ids = batch["ids"]
    B, S = ids.shape
    H = emb.shape[1]
    x = emb[ids].reshape(B * S, H)
    experts, loads, routed, delta = [], [], [], []
    for i, kind in enumerate(model["layer_types"]):
        (norm,) = take(1)
        a = _norm(x, norm, eps)
        if kind == "linear_attention":
            mixed, o = delta_net(a, take(7), model, B, S)
            delta.append(jnp.mean(jnp.linalg.norm(o, axis=-1)))
        else:
            mixed = gated_attention(a, take(6), model, B, S)
        h = x + mixed
        (ffn_norm,) = take(1)
        m = _norm(h, ffn_norm, eps)
        w_router, w_gate, w_up, w_down = take(4)
        moe, top_i, load = expert_layer(
            m, w_router, w_gate, w_up, w_down, model, held=held,
            chosen=None if chosen is None else chosen[i])
        s_gate, s_up, s_down, s_w = take(4)
        x = h + moe + jax.nn.sigmoid(m @ s_w) * _swiglu(m, s_gate, s_up,
                                                        s_down)
        experts.append(jnp.sort(top_i, axis=-1))
        loads.append(load)
        routed.append(jnp.mean(jnp.linalg.norm(moe, axis=-1)))

    final_norm, head = take(2)
    xn = _norm(x, final_norm, eps)
    labels = batch["labels"].reshape(-1)
    rows = _block(B * S, HEAD_ROWS)

    def decode(arg):
        hb, lb = arg
        logp = jax.nn.log_softmax(hb @ head, axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=1)[:, 0]

    each = jax.lax.map(decode, (xn.reshape(-1, rows, H),
                                labels.reshape(-1, rows))).reshape(-1)
    blocks = jnp.mean(each.reshape(-1, check_block(S)), axis=1)
    routed, delta = jnp.stack(routed), jnp.stack(delta)
    return {"loss": jnp.mean(each), "positions": each, "routed": routed,
            "delta": delta,
            "each": jnp.concatenate([blocks, routed, delta]),
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
