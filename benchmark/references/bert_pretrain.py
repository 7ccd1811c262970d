"""Plain reference for the BERT pre-training loss: straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
no kernels, no framework op. Written from Devlin et al. 2018 (arXiv:1810.04805)
and google-research/bert ``modeling.py`` / ``run_pretraining.py``; independent
of ``paddle_tpu/models/bert.py`` except for the order in which parameters are
created, which is how weights are handed over.

Departures from the published model, each because the program under test
makes the same choice (a reference that differed there would measure the
choice, not the precision):

- layer-norm epsilon 1e-5 (published: 1e-12), the framework's default;
- the masked-LM decoder is the transposed word embedding plus an output bias
  (published too), the tanh form of GELU (what ``modeling.py`` computes);
- dropout is off: the comparison is made on the test-mode clone.

Memory: the encoder runs over chunks of about ``CHUNK_TOKENS`` tokens and the
decoder over chunks of ``CHUNK_MASKED`` masked positions (``lax.map``), so the
check never becomes the process's peak.
"""
from __future__ import annotations

import math

def tolerance(model: dict) -> dict:
    """``loss``: |program - reference| <= tol * |reference| on the mean.
    ``each``: the same on the loss of every masked position and of every
    sequence, relative to the largest of them.

    The program computes in bfloat16 (8 bits of mantissa: one rounding is
    2^-9, 0.2% relative) with float32 accumulation, layer norm and softmax.
    Every layer adds its roundings to the residual stream, so a position's
    error grows with depth: ``each`` is 0.35 x layers x 2^-9. Measured on the
    chip at 12 layers (PERF.md section 6, PR 22): worst position 1.6e-3 to
    1.9e-3 over the seeds, against a tolerance of 8.2e-3; on the CPU at 2
    layers 2e-4 to 4e-4 against 1.4e-3. In the mean over thousands of
    positions the errors cancel (measured 1e-6 to 2e-5 on the chip), so the
    mean alone would pass a far lower precision -- hence both. float8 (e4m3,
    3 bits of mantissa) weights fail ``each``: shown on the small model in
    tests/benchmark/test_benchmark_reference.py."""
    return {"loss": 2e-4,
            "each": 0.35 * model["num_hidden_layers"] * 2.0 ** -9}


CHUNK_TOKENS = 4096
CHUNK_MASKED = 2560


def _layer_norm(x, w, b, eps=1e-5):
    import jax
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _gelu(x):
    import jax.numpy as jnp
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _chunks(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is at most ``target`` (at least 1)."""
    c = max(1, min(n, target))
    while n % c:
        c -= 1
    return c


def loss(weights: list, batch: dict, model: dict, params: dict):
    """``weights``: the program's parameters in creation order, any dtype.
    Returns ``{"loss": masked-LM mean + next-sentence mean, "each": the loss
    of every masked position, then of every sequence}`` in float32."""
    import jax
    import jax.numpy as jnp

    heads = model["num_attention_heads"]
    n_layers = model["num_hidden_layers"]

    def f(weights, batch):
        it = iter([jnp.asarray(w, jnp.float32) for w in weights])
        take = lambda n=1: [next(it) for _ in range(n)]  # noqa: E731
        word, pos, sent, ln_w, ln_b = take(5)
        layers = [take(12) for _ in range(n_layers)]
        (trans_w, trans_b, hln_w, hln_b, out_bias,
         pool_w, pool_b, nsp_w, nsp_b) = take(9)
        B, S = batch["src_ids"].shape
        H = word.shape[1]
        d = H // heads

        def encode(ids):
            src, p, t, m = ids                          # [b, S] each
            x = word[src] + pos[p] + sent[t]
            x = _layer_norm(x, ln_w, ln_b)
            bias = ((m - 1.0) * 1e4)[:, None, None, :]  # [b,1,1,S]
            for (qkv_w, qkv_b, o_w, o_b, l1w, l1b,
                 f1w, f1b, f2w, f2b, l2w, l2b) in layers:
                q, k, v = jnp.split(x @ qkv_w + qkv_b, 3, axis=-1)
                sh = lambda t: t.reshape(t.shape[0], S, heads, d) \
                    .transpose(0, 2, 1, 3)              # noqa: E731
                s = sh(q) @ sh(k).transpose(0, 1, 3, 2) / math.sqrt(d) + bias
                a = jax.nn.softmax(s, axis=-1) @ sh(v)  # [b,h,S,d]
                a = a.transpose(0, 2, 1, 3).reshape(-1, S, H)
                x = _layer_norm(x + a @ o_w + o_b, l1w, l1b)
                x = _layer_norm(x + _gelu(x @ f1w + f1b) @ f2w + f2b,
                                l2w, l2b)
            return x

        cb = _chunks(B, max(1, CHUNK_TOKENS // S))
        ids = [batch[k].reshape(B // cb, cb, S) for k in
               ("src_ids", "pos_ids", "sent_ids")]
        ids.append(batch["input_mask"].astype(jnp.float32)
                   .reshape(B // cb, cb, S))
        enc = jax.lax.map(encode, tuple(ids)).reshape(B, S, H)

        mpos = batch["mask_pos"].reshape(-1)
        mlabel = batch["mask_label"].reshape(-1)
        cm = _chunks(mpos.shape[0], CHUNK_MASKED)

        def decode(arg):
            h, label = arg
            h = _layer_norm(_gelu(h @ trans_w + trans_b), hln_w, hln_b)
            logp = jax.nn.log_softmax(h @ word.T + out_bias, axis=-1)
            return -jnp.take_along_axis(logp, label[:, None], axis=1)[:, 0]

        masked = enc.reshape(B * S, H)[mpos]
        mlm = jax.lax.map(decode, (masked.reshape(-1, cm, H),
                                   mlabel.reshape(-1, cm)))
        pooled = jnp.tanh(enc[:, 0] @ pool_w + pool_b)
        nsp_logp = jax.nn.log_softmax(pooled @ nsp_w + nsp_b, axis=-1)
        nsp = -jnp.take_along_axis(
            nsp_logp, batch["nsp_label"].reshape(-1, 1), axis=1)
        mlm, nsp = mlm.reshape(-1), nsp.reshape(-1)
        return {"loss": jnp.mean(mlm) + jnp.mean(nsp),
                "each": jnp.concatenate([mlm, nsp])}

    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(list(weights), dict(batch))
