"""Plain reference for the DeepFM loss: ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from Guo et al. 2017
(arXiv:1703.04247) eq. 1-4: y = sigmoid(y_FM + y_DNN), y_FM = <w, x> + the
pairwise <v_i, v_j> x_i x_j terms through the (sum^2 - sum of squares) / 2
identity, the DNN over the concatenated field embeddings.

Departures, each shared with the program under test: the dense features are
concatenated to the DNN's input as they are (the paper discretises them into
fields), and there is no global bias term.
"""
from __future__ import annotations

def tolerance(model: dict) -> dict:
    """``loss``: |program - reference| <= tol * |reference| on the mean.
    ``each``: the same on every example's loss, relative to the largest.
    The program computes in float32. Measured on the chip (PERF.md section
    6, PR 22): the worst example is off by 4e-6 to 6e-6, the mean by under
    1e-7, so the tolerances sit about ten times above that. Weights rounded
    to float8 miss ``each`` by two orders of magnitude (shown in
    tests/benchmark/test_benchmark_reference.py)."""
    return {"loss": 5e-6, "each": 5e-5}


def loss(weights: list, batch: dict, model: dict, params: dict):
    """``weights``: the program's parameters in creation order."""
    import jax
    import jax.numpy as jnp

    n_hidden = len(model["mlp_hidden"])

    def f(weights, batch):
        it = iter([jnp.asarray(w, jnp.float32) for w in weights])
        w1, v = next(it), next(it)                   # [V, 1], [V, E]
        mlp = [(next(it), next(it)) for _ in range(n_hidden)]
        out_w, out_b = next(it), next(it)
        ids = batch["ids"]                           # [B, F]
        emb = v[ids]                                 # [B, F, E]
        first = jnp.sum(w1[ids][..., 0], axis=1, keepdims=True)
        second = 0.5 * jnp.sum(jnp.square(jnp.sum(emb, axis=1))
                               - jnp.sum(jnp.square(emb), axis=1),
                               axis=1, keepdims=True)
        h = jnp.concatenate([emb.reshape(ids.shape[0], -1),
                             batch["dense"].astype(jnp.float32)], axis=1)
        for w, b in mlp:
            h = jax.nn.relu(h @ w + b)
        logit = first + second + h @ out_w + out_b
        y = batch["label"].astype(jnp.float32)
        each = (jnp.maximum(logit, 0.0) - logit * y
                + jnp.log1p(jnp.exp(-jnp.abs(logit)))).reshape(-1)
        return {"loss": jnp.mean(each), "each": each}

    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(list(weights), dict(batch))
