"""DeepFM click-through model (Guo et al. 2017) on the Criteo display-ads
layout through ``models/deepfm.py``, float32 and Adam, static shapes -- as
``bench_workloads.py:bench_deepfm_e2e`` builds it.

``rows`` is the traffic generator of the dataset-fed job: the columns of
``n`` examples, ids Zipf-skewed over the hash space.
"""
from __future__ import annotations

import numpy as np

def build(model: dict, params: dict) -> dict:
    import paddle_tpu as fluid
    from paddle_tpu.models import deepfm

    batch = params["batch"]
    fields, n_dense = model["categorical_fields"], model["dense_fields"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0        # the run's seed enters as data:
    startup.random_seed = 0     # probe.seed_programs
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [batch, fields], "int64", **A)
        dense = fluid.data("dense", [batch, n_dense], "float32", **A)
        label = fluid.data("label", [batch, 1], "int64", **A)
        loss, _, _ = deepfm.deepfm(
            ids, dense, label, num_fields=fields,
            vocab_size=model["hash_size"], embed_dim=model["embedding_dim"],
            hidden=tuple(model["mlp_hidden"]))
        test = main.clone(for_test=True)
        fluid.optimizer.Adam(model["learning_rate"]).minimize(loss)
    each = [op.outputs["Out"][0] for op in test.global_block().ops
            if op.type == "sigmoid_cross_entropy_with_logits"]
    return {"main": main, "startup": startup, "test": test, "loss": loss,
            "check": {"loss": [loss.name], "each": each},
            "params": [p.name for p in main.global_block().all_parameters()],
            "feed_vars": [ids, dense, label],
            "units_per_step": batch, "unit": "examples"}


def rows(model: dict, params: dict, rng: np.random.RandomState,
         n: int) -> dict:
    """``n`` examples as columns. Ids: per field, a rank drawn from a Zipf
    law (exponent ``zipf_exponent``, truncated at the hash size, by inverting
    the continuous power law's CDF) and scattered over the table by a
    per-field odd multiplier, so hot ids are spread through the rows as
    hashing spreads them. Dense: uniform in [0, 1) with four decimals, as the
    text format carries them. Labels: a logistic model of the dense features,
    so the loss can fall."""
    fields, n_dense = model["categorical_fields"], model["dense_fields"]
    vocab, a = model["hash_size"], params["zipf_exponent"]
    u = rng.rand(n, fields)
    # P(rank <= r) ~ (r^(1-a) - 1) / (V^(1-a) - 1) on [1, V]
    rank = (1.0 + u * (float(vocab) ** (1.0 - a) - 1.0)) ** (1.0 / (1.0 - a))
    rank = np.minimum(rank.astype(np.int64), vocab) - 1
    mult = 2 * rng.randint(1, vocab // 2, fields).astype(np.int64) + 1
    shift = rng.randint(0, vocab, fields).astype(np.int64)
    ids = (rank * mult + shift) % vocab
    dense = rng.randint(0, 10000, (n, n_dense)) / 10000.0
    w = rng.randn(n_dense)
    p = 1.0 / (1.0 + np.exp(-4.0 * (dense - 0.5) @ w / np.sqrt(n_dense)))
    label = (rng.rand(n) < p).astype(np.int64)
    return {"ids": ids.astype(np.int32), "dense": dense.astype(np.float32),
            "label": label.reshape(n, 1).astype(np.int32)}


def batch(model: dict, params: dict, rng: np.random.RandomState) -> dict:
    """One host batch (the reference check's, and a feed-fed job's)."""
    return rows(model, params, rng, params["batch"])
