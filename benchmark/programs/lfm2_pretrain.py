"""LFM2 pre-training (next-token prediction over the held vocabulary slice)
through ``models/decoder_lm.py``, as one chip's share of each layer: bf16
activations, AdamW, the router's selection bias updated from the step's
load, static shapes -- the program a user of the framework writes.

``model`` holds the published ``config.json`` keys plus the deployment's and
the recipe's (``assumed`` in the configuration file); ``params`` the job
(batch, seq).
"""
from __future__ import annotations

import numpy as np


def build(model: dict, params: dict) -> dict:
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_lm
    from benchmark.references.lfm2_pretrain import check_block

    batch, seq = params["batch"], params["seq"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0        # the run's seed enters as data:
    startup.random_seed = 0     # probe.seed_programs
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [batch, seq], "int64", **A)
        labels = fluid.data("labels", [batch * seq, 1], "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        # what the reference is compared on beside the loss: every
        # position's cross-entropy, averaged over blocks of consecutive
        # positions (references/lfm2_pretrain.py says why)
        block = check_block(seq)
        blocks = fluid.layers.reduce_mean(fluid.layers.reshape(
            out["each"], [batch * seq // block, block]), dim=1)
        # no backward, no optimizer, no bias update: what the reference
        # equals
        test = main.clone(for_test=True)
        fluid.optimizer.AdamW(
            model["learning_rate"], weight_decay=model["weight_decay"],
            beta1=model["adam_beta1"], beta2=model["adam_beta2"],
            epsilon=model["adam_epsilon"]).minimize(out["loss"])
        decoder_lm.balance_experts(out, model["bias_update_rate"])
    bias = [v.name for v in out["expert_bias"]]
    return {"main": main, "startup": startup, "test": test,
            "loss": out["loss"],
            "check": {"loss": [out["loss"].name], "each": [blocks.name]},
            "positions": out["each"].name,
            # what the reference is handed: the parameters in creation
            # order, then the selection biases (state no optimizer owns)
            "params": [p.name for p in
                       main.global_block().all_parameters()] + bias,
            # not read by the jobs: the router's variables, for whoever
            # fetches them beside the loss (tests, the builder's chip run)
            "expert_bias": bias,
            "expert_load": [v.name for v in out["expert_load"]],
            "expert_index": [v.name for v in out["expert_index"]],
            "units_per_step": batch * seq, "unit": "tokens"}


def batch(model: dict, params: dict, rng: np.random.RandomState) -> dict:
    """One host batch: uniformly random tokens from the held slice of the
    vocabulary, no padding; the label of a position is the token that
    follows it (``seq + 1`` tokens are drawn)."""
    b, s = params["batch"], params["seq"]
    tokens = rng.randint(0, model["vocab_size"], (b, s + 1)).astype(np.int32)
    return {"ids": np.ascontiguousarray(tokens[:, :-1]),
            "labels": np.ascontiguousarray(tokens[:, 1:]).reshape(-1, 1)}
