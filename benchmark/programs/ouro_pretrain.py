"""Ouro (LoopLM) pre-training through ``models/decoder_lm.py``: a stack of
layers run ``total_ut_steps`` times a step on weights that exist once (one
``scan`` op, ``layers.Scan(steps=...)``), sandwich norms, one head and one
exit gate over the passes' states, the expected loss over the exit
distribution; bf16 activations,
AdamW, recomputation by layer (``RecomputeOptimizer`` with every layer's
output inside the loop a checkpoint), static shapes -- the program a user of
the framework writes.

``model`` holds the published ``config.json`` keys plus the recipe's
(``assumed`` in the configuration file); ``params`` the job (batch, seq).
"""
from __future__ import annotations

import numpy as np


def build(model: dict, params: dict) -> dict:
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_lm
    from paddle_tpu.ops import pallas_mode

    if pallas_mode.on_tpu():
        # a chip run that could not lower the flash kernels inside the
        # loop's sub-block (forward, recomputed forward, backward) fails at
        # its compile: it never measures the composed form in silence
        model = dict(model, attention_impl="pallas")
    batch, seq = params["batch"], params["seq"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0        # the run's seed enters as data:
    startup.random_seed = 0     # probe.seed_programs
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [batch, seq], "int64", **A)
        labels = fluid.data("labels", [batch * seq, 1], "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        # no backward, no optimizer, nothing kept or recomputed: what the
        # reference equals
        test = main.clone(for_test=True)
        optimizer = fluid.optimizer.AdamW(
            model["learning_rate"], weight_decay=model["weight_decay"],
            beta1=model["adam_beta1"], beta2=model["adam_beta2"],
            epsilon=model["adam_epsilon"])
        if model.get("recompute", "none") == "layer":
            optimizer = fluid.optimizer.RecomputeOptimizer(
                optimizer)._set_checkpoints(out["loop_checkpoints"])
        optimizer.minimize(out["loss"])
    return {"main": main, "startup": startup, "test": test,
            "loss": out["loss"],
            # the reference is compared on the loss, and on every pass's
            # cross-entropy of every position followed by every position's
            # exit probabilities (references/ouro_pretrain.py)
            "check": {"loss": [out["loss"].name],
                      "each": [out["each"].name, out["exit_p"].name]},
            # what the reference is handed: the parameters in creation order
            "params": [p.name for p in main.global_block().all_parameters()],
            "units_per_step": batch * seq, "unit": "tokens"}


def batch(model: dict, params: dict, rng: np.random.RandomState) -> dict:
    """One host batch: uniformly random tokens over the vocabulary, no
    padding; the label of a position is the token that follows it (``seq +
    1`` tokens are drawn)."""
    b, s = params["batch"], params["seq"]
    tokens = rng.randint(0, model["vocab_size"], (b, s + 1)).astype(np.int32)
    return {"ids": np.ascontiguousarray(tokens[:, :-1]),
            "labels": np.ascontiguousarray(tokens[:, 1:]).reshape(-1, 1)}
