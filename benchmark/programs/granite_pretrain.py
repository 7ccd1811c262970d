"""Granite 4.0-H pre-training (next-token prediction over the held vocabulary
slice) through ``models/decoder_lm.py``: Mamba-2 layers around a position-free
attention layer, tied embedding, the four Granite multipliers, bf16
activations, AdamW, static shapes -- the program a user of the framework
writes.

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
        # a chip run that could not lower the scan's kernels fails at its
        # compile: it never measures the composed form in silence
        model = dict(model, ssd_scan_impl="pallas")
    batch, seq = params["batch"], params["seq"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0        # the run's seed enters as data:
    startup.random_seed = 0     # probe.seed_programs
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [batch, seq], "int64", **A)
        labels = fluid.data("labels", [batch * seq, 1], "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        # no backward, no optimizer: what the reference equals
        test = main.clone(for_test=True)
        fluid.optimizer.AdamW(
            model["learning_rate"], weight_decay=model["weight_decay"],
            beta1=model["adam_beta1"], beta2=model["adam_beta2"],
            epsilon=model["adam_epsilon"]).minimize(out["loss"])
    return {"main": main, "startup": startup, "test": test,
            "loss": out["loss"],
            # the reference is compared on the loss and on every position's
            # cross-entropy (references/granite_pretrain.py says why single
            # positions carry it)
            "check": {"loss": [out["loss"].name], "each": [out["each"].name]},
            # what the reference is handed: the parameters in creation order
            "params": [p.name for p in main.global_block().all_parameters()],
            "units_per_step": batch * seq, "unit": "tokens"}


def batch(model: dict, params: dict, rng: np.random.RandomState) -> dict:
    """One host batch: uniformly random tokens from the held slice of the
    vocabulary, no padding; the label of a position is the token that
    follows it (``seq + 1`` tokens are drawn)."""
    b, s = params["batch"], params["seq"]
    tokens = rng.randint(0, model["vocab_size"], (b, s + 1)).astype(np.int32)
    return {"ids": np.ascontiguousarray(tokens[:, :-1]),
            "labels": np.ascontiguousarray(tokens[:, 1:]).reshape(-1, 1)}
