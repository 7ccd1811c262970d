"""GLM-4.7-Flash pre-training (next-token prediction and the
multi-token-prediction module's second loss, both over the held vocabulary
slice) through ``models/decoder_lm.py``, as one chip's share of the leading
dense layer, the four expert layers after it and the prediction module:
latent attention in every block (a 768-wide query latent, a 512-wide
key/value latent, one rotary key head shared by 20 heads of 256), the held
experts of a bias-chosen sigmoid router under a stated row budget beside a
shared expert, the embedding table read at two places and the head applied
twice, bf16 activations, AdamW, the bias update, static shapes -- the program
a user of the framework writes.

``model`` holds the published ``config.json`` keys plus the deployment's and
the recipe's (``assumed`` in the configuration file); ``params`` the job
(batch, seq).
"""
from __future__ import annotations

import numpy as np


def build(model: dict, params: dict) -> dict:
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_lm
    from benchmark.references.glm_4_7_flash_pretrain import check_block

    batch, seq = params["batch"], params["seq"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0        # the run's seed enters as data:
    startup.random_seed = 0     # probe.seed_programs
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [batch, seq], "int64", **A)
        labels = fluid.data("labels", [batch * seq, 1], "int64", **A)
        labels_next = fluid.data("labels_next", [batch * seq, 1], "int64",
                                 **A)
        out = decoder_lm.build(model, ids, labels, labels_next)
        # what the reference is compared on beside the loss
        # (references/glm_4_7_flash_pretrain.py:tolerance says why): the
        # trunk's cross-entropy averaged over blocks of consecutive
        # positions, the module's mean cross-entropy and its block means,
        block = check_block(seq)

        def blocks(each):
            return fluid.layers.reduce_mean(fluid.layers.reshape(
                each, [batch * seq // block, block]), dim=1)
        # and, a sparse layer each (the module's last), the norm of the
        # routed experts' output before the shared expert's is added, summed
        # over the tokens and divided by the sum of sqrt(c), c the number of
        # a token's chosen experts that are held here (the experts' outputs
        # are near orthogonal, so a token's norm goes as sqrt(c)): the
        # router's scale, the row budget and a dropped row show there and
        # hardly in the loss (8 of 64 held). In this form because a 4th /
        # 5th expert that flips under bfloat16 between one held here and one
        # held elsewhere moves both sums alike, whatever c was
        first, held = model.get("first_expert_held", 0), model[
            "n_routed_experts"]
        one = fluid.layers.fill_constant([1], "float32", 1.0)

        def held_norm(routed, index):
            norm = fluid.layers.sqrt(fluid.layers.reduce_sum(
                fluid.layers.square(fluid.layers.cast(routed, "float32")),
                dim=1))
            index = fluid.layers.cast(index, "float32")
            here = fluid.layers.cast(fluid.layers.logical_and(
                fluid.layers.greater_than(index, one * (first - 0.5)),
                fluid.layers.less_than(index, one * (first + held - 0.5))),
                "float32")
            weight = fluid.layers.reduce_sum(fluid.layers.sqrt(
                fluid.layers.reduce_sum(here, dim=1)))
            return fluid.layers.reduce_sum(norm) / fluid.layers.elementwise_max(
                weight, one)
        norms = [held_norm(r, index) for r, index in
                 zip(out["expert_routed"], out["expert_index"])]
        each = [blocks(out["each"]), out["mtp_ce"], blocks(out["mtp_each"])]
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
            "check": {"loss": [out["loss"].name],
                      "each": [v.name for v in each + norms]},
            "positions": out["each"].name,
            "mtp_positions": out["mtp_each"].name,
            # what the reference is handed: the parameters in creation
            # order, then the selection biases (state no optimizer owns)
            "params": [p.name for p in
                       main.global_block().all_parameters()] + bias,
            # not read by the jobs: the router's variables, for whoever
            # fetches them beside the loss (tests, tools/glm_probe.py)
            "expert_bias": bias,
            "expert_load": [v.name for v in out["expert_load"]],
            "expert_index": [v.name for v in out["expert_index"]],
            "expert_dropped": [v.name for v in out["expert_dropped"]],
            "units_per_step": batch * seq, "unit": "tokens"}


def batch(model: dict, params: dict, rng: np.random.RandomState) -> dict:
    """One host batch: uniformly random tokens from the held slice of the
    vocabulary, no padding; a position's two labels are the token that
    follows it and the one after (``seq + 2`` tokens are drawn)."""
    b, s = params["batch"], params["seq"]
    tokens = rng.randint(0, model["vocab_size"], (b, s + 2)).astype(np.int32)
    return {"ids": np.ascontiguousarray(tokens[:, :-2]),
            "labels": np.ascontiguousarray(tokens[:, 1:-1]).reshape(-1, 1),
            "labels_next": np.ascontiguousarray(tokens[:, 2:]).reshape(-1, 1)}
