"""Xing4.0-29B-A4B pre-training (next-token prediction over the held
vocabulary slice) through ``models/decoder_lm.py``, as one chip's share of
one leading dense layer and the four expert layers after it: a residual
state of four streams a token, read, written and mixed by
manifold-constrained hyper-connections around every sub-layer (two ops a
sub-layer, ``hyper_connection_pre`` / ``_post``), latent attention under
YaRN in every block (a 768-wide query latent, a 512-wide key/value latent,
one rotary key head shared by 32 heads of 128 + 64, values of 128), the held
experts of a bias-chosen sigmoid router under a stated row budget beside a
shared expert, bf16 activations, AdamW, the bias update, static shapes --
the program a user of the framework writes.

``model`` holds the published ``config.json`` keys plus the deployment's and
the recipe's (``assumed`` in the configuration file); ``params`` the job
(batch, seq).
"""
from __future__ import annotations

import numpy as np


def build(model: dict, params: dict) -> dict:
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_lm
    from benchmark.references.xing4_0_pretrain import check_block

    batch, seq = params["batch"], params["seq"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0        # the run's seed enters as data:
    startup.random_seed = 0     # probe.seed_programs
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [batch, seq], "int64", **A)
        labels = fluid.data("labels", [batch * seq, 1], "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        L = fluid.layers
        # what the reference is compared on beside the loss
        # (references/xing4_0_pretrain.py:tolerance says why): the
        # cross-entropy averaged over blocks of consecutive positions,
        block = check_block(seq)
        each = [L.reduce_mean(L.reshape(
            out["each"], [batch * seq // block, block]), dim=1)]
        # a sparse layer each, the held routed experts' norm in the
        # GLM-4.7-Flash program's form (programs/glm_4_7_flash_pretrain.py
        # says why: over the sum of sqrt(c), c a token's chosen experts held
        # here, a choice that flips under bfloat16 moves both sums alike),
        first, held = model.get("first_expert_held", 0), model[
            "n_routed_experts"]
        one = L.fill_constant([1], "float32", 1.0)

        def held_norm(routed, index):
            norm = L.sqrt(L.reduce_sum(
                L.square(L.cast(routed, "float32")), dim=1))
            index = L.cast(index, "float32")
            here = L.cast(L.logical_and(
                L.greater_than(index, one * (first - 0.5)),
                L.less_than(index, one * (first + held - 0.5))), "float32")
            weight = L.reduce_sum(L.sqrt(L.reduce_sum(here, dim=1)))
            return L.reduce_sum(norm) / L.elementwise_max(weight, one)
        each += [held_norm(r, index) for r, index in
                 zip(out["expert_routed"], out["expert_index"])]
        # and, a block each, the root mean square of each stream of its
        # output state: H_res, H_post and the collapse show there
        n = model["hc_mult"]
        each += [L.sqrt(L.reduce_mean(L.square(L.cast(L.reshape(
            state, [batch * seq, n, model["hidden_size"]]), "float32")),
            dim=[0, 2])) for state in out["stream_states"]]
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
                      "each": [v.name for v in each]},
            "positions": out["each"].name,
            # what the reference is handed: the parameters in creation
            # order, then the selection biases (state no optimizer owns)
            "params": [p.name for p in
                       main.global_block().all_parameters()] + bias,
            # not read by the jobs: the router's variables and the states,
            # for whoever fetches them beside the loss (tests,
            # tools/xing4_0_probe.py)
            "expert_bias": bias,
            "expert_load": [v.name for v in out["expert_load"]],
            "expert_index": [v.name for v in out["expert_index"]],
            "expert_dropped": [v.name for v in out["expert_dropped"]],
            "stream_states": [v.name for v in out["stream_states"]],
            "units_per_step": batch * seq, "unit": "tokens"}


def batch(model: dict, params: dict, rng: np.random.RandomState) -> dict:
    """One host batch: uniformly random tokens from the held slice of the
    vocabulary, no padding, one document a sequence; a position's label is
    the token that follows it (``seq + 1`` tokens are drawn)."""
    b, s = params["batch"], params["seq"]
    tokens = rng.randint(0, model["vocab_size"], (b, s + 1)).astype(np.int32)
    return {"ids": np.ascontiguousarray(tokens[:, :-1]),
            "labels": np.ascontiguousarray(tokens[:, 1:]).reshape(-1, 1)}
