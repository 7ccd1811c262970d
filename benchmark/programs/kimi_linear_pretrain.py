"""Kimi-Linear-48B-A3B pre-training (next-token prediction over the held
vocabulary slice) through ``models/decoder_lm.py``, as one chip's share of
the leading dense layer and one period of four sparse layers: four Kimi
Delta Attention layers (a gated delta rule whose decay is a vector over the
128 key channels, behind a 4-tap convolution over q | k | v and low-rank
decay and output gates, a sigmoid-gated per-head norm after it) around one
latent-attention layer without positions or a query latent (32 heads, q / k
192 wide, v 128), the held experts of a bias-chosen sigmoid router under a
stated row budget beside a shared expert, bf16 activations, AdamW, the bias
update, static shapes -- the program a user of the framework writes.

``model`` holds the published ``config.json`` keys plus the deployment's and
the recipe's (``assumed`` in the configuration file); ``params`` the job
(batch, seq).
"""
from __future__ import annotations

import numpy as np


def build(model: dict, params: dict) -> dict:
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_lm
    from paddle_tpu.ops import pallas_mode
    from benchmark.references.kimi_linear_pretrain import O_SCALE, check_block

    if pallas_mode.on_tpu():
        # a chip run that could not lower the delta rule's kernels fails at
        # its compile: it never measures the composed form in silence
        model = dict(model, delta_rule_impl="pallas")
    batch, seq = params["batch"], params["seq"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0        # the run's seed enters as data:
    startup.random_seed = 0     # probe.seed_programs
    L = fluid.layers
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [batch, seq], "int64", **A)
        labels = fluid.data("labels", [batch * seq, 1], "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        # what the reference is compared on beside the loss
        # (references/kimi_linear_pretrain.py:tolerance says why): the
        # cross-entropy averaged over blocks of consecutive positions,
        block = check_block(seq)
        blocks = L.reduce_mean(L.reshape(
            out["each"], [batch * seq // block, block]), dim=1)
        # a sparse layer each, the norm of the routed experts' output before
        # the shared expert's is added, summed over the tokens and divided
        # by the sum of sqrt(c), c the number of a token's chosen experts
        # that are held here (GLM's entry, for GLM's reason),
        first, held = model.get("first_expert_held", 0), model["num_experts"]
        one = L.fill_constant([1], "float32", 1.0)

        def held_norm(routed, index):
            norm = L.sqrt(L.reduce_sum(L.square(L.cast(routed, "float32")),
                                       dim=1))
            index = L.cast(index, "float32")
            here = L.cast(L.logical_and(
                L.greater_than(index, one * (first - 0.5)),
                L.less_than(index, one * (first + held - 0.5))), "float32")
            weight = L.reduce_sum(L.sqrt(L.reduce_sum(here, dim=1)))
            return L.reduce_sum(norm) / L.elementwise_max(weight, one)
        norms = [held_norm(r, index) for r, index in
                 zip(out["expert_routed"], out["expert_index"])]
        # and a KDA layer each, the mean over tokens and heads of the norm
        # of a head's o before the gated norm, which divides a wrong scale
        # out again (times O_SCALE: the entry then reads near the
        # cross-entropy, whose largest block the check divides by)
        d = model["linear_attn_config"]["head_dim"]

        def o_norm(o):
            rows = L.reshape(L.cast(o, "float32"), [-1, d])
            return L.scale(L.reduce_mean(L.sqrt(L.reduce_sum(
                L.square(rows), dim=1))), float(O_SCALE))
        sizes = [o_norm(main.global_block().var(op.outputs["Out"][0]))
                 for op in main.global_block().ops
                 if op.type == "gated_delta_rule"]
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
                      "each": [v.name for v in [blocks] + norms + sizes]},
            "positions": out["each"].name,
            # what the reference is handed: the parameters in creation
            # order, then the selection biases (state no optimizer owns)
            "params": [p.name for p in
                       main.global_block().all_parameters()] + bias,
            # not read by the jobs: the router's variables, for whoever
            # fetches them beside the loss (tests, tools/kimi_linear_probe.py)
            "expert_bias": bias,
            "expert_load": [v.name for v in out["expert_load"]],
            "expert_index": [v.name for v in out["expert_index"]],
            "expert_dropped": [v.name for v in out["expert_dropped"]],
            "units_per_step": batch * seq, "unit": "tokens"}


def batch(model: dict, params: dict, rng: np.random.RandomState) -> dict:
    """One host batch: uniformly random tokens from the held slice of the
    vocabulary, no padding; a position's label is the token that follows it
    (``seq + 1`` tokens are drawn a sequence)."""
    b, s = params["batch"], params["seq"]
    tokens = rng.randint(0, model["vocab_size"], (b, s + 1)).astype(np.int32)
    return {"ids": np.ascontiguousarray(tokens[:, :-1]),
            "labels": np.ascontiguousarray(tokens[:, 1:]).reshape(-1, 1)}
