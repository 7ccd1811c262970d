"""Qwen3-Next-80B-A3B pre-training (next-token prediction over the held
vocabulary slice) through ``models/decoder_lm.py``, as one chip's share of
one period of four layers: three Gated DeltaNet layers (``layers.gated_
delta_rule`` behind a short convolution, a gated per-head norm after it) to
one gated softmax-attention layer, zero-centred norms, the held experts
under a stated row budget beside a sigmoid-gated shared expert, bf16
activations, AdamW, static shapes -- the program a user of the framework
writes.

``model`` holds the published ``config.json`` keys plus the deployment's and
the recipe's (``assumed`` in the configuration file); ``params`` the job
(batch, seq).
"""
from __future__ import annotations

from benchmark.programs.laguna_pretrain import batch  # noqa: F401: the job's


def build(model: dict, params: dict) -> dict:
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_lm
    from paddle_tpu.ops import pallas_mode
    from benchmark.references.qwen3_next_pretrain import check_block

    if pallas_mode.on_tpu():
        # a chip run that could not lower the delta rule's kernels fails at
        # its compile: it never measures the composed form in silence
        model = dict(model, delta_rule_impl="pallas")
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
        # positions (references/qwen3_next_pretrain.py says why)
        block = check_block(seq)
        blocks = fluid.layers.reduce_mean(fluid.layers.reshape(
            out["each"], [batch * seq // block, block]), dim=1)

        def mean_norm(v):       # the mean over the tokens of a row's norm
            rows = fluid.layers.reshape(
                fluid.layers.cast(v, "float32"), [batch * seq, -1])
            return fluid.layers.reduce_mean(fluid.layers.sqrt(
                fluid.layers.reduce_sum(fluid.layers.square(rows), dim=1)))
        # a sparse layer each, the routed experts' output before the shared
        # expert's is added (the router's scale, the row budget and a
        # dropped row show there and hardly in the loss: 32 of 512 held);
        # a DeltaNet layer each, the delta rule's output before the gated
        # norm, which divides a wrong scale out again
        routed = [mean_norm(r) for r in out["expert_routed"]]
        delta = [mean_norm(main.global_block().var(op.outputs["Out"][0]))
                 for op in main.global_block().ops
                 if op.type == "gated_delta_rule"]
        # no backward, no optimizer: what the reference equals
        test = main.clone(for_test=True)
        fluid.optimizer.AdamW(
            model["learning_rate"], weight_decay=model["weight_decay"],
            beta1=model["adam_beta1"], beta2=model["adam_beta2"],
            epsilon=model["adam_epsilon"]).minimize(out["loss"])
    return {"main": main, "startup": startup, "test": test,
            "loss": out["loss"],
            "check": {"loss": [out["loss"].name],
                      "each": [blocks.name] + [v.name for v in routed + delta]},
            "positions": out["each"].name,
            # what the reference is handed: the parameters in creation order
            "params": [p.name for p in main.global_block().all_parameters()],
            # not read by the jobs: the router's variables, for whoever
            # fetches them beside the loss (tests, tools/qwen3_next_probe.py)
            "expert_load": [v.name for v in out["expert_load"]],
            "expert_index": [v.name for v in out["expert_index"]],
            "expert_dropped": [v.name for v in out["expert_dropped"]],
            "units_per_step": batch * seq, "unit": "tokens"}
