"""Mellum2-12B-A2.5B pre-training (next-token prediction over the whole
vocabulary) through ``models/decoder_lm.py``, as one four-chip host's period
of four layers taken whole: window and full attention layers with their own
rotary parameters, grouped-query heads with a norm a head, 64 softmax top-8
experts split over the mesh's data axis with their exchange, the embedding
table and the head split by vocabulary rows, bf16 activations, AdamW, static
shapes -- the program a user of the framework writes; the layout it runs
under is the workload file's (``jobs/common.py:Session.place``).

``model`` holds the published ``config.json`` keys plus the deployment's and
the recipe's (``assumed`` in the configuration file); ``params`` the job
(batch, seq: the global batch, all chips').
"""
from __future__ import annotations

import numpy as np


def build(model: dict, params: dict) -> dict:
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_lm
    from benchmark.references.mellum2_pretrain import check_block

    batch, seq = params["batch"], params["seq"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0        # the run's seed enters as data:
    startup.random_seed = 0     # probe.seed_programs
    # 21 GB of state: created on the deployment's mesh, the declared
    # variables split over it (the harness runs the startup program without
    # the layout's strategy)
    startup.state_mesh_shape = dict(model["mesh_shape"])
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [batch, seq], "int64", **A)
        labels = fluid.data("labels", [batch * seq, 1], "int64", **A)
        out = decoder_lm.build(model, ids, labels)
        # what the reference is compared on beside the loss: every
        # position's cross-entropy, averaged over blocks of consecutive
        # positions (references/mellum2_pretrain.py says why)
        block = check_block(seq)
        blocks = fluid.layers.reduce_mean(fluid.layers.reshape(
            out["each"], [batch * seq // block, block]), dim=1)
        # and, a layer each, the mean over the tokens of the norm of the
        # routed experts' output: a row the exchange loses, a chip's
        # experts left out or a wrong router weight show there at full size
        def mean_norm(rows):
            return fluid.layers.reduce_mean(fluid.layers.sqrt(
                fluid.layers.reduce_sum(fluid.layers.square(
                    fluid.layers.cast(rows, "float32")), dim=1)))
        routed = [mean_norm(r) for r in out["expert_routed"]]
        # no backward, no optimizer: what the reference equals
        test = main.clone(for_test=True)
        fluid.optimizer.AdamW(
            model["learning_rate"], weight_decay=model["weight_decay"],
            beta1=model["adam_beta1"], beta2=model["adam_beta2"],
            epsilon=model["adam_epsilon"]).minimize(out["loss"])
    return {"main": main, "startup": startup, "test": test,
            "loss": out["loss"],
            "check": {"loss": [out["loss"].name],
                      "each": [blocks.name] + [r.name for r in routed]},
            "positions": out["each"].name,
            # what the reference is handed: the parameters in creation order
            "params": [p.name for p in main.global_block().all_parameters()],
            # not read by the jobs: the router's variables, for whoever
            # fetches them beside the loss (tests, tools/mellum2_probe.py)
            "expert_load": [v.name for v in out["expert_load"]],
            "expert_index": [v.name for v in out["expert_index"]],
            "expert_dropped": [v.name for v in out["expert_dropped"]],
            "units_per_step": batch * seq, "unit": "tokens"}


def batch(model: dict, params: dict, rng: np.random.RandomState) -> dict:
    """One host batch: uniformly random tokens over the whole vocabulary, no
    padding; the label of a position is the token that follows it (``seq +
    1`` tokens are drawn)."""
    b, s = params["batch"], params["seq"]
    tokens = rng.randint(0, model["vocab_size"], (b, s + 1)).astype(np.int32)
    return {"ids": np.ascontiguousarray(tokens[:, :-1]),
            "labels": np.ascontiguousarray(tokens[:, 1:]).reshape(-1, 1)}
