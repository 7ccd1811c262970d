"""BERT pre-training (masked LM + next sentence) through ``models/bert.py``,
bf16 activations and Adam, static shapes -- the program a user of the
framework writes, built the way ``chip_smoke.py:bert_program`` builds it.

``model`` holds the published ``bert_config.json`` keys; ``params`` the job
(batch, seq, masks_per_seq, dropout).
"""
from __future__ import annotations

import numpy as np


def build(model: dict, params: dict) -> dict:
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    batch, seq = params["batch"], params["seq"]
    cfg = bert.BertConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        ffn_hidden=model["intermediate_size"],
        # assumed: the position table grows past the published 512 where the
        # cell's sequences are longer
        max_seq_len=max(model["max_position_embeddings"], seq),
        type_vocab=model["type_vocab_size"], dropout=params["dropout"],
        dtype=model["dtype"])
    n_mask = batch * params["masks_per_seq"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0        # the run's seed enters as data:
    startup.random_seed = 0     # probe.seed_programs
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        src = fluid.data("src_ids", [batch, seq], "int64", **A)
        pos = fluid.data("pos_ids", [batch, seq], "int64", **A)
        sent = fluid.data("sent_ids", [batch, seq], "int64", **A)
        mask = fluid.data("input_mask", [batch, seq], "float32", **A)
        mpos = fluid.data("mask_pos", [n_mask, 1], "int64", **A)
        mlabel = fluid.data("mask_label", [n_mask, 1], "int64", **A)
        nsp = fluid.data("nsp_label", [batch, 1], "int64", **A)
        loss, _, _ = bert.pretrain(src, pos, sent, mask, mpos, mlabel, nsp,
                                   cfg)
        # dropout off, no backward, no optimizer: what the reference equals
        test = main.clone(for_test=True)
        fluid.optimizer.Adam(model["learning_rate"]).minimize(loss)
    # the loss of every masked position and of every sequence, before the
    # means: the reference is compared there too (a mean hides rounding)
    each = [op.outputs["Loss"][0] for op in test.global_block().ops
            if op.type == "softmax_with_cross_entropy"]
    return {"main": main, "startup": startup, "test": test, "loss": loss,
            "check": {"loss": [loss.name], "each": each},
            "params": [p.name for p in main.global_block().all_parameters()],
            "units_per_step": batch * seq, "unit": "tokens"}


def batch(model: dict, params: dict, rng: np.random.RandomState) -> dict:
    """One host batch: random tokens, no padding, ``masks_per_seq`` distinct
    masked positions in every sequence (flat indices into [batch * seq])."""
    b, s, m = params["batch"], params["seq"], params["masks_per_seq"]
    vocab = model["vocab_size"]
    in_seq = np.argsort(rng.rand(b, s), axis=1)[:, :m]
    flat = (in_seq + np.arange(b)[:, None] * s).reshape(-1, 1)
    return {
        "src_ids": rng.randint(0, vocab, (b, s)).astype(np.int32),
        "pos_ids": np.tile(np.arange(s, dtype=np.int32), (b, 1)),
        "sent_ids": rng.randint(0, model["type_vocab_size"],
                                (b, s)).astype(np.int32),
        "input_mask": np.ones((b, s), np.float32),
        "mask_pos": flat.astype(np.int32),
        "mask_label": rng.randint(0, vocab, (b * m, 1)).astype(np.int32),
        "nsp_label": rng.randint(0, 2, (b, 1)).astype(np.int32),
    }
