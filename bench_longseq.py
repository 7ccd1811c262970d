"""Long-sequence BERT bench: the leg that exercises the flash-attention
Pallas kernel (VERDICT r4 #3).

Every other bench runs S=128 (BERT) or S=64 (NMT), below the
AUTO_PALLAS_MIN_S crossover (ops/pallas_attention.py: 256 since PR 27, where
the measured table is) -- so the Pallas kernel's on-TPU win was asserted
from a microbench, never recorded as a driver artifact. (The benchmark's
cells bert_base.pretrain_s512 / _s2048 and olmoe_1b_7b.pretrain_s4096 now
record it: PERF.md.) This bench pretrains BERT-base at S=2048 (the auto
policy's Pallas domain) twice -- impl='auto' (must select the flash kernel)
and impl='composed' (the XLA path) -- and prints:

  - bert_longseq_steps_per_sec (auto): the headline long-context number,
    with MFU counted by program_flops (attention matmuls included);
  - flash_vs_composed: the measured end-to-end step-time ratio. >1 means
    the Pallas kernel wins at this length, the claim that justifies its
    existence; if it ever drops below 1, rerun `python -m
    paddle_tpu.tuning --suite flash` and rewrite AUTO_PALLAS_MIN_S from it.

vs_baseline: null -- the reference publishes no V100 number for S=2048
pretraining (its max_position_embeddings caps at 512); the line exists to
be regression-tracked round over round.

Batch sizing: 4 sequences (8k tokens) -- measured largest batch where BOTH
variants fit v5e HBM without remat (batch 16 needs 32 GB: the composed
path's saved [B, 12, S, S] probabilities dominate; flash avoids them but
the A/B needs a common config).
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from bench import _timed_steps, _sync, _peak, _mfu_field


def bench_bert_longseq(impl, batch=4, seq=2048, n_masks=20):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.utils import program_flops

    cfg = bert.BertConfig(dtype="bfloat16", max_seq_len=seq, attn_impl=impl)
    M = batch * n_masks
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0
    startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        src = fluid.data("src_ids", [batch, seq], "int64", **A)
        pos = fluid.data("pos_ids", [batch, seq], "int64", **A)
        sent = fluid.data("sent_ids", [batch, seq], "int64", **A)
        mask = fluid.data("input_mask", [batch, seq], "float32", **A)
        mpos = fluid.data("mask_pos", [M, 1], "int64", **A)
        mlabel = fluid.data("mask_label", [M, 1], "int64", **A)
        nsp = fluid.data("nsp_label", [batch, 1], "int64", **A)
        total, _, _ = bert.pretrain(src, pos, sent, mask, mpos, mlabel, nsp,
                                    cfg)
        fluid.optimizer.Adam(1e-4).minimize(total)

    rng = np.random.RandomState(0)
    ids = lambda hi, shape: jax.device_put(
        rng.randint(0, hi, shape).astype(np.int32))
    feed = {
        "src_ids": ids(cfg.vocab_size, (batch, seq)),
        "pos_ids": jax.device_put(
            np.tile(np.arange(seq, dtype=np.int32), (batch, 1))),
        "sent_ids": ids(2, (batch, seq)),
        "input_mask": jax.device_put(np.ones((batch, seq), np.float32)),
        "mask_pos": ids(batch * seq, (M, 1)),
        "mask_label": ids(cfg.vocab_size, (M, 1)),
        "nsp_label": ids(2, (batch, 1)),
    }
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[], return_numpy=False)
        _sync(scope.find_var("word_emb"))
        per_step, per_step_cons = _timed_steps(
            lambda: exe.run(main, feed=feed, fetch_list=[],
                            return_numpy=False),
            lambda: scope.find_var("word_emb"), n_short=4, n_long=16)
    flops = program_flops(main, batch=1)["total"]
    peak, kind = _peak()
    if peak and flops / per_step / peak > 1.0:  # physical sanity (bench.py)
        per_step = per_step_cons
    return per_step, _mfu_field(flops, per_step, peak), kind


def main():
    from paddle_tpu.ops.pallas_attention import AUTO_PALLAS_MIN_S

    dt_auto, mfu, kind = bench_bert_longseq("auto")
    dt_comp, _, _ = bench_bert_longseq("composed")
    ratio = dt_comp / dt_auto
    print(json.dumps({
        "metric": "bert_longseq_s2048_steps_per_sec",
        "value": round(1.0 / dt_auto, 3),
        "unit": "steps/sec (batch=4 seq=2048, impl=auto)",
        "vs_baseline": None,
        "step_time_ms": round(dt_auto * 1e3, 2),
        **mfu,
        "device_kind": kind,
    }), flush=True)
    print(json.dumps({
        "metric": "flash_vs_composed_step_ratio_s2048",
        "value": round(ratio, 3),
        "unit": "x (composed step time / auto step time; >1 = flash wins)",
        "vs_baseline": None,
        "auto_policy_min_s": AUTO_PALLAS_MIN_S,
        "composed_step_ms": round(dt_comp * 1e3, 2),
    }), flush=True)


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache as _compile_cache
    _compile_cache.arm()
    main()
