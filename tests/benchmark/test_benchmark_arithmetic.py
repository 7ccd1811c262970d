"""The benchmark's own arithmetic and generators (CPU, small sizes): closed
forms against the program's FLOP walk, the peaks table, and traffic that
repeats byte for byte from its seed."""
import hashlib
import json
import os

import numpy as np
import pytest

from benchmark import flops
from benchmark.jobs import train_dataset
from benchmark.programs import bert_pretrain, deepfm_ctr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SMALL_BERT = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                  num_attention_heads=4, intermediate_size=256,
                  max_position_embeddings=512, type_vocab_size=2,
                  dtype="bfloat16", learning_rate=1e-4)
SMALL_CTR = dict(categorical_fields=26, dense_fields=13, hash_size=1 << 20,
                 embedding_dim=16, mlp_hidden=[400, 400, 400],
                 learning_rate=1e-3)


@pytest.mark.parametrize("job", [
    dict(batch=8, seq=16, masks_per_seq=4, dropout=0.1),
    dict(batch=2, seq=128, masks_per_seq=20, dropout=0.0)])
def test_bert_closed_form_within_1pct_of_program_flops(job):
    from paddle_tpu.utils.flops import program_flops
    built = bert_pretrain.build(SMALL_BERT, job)
    walked = program_flops(built["main"], batch=job["batch"])
    closed = flops.bert_pretrain(SMALL_BERT, job)
    assert closed["forward"] == pytest.approx(walked["forward"], rel=0.01)
    assert closed["total"] == pytest.approx(walked["total"], rel=0.01)
    assert closed["per_token"] * job["batch"] * job["seq"] == \
        pytest.approx(closed["total"])


def test_bert_base_flops_per_token_by_hand():
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      "bert_base.json")))
    job = dict(batch=128, seq=128, masks_per_seq=20)
    got = flops.bert_pretrain(cfg, job)["per_token"]
    # 6 x (12 layers x 7.08 M matmul weights) + attention 12 x 12 x S x H
    # + the decoder's 6 x H x V on 20 of 128 positions (+ small heads)
    layer = 768 * 2304 + 768 * 768 + 2 * 768 * 3072
    want = 6 * 12 * layer + 12 * 12 * 128 * 768 \
        + (20 / 128) * 6 * (768 * 30522 + 768 * 768)
    assert got == pytest.approx(want, rel=2e-3)


def test_flash_attention_need_and_roofline_bound():
    model = dict(hidden_size=768, num_hidden_layers=12)
    need = flops.flash_attention(model, dict(batch=8, seq=2048))
    assert need["flops"] == 12 * 12 * 8 * 2048 * 2048 * 768
    assert need["bytes"] == 12 * 12 * 8 * 2048 * 768 * 2
    secs, bound = flops.roofline_seconds(need, flops.peaks("TPU v5 lite"))
    assert bound == "flops"
    assert secs == pytest.approx(need["flops"] / 197e12)
    assert flops.roofline_seconds({"flops": 1.0, "bytes": 1e6},
                                  flops.peaks("TPU v5 lite"))[1] == "bytes"


def test_peaks_known_kind_and_unknown_kind():
    p = flops.peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    assert p["source"] and p["ici_note"]
    for kind in ("TPU v9", "cpu", "_note"):
        with pytest.raises(KeyError, match="no peaks for device_kind"):
            flops.peaks(kind)


def _digest(arrays):
    h = hashlib.sha256()
    for k in sorted(arrays):
        h.update(np.ascontiguousarray(arrays[k]).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("module,model,job", [
    (bert_pretrain, SMALL_BERT, dict(batch=8, seq=16, masks_per_seq=4)),
    (deepfm_ctr, SMALL_CTR, dict(batch=256, zipf_exponent=1.1))])
def test_batches_repeat_per_seed_and_differ_across_seeds(module, model, job):
    a = module.batch(model, job, np.random.RandomState(7))
    b = module.batch(model, job, np.random.RandomState(7))
    c = module.batch(model, job, np.random.RandomState(8))
    assert _digest(a) == _digest(b) != _digest(c)
    assert {k: (v.shape, v.dtype) for k, v in a.items()} == \
        {k: (v.shape, v.dtype) for k, v in c.items()}


def test_bert_batch_masks_distinct_positions_inside_each_sequence():
    job = dict(batch=8, seq=16, masks_per_seq=4)
    b = bert_pretrain.batch(SMALL_BERT, job, np.random.RandomState(0))
    pos = b["mask_pos"].reshape(8, 4)
    assert (pos // 16 == np.arange(8)[:, None]).all()
    assert all(len(set(row)) == 4 for row in pos)
    assert b["src_ids"].max() < 512 and (b["input_mask"] == 1).all()


def test_ctr_ids_are_skewed_and_stay_exact_in_float32():
    job = dict(batch=256, zipf_exponent=1.1)
    cols = deepfm_ctr.rows(dict(SMALL_CTR, hash_size=1 << 24), job,
                           np.random.RandomState(0), 20000)
    ids = cols["ids"]
    assert ids.min() >= 0 and ids.max() < 1 << 24     # native parser's limit
    _, counts = np.unique(ids[:, 0], return_counts=True)
    assert counts.max() > 0.03 * len(ids)             # a hot id: Zipf
    assert len(counts) > 0.2 * len(ids)               # and a long tail
    assert 0.3 < cols["label"].mean() < 0.7


def test_part_files_repeat_byte_for_byte_and_parse_back(tmp_path):
    from paddle_tpu import native
    job = dict(batch=256, zipf_exponent=1.1)
    digests = []
    for seed in (3, 3, 4):
        cols = deepfm_ctr.rows(SMALL_CTR, job, np.random.RandomState(seed),
                               1000)
        path = str(tmp_path / f"part-{len(digests)}.txt")
        train_dataset.write_multislot(
            path, [cols[k] for k in ("ids", "dense", "label")])
        digests.append(hashlib.sha256(open(path, "rb").read()).hexdigest())
    assert digests[0] == digests[1] != digests[2]
    first = open(path).readline()
    assert first.count(";") == 2 and len(first.split(";")[0].split()) == 26
    if not native.available():
        pytest.skip("no g++ toolchain")
    n, parsed = native.parse_slot_file(path, 3, n_threads=2)
    assert n == 1000
    assert np.array_equal(parsed[0].astype(np.int32), cols["ids"])
    assert np.allclose(parsed[1], cols["dense"], atol=1e-7)
    assert np.array_equal(parsed[2].astype(np.int32), cols["label"])
