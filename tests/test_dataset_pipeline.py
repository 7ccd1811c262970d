"""Canned datasets + end-to-end input pipeline (VERDICT r2 #9): the book-test
shape -- dataset reader -> shuffle/batch decorators -> DataLoader (prefetch to
device) -> train loop on a real data path (reference book/test_recognize_digits
pattern)."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import reader as reader_mod


def test_mnist_reader_contract():
    r = fluid.dataset.mnist.train()
    first = next(iter(r()))
    img, label = first
    assert img.shape == (784,) and img.dtype == np.float32
    assert img.min() >= -1.0 and img.max() <= 1.0
    assert isinstance(label, int) and 0 <= label < 10
    # deterministic across creations
    second = next(iter(fluid.dataset.mnist.train()()))
    np.testing.assert_array_equal(first[0], second[0])


def test_cifar_and_housing_contracts():
    img, label = next(iter(fluid.dataset.cifar.train10()()))
    assert img.shape == (3072,) and 0 <= label < 10
    img100, label100 = next(iter(fluid.dataset.cifar.train100()()))
    assert 0 <= label100 < 100
    x, y = next(iter(fluid.dataset.uci_housing.train()()))
    assert x.shape == (13,) and y.shape == (1,)


def test_book_mnist_end_to_end():
    """Train softmax-MLP on dataset.mnist through the full pipeline; accuracy
    on a held-out batch must clearly beat chance."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    startup.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [784], "float32")
        label = fluid.data("label", [1], "int64")
        h = fluid.layers.fc(img, 64, act="relu")
        logits = fluid.layers.fc(h, 10)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        acc = fluid.layers.accuracy(fluid.layers.softmax(logits), label)
        test_prog = main.clone(for_test=True)
        fluid.optimizer.Adam(0.003).minimize(loss)

    train_reader = reader_mod.batch(
        reader_mod.shuffle(fluid.dataset.mnist.train(), buf_size=2048,
                           seed=0),
        batch_size=128, drop_last=True)
    loader = fluid.DataLoader.from_generator([img, label], capacity=4)
    loader.set_sample_list_generator(train_reader)

    test_batch = list(reader_mod.batch(fluid.dataset.mnist.test(),
                                       batch_size=512)())[0]
    tx = np.stack([s[0] for s in test_batch])
    ty = np.array([[s[1]] for s in test_batch], "int64")

    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        losses = []
        for epoch in range(3):
            for feed in loader:
                feed["label"] = np.asarray(feed["label"]).reshape(-1, 1)
                lv, = exe.run(main, feed=feed, fetch_list=[loss])
                losses.append(float(np.asarray(lv).reshape(())))
        accv, = exe.run(test_prog, feed={"img": tx, "label": ty},
                        fetch_list=[acc])
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])
    assert float(np.asarray(accv).reshape(())) > 0.5, accv  # chance = 0.1


def test_dataloader_shard_by_host_flag():
    """shard_by_host=True with one process is the identity (the multihost
    2-proc path is covered by dist_mlp_runner); explicit False disables."""
    x = fluid.Program()
    with fluid.program_guard(x, fluid.Program()):
        v = fluid.data("v", [4], "float32")
    loader = fluid.DataLoader.from_generator([v], shard_by_host=True)

    def gen():
        for i in range(3):
            yield (np.full((6, 4), i, "float32"),)

    loader.set_batch_generator(gen)
    seen = [np.asarray(b["v"]) for b in loader]
    assert all(s.shape == (6, 4) for s in seen)
    np.testing.assert_array_equal(seen[2], np.full((6, 4), 2))


def test_data_generator_to_dataset_roundtrip(tmp_path):
    """incubate.data_generator writes the MultiSlot text format the
    DatasetFactory (native C++ parser or numpy fallback) reads; the full
    generate -> file -> InMemoryDataset -> train_from_dataset path runs."""
    from paddle_tpu.incubate.data_generator import MultiSlotDataGenerator

    class Gen(MultiSlotDataGenerator):
        def generate_sample(self, line):
            def it():
                parts = line.strip().split(",")
                ids = [int(p) for p in parts[:3]]
                label = [int(parts[3])]
                yield [("ids", ids), ("label", label)]
            return it

    raw = tmp_path / "raw.txt"
    raw.write_text("1,2,3,0\n4,5,6,1\n7,8,9,0\n2,4,6,1\n")
    out = str(tmp_path / "data.txt")
    Gen().run_from_files([raw], out)
    lines = open(out).read().splitlines()
    assert lines[0] == "1 2 3;0"

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = fluid.data("ids", [3], "int64")
        label = fluid.data("label", [1], "int64")
        emb = fluid.layers.embedding(ids, [16, 4])
        pooled = fluid.layers.reduce_sum(emb, dim=1)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(pooled, 2), label))
        fluid.optimizer.SGD(0.1).minimize(loss)

    ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    ds.set_batch_size(2)
    ds.set_use_var([ids, label])
    ds.set_filelist([out])
    ds.load_into_memory()
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.train_from_dataset(main, ds, fetch_list=[loss])

    # string variant + run_from_memory
    from paddle_tpu.incubate.data_generator import MultiSlotStringDataGenerator

    class SGen(MultiSlotStringDataGenerator):
        def generate_sample(self, line):
            def it():
                yield [("words", line.strip().split()), ("label", ["1"])]
            return it

    outs = SGen().run_from_memory(lines=["a b c"])
    assert outs == ["a b c;1\n"]


def test_data_generator_batch_hook_and_generator_style(tmp_path):
    """generate_batch actually runs per set_batch group, and plain-generator
    generate_sample (no inner callable) works too."""
    from paddle_tpu.incubate.data_generator import MultiSlotDataGenerator

    class Gen(MultiSlotDataGenerator):
        def generate_sample(self, line):     # plain generator style
            yield [("x", [int(line)]), ("y", [0])]

        def generate_batch(self, samples):   # reverse within each batch
            return list(reversed(samples))

    g = Gen()
    g.set_batch(2)
    outs = g.run_from_memory(lines=["1", "2", "3", "4", "5"])
    assert outs == ["2;0\n", "1;0\n", "4;0\n", "3;0\n", "5;0\n"]


def test_conll05_props_parser(tmp_path, monkeypatch):
    """The cached-corpus branch (ADVICE r4): a words/props pair in the data
    home is parsed from the bracketed-span column format into BIO labels,
    one sample per predicate, and test() yields the 9-slot SRL tuple."""
    from paddle_tpu.dataset import conll05

    # sentence 1: one predicate (sat): (A0* ... *) spans; sentence 2: bark
    props1 = ["-  (A0*", "-  *)", "sat  (V*)", "-  *"]
    props2 = ["-  (A0*)", "bark  (V*)", "-  *"]
    (tmp_path / "test.wsj.words").write_text(
        "The\ncat\nsat\n.\n\nDogs\nbark\n.\n")
    (tmp_path / "test.wsj.props").write_text(
        "\n".join(props1) + "\n\n" + "\n".join(props2) + "\n")
    monkeypatch.setattr(conll05, "_home", lambda: str(tmp_path))

    samples = conll05._real_corpus(str(tmp_path / "test.wsj.words"),
                                   str(tmp_path / "test.wsj.props"))
    assert len(samples) == 2
    w0, vpos0, lemma0, bio0 = samples[0]
    assert w0 == ["The", "cat", "sat", "."] and vpos0 == 2
    assert lemma0 == "sat" and bio0 == ["B-A0", "I-A0", "B-V", "O"]
    w1, vpos1, lemma1, bio1 = samples[1]
    assert bio1 == ["B-A0", "B-V", "O"] and vpos1 == 1

    word_dict, verb_dict, label_dict = conll05.get_dict()
    assert "sat" in verb_dict and "B-A0" in label_dict
    rows = list(conll05.test()())
    assert len(rows) == 2
    sent, c2, c1, c0, p1, p2, verbs, mark, labels = rows[0]
    n = len(sent)
    assert all(len(s) == n for s in (c2, c1, c0, p1, p2, verbs, mark, labels))
    assert mark[vpos0] == 1 and sum(mark) == 1
    assert c0 == [sent[vpos0]] * n  # predicate context broadcast


def test_imdb_cutoff_semantics():
    """ADVICE r4: build_dict drops words with freq <= cutoff (the reference
    imdb.py:41 rule); the synthetic path keeps every word (cutoff 0)."""
    from paddle_tpu.dataset import imdb

    docs = [(["a"] * 5 + ["b"] * 2 + ["c"], 1)]
    d = imdb.build_dict(docs, cutoff=2)
    assert "a" in d and "b" not in d and "c" not in d and "<unk>" in d
    d0 = imdb.build_dict(docs, cutoff=0)
    assert "a" in d0 and "b" in d0 and "c" in d0  # freq > 0: all kept


class _SlowDataset:
    """Feed-bound dataset stub: each batch costs parse_s of host time (the
    executor only uses _iter_batches, like the reference's DataFeed)."""

    def __init__(self, batches, parse_s):
        self.batches = batches
        self.parse_s = parse_s
        self.thread_num = 0

    def _iter_batches(self):
        import time
        for b in self.batches:
            time.sleep(self.parse_s)
            yield b


def _feed_bound_rig(width=768, n_batches=10, bs=256):
    rng = np.random.RandomState(0)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0
    startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [width], "float32")
        label = fluid.data("label", [1], "int64")
        h = x
        for _ in range(4):
            h = fluid.layers.fc(h, width, act="relu")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(h, 10), label))
        fluid.optimizer.SGD(0.01).minimize(loss)
    batches = [{"x": rng.randn(bs, width).astype(np.float32),
                "label": rng.randint(0, 10, (bs, 1)).astype(np.int64)}
               for _ in range(n_batches)]
    return main, startup, loss, batches


def test_train_from_dataset_overlaps_parse_and_compute():
    """VERDICT r4 #5: epoch time must approach max(parse, compute), not
    their sum -- the prefetch thread runs the dataset generator ahead of
    the device loop."""
    import time

    main, startup, loss, batches = _feed_bound_rig()
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        # calibrate: pure compute time per step (warm, no parse cost)
        for b in batches[:2]:
            exe.run(main, feed=b, fetch_list=[loss])
        t0 = time.perf_counter()
        for b in batches:
            exe.run(main, feed=b, fetch_list=[loss])
        compute_total = time.perf_counter() - t0
    parse_s = max(0.02, compute_total / len(batches))  # feed ~ compute
    ds = _SlowDataset(batches, parse_s)
    parse_total = parse_s * len(batches)

    exe2 = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe2.run(startup)
        exe2.run(main, feed=batches[0], fetch_list=[loss])  # compile warm
        t0 = time.perf_counter()
        exe2.train_from_dataset(main, dataset=ds, fetch_list=[loss])
        wall = time.perf_counter() - t0
    serial = parse_total + compute_total
    # with parse ~= compute, full overlap halves the epoch; require >=25%
    # savings to stay robust under CI timing noise
    assert wall < 0.75 * serial, (wall, parse_total, compute_total)


def test_train_from_dataset_prefetch_preserves_order_and_errors():
    """Single prefetch worker: batch order (and thus the final weights) is
    identical to the synchronous loop; generator errors surface."""
    main, startup, loss, batches = _feed_bound_rig(width=64, n_batches=6,
                                                   bs=32)
    def final_w(run_via_dataset):
        # per-program PRNG run counters advance across calls; reset so both
        # runs see identical init and per-step keys
        main._rng_run_counter = 0
        startup._rng_run_counter = 0
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            if run_via_dataset:
                exe.train_from_dataset(main,
                                       dataset=_SlowDataset(batches, 0.0),
                                       fetch_list=[loss])
            else:
                for b in batches:
                    exe.run(main, feed=b, fetch_list=[loss])
            return np.asarray(fluid.global_scope().find_var("fc_0.w_0"))

    np.testing.assert_allclose(final_w(True), final_w(False))

    class _Boom(_SlowDataset):
        def _iter_batches(self):
            yield batches[0]
            raise RuntimeError("parse exploded")

    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with pytest.raises(RuntimeError, match="parse exploded"):
            exe.train_from_dataset(main, dataset=_Boom(batches, 0.0),
                                   fetch_list=[loss])


def test_queue_dataset_streaming_matches_eager(tmp_path):
    """QueueDataset's streaming _iter_batches (per-file parse, remainder
    carry, striping by global row) yields byte-identical batches to the
    eager base-class path, across multiple files with odd sizes."""
    x = fluid.Program()
    with fluid.program_guard(x, fluid.Program()):
        ids = fluid.data("ids", [3], "int64")
        label = fluid.data("label", [1], "int64")

    rng = np.random.RandomState(0)
    paths = []
    row = 0
    for fi, n in enumerate([5, 3, 7]):   # odd sizes force remainder carry
        p = tmp_path / f"part-{fi}.txt"
        with open(p, "w") as f:
            for _ in range(n):
                f.write(f"{row} {row+1} {row+2};{row % 2}\n")
                row += 1
        paths.append(str(p))

    def batches(cls, stripe=None, drop_last=False):
        ds = fluid.DatasetFactory().create_dataset(cls)
        ds.set_batch_size(4)
        ds.set_use_var([ids, label])
        ds.set_filelist(paths)
        ds.drop_last = drop_last
        if stripe:
            ds._stripe = stripe
        if cls == "InMemoryDataset":
            ds.load_into_memory()
        return list(ds._iter_batches())

    for stripe in (None, (0, 2), (1, 2)):
        for drop_last in (False, True):
            q = batches("QueueDataset", stripe, drop_last)
            m = batches("InMemoryDataset", stripe, drop_last)
            assert len(q) == len(m), (stripe, drop_last, len(q), len(m))
            for bq, bm in zip(q, m):
                np.testing.assert_array_equal(bq["ids"], bm["ids"])
                np.testing.assert_array_equal(bq["label"], bm["label"])


def test_read_files_mixed_format_demotion(tmp_path, monkeypatch):
    """ISSUE 14 satellite: pin the mixed native/columnar demotion path in
    DatasetBase._read_files (dataset_factory.py) -- a columnar-parsed
    prefix followed by a Python-parsed file must demote to rows with no
    samples lost or reordered.  The native parser is simulated so the pin
    holds whether or not the native library is present."""
    from paddle_tpu.dataset_factory import DatasetBase

    x = fluid.Program()
    with fluid.program_guard(x, fluid.Program()):
        ids = fluid.data("ids", [2], "float32")

    paths = []
    for fi, rows in enumerate([(0, 1, 2), (3, 4), (5, 6, 7)]):
        p = tmp_path / f"part-{fi}.txt"
        with open(p, "w") as f:
            for r in rows:
                f.write(f"{r} {r + 0.5}\n")
        paths.append(str(p))

    real_read_native = DatasetBase._read_native

    def fake_native(self, path):
        # files 0 and 2 parse "natively" (columnar [N, 2] matrices),
        # file 1 falls back to the Python line parser
        if path.endswith("part-1.txt"):
            return None
        rows = [[float(v) for v in ln.split()]
                for ln in open(path) if ln.strip()]
        return [np.asarray(rows, dtype="float32")]

    monkeypatch.setattr(DatasetBase, "_read_native", fake_native)
    ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    ds.set_batch_size(3)
    ds.set_use_var([ids])
    ds.set_filelist(paths)
    ds.load_into_memory()
    # demoted to a row list (file 1 broke the columnar run), all 8 rows
    # present in file order
    assert ds.get_memory_data_size() == 8
    assert not ds._is_columnar(ds._samples)
    got = np.concatenate([b["ids"] for b in ds._iter_batches()])
    np.testing.assert_allclose(got[:, 0], np.arange(8, dtype="float32"))

    # all-native stays columnar (the fast path is not regressed)
    monkeypatch.setattr(DatasetBase, "_read_native", fake_native)
    ds2 = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    ds2.set_batch_size(3)
    ds2.set_use_var([ids])
    ds2.set_filelist([paths[0], paths[2]])
    ds2.load_into_memory()
    assert ds2._is_columnar(ds2._samples)
    monkeypatch.setattr(DatasetBase, "_read_native", real_read_native)


def test_on_missing_file_policy(tmp_path):
    """ISSUE 14 satellite: on_missing_file='skip' keeps the multi-file
    load alive (journaled source_skipped), default 'raise' preserves the
    historical abort; a skipped LAST file still flushes the streaming
    remainder."""
    from paddle_tpu.observability import journal

    x = fluid.Program()
    with fluid.program_guard(x, fluid.Program()):
        ids = fluid.data("ids", [1], "float32")
    present = tmp_path / "ok.txt"
    with open(present, "w") as f:
        f.write("1\n2\n3\n")
    gone = str(tmp_path / "gone.txt")

    for cls in ("InMemoryDataset", "QueueDataset"):
        ds = fluid.DatasetFactory().create_dataset(cls)
        ds.set_batch_size(2)
        ds.set_use_var([ids])
        ds.set_filelist([str(present), gone])
        with pytest.raises(FileNotFoundError):
            (ds.load_into_memory() if cls == "InMemoryDataset"
             else list(ds._iter_batches()))

        ds2 = fluid.DatasetFactory().create_dataset(cls)
        ds2.set_batch_size(2)
        ds2.set_use_var([ids])
        ds2.set_filelist([str(present), gone])   # missing LAST file
        ds2.set_missing_file_policy("skip")
        if cls == "InMemoryDataset":
            ds2.load_into_memory()
        batches = list(ds2._iter_batches())
        # 3 rows -> [2, 1]: the remainder flushed despite the skipped tail
        assert [b["ids"].shape[0] for b in batches] == [2, 1], cls
    assert any(e.get("event") == "source_skipped"
               for e in journal.recent())
    with pytest.raises(ValueError):
        ds2.set_missing_file_policy("bogus")


def test_parse_error_carries_source_position(tmp_path):
    """ISSUE 14 satellite: a slot-count mismatch (and a value parse
    failure) names the offending file:line."""
    x = fluid.Program()
    with fluid.program_guard(x, fluid.Program()):
        ids = fluid.data("ids", [1], "float32")
        lab = fluid.data("lab", [1], "int64")
    p = tmp_path / "bad.txt"
    with open(p, "w") as f:
        f.write("1;0\n2;0;9\n")
    ds = fluid.DatasetFactory().create_dataset("QueueDataset")
    ds.set_batch_size(1)
    ds.set_use_var([ids, lab])
    ds.set_filelist([str(p)])
    with pytest.raises(ValueError, match=r"bad\.txt:2"):
        list(ds._iter_batches())
    with open(p, "w") as f:
        f.write("notafloat;0\n")
    ds2 = fluid.DatasetFactory().create_dataset("QueueDataset")
    ds2.set_batch_size(1)
    ds2.set_use_var([ids, lab])
    ds2.set_filelist([str(p)])
    with pytest.raises(ValueError, match=r"bad\.txt:1"):
        list(ds2._iter_batches())


# ------------------------------------------- the one train_from_dataset loop --

def _small_rig(n_batches):
    """The feed-bound rig at a toy width: ``(main, startup, loss, dataset)``."""
    main, startup, loss, batches = _feed_bound_rig(width=8,
                                                   n_batches=n_batches, bs=4)
    return main, startup, loss, _SlowDataset(batches, 0.0)


@pytest.mark.parametrize("return_numpy", [False, True])
def test_train_from_dataset_return_numpy_false_is_lazy(return_numpy):
    """``return_numpy`` threads through the loop: False returns the last
    step's fetches as live device arrays, True as host copies."""
    main, startup, loss, ds = _small_rig(5)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        last = exe.train_from_dataset(main, ds, fetch_list=[loss],
                                      return_numpy=return_numpy)
    assert isinstance(last[0], np.ndarray) == return_numpy
    assert np.isfinite(np.asarray(last[0])).all()


def test_prefetch_unfused_contract_unchanged():
    """The prefetch worker hands on the dataset's feed dicts, in order."""
    batches = _small_rig(4)[3].batches
    items = list(fluid.Executor._prefetch_batches(iter(batches), 2))
    assert len(items) == 4 and isinstance(items[0], dict)
    for got, want in zip(items, batches):
        np.testing.assert_array_equal(got["x"], want["x"])


def test_train_from_dataset_journal_and_debug_materializer(
        tmp_path, monkeypatch, capsys):
    """Every step journals a ``run`` event; debug printing materializes
    through materialize_fetches ONCE per ``print_period`` boundary instead
    of syncing every step."""
    from paddle_tpu.core import executor as executor_mod
    from paddle_tpu.observability import journal
    monkeypatch.setenv("PADDLE_TPU_OBS", "1")
    monkeypatch.setenv("PADDLE_TPU_OBS_JOURNAL",
                       str(tmp_path / "journal.jsonl"))
    journal.clear()
    calls = []
    real = executor_mod.materialize_fetches

    def spy(fetches):
        calls.append(1)
        return real(fetches)

    monkeypatch.setattr(executor_mod, "materialize_fetches", spy)
    main, startup, loss, ds = _small_rig(8)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.train_from_dataset(main, ds, fetch_list=[loss], debug=True,
                               print_period=4, return_numpy=False)
    runs = [e for e in journal.recent(event="run")
            if e["program"] == id(main)]
    assert len(runs) == 8
    assert runs[0]["cache"] == "miss" and runs[1]["cache"] == "hit"
    assert all(e["run_ms"] is not None for e in runs)
    # 8 steps, period 4 -> boundaries at steps 0 and 4: exactly 2
    assert len(calls) == 2
    out = capsys.readouterr().out
    assert "batch 0:" in out and "batch 4:" in out and "batch 1:" not in out


def test_obs_off_train_loop_guard_no_files_no_syncs(tmp_path, monkeypatch):
    """Tier-1 guard: with every obs env unset, a warm train_from_dataset
    epoch opens NO files, never runs the health scan, and returns
    un-materialized device arrays (zero fetch d2h syncs)."""
    import builtins
    from paddle_tpu.core import executor as executor_mod
    from paddle_tpu.observability import health
    for var in ("PADDLE_TPU_OBS", "PADDLE_TPU_OBS_HEALTH",
                "PADDLE_TPU_OBS_HEALTH_STATE", "PADDLE_TPU_FAULTS"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("PADDLE_TPU_OBS_JOURNAL",
                       str(tmp_path / "guard.jsonl"))
    monkeypatch.chdir(tmp_path)
    scans, syncs = [], []
    monkeypatch.setattr(health, "nonfinite_names",
                        lambda named: scans.append(1) or [])
    monkeypatch.setattr(executor_mod, "materialize_fetches",
                        lambda fetches: syncs.append(1) or list(fetches))
    main, startup, loss, ds = _small_rig(4)
    exe = fluid.Executor()
    opened = []
    real_open = builtins.open
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.train_from_dataset(main, ds, fetch_list=[loss],
                               return_numpy=False)   # compile

        def spy_open(file, *a, **k):
            opened.append(str(file))
            return real_open(file, *a, **k)
        monkeypatch.setattr(builtins, "open", spy_open)
        try:
            for _ in range(3):
                vals = exe.train_from_dataset(main, ds, fetch_list=[loss],
                                              return_numpy=False)
        finally:
            monkeypatch.setattr(builtins, "open", real_open)
        assert not isinstance(vals[0], np.ndarray)
    watched = [p for p in opened
               if "journal" in p or "trace" in p or p.endswith(".jsonl")
               or "paddle_tpu" in p]
    assert watched == [], f"warm train loop opened files: {watched}"
    assert scans == [], "the health scan must not run with the mode off"
    assert syncs == [], "return_numpy=False must not sync a fetch"
    assert list(tmp_path.iterdir()) == []
