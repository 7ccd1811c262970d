"""Click-to-updated-model benchmark for the online learning subsystem.

The closed loop under measurement (the reference stack's async-pserver
online recsys promise, on the TPU-native stack): a paced click stream is
ingested through ``StreamingDataset`` -> ``StepGuardian`` trains the host
embedding table -> ``OnlinePublisher`` exports the dirty rows at a step
cadence and hot-pushes them into a live ``PredictorPool`` serving
sustained ``--serve-qps`` load the whole time.

Everything is stamped on ONE clock (``time.monotonic``): each record's
ingest time (the "click"), each publish's commit time, and the pool's
``model_staleness_seconds``.  Reported per run:

- ``online_click_to_model_ms`` -- commit - click latency per publish,
  freshest click (the last record the delta was trained through) and
  oldest unshipped click side by side;
- ``online_publish_bytes_pct_of_full`` -- on-wire delta bytes vs the
  full-table publish, on a skewed (hot-row) update workload;
- ``online_publish_cost_ms`` -- incremental delta publish wall vs a
  forced full-table publish through the same apply path;
- ``online_staleness_drop`` -- the serve-side staleness gauge observed
  to fall after every publish;
- ``online_serve_during_publish`` -- open-loop serving leg across the
  publishes: sustained qps, ZERO shed, and the predictor executable
  cache miss count byte-stable (partial push => no recompile).

Run: ``python bench_online.py [--serve-qps N] > BENCH_ONLINE_rNN.json``
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from bench import _peak


def _build_model(dirname, table_name, vocab, dim, fields, seed=0):
    """Train program (host_embedding -> fc -> mse) + its saved inference
    model; returns what the training loop needs."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.initializer import NumpyArrayInitializer
    from paddle_tpu.layer_helper import ParamAttr

    rng = np.random.RandomState(seed)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = layers.data("ids", shape=[fields], dtype="int64")
        y = layers.data("y", shape=[1], dtype="float32")
        emb = layers.host_embedding(
            ids, (vocab, dim), name=table_name, optimizer="sgd",
            learning_rate=0.05,
            initializer=rng.uniform(-0.05, 0.05,
                                    (vocab, dim)).astype(np.float32))
        flat = layers.reshape(emb, [-1, fields * dim])
        pred = layers.fc(flat, 1, param_attr=ParamAttr(
            name="bench_online_fc_w",
            initializer=NumpyArrayInitializer(
                rng.uniform(-0.05, 0.05,
                            (fields * dim, 1)).astype(np.float32))),
            bias_attr=False)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(dirname, ["ids"], [pred], exe, main)
    block = main.global_block()
    return main, scope, exe, loss, block.vars["ids"], block.vars["y"]


def _click_stream(n_records, fields, vocab, hot_rows, stream_qps, seed=1):
    """Paced synthetic click lines with a skewed id distribution: 90% of
    lookups hit a ``hot_rows``-sized head (the sparse-update workload
    where delta publishing pays).  Returns (factory, t_click list) --
    the factory stamps each record's ingest time on yield."""
    rng = np.random.RandomState(seed)
    lines = []
    for _ in range(n_records):
        hot = rng.random_sample(fields) < 0.9
        ids = np.where(hot, rng.randint(0, hot_rows, fields),
                       rng.randint(0, vocab, fields))
        lines.append(" ".join(str(int(i)) for i in ids) +
                     f";{rng.randn():.4f}")
    t_click = []
    period = 1.0 / float(stream_qps)

    def factory():
        def gen():
            t0 = time.monotonic()
            for i, line in enumerate(lines):
                delay = t0 + i * period - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                t_click.append(time.monotonic())
                yield line
        return gen()

    return factory, t_click


def _serve_loop(pool, fields, qps, stop, out):
    """Open-loop single-row load against the pool until ``stop`` is set;
    samples the staleness gauge alongside (same clock)."""
    from paddle_tpu.serving import RequestShed, RequestTimeout, ServingError

    rng = np.random.RandomState(2)
    feeds = [rng.randint(0, 64, (1, fields)).astype(np.int64)
             for _ in range(32)]
    lats, futures = [], []
    shed = errors = 0
    i, t0 = 0, time.monotonic()
    while not stop.is_set():
        target = t0 + i / qps
        delay = target - time.monotonic()
        if delay > 0:
            time.sleep(min(delay, 0.05))
            continue
        try:
            futures.append(pool.submit({"ids": feeds[i % len(feeds)]},
                                       tenant=f"t{i % 2}"))
        except RequestShed:
            shed += 1
        out["staleness"].append((time.monotonic(),
                                 pool.model_staleness_seconds()))
        i += 1
    for f in futures:
        try:
            f.result(timeout=60)
            lats.append(f.t_done - f.t_submit)
        except RequestTimeout:
            errors += 1
        except (RequestShed, ServingError):
            shed += 1
    dt = max(time.monotonic() - t0, 1e-9)
    lats.sort()
    out["serve"] = {
        "offered_qps": qps, "sustained_qps": len(lats) / dt,
        "n_ok": len(lats), "shed": shed, "errors": errors,
        "p50_ms": lats[len(lats) // 2] * 1e3 if lats else float("inf"),
        "p99_ms": (lats[min(len(lats) - 1, int(0.99 * len(lats)))] * 1e3
                   if lats else float("inf"))}


def run(serve_qps=60.0, stream_qps=40.0, n_records=240, batch=8,
        every_steps=8, vocab=20000, dim=16, fields=8, hot_rows=256,
        encoding="int8", pool_size=1, emit=print):
    import paddle_tpu as fluid
    from paddle_tpu.data import GeneratorSource, StreamingDataset
    from paddle_tpu.observability.metrics import REGISTRY
    from paddle_tpu.online import OnlinePublisher, delta_nbytes, warm_codec
    from paddle_tpu.ops import host_table as ht
    from paddle_tpu.resilience import recovery
    from paddle_tpu.serving import PredictorPool

    results = []

    def line(d):
        results.append(d)
        emit(json.dumps(d), flush=True)

    os.environ.setdefault("PADDLE_TPU_OBS_PORT", "0")
    _, kind = _peak()
    table_name = "bench_online_emb"
    ht.drop_table(table_name)
    with tempfile.TemporaryDirectory() as d:
        main, scope, exe, loss, ids_var, y_var = _build_model(
            d, table_name, vocab, dim, fields)
        table = ht.get_table(table_name)

        pool = PredictorPool(d, size=pool_size, max_batch=16,
                             max_wait_ms=1.0, max_queue=4096,
                             sparse_tables={table_name: table})
        try:
            pool.warmup({"ids": np.zeros((1, fields), np.int64)})
            factory, t_click = _click_stream(n_records, fields, vocab,
                                             hot_rows, stream_qps)
            ds = StreamingDataset()
            ds.add_source(GeneratorSource(factory, name="clicks"))
            ds.set_use_var([ids_var, y_var])
            ds.set_batch_size(batch)
            pub = OnlinePublisher(table, pool, every_steps=every_steps,
                                  encoding=encoding, dataset=ds)
            # pre-trace the codec for the chunk shapes this run will see
            # (hot-set deltas and the forced full publish) so the first
            # publish's click-to-model window doesn't pay a compile
            warm_codec(encoding, dim, rows=2 * hot_rows)
            warm_codec(encoding, dim, rows=vocab)
            # warm the TRAINING executable before the measured window so
            # the first cadence interval isn't dominated by one compile
            with fluid.scope_guard(scope):
                exe.run(main, feed={
                    "ids": np.zeros((batch, fields), np.int64),
                    "y": np.zeros((batch, 1), np.float32)},
                    fetch_list=[loss])

            def misses():
                return REGISTRY.counter("predictor_executable_cache_total",
                                        outcome="miss").value

            misses0 = misses()
            stop, sout = threading.Event(), {"staleness": []}
            server = threading.Thread(
                target=_serve_loop, args=(pool, fields, serve_qps,
                                          stop, sout), daemon=True)
            server.start()
            with fluid.scope_guard(scope):
                g = recovery.StepGuardian(exe, main)
                g.train_from_dataset(dataset=ds, fetch_list=[loss],
                                     step_cb=pub.step_cb)
                g.close()
            # measure a forced FULL-table publish through the same apply
            # path (since below the dirty floor => full=True) while the
            # serve load is still on
            t0 = time.monotonic()
            full_delta = table.export_delta(-1, encoding=encoding)
            pool.apply_delta(full_delta)
            t_full_commit = time.monotonic()
            full_publish_s = t_full_commit - t0
            time.sleep(0.3)                 # staleness samples post-full
            stop.set()
            server.join(timeout=90)
            misses_end = misses()
        finally:
            pool.close()
            ht.drop_table(table_name)

    pubs = pub.history
    assert full_delta["full"] and full_delta["rows_total"] == vocab
    # click-to-updated-model: commit minus ingest, freshest and oldest
    # click covered by each publish (watermark records are 1-based counts)
    fresh, oldest, prev = [], [], 0
    for rec in pubs:
        wm = (rec["watermark"] or {}).get("records", 0)
        if wm and wm <= len(t_click):
            fresh.append(rec["t_commit"] - t_click[wm - 1])
            oldest.append(rec["t_commit"] - t_click[prev])
            prev = wm
    full_bytes = delta_nbytes(full_delta)
    delta_bytes = [r["bytes"] for r in pubs]
    # staleness must fall across every publish commit
    stale = sout["staleness"]
    drops = []
    for rec in pubs + [{"t_commit": t_full_commit}]:
        tc = rec["t_commit"]
        before = [v for t, v in stale if t < tc]
        after = [v for t, v in stale if tc <= t < tc + 0.5]
        if before and after:
            drops.append(min(after) < before[-1])
    serve = sout["serve"]

    line({"metric": "online_publish_count", "value": len(pubs),
          "unit": f"delta publishes (every {every_steps} steps, "
                  f"{encoding}-encoded) + 1 forced full",
          "failures": pub.failures,
          "table_version": pub.committed_version,
          "device_kind": kind})
    line({"metric": "online_click_to_model_ms",
          "value": round(1e3 * float(np.mean(fresh)), 1),
          "unit": "freshest click -> updated rows serving (mean over "
                  "publishes, one monotonic clock)",
          "fresh_ms": [round(1e3 * v, 1) for v in fresh],
          "oldest_unshipped_ms": [round(1e3 * v, 1) for v in oldest],
          "stream_qps": stream_qps, "batch": batch,
          "device_kind": kind})
    line({"metric": "online_publish_bytes_pct_of_full",
          "value": round(100.0 * float(np.mean(delta_bytes)) / full_bytes,
                         2),
          "unit": f"mean on-wire delta bytes / full-table publish bytes "
                  f"(hot_rows={hot_rows} of vocab={vocab})",
          "delta_bytes": delta_bytes, "full_bytes": full_bytes,
          "rows_per_delta": [r["rows"] for r in pubs],
          "under_20pct": bool(np.mean(delta_bytes) < 0.2 * full_bytes),
          "device_kind": kind})
    line({"metric": "online_publish_cost_ms",
          "value": round(1e3 * float(np.mean([r["publish_s"]
                                              for r in pubs])), 2),
          "unit": "delta publish wall (export+encode+verify+apply) vs "
                  "forced full-table publish through the same path",
          "full_publish_ms": round(1e3 * full_publish_s, 2),
          "speedup_vs_full": round(
              full_publish_s / max(np.mean([r["publish_s"]
                                            for r in pubs]), 1e-9), 1),
          "device_kind": kind})
    line({"metric": "online_staleness_drop", "value": int(all(drops)),
          "unit": "model_staleness_seconds fell across every publish "
                  "commit (serve-side gauge, same clock)",
          "n_publishes_checked": len(drops),
          "max_staleness_s": round(max(v for _, v in stale), 3),
          "device_kind": kind})
    line({"metric": "online_serve_during_publish",
          "value": round(serve["sustained_qps"], 1),
          "unit": f"sustained qps across {len(pubs)} delta publishes + 1 "
                  f"full publish (open-loop, offered {serve_qps})",
          "n_ok": serve["n_ok"], "shed": serve["shed"],
          "errors": serve["errors"],
          "p50_ms": round(serve["p50_ms"], 3),
          "p99_ms": round(serve["p99_ms"], 3),
          "zero_shed": serve["shed"] == 0,
          "compile_cache_miss_delta": misses_end - misses0,
          "device_kind": kind})
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="bench_online.py",
        description="click-to-updated-model latency under sustained "
                    "serving load (online learning closed loop)")
    ap.add_argument("--serve-qps", type=float, default=60.0,
                    help="open-loop serving load during the run")
    ap.add_argument("--stream-qps", type=float, default=40.0,
                    help="click-stream ingest rate (records/s)")
    ap.add_argument("--records", type=int, default=240)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--publish-every-steps", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=20000)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--fields", type=int, default=8)
    ap.add_argument("--hot-rows", type=int, default=256)
    ap.add_argument("--encoding", default="int8",
                    choices=("off", "bf16", "int8"))
    ap.add_argument("--pool", type=int, default=1)
    args = ap.parse_args(argv)
    run(serve_qps=args.serve_qps, stream_qps=args.stream_qps,
        n_records=args.records, batch=args.batch,
        every_steps=args.publish_every_steps, vocab=args.vocab,
        dim=args.dim, fields=args.fields, hot_rows=args.hot_rows,
        encoding=args.encoding, pool_size=args.pool)


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache as _compile_cache
    _compile_cache.arm()
    main()
