"""BASELINE benchmark triple: ResNet-50 img/s, BERT-base steps/s, c_allreduce GB/s.

Prints one JSON line per metric: {"metric", "value", "unit", "vs_baseline", ...}.
The ResNet-50 line is printed LAST (the driver's headline metric).

Baselines (BASELINE.md): the bar is >=0.8x per-chip throughput vs a V100 running the
reference's fp32 CUDA path.
  - ResNet-50 train: ~360 img/s on 1xV100 fp32 (era-standard; the reference's own
    float16_benchmark.md covers only inference).
  - BERT-base pretrain seq128: ~42 seq/s on 1xV100 fp32 (NVIDIA DeepLearningExamples
    era number). vs_baseline is computed on sequences/sec.
  - c_allreduce: no published number (BASELINE.json lists "measured over ICI");
    vs_baseline is null. On a single device there is no interconnect and the
    line is not printed at all.

Method notes:
  - bf16 activations/weights (MXU-native), f32 batch-norm statistics / loss.
  - batches sized for per-chip throughput (ResNet 128, BERT 128; both swept
    each round -- larger regresses): measured MFU
    rises ~5 points over the V100-era batch sizes and vs_baseline compares
    throughput, which is the per-chip claim BASELINE.md makes.
  - BERT runs with dropout=0.1 (as the reference pretrain config does) under
    FLAGS_prng_impl=rbg, the TPU-fast PRNG: round-4 tracing showed threefry
    mask generation cost ~30 ms/step at batch 128 (VPU-bound + fusion
    breaking). With rbg + the bf16 weight-tied MLM decode
    (BertConfig.tie_mlm_weight, the reference LARK pattern) + tanh-form GELU
    (what google-research BERT computes; ~7 ms cheaper than erf on the VPU)
    the step went 132.7 -> 91 ms (MFU 0.342 -> ~0.50, within ~2% of a
    hand-written pure-JAX formulation of the same model).
  - ResNet runs the TPU-preferred formulation: NHWC (channels-last) layout and
    a 2x2 space-to-depth stem (the MLPerf factorization of the 7x7/s2 conv;
    see models/resnet.py). Round-4 finding: a hand-written pure-JAX ResNet-50
    with the stock formulation measures the same MFU as the framework path
    (0.318 vs 0.317) -- the framework's whole-program jit adds no overhead.
    Decomposition on the same chip: the pure-JAX step is 46.7 ms with
    train-mode batch-norm and 29.9 ms with BN swapped for bias-adds, i.e.
    ~17 ms (36%) is the BN-statistics HBM traffic XLA cannot fuse away and
    the conv+elementwise core alone runs at ~53% MFU. Raising ResNet MFU
    further means a fused conv+BN-stat Pallas kernel, not formulation work.
  - feeds are pre-staged on device; this measures the compiled train-step (the
    input pipeline is exercised by tests/test_io_reader.py, not here).
  - Every timed segment ends with a 1-element device->host read of a value
    the last step wrote, and per-step time is derived from TWO segment
    lengths -- per_step = (t_long - t_short) / (n_long - n_short) -- which
    cancels whatever fixed cost a segment carries (dispatch ramp-up, the
    final read). The estimator was built for the rounds' earlier shared-TPU
    plug-in, whose sync was unreliable; on the current runtime
    block_until_ready synchronizes, and a plain timed window would do
    (left for the one-benchmark rewrite, ROADMAP S0/D7).
  - mfu = sustained matmul-class FLOP/s / chip peak (from the device kind table in
    paddle_tpu/utils/flops.py). FLOPs are counted from the Program IR with the
    strict mul+add convention (2x MACs), elementwise ignored -> slight underestimate.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


def _sync(val):
    """Wait for ``val``: pull one element to host."""
    idx = tuple(0 for _ in getattr(val, "shape", ()))
    return np.asarray(val[idx] if idx else val)


def _timed_steps(run_one, state_probe, n_short=8, n_long=40):
    """(per_step, per_step_conservative) seconds; the first has the
    per-segment fixed overhead cancelled by differencing, the second is the
    overhead-inclusive long-segment mean (an overestimate of step time --
    the fallback when the differenced value fails a physical-sanity check)."""
    from paddle_tpu.utils.benchtime import median_differenced_estimate

    times = {}
    for n in (n_short, n_long):
        t0 = time.perf_counter()
        for _ in range(n):
            run_one()
        _sync(state_probe())
        times[n] = time.perf_counter() - t0
    cons = times[n_long] / n_long
    return median_differenced_estimate([times[n_short]], [times[n_long]],
                                       n_short, n_long, fallback=cons), cons


def _peak():
    """(peak bf16 FLOP/s, device_kind). The peak is None off TPU -- rows
    then carry no ``mfu`` key -- and an unknown TPU kind raises."""
    import jax
    from paddle_tpu.utils import device_peak_flops
    kind = jax.devices()[0].device_kind
    return device_peak_flops(kind), kind


def _mfu_field(flops, per_step, peak):
    """``{"mfu": ...}`` where there is a peak to divide by, else nothing."""
    return {"mfu": round(flops / per_step / peak, 3)} if peak else {}


def _mfu_guard(per_step, per_step_cons, flops):
    """(step_time, suspect): a step time implying MFU > 1 is impossible (a
    timed segment that did not wait for the device); fall back to the
    overhead-inclusive conservative step time and flag the metric so a
    clamped round is distinguishable from a clean measurement."""
    peak, _ = _peak()
    if peak and flops / per_step / peak > 1.0:
        return per_step_cons, True
    return per_step, False


def bench_resnet50(batch=128, image=224, dtype="bfloat16", data_format="NHWC",
                   conv1_space_to_depth=True):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet
    from paddle_tpu.utils import program_flops

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0
    startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ishape = [3, image, image] if data_format == "NCHW" else [image, image, 3]
        img = fluid.data("img", ishape, dtype)
        label = fluid.data("label", [1], "int64")
        loss, acc, _ = resnet.resnet50(img, label, num_classes=1000,
                                       data_format=data_format,
                                       conv1_space_to_depth=conv1_space_to_depth)
        fluid.optimizer.Momentum(0.1, 0.9).minimize(loss)

    rng = np.random.RandomState(0)
    img_np = rng.randn(batch, 3, image, image).astype(np.float32)
    if data_format == "NHWC":
        img_np = np.ascontiguousarray(img_np.transpose(0, 2, 3, 1))
    feed = {
        "img": jax.device_put(jax.numpy.asarray(img_np, dtype=dtype)),
        "label": jax.device_put(rng.randint(0, 1000, (batch, 1)).astype(np.int32)),
    }

    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[], return_numpy=False)
        _sync(scope.find_var("fc_0.w_0"))
        per_step, per_step_cons = _timed_steps(
            lambda: exe.run(main, feed=feed, fetch_list=[], return_numpy=False),
            lambda: scope.find_var("fc_0.w_0"))
    flops = program_flops(main, batch=batch)["total"]
    per_step, suspect = _mfu_guard(per_step, per_step_cons, flops)
    return batch / per_step, per_step, flops, suspect


def bench_bert_base(batch=128, seq=128, n_masks=20, dtype="bfloat16"):
    """BERT-base (L12 H768 A12, vocab 30522) pretrain step: fwd+bwd+Adam."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.utils import program_flops

    cfg = bert.BertConfig(dtype=dtype)
    M = batch * n_masks
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0
    startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)  # static shapes -> exact FLOP count
        src = fluid.data("src_ids", [batch, seq], "int64", **A)
        pos = fluid.data("pos_ids", [batch, seq], "int64", **A)
        sent = fluid.data("sent_ids", [batch, seq], "int64", **A)
        mask = fluid.data("input_mask", [batch, seq], "float32", **A)
        mpos = fluid.data("mask_pos", [M, 1], "int64", **A)
        mlabel = fluid.data("mask_label", [M, 1], "int64", **A)
        nsp = fluid.data("nsp_label", [batch, 1], "int64", **A)
        total, mlm, nsp_acc = bert.pretrain(src, pos, sent, mask, mpos, mlabel,
                                            nsp, cfg)
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
            lambda: exe.run(main, feed=feed, fetch_list=[], return_numpy=False),
            lambda: scope.find_var("word_emb"))
    flops = program_flops(main, batch=1)["total"]  # shapes are fully static
    per_step, suspect = _mfu_guard(per_step, per_step_cons, flops)
    return 1.0 / per_step, per_step, flops, batch, suspect


def bench_allreduce(mbytes=256, sync_every=None):
    """c_allreduce bandwidth through the framework's op lowering.

    Jitted shard_map psum over the 'dp' axis of all local devices; reports
    bus bandwidth 2*(n-1)/n * bytes / t (the NCCL busbw convention,
    comparable to the reference's NCCL allreduce). On one device there is no
    interconnect to measure: returns None and nothing is printed under the
    metric's name.

    sync_every: block every k chained calls. The CPU-mesh test harness needs
    it (XLA's CPU thunk executor crashes on deep async collective chains);
    on TPU leave None so dispatch stays fully pipelined.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.core.registry import get as get_op, LowerCtx

    n = jax.device_count()
    if n < 2:
        return None
    nelem = mbytes * 1024 * 1024 // 4
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    opdef = get_op("c_allreduce_sum")

    def local(x):
        # psum over dp, scaled to keep the chained iterate bounded; each
        # device keeps its shard of the reduced result so the output
        # sharding matches the input and calls can be chained.
        ctx = LowerCtx({"axis_name": "dp"}, mesh=mesh)
        out = opdef.lower(ctx, {"X": [x]})["Out"][0]
        return out * np.float32(1.0 / n)

    step = jax.jit(shard_map(local, mesh=mesh, in_specs=P("dp"),
                             out_specs=P("dp")))
    x = jax.device_put(
        jnp.ones((nelem,), jnp.float32),
        jax.sharding.NamedSharding(mesh, P("dp")))
    mode = "ici_allreduce"
    bw_of = lambda dt: 2 * (n - 1) / n * (nelem * 4) / dt

    # chain each call on the previous so async dispatch can't overlap/elide
    # work. Segment lengths are sized from a probe so the differenced work is
    # seconds-scale against the per-segment fixed cost (the round-4 failure
    # mode: 40 ms of signal under ~0.3 s of sync jitter differenced to a
    # physically impossible 5,832 GB/s). bw_conservative is overhead-
    # inclusive (can only understate) for use when the estimate fails the
    # physical-sanity clamp in main().
    from paddle_tpu.utils.benchtime import sized_per_call

    out = step(x)
    _sync(out)

    def segment(k):
        cur = x
        t0 = time.perf_counter()
        for i in range(k):
            cur = step(cur)
            if sync_every and (i + 1) % sync_every == 0:
                jax.block_until_ready(cur)
        _sync(cur)
        return time.perf_counter() - t0

    per_call, per_call_ub = sized_per_call(segment)
    return bw_of(per_call) / 1e9, bw_of(per_call_ub) / 1e9, mode, n


def bench_comm_sweep(sizes_mb=(1, 4, 16, 64, 256),
                     modes=("off", "bf16", "int8"), out_path=None):
    """Quantized-allreduce message-size sweep: ``c_allreduce_avg`` through
    the framework's own op lowering (comm_compress attr) over a dp mesh of
    all local devices, sizes_mb x {f32, bf16, int8}.

    Reports EFFECTIVE (pre-compression) bandwidth per row -- the busbw
    convention on the f32 payload, so a compressed mode that halves the
    wire time shows ~2x effective GB/s -- plus the cost model's per-device
    wire bytes and the on-wire reduction vs f32.  On a bandwidth-flat CPU
    host the wall-clock gain collapses (the psum is memcpy over shared
    memory and the quantize arithmetic dominates); the on-wire reduction
    column is the TPU-expected gain there and is labeled as such.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.comm import compressed_bytes, wire_bytes
    from paddle_tpu.core.registry import LowerCtx, get as get_op

    n = jax.device_count()
    if n < 2:
        return {"error": f"comm sweep needs >=2 devices, have {n} "
                         f"(set XLA_FLAGS=--xla_force_host_platform_"
                         f"device_count=8 on a CPU host)"}
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    opdef = get_op("c_allreduce_avg")
    kind = jax.devices()[0].device_kind
    rows = []
    for mb in sizes_mb:
        nelem = int(mb) * 1024 * 1024 // 4
        nbytes = nelem * 4
        x = jax.device_put(
            jnp.linspace(-1.0, 1.0, nelem, dtype=jnp.float32),
            NamedSharding(mesh, P("dp")))
        base_t = None
        for mode in modes:
            def local(xl, mode=mode):
                ctx = LowerCtx({"axis_name": "dp", "comm_compress": mode},
                               mesh=mesh)
                return opdef.lower(ctx, {"X": [xl]})["Out"][0]

            fn = jax.jit(shard_map(local, mesh=mesh, in_specs=P("dp"),
                                   out_specs=P("dp"), check_vma=False))
            jax.block_until_ready(fn(x))   # compile + warm
            best = None
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x))
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            if mode == "off":
                base_t = best
            eff_gbps = 2 * (n - 1) / n * nbytes / best / 1e9
            wire = wire_bytes("allreduce",
                              compressed_bytes(nbytes, "float32", mode, n),
                              n)
            wire_f32 = wire_bytes("allreduce", nbytes, n)
            rows.append({
                "mbytes": int(mb), "mode": mode,
                "seconds_per_call": round(best, 6),
                "effective_gbps": round(eff_gbps, 3),
                "gain_vs_f32": (round(base_t / best, 3)
                                if base_t else None),
                "wire_bytes_per_device": int(wire),
                "wire_reduction_vs_f32": round(wire_f32 / wire, 3),
            })
            print(json.dumps({"metric": "c_allreduce_bandwidth_gbps",
                              "value": rows[-1]["effective_gbps"],
                              "unit": "GB/s effective (pre-compression)",
                              "vs_baseline": None, **rows[-1]}),
                  flush=True)
    at16 = [r for r in rows if r["mbytes"] >= 16]
    doc = {
        "metric": "comm_sweep", "n_devices": n, "device_kind": kind,
        "rows": rows,
        "best_gain_int8_at_16mb_plus": max(
            ((r["gain_vs_f32"] or 0) for r in at16 if r["mode"] == "int8"),
            default=None),
        "wire_reduction_int8": min(
            r["wire_reduction_vs_f32"] for r in rows
            if r["mode"] == "int8"),
        "wire_reduction_bf16": min(
            r["wire_reduction_vs_f32"] for r in rows
            if r["mode"] == "bf16"),
        "notes": "effective_gbps is pre-compression payload / wall; on a "
                 "bandwidth-flat host (CPU shared memory) the wall gain "
                 "collapses and wire_reduction_vs_f32 is the TPU-expected "
                 "gain (bandwidth-bound interconnects track on-wire "
                 "bytes).",
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"[bench] comm sweep written to {out_path}", file=sys.stderr)
    return doc


# --------------------------------------------------------------- warm store --

_WARMSTORE_CHILD = r'''
"""Warm-store bench child: one fresh process = one leg.

Trains a small fc net (startup + main program compiles), saves it, and
serves one Predictor request -- timing the first step and the serving
cold start, then reporting the warm-store counters so the parent can
tell a compile from a restore.  The store root arrives via
PADDLE_TPU_WARMSTORE in the environment; argv[1] is a scratch dir.
"""
import json
import os
import sys
import time

import numpy as np

import paddle_tpu as fluid

workdir = sys.argv[1]
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.data("x", [16], "float32")
    label = fluid.data("label", [1], "float32")
    h = fluid.layers.fc(x, 32, act="relu")
    y = fluid.layers.fc(h, 1)
    loss = fluid.layers.mean(fluid.layers.square(y - label))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
main.random_seed = 7

rng = np.random.RandomState(0)
feed = {"x": rng.randn(8, 16).astype("float32"),
        "label": rng.randn(8, 1).astype("float32")}
exe = fluid.Executor()
model_dir = os.path.join(workdir, "model")
with fluid.scope_guard(fluid.Scope()):
    exe.run(startup)
    t0 = time.perf_counter()
    first = exe.run(main, feed=feed, fetch_list=[loss.name])[0]
    t_first_step = time.perf_counter() - t0
    losses = [float(np.asarray(first))]
    for _ in range(2):
        losses.append(float(np.asarray(
            exe.run(main, feed=feed, fetch_list=[loss.name])[0])))
    fluid.io.save_inference_model(model_dir, ["x"], [y], exe, main)

t0 = time.perf_counter()
pred = fluid.inference.Predictor(model_dir)
out, = pred.run({"x": feed["x"]})
t_first_predict = time.perf_counter() - t0

import paddle_tpu.warmstore as ws  # noqa: E402

ws.flush()
from paddle_tpu.observability.metrics import REGISTRY  # noqa: E402


def _total(name, **match):
    fam = REGISTRY.get(name)
    if not fam:
        return 0
    tot = 0
    for lbl, c in fam.items():
        lbl = dict(lbl)
        if any(lbl.get(k) != v for k, v in match.items()):
            continue
        v = getattr(c, "count", None)
        if v is None:
            v = getattr(c, "value", 0)
        tot += int(v or 0)
    return tot


import jax  # noqa: E402

print(json.dumps({
    "platform": jax.devices()[0].platform,
    "device_kind": jax.devices()[0].device_kind,
    "t_first_step": t_first_step,
    "t_first_predict": t_first_predict,
    "executor_compiles": _total("executor_compile_seconds"),
    "warm_restores": _total("warmstore_restore_seconds"),
    "ws_hits": _total("warmstore_hits_total"),
    "ws_tier_b_hits": _total("warmstore_hits_total", tier="b"),
    "ws_misses": _total("warmstore_misses_total"),
    "losses": losses,
    "out_sum": float(np.asarray(out).sum()),
}), flush=True)
'''


def bench_warmstore(out_path="BENCH_WARMSTORE_r01.json"):
    """Warm-start measurement: two identical processes share one store.
    Process A (cold) populates it -- every program is a compile miss;
    process B (warm) must compile strictly fewer programs (tier-B hits
    on the train step, the fused startup, and the Predictor signature)
    and see a smaller first-step wall.  Rows land in ``out_path`` for
    the bench trajectory sentinel (BENCH_WARMSTORE_r*.json).

    The children inherit this process's platform and stamp the rows with
    the device THEY ran on; this parent never touches JAX, so on a TPU
    host each child in turn has the chip to itself."""
    import subprocess
    import tempfile
    here = os.path.dirname(os.path.abspath(__file__))
    results = {}
    with tempfile.TemporaryDirectory(prefix="paddle_tpu_ws_bench_") as td:
        store = os.path.join(td, "store")
        child = os.path.join(td, "child.py")
        with open(child, "w") as f:
            f.write(_WARMSTORE_CHILD)
        for leg in ("cold", "warm"):
            workdir = os.path.join(td, leg)
            os.makedirs(workdir)
            env = dict(os.environ, PADDLE_TPU_WARMSTORE=store,
                       PYTHONPATH=here + os.pathsep
                       + os.environ.get("PYTHONPATH", ""))
            t0 = time.perf_counter()
            p = subprocess.run([sys.executable, child, workdir],
                               capture_output=True, text=True, env=env,
                               timeout=600)
            wall = time.perf_counter() - t0
            if p.returncode != 0:
                return {"error": f"warm-store {leg} leg failed "
                                 f"(rc {p.returncode}): {p.stderr[-800:]}"}
            doc = json.loads(p.stdout.strip().splitlines()[-1])
            doc["process_wall_seconds"] = round(wall, 3)
            results[leg] = doc
    cold, warm = results["cold"], results["warm"]
    if (cold["platform"], cold["device_kind"]) != \
            (warm["platform"], warm["device_kind"]):
        return {"error": f"legs ran on different devices: {cold['platform']}"
                         f"/{cold['device_kind']} vs {warm['platform']}/"
                         f"{warm['device_kind']}"}
    kind = warm["device_kind"]
    identical = cold["losses"] == warm["losses"] and \
        cold["out_sum"] == warm["out_sum"]
    rows = [
        {"metric": "warmstore_cold_first_step_wall_seconds",
         "value": round(cold["t_first_step"], 4),
         "unit": "s (process A: first train step, compile miss)",
         "executor_compiles": cold["executor_compiles"],
         "device_kind": kind},
        {"metric": "warmstore_warm_first_step_wall_seconds",
         "value": round(warm["t_first_step"], 4),
         "unit": "s (process B: first train step, store restore)",
         "speedup_vs_cold": round(
             cold["t_first_step"] / warm["t_first_step"], 2)
         if warm["t_first_step"] else None,
         "device_kind": kind},
        {"metric": "warmstore_cold_first_predict_wall_seconds",
         "value": round(cold["t_first_predict"], 4),
         "unit": "s (process A: Predictor load + first run, AOT compile)",
         "device_kind": kind},
        {"metric": "warmstore_warm_first_predict_wall_seconds",
         "value": round(warm["t_first_predict"], 4),
         "unit": "s (process B: Predictor load + first run, store "
                 "restore)",
         "speedup_vs_cold": round(
             cold["t_first_predict"] / warm["t_first_predict"], 2)
         if warm["t_first_predict"] else None,
         "device_kind": kind},
        {"metric": "warmstore_warm_tier_hits",
         "value": warm["ws_hits"],
         "unit": "store hits in process B (tier b on this build)",
         "tier_b": warm["ws_tier_b_hits"],
         "cold_hits": cold["ws_hits"],
         "cold_misses": cold["ws_misses"],
         "device_kind": kind},
        {"metric": "warmstore_warm_executor_compile_count",
         "value": warm["executor_compiles"],
         "unit": "fresh executor compiles in process B (cold compiled "
                 "strictly more)",
         "cold_compiles": cold["executor_compiles"],
         "warm_restores": warm["warm_restores"],
         "outputs_byte_identical": identical,
         "device_kind": kind},
    ]
    for r in rows:
        print(json.dumps(r), flush=True)
    doc = {"rows": rows, "cold": cold, "warm": warm}
    if warm["executor_compiles"] >= cold["executor_compiles"]:
        doc["error"] = (f"warm leg did not compile strictly fewer "
                        f"programs ({warm['executor_compiles']} vs "
                        f"{cold['executor_compiles']})")
    elif not identical:
        doc["error"] = "warm-leg outputs differ from cold-leg outputs"
    if out_path and "error" not in doc:
        with open(out_path, "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"[bench] warm-store round written to {out_path}",
              file=sys.stderr)
    return doc


def bench_checkpoint(n_saves=4, width=1024):
    """Save-stall microbench: blocked time per checkpoint save with async
    off vs on (ISSUE 9 acceptance).  Sync saves block the training loop
    for the whole serialize+write+rotate; async saves block only for the
    d2h state snapshot, with the write landing on the background thread.
    Writes go to a temp dir; the state is a ~width^2 fp32 MLP (+SGD)."""
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu.observability import journal as _journal
    from paddle_tpu.utils.checkpointer import Checkpointer

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 9
    with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
        x = fluid.data("x", [width], "float32")
        loss = fluid.layers.mean(fluid.layers.fc(
            fluid.layers.fc(x, width), width))
        fluid.optimizer.Momentum(0.01, 0.9).minimize(loss)
    feed = {"x": np.random.RandomState(0).rand(8, width).astype("float32")}
    scope = fluid.Scope()
    out = {}
    with fluid.scope_guard(scope), tempfile.TemporaryDirectory() as td:
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main_p, feed=feed, fetch_list=[loss])
        for mode, async_ in (("sync", False), ("async", True)):
            ck = Checkpointer(exe, main_p, os.path.join(td, mode),
                              max_to_keep=2, async_save=async_)
            blocked = []
            ck.save(0)          # warm (dir creation, first-write costs)
            ck.wait()
            for i in range(1, n_saves + 1):
                # wait() outside the timed region: measured is the stall
                # a training step sees when the previous write has landed
                # (steady state with compute between saves)
                ck.wait()
                t0 = time.perf_counter()
                ck.save(i)
                blocked.append(time.perf_counter() - t0)
            ck.close()
            out[f"blocked_ms_{mode}"] = round(
                1e3 * sum(blocked) / len(blocked), 3)
        writes = [e.get("write_ms") for e in _journal.recent()
                  if e.get("event") == "ckpt_save" and e.get("async")]
        if writes:
            out["write_ms_async"] = round(
                sum(writes[-n_saves:]) / len(writes[-n_saves:]), 3)
        exe.close()
    if out.get("blocked_ms_sync"):
        out["stall_reduction_pct"] = round(
            (1 - out["blocked_ms_async"] / out["blocked_ms_sync"]) * 100, 1)
    return out


def main():
    peak, kind = _peak()

    ck = bench_checkpoint()
    print(json.dumps({
        "metric": "checkpoint_save_blocked_ms_async",
        "value": ck.get("blocked_ms_async"),
        "unit": "ms blocked/save (async d2h snapshot only)",
        "vs_baseline": None,
        "blocked_ms_sync": ck.get("blocked_ms_sync"),
        "write_ms_async_background": ck.get("write_ms_async"),
        "stall_reduction_pct": ck.get("stall_reduction_pct"),
    }), flush=True)

    bert_sps, bert_dt, bert_flops, bert_batch, bert_susp = bench_bert_base()
    seqs = bert_sps * bert_batch
    print(json.dumps({
        "metric": "bert_base_pretrain_steps_per_sec",
        "value": round(bert_sps, 3),
        "unit": f"steps/sec (batch={bert_batch} seq=128)",
        "vs_baseline": round(seqs / 42.0, 3),
        "seqs_per_sec": round(seqs, 1),
        "step_time_ms": round(bert_dt * 1e3, 2),
        **_mfu_field(bert_flops, bert_dt, peak),
        "suspect": bert_susp,
        "device_kind": kind,
    }), flush=True)
    allreduce = bench_allreduce()
    if allreduce is not None:       # one device: no interconnect, no line
        bw, bw_cons, mode, n = allreduce
        from paddle_tpu.utils import bandwidth_sanity
        reported, suspect, bound = bandwidth_sanity(bw, kind, "ici")
        if suspect:
            # differencing exceeded physics: report the overhead-inclusive
            # conservative estimate instead (can only understate), re-clamped
            reported = min(bw_cons, bound)
        print(json.dumps({
            "metric": "c_allreduce_bandwidth_gbps",
            "value": round(reported, 1),
            "unit": "GB/s",
            "vs_baseline": None,
            "mode": mode,
            "n_devices": n,
            "suspect": suspect,
            "raw_estimate": round(bw, 1),
            "physical_bound": round(bound, 1) if bound else None,
            "device_kind": kind,
        }), flush=True)

    rn_ips, rn_dt, rn_flops, rn_susp = bench_resnet50()
    print(json.dumps({
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(rn_ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(rn_ips / 360.0, 3),
        "step_time_ms": round(rn_dt * 1e3, 2),
        **_mfu_field(rn_flops, rn_dt, peak),
        "suspect": rn_susp,
        "device_kind": kind,
    }), flush=True)


def _parse_args(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--emit-metrics", metavar="PATH", default=None,
                    help="after the run, dump the observability metrics "
                         "registry (cache hits, compile/run histograms, "
                         "per-program FLOPs/bytes gauges; MFU too when step "
                         "timing is synchronous -- PADDLE_TPU_OBS=1 or the "
                         "benchmark flag) as JSON to PATH -- pairs the "
                         "BENCH_*.json throughput rounds with telemetry")
    ap.add_argument("--tune", action="store_true",
                    help="pre-tune the bench suites before measuring: run "
                         "the autotuner's empirical search (Pallas-vs-XLA "
                         "backends, flash block sizes) over the ResNet "
                         "conv+BN and attention shapes, persist the winners "
                         "in the decision cache, and let the bench runs "
                         "pick them up (PADDLE_TPU_TUNE=cached default)")
    ap.add_argument("--comm-sweep", metavar="PATH", nargs="?",
                    const="BENCH_COMM_r01.json", default=None,
                    help="run ONLY the quantized-allreduce message-size "
                         "sweep (1..256 MB x f32/bf16/int8 through the "
                         "c_allreduce_avg lowering over a dp mesh of all "
                         "devices) and write the JSON report to PATH "
                         "(default BENCH_COMM_r01.json); needs >=2 "
                         "devices -- on a CPU host export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 first")
    ap.add_argument("--warm-store", metavar="PATH", nargs="?",
                    const="BENCH_WARMSTORE_r01.json", default=None,
                    help="run ONLY the warm-start measurement: two "
                         "identical processes share one "
                         "PADDLE_TPU_WARMSTORE store; the cold leg "
                         "populates it, the warm leg must compile "
                         "strictly fewer programs (tier-B hits on the "
                         "train step and Predictor signature) with "
                         "byte-identical outputs; rows go to PATH "
                         "(default BENCH_WARMSTORE_r01.json)")
    ap.add_argument("--comm-sweep-sizes", default=None,
                    help="comma-separated MB sizes for --comm-sweep "
                         "(default 1,4,16,64,256)")
    ap.add_argument("--emit-hlo", metavar="DIR", default=None,
                    help="capture every compiled program's optimized HLO + "
                         "IR->HLO cost attribution as hlo_<label>.json "
                         "artifacts under DIR (next to the --emit-metrics "
                         "dump); diff two artifacts with python -m "
                         "tools.hlo_diff A B. Degrades with a warning on "
                         "backends without as_text()")
    ap.add_argument("--emit-trace", metavar="PATH", default=None,
                    help="after the run, export the flight-recorder timeline "
                         "(executor feed-prep/dispatch/fetch phase spans, "
                         "RecordEvent host spans, device-memory counter "
                         "track) as Chrome-trace/Perfetto JSON to PATH; "
                         "arms PADDLE_TPU_OBS=1 if unset -- phase spans "
                         "only mean anything with synchronous step timing")
    return ap.parse_args(argv)


if __name__ == "__main__":
    _args = _parse_args()
    if not _args.warm_store:    # that parent stays off JAX (see the leg)
        from paddle_tpu.utils import compile_cache as _compile_cache
        _compile_cache.arm()
    if _args.warm_store:
        _doc = bench_warmstore(out_path=_args.warm_store)
        if "error" in _doc:
            print(f"[bench] warm-store FAILED: {_doc['error']}",
                  file=sys.stderr)
        sys.exit(2 if "error" in _doc else 0)
    if _args.comm_sweep:
        _sizes = tuple(int(s) for s in _args.comm_sweep_sizes.split(",")) \
            if _args.comm_sweep_sizes else (1, 4, 16, 64, 256)
        _doc = bench_comm_sweep(sizes_mb=_sizes, out_path=_args.comm_sweep)
        if _args.emit_metrics:
            from paddle_tpu.observability import export as _obs_export
            _obs_export.dump_json(_args.emit_metrics)
            print(f"[bench] metrics registry written to "
                  f"{_args.emit_metrics}", file=sys.stderr)
        sys.exit(2 if "error" in _doc else 0)
    if _args.emit_trace:
        # arm the host-span recorder so the exported timeline carries
        # RecordEvent spans (one per executor run) next to the flight
        # recorder's feed-prep/dispatch/fetch phases -- and observability
        # itself: without it (or the benchmark flag) the executor never
        # blocks on the step, so dispatch spans would be microseconds of
        # async enqueue and fetch_sync would never record
        os.environ.setdefault("PADDLE_TPU_OBS", "1")
        # the obs toggle also opens the journal sink; unless the user chose
        # a path, keep it next to the trace instead of littering the CWD
        # with a surprise paddle_tpu_obs.jsonl
        os.environ.setdefault("PADDLE_TPU_OBS_JOURNAL",
                              _args.emit_trace + ".journal.jsonl")
        from paddle_tpu import flags as _flagsmod
        from paddle_tpu import profiler as _prof
        _flagsmod.set_flag("profile_executor", True)
        _prof.start_profiler()
    if _args.emit_hlo:
        # arm the attribution capture before any compile happens: every
        # compile miss from here on writes an hlo_<label>.json artifact
        # (HLO text + per-IR-op cost attribution) into the directory
        from paddle_tpu.observability import attribution as _obs_attrib
        _obs_attrib.arm_capture(_args.emit_hlo)
    if _args.tune:
        from paddle_tpu import tuning as _tuning
        _entries = _tuning.tune_suite("all", mode="search")
        _searched = sum(1 for e in _entries if e["source"] == "search")
        print(f"[bench] autotune: {len(_entries)} decisions "
              f"({_searched} newly searched) -> {_tuning.cache.CACHE.path}",
              file=sys.stderr)
    main()
    if _args.emit_trace:
        from paddle_tpu import profiler as _prof
        _prof.stop_profiler(profile_path=os.devnull)
    if _args.emit_metrics:
        # goodput breakdown rides along: classify this process's wall-clock
        # (ledger over the always-on phase spans + journal -- no extra
        # timers ran), publish the gauges/counters into the registry so the
        # dump carries them, and print the per-run summary as a metric line
        from paddle_tpu.observability import goodput as _goodput
        _gr = _goodput.export(_goodput.compute_live())
        print(json.dumps({
            "metric": "goodput_fraction",
            "value": round(_gr.goodput_fraction, 4),
            "unit": "fraction of wall-clock spent in productive step "
                    "execution",
            "vs_baseline": None,
            "wall_seconds": round(_gr.wall_seconds, 3),
            "lost_seconds": {c: round(s, 3)
                             for c, s in sorted(_gr.lost.items()) if s},
        }), flush=True)
        # trajectory sentinel rides along too: scan the checked-in bench
        # rounds so fresh regressions land in this dump as journal
        # bench_regression events + bench_regressions_total counters
        # (same alert/journal plane as the runtime; degrades silently)
        try:
            import glob as globmod
            from tools import bench_compare as _bcmp
            _rounds = sorted(globmod.glob(os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_WORKLOADS_r*.json")))
            if _rounds:
                _cmp = _bcmp.compare_files(
                    _rounds, baseline=os.path.join(
                        os.path.dirname(os.path.abspath(__file__)),
                        "tools", "bench_baseline.jsonl"))
                if _cmp["fresh"]:
                    print(f"[bench] trajectory sentinel: "
                          f"{len(_cmp['fresh'])} fresh regression(s) "
                          f"journaled", file=sys.stderr)
        except Exception as _e:   # the sentinel must never fail a bench
            print(f"[bench] trajectory sentinel skipped: {_e}",
                  file=sys.stderr)
        from paddle_tpu.observability import export as _obs_export
        _obs_export.dump_json(_args.emit_metrics)
        print(f"[bench] metrics registry written to {_args.emit_metrics}",
              file=sys.stderr)
    if _args.emit_hlo:
        from paddle_tpu.observability import attribution as _obs_attrib
        _n_hlo = len([f for f in os.listdir(_args.emit_hlo)
                      if f.startswith("hlo_")])
        print(f"[bench] {_n_hlo} HLO attribution artifact(s) in "
              f"{_args.emit_hlo} (diff: python -m tools.hlo_diff A B)",
              file=sys.stderr)
        _obs_attrib.arm_capture(None)
    if _args.emit_trace:
        from paddle_tpu.observability import timeline as _obs_timeline
        _obs_timeline.export_chrome_trace(_args.emit_trace)
        print(f"[bench] flight-recorder trace written to {_args.emit_trace} "
              f"(load in chrome://tracing or ui.perfetto.dev)",
              file=sys.stderr)
