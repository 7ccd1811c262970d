#!/usr/bin/env python3
"""chip_smoke.py -- the quickest proof that the Program -> Executor path
still starts on the chip.

One process, no network, no git, every input generated from a seed. Drives
the main path through the entry points a user calls (``layers.*`` ->
``Program`` -> ``Executor.run`` / ``train_from_dataset`` ->
``save_inference_model`` -> ``Predictor`` / ``PredictorPool``, and the same
``Program`` under ``CompiledProgram.with_strategy`` when four chips are
there), at the full width of BERT-base, and checks what comes out.

    python3 chip_smoke.py                 # what the driver runs; needs a TPU
    python3 chip_smoke.py --phases train,trace
    python3 chip_smoke.py --cpu-rehearsal # tiny shapes, every line labelled,
                                          # never exits 0: debugging only

It exits non-zero, printing no result line, when JAX finds no TPU; it never
selects a platform itself. Any phase failure propagates: there is no
try/except around a phase. The times it prints are observations stamped
with the device, not metrics. The line before last is ``summary: {...}``
(versions, compile-cache state, per-phase wall / compile seconds); the last
line of stdout is the verdict alone, one JSON object with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

PHASES = ("train", "trace", "kernels", "serve", "dataset", "mesh")

# Full width of BERT-base as bench.py runs it; depth and widths are never cut
# here, the rehearsal sizes exist only so the script can be debugged on a CPU.
FULL = dict(
    bert=dict(vocab_size=30522, hidden=768, n_layers=12, n_heads=12),
    train=dict(batch=128, seq=128, steps=10),
    longseq=dict(batch=4, seq=2048),
    midseq=dict(batch=32, seq=512),     # bert_base.pretrain_s512's attention
    causal=dict(batch=2, heads=16, seq=4096, head_dim=128),  # OLMoE's flash
    int8=4096,
    convbn=dict(batch=128, hw=14, cin=1024, cout=256),  # res4 1x1 expand
    serve=dict(image=224, batches=(1, 16), requests=36),
    dataset=dict(rows=40960, parts=4, batch=4096, vocab=1_000_000),
)
TINY = dict(
    bert=dict(vocab_size=512, hidden=64, n_layers=2, n_heads=4),
    train=dict(batch=8, seq=16, steps=4),
    longseq=dict(batch=1, seq=256),
    midseq=dict(batch=4, seq=128),
    causal=dict(batch=1, heads=2, seq=256, head_dim=32),
    int8=256,
    convbn=dict(batch=7, hw=8, cin=128, cout=128),
    serve=dict(image=32, batches=(1, 4), requests=8),
    dataset=dict(rows=2048, parts=2, batch=256, vocab=1000),
)

#: bf16 has 8 mantissa bits: one rounding is 2^-8 = 0.4% relative. A loss is
#: a mean over thousands of such roundings; two lowerings of the same math
#: are held to 1% of it, single outputs to 2% of the largest reference value.
LOSS_RTOL = 1e-2
BF16_TOL = 2e-2

_LABEL = ""


def say(msg: str = "") -> None:
    print(f"{_LABEL}{msg}", flush=True)


def compile_spans():
    """``(seconds, persistent-cache hits, misses)`` of the program's compiles
    so far, from the spans JAX's own events become under an executor compile
    (``observability/timeline.py``, category ``jax``): the seconds and count
    of ``backend_compile``, of which those with a ``cache_load`` child hit.
    Read off their ``phase_seconds`` histograms, which never wrap. What
    compiles outside an ``Executor`` (the kernels phase's bare ``jax.jit``s,
    eager ``jax.numpy``) is not the program's and is not counted."""
    from paddle_tpu.observability.metrics import REGISTRY

    def of(phase):
        h = REGISTRY.histogram("phase_seconds", phase=phase, cat="jax")
        return h.sum, h.count
    (seconds, compiles), (_, hits) = of("backend_compile"), of("cache_load")
    return (seconds, hits, compiles - hits)


def counter(name: str, **labels) -> int:
    """A counter of the program's own metrics registry."""
    from paddle_tpu.observability.metrics import REGISTRY
    return int(REGISTRY.counter(name, **labels).value)


def executor_compiles() -> int:
    """The executor's own compile counter (compile-cache misses)."""
    return counter("executor_cache_misses_total", cache="compile")


def newest_step(exe):
    """The executor's most recently compiled step."""
    return next(reversed(exe._cache.values()))


def mosaic_calls(hlo_text: str):
    """(forward, backward) counts of Mosaic custom calls in optimized HLO:
    backward where a grad op's scope (``<type>_grad#<idx>``, whether its
    lowering is the op's own or the generic vjp) or a bare ``jax.vjp``'s
    transpose holds the call."""
    lines = [ln for ln in hlo_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    bwd = sum(1 for ln in lines if "transpose(" in ln or "_grad#" in ln)
    return len(lines) - bwd, bwd


# ------------------------------------------------------------------ models --

def bert_program(sizes, batch, seq, dropout, attn_impl="auto", n_masks=20):
    """BERT pretrain (bf16, Adam) with static shapes, as bench.py builds it."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    cfg = bert.BertConfig(dtype="bfloat16", dropout=dropout,
                          max_seq_len=max(512, seq), attn_impl=attn_impl,
                          **sizes["bert"])
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
    return main, startup, total, bert_feed(sizes, batch, seq, n_masks)


def bert_feed(sizes, batch, seq, n_masks=20):
    """One fixed batch for ``bert_program`` (seed 0)."""
    vocab, M = sizes["bert"]["vocab_size"], batch * n_masks
    rng = np.random.RandomState(0)
    return {
        "src_ids": rng.randint(0, vocab, (batch, seq)).astype("int32"),
        "pos_ids": np.tile(np.arange(seq, dtype=np.int32), (batch, 1)),
        "sent_ids": rng.randint(0, 2, (batch, seq)).astype("int32"),
        "input_mask": np.ones((batch, seq), np.float32),
        "mask_pos": rng.randint(0, batch * seq, (M, 1)).astype("int32"),
        "mask_label": rng.randint(0, vocab, (M, 1)).astype("int32"),
        "nsp_label": rng.randint(0, 2, (batch, 1)).astype("int32"),
    }


def first_loss(main, startup, loss, feed):
    """Startup + one step on a fresh scope; (loss, executor)."""
    import paddle_tpu as fluid
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        lv, = exe.run(main, feed=feed, fetch_list=[loss])
    return float(np.asarray(lv).reshape(-1)[0]), exe


# ------------------------------------------------------------------ phases --

def phase_train(sizes, ctx):
    """BERT-base pretrain, full width, through Executor.run."""
    import jax
    import paddle_tpu as fluid

    t = sizes["train"]
    main, startup, total, feed = bert_program(sizes, t["batch"], t["seq"],
                                              dropout=0.1)
    feed = {k: jax.device_put(v) for k, v in feed.items()}
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        before_main = executor_compiles()
        losses = []
        for _ in range(3):                       # warm-up (first one compiles)
            out = exe.run(main, feed=feed, fetch_list=[total])
            losses.append(float(out[0].reshape(-1)[0]))
        after_warm = executor_compiles()
        t0 = time.perf_counter()
        for _ in range(t["steps"]):
            out = exe.run(main, feed=feed, fetch_list=[total])
            losses.append(float(out[0].reshape(-1)[0]))
        step_s = (time.perf_counter() - t0) / t["steps"]
    assert isinstance(out[0], np.ndarray), type(out[0])
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert after_warm - before_main == 1, \
        f"{after_warm - before_main} compiles of the train step in warm-up"
    assert executor_compiles() == after_warm, \
        f"{executor_compiles() - after_warm} recompiles after warm-up"
    step = newest_step(exe)
    assert step.executable is not None, "AOT path not taken"
    platforms = {d.platform for n in scope.var_names()
                 for d in scope.find_var(n).devices()}
    assert platforms == {ctx["platform"]}, \
        f"state buffers live on {platforms}"
    ctx["train"] = (exe, scope, main, total, feed)
    return {"asserted": "loss finite and falling; state on device; one "
                        "compile, none after warm-up; numpy fetch; AOT",
            "loss_first": round(losses[0], 4), "loss_last": round(losses[-1], 4),
            "state_vars": len(scope.var_names()),
            "observed_step_seconds_with_loss_fetch": round(step_s, 4)}


def phase_trace(sizes, ctx):
    """jax.profiler around 3 warm BERT steps of the train phase's executor.
    The TPU plane's op events are named by HLO instruction; joined to the
    compiled step's HLO (observability.attribution) they resolve to the
    ``named_scope("<op_type>#<idx>")`` of the Program op that produced them
    -- the join a trace -> metrics reduction needs."""
    import glob
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.observability import attribution

    exe, scope, main, total, feed = ctx["train"]
    comps, _, _ = attribution.parse_hlo_computations(
        newest_step(exe).executable.as_text())
    scope_of = {i.name: i.ir_op() for instrs in comps.values()
                for i in instrs}
    instr_name = re.compile(r"^%?([\w.\-]+) = ")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_trace_") as d:
        jax.profiler.start_trace(d)
        with fluid.scope_guard(scope):
            for _ in range(3):
                out = exe.run(main, feed=feed, fetch_list=[total])
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        assert len(paths) == 1, paths
        with open(paths[0], "rb") as f:
            raw = f.read()
        data = jax.profiler.ProfileData.from_file(paths[0])
        planes, op_events, scoped_ns = {}, 0, {}
        for plane in data.planes:
            planes[plane.name] = 0
            for line in plane.lines:
                for ev in line.events:
                    planes[plane.name] += 1
                    if not (plane.name.startswith("/device:TPU:")
                            and line.name == "XLA Ops"):
                        continue
                    op_events += 1
                    m = instr_name.match(ev.name)
                    ir_op = scope_of.get(m.group(1)) if m else None
                    if ir_op:
                        scoped_ns[ir_op] = scoped_ns.get(ir_op, 0.0) \
                            + ev.duration_ns
    assert np.isfinite(out[0]).all()
    on_tpu = ctx["platform"] == "tpu"
    want = "/device:TPU:" if on_tpu else "/host:"
    assert any(n.startswith(want) and c for n, c in planes.items()), \
        f"no {want} plane with events in {planes}"
    if on_tpu:
        assert scoped_ns, "no device op event resolves to '<op_type>#<idx>'"
    top = sorted(scoped_ns.items(), key=lambda kv: -kv[1])[:4]
    return {"asserted": "xplane has a TPU device plane with events; its op "
                        "events resolve to named_scope '<op_type>#<idx>' "
                        "through the compiled HLO",
            "xplane_bytes": len(raw),
            "planes": {n: c for n, c in planes.items() if c},
            "device_op_events": op_events,
            "scopes_resolved": len(scoped_ns),
            "scope_token_in_xplane_bytes": bool(
                re.search(rb"[a-z][a-z0-9_]*#\d+", raw)),
            "observed_top_scopes_device_ms_3_steps": {
                k: round(v / 1e6, 2) for k, v in top}}


def phase_kernels(sizes, ctx):
    """The Pallas kernels under Mosaic: flash attention (forward, backward,
    in-kernel dropout PRNG) inside the long-sequence BERT step, the int8
    matmul through quantized_mul, and the fused conv+BN op."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.contrib import quantize
    from paddle_tpu.core import registry

    on_tpu = ctx["platform"] == "tpu"
    ls = sizes["longseq"]
    facts = {}

    def long_step(dropout, attn_impl):
        """First-step loss of the long-sequence model and the Mosaic
        (forward, backward) call counts of its compiled step."""
        loss, exe = first_loss(*bert_program(
            sizes, ls["batch"], ls["seq"], dropout=dropout,
            attn_impl=attn_impl))
        calls = mosaic_calls(newest_step(exe).executable.as_text())
        exe.close()
        assert np.isfinite(loss), loss
        return loss, calls

    # forward + backward with dropout: the step that trains at S=2048
    loss_d, (fwd, bwd) = long_step(0.1, "auto")
    if on_tpu:
        assert fwd >= 1 and bwd >= 1, \
            f"flash kernel not in the compiled step (fwd {fwd}, bwd {bwd})"
    facts["flash_dropout_step"] = {"loss": round(loss_d, 4),
                                   "mosaic_fwd": fwd, "mosaic_bwd": bwd}

    # dropout off: kernel and composed lowering must agree on the first loss
    loss_auto, calls = long_step(0.0, "auto")
    loss_comp, calls_comp = long_step(0.0, "composed")
    assert calls_comp == (0, 0), calls_comp
    if on_tpu:
        assert min(calls) >= 1, calls
    assert abs(loss_auto - loss_comp) <= LOSS_RTOL * abs(loss_comp), \
        f"flash {loss_auto} vs composed {loss_comp}"
    facts["flash_vs_composed"] = {"loss_auto": round(loss_auto, 5),
                                  "loss_composed": round(loss_comp, 5),
                                  "rtol": LOSS_RTOL}

    # The kernel's forward and backward must draw the same dropout mask. With
    # the seed fixed the output is linear in V, so <g, f(v2)> == <dV(g), v2>
    # for any g, v2, and only then: an independent mask on either side moves
    # a tenth of the terms. Each product carries one bf16 rounding (half a
    # step, 2^-9) of its kernel-made factor, out or dV, and one of the
    # probabilities inside; independent, so each side is off by about
    # sqrt(2) x 2^-9 x the 2-norm of its terms. Held to 2^-8 x both norms.
    # Three times: BERT's shape (d=64, padding bias, dropout) at S=2048 and
    # at S=512 (the kernels' shortest cell), and the decoder's (causal,
    # d=128, S=4096, no bias, no dropout), where the identity holds only if
    # both kernels mask the same triangle. And each time two ways: through
    # jax.vjp of the kernels (the custom VJP, its own statistics) and
    # through a Program, whose grad op draws the forward op's seed again
    # and hands the forward op's Lse to the backward kernel.
    from paddle_tpu.ops import pallas_attention as pa

    def adjoint(b, heads, seq, d, dropout, causal, padded):
        rng = np.random.RandomState(3)
        q, k, g, v2 = (jnp.asarray(rng.randn(b, heads, seq, d), jnp.bfloat16)
                       for _ in range(4))
        bias = jnp.asarray(
            np.where(np.arange(seq) < seq - seq // 8, 0.0, -1e4)
            .reshape(1, 1, 1, seq).repeat(b, 0), jnp.float32) \
            if padded else None

        def attend(v):
            if on_tpu:
                return pa._flash(q, k, v, bias, jnp.int32(11), d ** -0.5,
                                 dropout, causal, False)
            return pa.composed_attention(q, k, v, bias, d ** -0.5, dropout,
                                         causal, jax.random.PRNGKey(11))

        def through_vjp(v2, g):
            out, vjp = jax.vjp(attend, v2)
            return out, vjp(g)[0]

        def through_program(v2, g):
            """(out, dV) of one run of a Program: the fused_attention op and
            the fused_attention_grad op that append_backward gives it."""
            main, startup = fluid.Program(), fluid.Program()
            feed = {"q": q, "k": k, "v": v2, "g": g}
            if padded:
                feed["bias"] = bias
            with fluid.unique_name.guard(), \
                    fluid.program_guard(main, startup):
                data = {n: fluid.data(n, list(x.shape), str(x.dtype),
                                      append_batch_size=False)
                        for n, x in feed.items()}
                data["v"].stop_gradient = False
                v = fluid.layers.scale(data["v"], 1.0)
                out = fluid.layers.fused_attention(
                    data["q"], data["k"], v, bias=data.get("bias"),
                    scale=d ** -0.5, dropout_prob=dropout, causal=causal)
                cast = fluid.layers.cast
                fluid.append_backward(fluid.layers.reduce_sum(
                    fluid.layers.elementwise_mul(cast(out, "float32"),
                                                 cast(data["g"], "float32"))))
            exe = fluid.Executor()
            with fluid.scope_guard(fluid.Scope()):
                exe.run(startup)
                got = exe.run(main, feed=feed, fetch_list=[out, "v@GRAD"])
                step = newest_step(exe).executable.as_text()
            exe.close()
            if on_tpu:
                assert mosaic_calls(step) == (1, 1), \
                    f"a Program's attention at {(b, heads, seq, d)} holds " \
                    f"{mosaic_calls(step)} (forward, backward) kernels"
            return jnp.asarray(got[0]), jnp.asarray(got[1])

        @jax.jit
        def adjoint_sides(out, dv, v2, g):
            f32 = jnp.float32
            lhs = g.astype(f32) * out.astype(f32)
            rhs = dv.astype(f32) * v2.astype(f32)
            return (lhs.sum(), rhs.sum(),
                    jnp.sqrt((lhs * lhs).sum()) + jnp.sqrt((rhs * rhs).sum()))

        fact = {}
        for way, sides in (("vjp", jax.jit(through_vjp)),
                           ("program", through_program)):
            lhs, rhs, norms = (float(x) for x in adjoint_sides(
                *sides(v2, g), v2, g))
            assert abs(lhs - rhs) <= 2.0 ** -8 * norms, \
                f"flash forward and backward disagree at " \
                f"{(b, heads, seq, d)} causal={causal} through {way}: " \
                f"<g, f(v2)> {lhs} vs <dV(g), v2> {rhs}, " \
                f"allowed {2.0 ** -8 * norms}"
            fact[way] = {"lhs": lhs, "rhs": rhs, "allowed": 2.0 ** -8 * norms}
        return fact

    heads = sizes["bert"]["n_heads"]
    facts["flash_dropout_adjoint"] = adjoint(
        ls["batch"], heads, ls["seq"], sizes["bert"]["hidden"] // heads,
        0.1, False, True)
    ms = sizes["midseq"]        # one Q block a head (block_q = S)
    facts["flash_dropout_adjoint_s512"] = adjoint(
        ms["batch"], heads, ms["seq"], sizes["bert"]["hidden"] // heads,
        0.1, False, True)
    c = sizes["causal"]
    facts["flash_causal_adjoint"] = adjoint(
        c["batch"], c["heads"], c["seq"], c["head_dim"], 0.0, True, False)

    # int8 matmul: fc -> quantize_weights(int8_compute) -> quantized_mul
    n = sizes["int8"]
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data("x", [n, n], "bfloat16", append_batch_size=False)
        y = fluid.layers.fc(x, n, bias_attr=False,
                            param_attr=fluid.ParamAttr(name="w"))
    exe = fluid.Executor()
    scope = fluid.Scope()
    xv = jnp.asarray(np.random.RandomState(1).randn(n, n), jnp.bfloat16)
    with fluid.scope_guard(scope):
        exe.run(startup)
        quantize.quantize_weights(main, scope, int8_compute=True)
        got, = exe.run(main, feed={"x": xv}, fetch_list=[y])
        ref = jax.jit(quantize.int8_matmul_xla)(
            xv, scope.find_var("w"), scope.find_var("w@scale"))
    assert [op.type for op in main.global_block().ops] == ["quantized_mul"]
    fwd, _ = mosaic_calls(newest_step(exe).executable.as_text())
    exe.close()
    if on_tpu:
        assert fwd == 1, "quantized_mul did not lower to the Pallas kernel"
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.abs(got - ref).max())
    assert err <= BF16_TOL * float(np.abs(ref).max()), (err, np.abs(ref).max())
    facts["int8_matmul"] = {"n": n, "mosaic_calls": fwd, "max_abs_err": err,
                            "ref_abs_max": float(np.abs(ref).max())}

    # fused 1x1-conv + BN op (default backend: the Pallas kernel wherever it
    # can run) against plain f32 jnp. ROADMAP D2 deletes this path; a
    # refusal would be recorded, not repaired -- Mosaic accepts it.
    c = sizes["convbn"]
    rng = np.random.RandomState(2)
    xs = jnp.asarray(rng.randn(c["batch"], c["hw"], c["hw"], c["cin"]),
                     jnp.bfloat16)
    w = jnp.asarray(rng.randn(c["cout"], c["cin"], 1, 1) * 0.03, jnp.bfloat16)
    ones = jnp.ones((c["cout"],), jnp.float32)
    zeros = jnp.zeros((c["cout"],), jnp.float32)

    def fused(xs, w):
        lctx = registry.LowerCtx({"epsilon": 1e-5, "momentum": 0.9})
        return registry.get("conv2d_bn_fused").lower(lctx, {
            "Input": [xs], "Filter": [w], "Scale": [ones], "Bias": [zeros],
            "Mean": [zeros], "Variance": [ones]})["Y"][0]

    compiled = jax.jit(fused).lower(xs, w).compile()
    fwd, _ = mosaic_calls(compiled.as_text())
    if on_tpu:
        assert fwd == 1, "conv2d_bn_fused did not lower to the Pallas kernel"
    yv = np.asarray(compiled(xs, w), np.float32)
    x2 = np.asarray(xs, np.float32).reshape(-1, c["cin"])
    y2 = np.asarray(jnp.asarray(
        x2 @ np.asarray(w, np.float32).reshape(c["cout"], c["cin"]).T,
        jnp.bfloat16), np.float32)
    want = (y2 - y2.mean(0)) / np.sqrt(y2.var(0) + 1e-5)
    err = float(np.abs(yv.reshape(want.shape) - want).max())
    assert err <= BF16_TOL * float(np.abs(want).max()), err
    facts["conv2d_bn_fused"] = {"mosaic_calls": fwd, "max_abs_err": err}
    return {"asserted": "Mosaic custom calls in the S=2048 step (fwd+bwd, "
                        "dropout 0.1), one dropout mask in the flash "
                        "forward and backward (adjoint identity), the int8 "
                        "matmul and conv+BN; "
                        "numerics within bf16 tolerance of the XLA paths",
            **facts}


def phase_serve(sizes, ctx):
    """ResNet-50 (bf16, NHWC): save_inference_model -> Predictor.run ->
    PredictorPool.submit from two threads."""
    import ml_dtypes
    import paddle_tpu as fluid
    from paddle_tpu.inference import Predictor
    from paddle_tpu.models import resnet
    from paddle_tpu.serving.pool import PredictorPool

    s = sizes["serve"]
    image, (b_small, b_big) = s["image"], s["batches"]
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data("img", [image, image, 3], "bfloat16")
        logits = resnet.resnet50(img, None, is_test=True, data_format="NHWC")
    xs = np.random.RandomState(3).rand(b_big, image, image, 3).astype(
        ml_dtypes.bfloat16)
    misses = lambda: counter(                       # noqa: E731
        "predictor_executable_cache_total", outcome="miss")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_model_") as d:
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            fluid.io.save_inference_model(d, ["img"], [logits], exe,
                                          main_program=main)
        exe.close()
        m0 = misses()
        pred = Predictor(d)
        t0 = time.perf_counter()
        solo_small, = pred.run({"img": xs[:b_small]})
        solo, = pred.run({"img": xs})
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        again, = pred.run({"img": xs})
        warm_s = time.perf_counter() - t0
        assert isinstance(solo, np.ndarray) and solo.shape == (b_big, 1000)
        assert solo_small.shape == (b_small, 1000)
        solo = solo.astype(np.float32)
        assert np.isfinite(solo).all()
        assert np.array_equal(again.astype(np.float32), solo)
        scale = float(np.abs(solo).max())
        assert np.abs(solo_small.astype(np.float32) - solo[:b_small]).max() \
            <= BF16_TOL * scale

        # requests are row ranges of the solo batch, so every answer has a
        # solo row to equal; row counts 1..5 land in pow2 buckets up to b_big
        rng = np.random.RandomState(4)
        spans = []
        for _ in range(s["requests"]):
            rows = int(rng.randint(1, min(5, b_big) + 1))
            lo = int(rng.randint(0, b_big - rows + 1))
            spans.append((lo, lo + rows))
        pool = PredictorPool(predictors=[pred], max_batch=b_big,
                             max_wait_ms=5.0)
        futures = [None] * len(spans)

        def client(idx):
            for i in idx:
                lo, hi = spans[i]
                futures[i] = pool.submit({"img": xs[lo:hi]})

        threads = [threading.Thread(target=client,
                                    args=(range(k, len(spans), 2),))
                   for k in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
            assert not th.is_alive(), "client thread did not finish"
        worst = 0.0
        for (lo, hi), fut in zip(spans, futures):
            out, = fut.result(600)
            assert out.shape == (hi - lo, 1000), out.shape
            worst = max(worst, float(np.abs(
                out.astype(np.float32) - solo[lo:hi]).max()))
        pool.close()
        signatures = len(pred._compiled)
        compiled = misses() - m0
    assert worst <= BF16_TOL * scale, \
        f"pool rows differ from solo rows by {worst} (scale {scale})"
    assert compiled == signatures, \
        f"{compiled} Predictor compiles for {signatures} signatures"
    return {"asserted": "Predictor.run numpy in/out at two batch sizes; "
                        "pool answers equal the solo rows (bf16 tolerance "
                        "across row buckets); no signature compiled twice",
            "signatures_compiled": signatures, "requests": len(spans),
            "pool_vs_solo_max_abs_err": worst, "solo_abs_max": scale,
            "observed_two_cold_runs_seconds": round(cold_s, 3),
            f"observed_warm_run_b{b_big}_seconds": round(warm_s, 4),
            "pool_device_note": "every pool worker device_puts with no "
                                "device: all share device 0"}


def phase_dataset(sizes, ctx):
    """DeepFM: part files -> QueueDataset -> native parse -> prefetch ->
    train_from_dataset."""
    import paddle_tpu as fluid
    from paddle_tpu import native
    from paddle_tpu.models import deepfm

    s = sizes["dataset"]
    fields, batch = 26, s["batch"]
    assert native.available(), "native parser not built (no g++?)"
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0
    startup.random_seed = 0
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        A = dict(append_batch_size=False)
        ids = fluid.data("ids", [batch, fields], "int64", **A)
        dense = fluid.data("dense", [batch, 13], "float32", **A)
        label = fluid.data("label", [batch, 1], "int64", **A)
        loss, _, _ = deepfm.deepfm(ids, dense, label, num_fields=fields,
                                   vocab_size=s["vocab"], embed_dim=16)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    rng = np.random.RandomState(5)
    runs = lambda: counter("executor_runs_total")   # noqa: E731
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ctr_") as d:
        paths = []
        per_part = s["rows"] // s["parts"]
        for p in range(s["parts"]):
            paths.append(os.path.join(d, f"part-{p}.txt"))
            idv = rng.randint(0, s["vocab"], (per_part, fields))
            dv = rng.rand(per_part, 13)
            lv = rng.randint(0, 2, per_part)
            with open(paths[-1], "w") as f:
                for r in range(per_part):
                    f.write(" ".join(map(str, idv[r])) + ";" +
                            " ".join(f"{v:.4f}" for v in dv[r]) + ";" +
                            f"{lv[r]}\n")
        ds = fluid.DatasetFactory().create_dataset("QueueDataset")
        ds.set_batch_size(batch)
        ds.set_thread(4)
        ds.set_use_var([ids, dense, label])
        ds.set_filelist(paths)
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            r0 = runs()
            t0 = time.perf_counter()
            last = exe.train_from_dataset(main, dataset=ds,
                                          fetch_list=[loss])
            epoch_s = time.perf_counter() - t0
            steps = runs() - r0
        exe.close()
    examples = steps * batch
    assert examples == s["rows"], f"{examples} examples of {s['rows']} rows"
    assert np.isfinite(last[0]).all()
    return {"asserted": "native parser built from source; the epoch's "
                        "example count is exact; loss finite",
            "examples": examples, "steps": steps,
            "loss_last": round(float(np.asarray(last[0]).reshape(-1)[0]), 4),
            "observed_epoch_seconds_with_compile": round(epoch_s, 3)}


def phase_mesh(sizes, ctx):
    """One process, four chips: BERT-base under CompiledProgram.with_strategy
    as dp=4 and dp=2 x mp=2, dp=4 again at bert_base.pretrain_s512's length
    (where one chip takes the flash kernels and a mesh without sp must not:
    GSPMD cannot partition a Mosaic call), then the dry run's sp / ep / pp
    layouts."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert
    import __graft_entry__ as layouts

    t = sizes["train"]
    build = lambda: bert_program(sizes, t["batch"], t["seq"],  # noqa: E731
                                 dropout=0.0)[:3]
    feed = bert_feed(sizes, t["batch"], t["seq"])
    data_rules = [("mask_pos|mask_label", ()), ("nsp_label", ("dp",)),
                  ("src_ids|pos_ids|sent_ids|input_mask", ("dp",))]
    hidden = sizes["bert"]["hidden"]
    one_chip = layouts.one_device_loss(build, feed)
    records = [
        layouts.run_layout(
            "bert-base dp4", build, feed,
            fluid.DistributedStrategy(mesh_shape={"dp": 4},
                                      data_rules=data_rules),
            "layer0_attn_qkv_w", want_shard=(hidden, 3 * hidden),
            tol=LOSS_RTOL, reference_loss=one_chip),
        layouts.run_layout(
            "bert-base dp2 x mp2", build, feed,
            fluid.DistributedStrategy(mesh_shape={"dp": 2, "mp": 2},
                                      param_rules=bert.tp_param_rules(),
                                      data_rules=data_rules),
            "layer0_attn_qkv_w", want_shard=(hidden, 3 * hidden // 2),
            tol=LOSS_RTOL, reference_loss=one_chip),
    ]
    # the length at which one chip takes the flash kernels: the dp mesh has
    # to keep XLA's composed lowering and still give the one-device loss
    from paddle_tpu.observability.metrics import REGISTRY
    m = sizes["midseq"]

    def lowerings():
        total = {}
        for k, c in (REGISTRY.get("attention_lowering_total") or {}).items():
            if dict(k)["s"] == str(m["seq"]):
                impl = dict(k)["impl"]
                total[impl] = total.get(impl, 0) + c.value
        return total
    mid_build = lambda: bert_program(                       # noqa: E731
        sizes, m["batch"], m["seq"], dropout=0.0)[:3]
    mid_feed = bert_feed(sizes, m["batch"], m["seq"])
    mid_one_chip = layouts.one_device_loss(mid_build, mid_feed)
    before = lowerings()
    records.append(layouts.run_layout(
        f"bert-base dp4 S={m['seq']}", mid_build, mid_feed,
        fluid.DistributedStrategy(mesh_shape={"dp": 4},
                                  data_rules=data_rules),
        "layer0_attn_qkv_w", want_shard=(hidden, 3 * hidden),
        tol=LOSS_RTOL, reference_loss=mid_one_chip))
    under_dp = {k: int(v - before.get(k, 0)) for k, v in lowerings().items()
                if v != before.get(k, 0)}
    say(f"  mesh: fused_attention at S={m['seq']}: one device "
        f"{json.dumps({k: int(v) for k, v in before.items()})}, dp4 "
        f"{json.dumps(under_dp)}")
    assert set(under_dp) == {"xla"}, under_dp
    records += layouts.run_layouts(4)
    for rec in records:
        say(f"  mesh: {json.dumps(rec)}")
    return {"asserted": "per layout: first-step loss equals the one-device "
                        "loss; params and feeds on four distinct devices "
                        "with the shard shapes the specs imply; bytes in "
                        "use grew on every device; dp4 at the length one "
                        "chip gives the flash kernels lowers every "
                        "fused_attention on XLA's composed path",
            "layouts": [r["layout"] for r in records]}


# -------------------------------------------------------------------- main --

def main(argv=None) -> int:
    global _LABEL
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma-separated subset of {','.join(PHASES)}")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="debug the script without a TPU: tiny shapes, "
                         "every line labelled, exit code 3, never a pass")
    args = ap.parse_args(argv)
    wanted = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(wanted) - set(PHASES))
    if unknown:
        ap.error(f"unknown phases {unknown}")
    if "trace" in wanted and "train" not in wanted:
        ap.error("the trace phase profiles the train phase's executor")

    import jax
    import jaxlib
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    rehearsal = args.cpu_rehearsal
    if rehearsal:
        _LABEL = f"[cpu-rehearsal on {dev.platform}, not a chip run] "
    say(f"jax {jax.__version__}  platform {dev.platform}  device_kind "
        f"{dev.device_kind}  device_count {jax.device_count()}")
    if dev.platform != "tpu" and not rehearsal:
        print(f"chip_smoke: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU; nothing was run",
              file=sys.stderr)
        return 2
    if dev.platform == "tpu" and rehearsal:
        ap.error("--cpu-rehearsal on a TPU: run without it")

    from paddle_tpu.tuning import cache as tune_cache
    from paddle_tpu.utils import compile_cache
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    cache_dir = compile_cache.arm()
    entries_at_start = compile_cache.entry_count(cache_dir)
    tune_cache.CACHE.load()
    say(f"jaxlib {jaxlib.__version__}  libtpu {libtpu_version}  python "
        f"{sys.version.split()[0]}")
    say(f"compile cache {cache_dir} ({entries_at_start} entries at start, "
        f"placed by {'env' if os.environ.get(compile_cache.ENV) else 'helper'})")
    say(f"autotune decisions {tune_cache.CACHE.path} "
        f"({len(tune_cache.CACHE.items())} entries, mode {tune_cache.mode()})")

    sizes = TINY if rehearsal else FULL
    ctx = {"platform": dev.platform}
    phases = {}
    t_all = time.perf_counter()
    for name in PHASES:
        if name not in wanted:
            continue
        if name == "mesh" and jax.device_count() < 4:
            phases["mesh"] = f"not run ({jax.device_count()} device)"
            say(f"mesh: {phases['mesh']}")
            continue
        s0, h0, m0 = compile_spans()
        t0 = time.perf_counter()
        facts = globals()[f"phase_{name}"](sizes, ctx)
        wall = time.perf_counter() - t0
        if name == "trace" or "trace" not in wanted:
            ctx.pop("train", None)               # free the BERT state
        s1, h1, m1 = compile_spans()
        phases[name] = {"ok": True, "wall_seconds": round(wall, 2),
                        "compile_seconds": round(s1 - s0, 2),
                        "cache_hits": h1 - h0, "cache_misses": m1 - m0}
        say(f"{name}: ok  wall {wall:.1f}s  compile {s1 - s0:.1f}s  "
            f"persistent-cache hits {h1 - h0} misses {m1 - m0}")
        for k, v in facts.items():
            say(f"  {k}: {v}")
    seconds, hits, misses = compile_spans()
    summary = {
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version},
        "compile_cache": {"dir": cache_dir,
                          "entries_at_start": entries_at_start,
                          "entries_at_end": compile_cache.entry_count(
                              cache_dir),
                          "hits": hits, "misses": misses},
        "compile_seconds": round(seconds, 2),
        "wall_seconds": round(time.perf_counter() - t_all, 2),
        "phases": phases,
    }
    if "mesh" not in phases:
        summary["phases"]["mesh"] = "not run (not asked for)"
    say(f"summary: {json.dumps(summary)}")
    # The verdict line: these keys and no others. Every phase that ran
    # passed, or its exception ended the process above.
    say(json.dumps({"ok": not rehearsal, "device": device}))
    return 3 if rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
