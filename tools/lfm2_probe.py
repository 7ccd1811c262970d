"""What the benchmark's harness does not fetch from the cell
``lfm2_8b_a1b.pretrain_s4096`` (it reads the loss alone): the builder's and
the reviewer's chip readings of PERF.md section 6, PR 32. On a TPU through
``chiprun -- python3 tools/lfm2_probe.py <mode> ...``; ``--rehearsal`` runs
the data files' rehearsal sizes on the CPU (a debug run: no device number).

``load``      the job's own set-up, then ``--steps`` train steps fetching
              every expert layer's load and selection bias beside the loss:
              the share of the tokens x top-k assignments held here, by
              windows of 20 steps; the bias against the rule stepped on the
              fetched loads; the reference check once more on the moved state.
``controls``  at the cell's own check (seeded weights, zero bias, before any
              step): the reference check as it is, with float8 (e4m3) weights
              in the program's place (must fail the check), and with the
              parts the configuration states in float32 (RMSNorm, router,
              short convolution) lowered in bfloat16 (a reading: on the chip
              it stays inside the program's own range, PERF.md section 7).
``grads``     one train step at ``--batch`` x ``--seq`` and the published
              widths: every parameter's gradient as the step computes it (the
              Pallas kernels' backward: megablox ``gmm`` / ``tgmm`` over the
              held groups, the grouped-query flash backward, ``short_conv``)
              against ``jax.grad`` of the plain float32 reference, by leaf.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "lfm2_8b_a1b.pretrain_s4096"
_T0 = time.perf_counter()


def say(message: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.2f}s] {message}", flush=True)


def load_cell(args) -> dict:
    from benchmark import run
    cell = run.load_cell(CELL, args.rehearsal)
    for key in ("batch", "seq", "ring"):
        if getattr(args, key, None):
            cell["params"][key] = getattr(args, key)
    if getattr(args, "lr", None):
        cell["model"]["learning_rate"] = args.lr
    return cell


def held_shares(args) -> dict:
    from benchmark.jobs import common, train_feed
    cell = load_cell(args)
    s = train_feed.setup(cell, args.seed, say)
    built, model = s.built, s.model
    tokens = s.params["batch"] * s.params["seq"]
    k, held = model["num_experts_per_tok"], model["num_experts"]
    n = len(built["expert_load"])
    names = built["expert_load"] + built["expert_bias"]
    bias = np.stack([np.asarray(s.scope.find_var(v))
                     for v in built["expert_bias"]])
    shares, losses, uneven, worst = [], [], [], 0.0
    for _ in range(args.steps):
        out = s.exe.run(s.program, feed=s.ring[s.step % len(s.ring)],
                        fetch_list=[s.loss] + names, scope=s.scope)
        s.step += 1
        losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        load = np.stack(out[1:1 + n]).astype(np.float64)
        assert (load.sum(1) == tokens * k).all(), load.sum(1)
        shares.append(load[:, :held].sum(1) / (tokens * k))
        uneven.append(load.max(1) / load.mean(1))
        bias = bias + np.float32(model["bias_update_rate"]) * np.sign(
            load.mean(1, keepdims=True) - load).astype(np.float32)
        worst = max(worst, float(np.abs(np.stack(out[1 + n:]) - bias).max()))
    shares, uneven = np.array(shares), np.array(uneven)
    say(f"ring {len(s.ring)}, lr {model['learning_rate']}, {args.steps} "
        f"steps after the 2 of warm-up; loss every 10th step: "
        + " ".join(f"{v:.3f}" for v in losses[::10]) + f" last {losses[-1]:.4f}")
    say("held share of tokens x top-k, all layers, by windows of 20 steps: "
        + " ".join(f"{shares[i:i + 20].mean():.4f}"
                   for i in range(0, len(shares), 20)))
    say(f"by layer over all steps {shares.mean(0).round(4).tolist()}; single "
        f"step and layer min {shares.min():.4f} max {shares.max():.4f}; "
        f"all {shares.mean():.4f}")
    say("max load / mean over the routed experts, mean by windows of 20 "
        "steps: " + " ".join(f"{uneven[i:i + 20].mean():.3f}"
                             for i in range(0, len(uneven), 20)))
    say(f"bias against the rule stepped on the fetched loads: worst "
        f"|difference| {worst:.3e}; |bias| max {np.abs(bias).max():.4f}")
    ok = common.reference_check(
        s, s.builder.batch(s.model, s.params, np.random.RandomState(args.seed + 1)))
    result = {"mode": "load", "seed": args.seed, "ring": len(s.ring),
              "lr": model["learning_rate"], "steps": args.steps,
              "share": float(shares.mean()),
              "share_by_20": [float(shares[i:i + 20].mean())
                              for i in range(0, len(shares), 20)],
              "share_min": float(shares.min()),
              "share_max": float(shares.max()),
              "loss_first": s.first_loss, "loss_last": losses[-1],
              "bias_error": worst, "reference_after": ok}
    s.close()
    return result


def _bfloat16_lowerings():
    """The three ops whose arithmetic the configuration states in float32,
    in bfloat16 throughout (their results too, as they already are)."""
    import jax
    import jax.numpy as jnp
    bf = jnp.bfloat16

    def rms_norm(ctx, ins):
        x = ins["X"][0].astype(bf)
        y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + jnp.asarray(ctx.attr("epsilon", 1e-5), bf))
        scale = ins.get("Scale", [None])[0]
        if scale is not None:
            y = y * scale.astype(bf)
        return {"Y": [y.astype(ins["X"][0].dtype)]}

    def moe_router(ctx, ins):
        assert ctx.attr("scoring", "softmax") == "sigmoid"
        score = jax.nn.sigmoid(jnp.dot(ins["X"][0].astype(bf),
                                       ins["W"][0].astype(bf)))
        bias = ins.get("Bias", [None])[0]
        _, index = jax.lax.top_k(
            score if bias is None else score + bias.astype(bf),
            int(ctx.attr("k")))
        weight = jnp.take_along_axis(score, index, axis=-1)
        if ctx.attr("norm_topk", False):
            weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                               + jnp.asarray(1e-6, bf))
        weight = (weight * jnp.asarray(ctx.attr("scale", 1.0), bf))
        return {"Weight": [weight.astype(jnp.float32)],
                "Index": [index.astype(jnp.int32)],
                "Prob": [score.astype(jnp.float32)]}

    def short_conv(ctx, ins):
        x, w = ins["X"][0].astype(bf), ins["W"][0].astype(bf)
        seq, (rows, wide) = int(ctx.attr("seq")), x.shape
        chan, taps = wide // 3, w.shape[1]
        z = (x[:, :chan] * x[:, 2 * chan:]).reshape(rows // seq, seq, chan)
        conv = z * w[:, taps - 1]
        for back in range(1, taps):
            past = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :seq]
            conv = conv + past * w[:, taps - 1 - back]
        return {"Out": [(x[:, chan:2 * chan] * conv.reshape(rows, chan))
                        .astype(ins["X"][0].dtype)]}

    return {"rms_norm": rms_norm, "moe_router": moe_router,
            "short_conv": short_conv}


def _errors(s, batch) -> dict:
    """``common.reference_check``'s two errors, as numbers."""
    import importlib
    ref = importlib.import_module(f"benchmark.references.{s.cell['reference']}")
    names = s.built["check"]["loss"] + s.built["check"]["each"]
    got = s.exe.run(s.built["test"], feed=batch, fetch_list=names,
                    scope=s.scope)
    want = s.reference_result
    if want is None:
        want = s.reference_result = ref.loss(
            [s.scope.find_var(n) for n in s.built["params"]], batch, s.model,
            s.params)
    got_loss = float(np.asarray(got[0], np.float32).reshape(-1)[0])
    got_each = np.concatenate([np.asarray(g, np.float32).reshape(-1)
                               for g in got[1:]])
    want_each = np.asarray(want["each"], np.float32)
    tol = ref.tolerance(s.model)
    out = {"loss": abs(got_loss - float(want["loss"])) / abs(float(want["loss"])),
           "each": float(np.abs(got_each - want_each).max()
                         / np.abs(want_each).max())}
    out["ok"] = bool(out["loss"] <= tol["loss"] and out["each"] <= tol["each"])
    return out


def controls(args) -> dict:
    import jax.numpy as jnp
    from benchmark.jobs import common
    from paddle_tpu.core import registry
    cell = load_cell(args)
    s = common.Session(cell, args.seed, say)
    s.reference_result = None
    rng = np.random.RandomState(args.seed)
    for _ in range(s.params["ring"]):       # the batch the cell's check draws
        s.builder.batch(s.model, s.params, rng)
    batch = s.builder.batch(s.model, s.params, rng)
    result = {"mode": "controls", "seed": args.seed}
    result["as_it_is"] = _errors(s, batch)
    say(f"as it is: {result['as_it_is']}")
    # float8 weights in the program's place; the reference keeps its result
    originals = {n: s.scope.find_var(n) for n in s.built["params"]}
    for n, v in originals.items():
        s.scope.set_var(n, jnp.asarray(v).astype(jnp.float8_e4m3fn)
                        .astype(v.dtype))
    result["float8_weights"] = _errors(s, batch)
    say(f"float8 (e4m3) weights in the program's place: "
        f"{result['float8_weights']} (must not be ok)")
    for n, v in originals.items():
        s.scope.set_var(n, v)
    # the float32-stated parts in bfloat16: a new clone, so a new compile
    real = {t: registry.get(t).lower for t in _bfloat16_lowerings()}
    try:
        for t, fn in _bfloat16_lowerings().items():
            registry.get(t).lower = fn
        s.built["test"] = s.built["test"].clone(for_test=True)
        result["bfloat16_parts"] = _errors(s, batch)
    finally:
        for t, fn in real.items():
            registry.get(t).lower = fn
    say(f"RMSNorm, router and short convolution in bfloat16: "
        f"{result['bfloat16_parts']}")
    s.close()
    return result


def leaf_errors(names, grads, want) -> list:
    """A step's gradients against the reference's, a row a leaf: the largest
    difference over the reference's largest entry, the difference's norm
    over the reference's, the cosine, and the least-squares scale."""
    rows = []
    for name, g, r in zip(names, grads, want):
        r = np.asarray(r, np.float32)
        rows.append({
            "name": name, "shape": list(r.shape),
            "max": float(np.abs(g - r).max() / np.abs(r).max()),
            "l2": float(np.linalg.norm(g - r) / np.linalg.norm(r)),
            "cos": float(np.vdot(g, r) / (np.linalg.norm(g)
                                          * np.linalg.norm(r))),
            "scale": float(np.vdot(g, r) / np.vdot(r, r))})
    return rows


def gradients(args) -> dict:
    import jax
    import jax.numpy as jnp
    from benchmark.jobs import common
    from benchmark.references import lfm2_pretrain as reference
    cell = load_cell(args)
    s = common.Session(cell, args.seed, say)
    built, model = s.built, s.model
    n_bias = len(built["expert_bias"])
    params = built["params"][:len(built["params"]) - n_bias]
    batch = s.builder.batch(s.model, s.params,
                            np.random.RandomState(args.seed))
    # host copies: the train step donates its state
    weights = [np.array(s.scope.find_var(n)) for n in built["params"]]
    fetch = [s.loss.name] + [n + "@GRAD" for n in params] \
        + built["expert_index"]
    got = s.exe.run(s.program, feed=batch, fetch_list=fetch, scope=s.scope)
    loss = float(np.asarray(got[0], np.float32).reshape(-1)[0])
    grads = [np.asarray(g, np.float32) for g in got[1:1 + len(params)]]
    index = np.stack([np.asarray(i) for i in got[1 + len(params):]])
    index = index.reshape(n_bias, -1, index.shape[-1])
    s.close()
    del s, got
    gc.collect()                    # the reference gets the chip to itself
    f32 = [jnp.asarray(w, jnp.float32) for w in weights]

    def loss_of(w, chosen):
        out = reference.forward(w + f32[len(params):], batch, model, chosen)
        return out["loss"], out["experts"]

    result = {"mode": "grads", "seed": args.seed, "loss": loss}
    grad = jax.jit(jax.value_and_grad(loss_of, has_aux=True))
    # the reference routing by itself, then along the program's own choice:
    # a 4th / 5th expert that flips under bfloat16 moves other rows through
    # an expert, which no rounding explains
    for routing, chosen in (("its_own", None), ("the_programs", index)):
        with jax.default_matmul_precision("highest"):
            (want_loss, experts), want = grad(f32[:len(params)], chosen)
        flips = reference.differing_share(index, np.asarray(experts))
        say(f"{s_tokens(cell)} tokens at the published widths, the reference "
            f"routing by {routing} choice: loss {loss:.6f} against "
            f"{float(want_loss):.6f}; assignments not the reference's "
            f"{flips:.4%}")
        rows = leaf_errors(params, grads, want)
        for row in sorted(rows, key=lambda r: -r["l2"]):
            say(f"  {row['name']:<24} {str(row['shape']):<18} |d|max/|ref|max"
                f" {row['max']:.3e} |d|/|ref| {row['l2']:.3e} cos "
                f"{row['cos']:.6f} scale {row['scale']:.4f}")
        worst = max(rows, key=lambda r: r["l2"])
        say(f"worst leaf by |d|/|ref|: {worst['name']} {worst['l2']:.3e}; by "
            f"|d|max/|ref|max: {max(r['max'] for r in rows):.3e}; smallest "
            f"cosine {min(r['cos'] for r in rows):.6f}")
        result[routing] = {
            "reference_loss": float(want_loss), "flips": flips,
            "worst_l2": worst, "worst_max": max(r["max"] for r in rows),
            "min_cos": min(r["cos"] for r in rows), "leaves": rows}
        del want
    return result


def s_tokens(cell) -> str:
    return f"{cell['params']['batch']} x {cell['params']['seq']}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("load", "controls", "grads"))
    ap.add_argument("--seed", type=int, default=2147480011)
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--seq", type=int)
    ap.add_argument("--ring", type=int)
    ap.add_argument("--lr", type=float)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None,
                    help="append the result as one JSON line to this file")
    args = ap.parse_args(argv)
    from paddle_tpu.utils import compile_cache
    compile_cache.arm()
    result = {"load": held_shares, "controls": controls,
              "grads": gradients}[args.mode](args)
    line = json.dumps(result)
    print(line if args.mode != "grads" else json.dumps({
        k: ({a: b for a, b in v.items() if a != "leaves"}
            if isinstance(v, dict) else v) for k, v in result.items()}),
        flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
