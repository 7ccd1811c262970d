"""What the benchmark's harness does not fetch from the cell
``ouro_2_6b.pretrain_s4096`` (it reads the loss alone): the builder's and the
reviewer's chip readings of PERF.md section 6, PR 57. On a TPU through
``chiprun -- python3 tools/ouro_probe.py <mode> ...``; ``--rehearsal`` runs
the data files' rehearsal sizes on the CPU (a debug run: no device number).

``load``      the train step at ``--batch`` x ``--seq`` with recomputation
              by layer and without (``--recompute layer,none``; without, the
              48 layer applications' activations do not fit at 4,096 tokens:
              give a shorter ``--seq``): XLA's analysis of the compiled step,
              what the loop op keeps (``loop_kept_bytes``), how often its
              sub-block was traced, the allocator's peak after ``--steps``
              steps, and their seconds.
``controls``  at the cell's own check (seeded weights, before any step),
              each through the harness's ``common.reference_check`` itself:
              as it is; with float8 (e4m3) weights in the program's place,
              the reference on the weights as they were (must fail the
              check); as it is with the gate's weights seeded (at the zero
              start every gate reads 1/2 in any precision). And, a reading
              with no program in it: how far the reference moves when its
              norms, softmax inputs, gate and exit distribution are rounded
              through bfloat16 (the parts the configuration states in
              float32; it moves by LESS than the program's own error, so the
              check does not hold them: the tests do, at float32).
``grads``     one train step at ``--batch`` x ``--seq`` and the published
              widths: every parameter's gradient as the step computes it
              (the loop op's pullback: the flash, rotary and norm backward
              inside the loop, the shared weights' sums over their four
              uses, the gate, the table and the head) against ``jax.grad``
              of the plain float32 reference (each layer application under
              ``jax.checkpoint``, so that it fits), by leaf. Each leaf is
              held to ``GRAD_L2`` and ``GRAD_COS``: bfloat16 activations
              through 48 layer applications and the four uses' terms added
              in bfloat16 read 3.9e-2 to 4.0e-2 / 0.99917 at the worst leaf
              over three seeds (my chip runs, PR 57); a wrong or missing use
              of a shared weight is off by a quarter or more.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys
import time
import types

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools.lfm2_probe import leaf_errors, say  # noqa: E402

CELL = "ouro_2_6b.pretrain_s4096"
GRAD_L2, GRAD_COS = 6e-2, 0.998     # |d| / |ref| and the cosine, a leaf


def load_cell(args, **model) -> dict:
    from benchmark import run
    cell = run.load_cell(CELL, args.rehearsal)
    for key in ("batch", "seq"):
        if getattr(args, key, None):
            cell["params"][key] = getattr(args, key)
    cell["model"].update(model)
    return cell


def _gauge(name: str, label: str):
    from paddle_tpu.observability.metrics import REGISTRY
    family = REGISTRY.get(name)
    found = [child.value for labels, child in (
        family.items() if family is not None else ())
        if dict(labels).get("program") == label]
    return found[0] if found else None


def load(args) -> dict:
    import jax
    from benchmark import probe
    from benchmark.jobs import common
    result = {"mode": "load", "seed": args.seed, "runs": []}
    for how in args.recompute.split(","):
        cell = load_cell(args, recompute=how)
        s = common.Session(cell, args.seed, say)
        batch = s.builder.batch(s.model, s.params,
                                np.random.RandomState(args.seed))
        t0 = time.perf_counter()
        try:
            out = s.exe.run(s.program, feed=batch, fetch_list=[s.loss],
                            scope=s.scope)
        except Exception as e:      # the compiler's: it does not fit
            row = {"recompute": how,
                   "tokens": s.params["batch"] * s.params["seq"],
                   "failed": str(e).strip().splitlines()[-1][:300]}
            say(json.dumps(row))
            result["runs"].append(row)
            s.close()
            del s
            gc.collect()
            continue
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = s.exe.run(s.program, feed=batch, fetch_list=[s.loss],
                            scope=s.scope, return_numpy=False)
        jax.block_until_ready(out)
        label = f"{id(s.program)}:v{s.program._version}"
        row = {"recompute": how, "tokens": s.params["batch"] * s.params["seq"],
               "first_step_s": first,
               "step_s": (time.perf_counter() - t0) / args.steps,
               "loss": common.loss_value(out[0]),
               "xla": probe.step_memory(s.exe),
               "loop_kept_bytes": _gauge("loop_kept_bytes", label),
               "loop_stack_lowerings": _gauge("loop_stack_lowerings_total",
                                              label),
               "peak_bytes": probe.peak_bytes(s.devices)}
        say(json.dumps(row))
        result["runs"].append(row)
        s.close()
        del s, out
        gc.collect()
    return result


_CHECK_LINE = re.compile(
    r"relative error of the mean (\S+) .* positions (\S+) \(tolerance")


def held(s, batch, program_weights=None) -> dict:
    """``common.reference_check`` of the session's test clone on ``batch``,
    its verdict and the two errors of its line. ``program_weights`` ``{name:
    value}``: the program runs on these while the reference reads the
    weights as they were (a control: what a lower precision in the
    program's place reads)."""
    from benchmark.jobs import common
    lines, kept = [], {}

    def run(program, feed, fetch_list, scope):
        return s.exe.run(program, feed=feed, fetch_list=fetch_list,
                         scope=s.scope)
    for n, v in (program_weights or {}).items():
        kept[n] = s.scope.find_var(n)
        s.scope.set_var(n, v)
    as_they_were = types.SimpleNamespace(
        find_var=lambda n: kept[n] if n in kept else s.scope.find_var(n))
    ok = common.reference_check(types.SimpleNamespace(
        cell=s.cell, model=s.model, params=s.params, built=s.built,
        place=s.place, scope=as_they_were, say=lines.append,
        exe=types.SimpleNamespace(run=run)), batch)
    for n, v in kept.items():
        s.scope.set_var(n, v)
    say(lines[-1])
    loss, each = _CHECK_LINE.search(lines[-1]).groups()
    return {"ok": ok, "loss": float(loss), "each": float(each)}


def controls(args) -> dict:
    import jax
    import jax.numpy as jnp
    from benchmark.jobs import common
    from benchmark.references import ouro_pretrain as reference
    cell = load_cell(args)
    s = common.Session(cell, args.seed, say)
    rng = np.random.RandomState(args.seed)
    for _ in range(s.params["ring"]):       # the batch the cell's check draws
        s.builder.batch(s.model, s.params, rng)
    batch = s.builder.batch(s.model, s.params, rng)
    tol = reference.tolerance(s.model)
    result = {"mode": "controls", "seed": args.seed, "tolerance": tol}
    say("as it is:")
    result["as_it_is"] = held(s, batch)
    say("float8 (e4m3) weights in the program's place (must FAIL):")
    result["float8_weights"] = held(s, batch, {
        n: jnp.asarray(v).astype(jnp.float8_e4m3fn).astype(v.dtype)
        for n in s.built["params"] for v in (s.scope.find_var(n),)})
    # a gate that reads something: at the zero start every lambda is 1/2
    shape = np.asarray(s.scope.find_var("exit_gate_w")).shape
    s.scope.set_var("exit_gate_w", jnp.asarray(np.random.RandomState(
        args.seed).randn(*shape).astype("float32") * 0.02))
    say("as it is, the gate's weights seeded at std 0.02:")
    result["seeded_gate"] = held(s, batch)
    # no program in this one: the reference against itself
    f32 = [jnp.asarray(s.scope.find_var(n), jnp.float32)
           for n in s.built["params"]]
    with jax.default_matmul_precision("highest"):
        want, low = (jax.jit(lambda w, b, cast=cast: reference.forward(
            w, b, s.model, cast))(f32, dict(batch))
            for cast in (None, jnp.bfloat16))
    each = np.asarray(want["each"], np.float32)
    result["bfloat16_inside"] = {
        "loss": abs(float(low["loss"]) - float(want["loss"]))
        / abs(float(want["loss"])),
        "each": float(np.abs(np.asarray(low["each"], np.float32) - each).max()
                      / np.abs(each).max())}
    say(f"the reference with its norms, softmax inputs, gate and exit "
        f"distribution rounded through bfloat16, against itself in float32 "
        f"(seeded gate; a reading: how far those parts in bfloat16 move a "
        f"float32 model): {result['bfloat16_inside']}")
    s.close()
    return result


def gradients(args) -> dict:
    import jax
    import jax.numpy as jnp
    from benchmark.jobs import common
    from benchmark.references import ouro_pretrain as reference
    cell = load_cell(args)
    s = common.Session(cell, args.seed, say)
    built, model = s.built, s.model
    params = built["params"]
    batch = s.builder.batch(s.model, s.params,
                            np.random.RandomState(args.seed))
    # host copies: the train step donates its state
    weights = [np.array(s.scope.find_var(n)) for n in params]
    got = s.exe.run(s.program, feed=batch, scope=s.scope,
                    fetch_list=[s.loss.name] + [n + "@GRAD" for n in params])
    loss = float(np.asarray(got[0], np.float32).reshape(-1)[0])
    grads = [np.asarray(g, np.float32) for g in got[1:]]
    size = f"{s.params['batch']} x {s.params['seq']}"
    s.close()
    del s, got
    gc.collect()                    # the reference gets the chip to itself
    f32 = [jnp.asarray(w, jnp.float32) for w in weights]
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda w: reference.forward(w, batch, model,
                                        remat=True)["loss"]))(f32)
    say(f"{size} tokens at the published widths: loss {loss:.6f} against "
        f"the reference's {float(want_loss):.6f}")
    rows = leaf_errors(params, grads, want)
    for row in sorted(rows, key=lambda r: -r["l2"]):
        if row["l2"] > 0.02 or not row["name"].startswith("layer"):
            say(f"  {row['name']:<28} {str(row['shape']):<14} |d|max/|ref|"
                f"max {row['max']:.3e} |d|/|ref| {row['l2']:.3e} cos "
                f"{row['cos']:.6f}")
    worst = max(rows, key=lambda r: r["l2"])
    say(f"{len(rows)} leaves; worst by |d|/|ref|: {worst['name']} "
        f"{worst['l2']:.3e}; by |d|max/|ref|max: "
        f"{max(r['max'] for r in rows):.3e}; smallest cosine "
        f"{min(r['cos'] for r in rows):.6f}")
    ok = all(r["l2"] <= GRAD_L2 and r["cos"] >= GRAD_COS for r in rows)
    say(f"every leaf within |d|/|ref| {GRAD_L2} and cosine {GRAD_COS}: {ok}")
    return {"mode": "grads", "seed": args.seed, "ok": ok, "loss": loss,
            "reference_loss": float(want_loss), "worst_l2": worst,
            "worst_max": max(r["max"] for r in rows),
            "min_cos": min(r["cos"] for r in rows), "leaves": rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("load", "controls", "grads"))
    ap.add_argument("--seed", type=int, default=2147480011)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--seq", type=int)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--recompute", default="layer,none")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None,
                    help="append the result as one JSON line to this file")
    args = ap.parse_args(argv)
    from paddle_tpu.utils import compile_cache
    compile_cache.arm()
    result = {"load": load, "controls": controls,
              "grads": gradients}[args.mode](args)
    line = json.dumps(result)
    print(json.dumps({k: v for k, v in result.items() if k != "leaves"}),
          flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
