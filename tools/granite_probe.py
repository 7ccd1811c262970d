"""What the benchmark's harness does not fetch from the cell
``granite_4_0_h_micro.pretrain_s4096`` (it reads the loss alone): the
builder's and the reviewer's chip readings of PERF.md section 6, PR 35. On a
TPU through ``chiprun -- python3 tools/granite_probe.py <mode> ...``;
``--rehearsal`` runs the data files' rehearsal sizes on the CPU (a debug run:
no device number).

``controls``  at the cell's own check (seeded weights, before any step): the
              reference check as it is; with float8 (e4m3) weights in the
              program's place; and against the reference with its scan's
              carried state kept in bfloat16 (the distance a bfloat16 state
              moves the result). Both controls must fail the check.
``grads``     one train step at ``--batch`` x ``--seq`` and the published
              widths: every parameter's gradient as the step computes it (the
              scan's backward kernels, the ungated convolution's, the
              grouped-query flash backward) against ``jax.grad`` of the plain
              float32 reference, by leaf; the small leaves (``A_log``,
              ``dt_bias``, ``D``, the filter and its bias) are where a
              scan's backward goes wrong, and each has its line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools.lfm2_probe import leaf_errors, say  # noqa: E402

CELL = "granite_4_0_h_micro.pretrain_s4096"


def load_cell(args) -> dict:
    from benchmark import run
    cell = run.load_cell(CELL, args.rehearsal)
    for key in ("batch", "seq"):
        if getattr(args, key, None):
            cell["params"][key] = getattr(args, key)
    return cell


def _errors(got, want, tol) -> dict:
    """``common.reference_check``'s two errors, as numbers."""
    got_loss = float(np.asarray(got[0], np.float32).reshape(-1)[0])
    got_each = np.asarray(got[1], np.float32).reshape(-1)
    want_each = np.asarray(want["each"], np.float32)
    out = {"loss": abs(got_loss - float(want["loss"]))
           / abs(float(want["loss"])),
           "each": float(np.abs(got_each - want_each).max()
                         / np.abs(want_each).max())}
    out["ok"] = bool(out["loss"] <= tol["loss"] and out["each"] <= tol["each"])
    return out


def controls(args) -> dict:
    import jax
    import jax.numpy as jnp
    from benchmark.jobs import common
    from benchmark.references import granite_pretrain as reference
    cell = load_cell(args)
    s = common.Session(cell, args.seed, say)
    rng = np.random.RandomState(args.seed)
    for _ in range(s.params["ring"]):       # the batch the cell's check draws
        s.builder.batch(s.model, s.params, rng)
    batch = s.builder.batch(s.model, s.params, rng)
    names = s.built["check"]["loss"] + s.built["check"]["each"]
    tol = reference.tolerance(s.model)
    originals = {n: s.scope.find_var(n) for n in s.built["params"]}

    def program():
        return s.exe.run(s.built["test"], feed=batch, fetch_list=names,
                         scope=s.scope)

    def plain(state_dtype=None):
        f32 = [jnp.asarray(v, jnp.float32) for v in originals.values()]
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda w, b: reference.forward(
                w, b, s.model, state_dtype))(f32, dict(batch))
    want = plain()
    result = {"mode": "controls", "seed": args.seed, "tolerance": tol}
    as_it_is = program()
    result["as_it_is"] = _errors(as_it_is, want, tol)
    say(f"as it is: {result['as_it_is']}")
    for n, v in originals.items():
        s.scope.set_var(n, jnp.asarray(v).astype(jnp.float8_e4m3fn)
                        .astype(v.dtype))
    result["float8_weights"] = _errors(program(), want, tol)
    say(f"float8 (e4m3) weights in the program's place: "
        f"{result['float8_weights']} (must not be ok)")
    for n, v in originals.items():
        s.scope.set_var(n, v)
    result["bfloat16_state"] = _errors(as_it_is, plain(jnp.bfloat16), tol)
    say(f"against the reference with its scan's state in bfloat16: "
        f"{result['bfloat16_state']} (must not be ok)")
    s.close()
    return result


def gradients(args) -> dict:
    import jax
    import jax.numpy as jnp
    from benchmark.jobs import common
    from benchmark.references import granite_pretrain as reference
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
            lambda w: reference.forward(w, batch, model)["loss"]))(f32)
    say(f"{size} tokens at the published widths: loss {loss:.6f} against "
        f"the reference's {float(want_loss):.6f}")
    rows = leaf_errors(params, grads, want)
    small = ("A_log", "dt_bias", "_D", "conv_w", "conv_b")
    for row in sorted(rows, key=lambda r: -r["l2"]):
        if row["l2"] > 0.02 or any(k in row["name"] for k in small):
            say(f"  {row['name']:<28} {str(row['shape']):<14} |d|max/|ref|"
                f"max {row['max']:.3e} |d|/|ref| {row['l2']:.3e} cos "
                f"{row['cos']:.6f}")
    worst = max(rows, key=lambda r: r["l2"])
    say(f"{len(rows)} leaves; worst by |d|/|ref|: {worst['name']} "
        f"{worst['l2']:.3e}; by |d|max/|ref|max: "
        f"{max(r['max'] for r in rows):.3e}; smallest cosine "
        f"{min(r['cos'] for r in rows):.6f}")
    return {"mode": "grads", "seed": args.seed, "loss": loss,
            "reference_loss": float(want_loss), "worst_l2": worst,
            "worst_max": max(r["max"] for r in rows),
            "min_cos": min(r["cos"] for r in rows), "leaves": rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("controls", "grads"))
    ap.add_argument("--seed", type=int, default=2147480011)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--seq", type=int)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None,
                    help="append the result as one JSON line to this file")
    args = ap.parse_args(argv)
    from paddle_tpu.utils import compile_cache
    compile_cache.arm()
    result = {"controls": controls, "grads": gradients}[args.mode](args)
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
