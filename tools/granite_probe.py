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
``kernels``   the scan's kernels alone at the cell's shapes (PR 60):
              milliseconds a layer, ``CALLS`` calls in a row, of the
              forward, the state pass and the whole backward (state pass,
              reverse kernel and the ``jax.numpy`` around them; the reverse
              kernel is the difference), by ``--heads`` (``pallas_ssd.
              HEADS``'s place) and by form: ``loop`` is ``tools/
              ssd_loop_form.py`` (the kernels before PR 60), ``package``
              what ``pallas_ssd`` holds, ``empty`` the package's grids
              with bodies that write zeros and compute nothing (what the
              grid's DMAs take); and at each ``--heads`` which of ``y``,
              the states, ``dx``, ``ddt``, ``dcum``, ``dB``, ``dC`` and
              ``dD`` differ in a bit between the package's and the loop
              form's at as many heads a step (sha256 of the float32 bytes,
              ``-0.0`` as ``0.0``), with the largest difference over the
              largest entry beside each that does.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools.lfm2_probe import leaf_errors, say  # noqa: E402

CELL = "granite_4_0_h_micro.pretrain_s4096"
CALLS = 20      # of a kernel in a row, a timing
OUTPUTS = ("y", "states", "dx", "ddt", "dcum", "dB", "dC", "dD")


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


def scan_feeds(batch, seq, heads, n, chunk, rng) -> tuple:
    """Seeded operands of ``pallas_ssd._fwd_call`` / ``_bwd_call``: x and
    its cotangent ``[B, S, heads * 64]``, B and C bfloat16, dt in mamba_ssm's
    range (a head's scale 1e-3 to 1e-1) with A in -(1..16), float32."""
    import jax.numpy as jnp
    from paddle_tpu.ops.decoder_ops import _chunk_sums
    bf = jnp.bfloat16
    x, dy = (jnp.asarray(rng.randn(batch, seq, heads * 64) * 0.5, bf)
             for _ in range(2))
    bm, cm = (jnp.asarray(rng.randn(batch, seq, n) * 0.3, bf)
              for _ in range(2))
    dt = jnp.asarray(
        np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (1, 1, heads)))
        * np.exp(rng.randn(batch, seq, heads) * 0.3), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)
    drow = jnp.repeat(jnp.asarray(rng.randn(heads), jnp.float32), 64)[None]
    return (x, dt, _chunk_sums(dt * a, chunk), bm, cm, drow), dy


def _empty_kernels() -> dict:
    """In ``pallas_ssd``'s kernels' place: every output block written with
    zeros, nothing read but by the grid's DMAs."""
    import jax.numpy as jnp

    def zero(*refs):
        for ref in refs:
            ref[...] = jnp.zeros_like(ref)
    return {"_fwd_kernel": lambda emit, *refs: zero(refs[-2]),
            "_bwd_kernel": lambda *refs: zero(*refs[9:14])}


def _outputs(form, ops, dy, chunk, interpret) -> list:
    """``OUTPUTS`` of ``form``'s calls as float32, ``-0.0`` as ``0.0``."""
    import jax.numpy as jnp
    return [np.asarray(v.astype(jnp.float32)) + 0.0 for v in (
        form._fwd_call(*ops, chunk, interpret),
        form._fwd_call(*ops, chunk, interpret, "states"),
        *form._bwd_call(*ops, dy, chunk, interpret))]


def in_a_row(call, dt, calls: int, repeats: int) -> float:
    """Median milliseconds of one ``call(dt)`` on the device: ``calls`` of
    them inside one executable, each reading ``dt`` plus a zero made from
    every output of the one before (a host call a kernel would time the
    host: one dispatch takes 0.2 ms, chip, PR 60, whatever it launches)."""
    import time
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(dt):
        def body(_, dt):
            return dt + 0.0 * sum(leaf.reshape(-1)[0].astype(jnp.float32)
                                  for leaf in jax.tree.leaves(call(dt)))
        return jax.lax.fori_loop(0, calls, body, dt)
    jax.block_until_ready(chain(dt))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(dt))
        times.append((time.perf_counter() - t0) / calls * 1e3)
    return float(np.median(times))


def kernels(args) -> dict:
    """The scan's kernels alone, by heads a grid step and by form."""
    import hashlib
    from paddle_tpu.ops import pallas_ssd
    from tools import ssd_loop_form
    from tools.kimi_linear_probe import swapped
    if args.rehearsal:      # the kernels' smallest shapes, in the interpreter
        batch, seq, heads, n, chunk = 1, 512, 16, 128, 256
    else:
        cell = load_cell(args)
        model, p = cell["model"], cell["params"]
        batch, seq = p["batch"], p["seq"]
        heads, n = model["mamba_n_heads"], model["mamba_d_state"]
        chunk = model["mamba_chunk_size"]
        assert model["mamba_d_head"] == pallas_ssd.HEAD_DIM
    interpret = bool(args.rehearsal)
    ops, dy = scan_feeds(batch, seq, heads, n, chunk,
                         np.random.RandomState(args.seed % (2 ** 31)))
    calls, repeats = (2, 1) if args.rehearsal else (CALLS, 5)
    x, dt, *rest = ops

    def times(form):
        ms = functools.partial(in_a_row, dt=dt, calls=calls, repeats=repeats)
        fwd = ms(lambda dt: form._fwd_call(x, dt, *rest, chunk, interpret))
        states = ms(lambda dt: form._fwd_call(x, dt, *rest, chunk, interpret,
                                              "states"))
        bwd = ms(lambda dt: form._bwd_call(x, dt, *rest, dy, chunk,
                                           interpret))
        return {"fwd_ms": fwd, "states_ms": states, "bwd_ms": bwd,
                "reverse_ms": bwd - states, "layer_ms": fwd + bwd}
    rows, bits = [], []
    for step in args.heads:
        if heads % step:
            say(f"{heads} heads are not whole blocks of {step}")
            continue
        what = (f"{batch} x {seq}, {heads} heads of 64, N {n}, chunk {chunk},"
                f" {step} heads a grid step")
        with swapped(pallas_ssd, HEADS=step), \
                swapped(ssd_loop_form, HEADS=step):
            was = _outputs(ssd_loop_form, ops, dy, chunk, interpret)
            now = _outputs(pallas_ssd, ops, dy, chunk, interpret)
            sha = {k: hashlib.sha256(v.tobytes()).hexdigest()
                   for k, v in zip(OUTPUTS, now)}
            differ = {k: float(np.abs(a - b).max() / np.abs(b).max())
                      for k, a, b in zip(OUTPUTS, now, was)
                      if not np.array_equal(a, b)}
            del was, now
            bits.append({"heads": step, "differ": differ, "sha256": sha})
            say(f"{what}: the package's {', '.join(OUTPUTS)} against the "
                f"loop form's, bit for bit: " + (
                    "equal" if not differ else "differ (largest difference "
                    f"over the largest entry) in {differ}"))
            for form in ("loop", "package", "empty"):
                if form == "loop":
                    row = times(ssd_loop_form)
                else:
                    with swapped(pallas_ssd, **(
                            _empty_kernels() if form == "empty" else {})):
                        row = times(pallas_ssd)
                rows.append({"heads": step, "form": form, **row})
                say(f"{what}, {form}: forward {row['fwd_ms']:.3f}, state "
                    f"pass {row['states_ms']:.3f}, backward "
                    f"{row['bwd_ms']:.3f} (the reverse kernel and the "
                    f"jax.numpy around it {row['reverse_ms']:.3f}), forward "
                    f"+ backward {row['layer_ms']:.3f} ms a layer")
    return {"mode": "kernels", "seed": args.seed, "calls": calls,
            "rows": rows, "bits": bits}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("controls", "grads", "kernels"))
    ap.add_argument("--heads", nargs="*", type=int, default=[8, 16],
                    help="kernels: heads a grid step, in pallas_ssd.HEADS' "
                         "place")
    ap.add_argument("--seed", type=int, default=2147480011)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--seq", type=int)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None,
                    help="append the result as one JSON line to this file")
    args = ap.parse_args(argv)
    from paddle_tpu.utils import compile_cache
    compile_cache.arm()
    result = {"controls": controls, "grads": gradients,
              "kernels": kernels}[args.mode](args)
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
