"""What the benchmark's harness does not fetch from the cell
``xing4_0_29b_a4b.pretrain_s4096`` (it reads the loss alone): the builder's
chip readings of PERF.md section 6, PR 61. On a TPU through ``chiprun --
python3 tools/xing4_0_probe.py <mode> ...``; ``--rehearsal`` runs the data
files' rehearsal sizes on the CPU (a debug run: no device number). It edits
no benchmark file. ``load`` is ``tools/laguna_probe.py``'s on this cell (the
held share of the assignments and the rows the budget dropped over
``--steps`` steps; the check once more on the moved state); this file adds:

``controls``  at the cell's own check (seeded weights, before any step),
              every verdict ``benchmark.jobs.common.reference_check``'s own
              and the error by part of what is compared beside each: the
              program as it is; float8 (e4m3) weights in the program's
              place; and the REFERENCE with one departure while the program
              is as it is (``references/xing4_0_pretrain.py:CONTROLS``) --
              the hyper-connections' float32 parts in bfloat16, 5 Sinkhorn
              iterations for 20, H_post without its factor 2, the softmax
              scale without mscale^2, the dynamic part of the coefficients
              left out, plain rotary frequencies in YaRN's place. What
              fails is what the chip check sees. ``--bias-std`` (several):
              the same at other widths of the static biases' seeded normal
              (``hc_bias_std``; ``assumed.recipe`` says why it is not 0).
"""
from __future__ import annotations

import contextlib
import functools
import os
import re
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools import laguna_probe  # noqa: E402
from tools.laguna_probe import say  # noqa: E402
from tools.ouro_probe import held  # noqa: E402

CELL = "xing4_0_29b_a4b.pretrain_s4096"

_laguna_load_cell = laguna_probe.load_cell


def load_cell(args, **model) -> dict:
    """The cell, with the held experts also under the key ``tools/laguna_
    probe.py`` reads them by, and ``model``'s keys over the file's."""
    cell = _laguna_load_cell(args)
    cell["model"]["num_experts"] = cell["model"]["n_routed_experts"]
    cell["model"].update(model)
    return cell


@contextlib.contextmanager
def departing(control: str):
    """The reference's forward with one departure, around one check."""
    from benchmark.references import xing4_0_pretrain as reference
    forward = reference.forward
    reference.forward = functools.partial(forward, control=control)
    try:
        yield
    finally:
        reference.forward = forward


def parts(s, batch) -> dict:
    """The check's error by part of what it compares, each over the
    reference's largest entry as ``reference_check`` divides: the
    cross-entropy's block means, the sparse layers' held norms, the streams'
    root mean squares (and the reference's own, a block each)."""
    from benchmark.references import xing4_0_pretrain as reference
    got = s.exe.run(s.place(s.built["test"]), feed=batch,
                    fetch_list=s.built["check"]["each"], scope=s.scope)
    got = [np.asarray(g, np.float32).reshape(-1) for g in got]
    weights = [s.scope.find_var(n) for n in s.built["params"]]
    want = np.asarray(reference.loss(weights, batch, s.model, s.params)[
        "each"], np.float32)
    cuts = np.cumsum([0] + [g.size for g in got])
    sparse = reference.sparse_layers(s.model)
    whole, out = np.abs(want).max(), {}
    for label, lo, hi in (("ce_blocks", 0, 1), ("held_norm", 1, 1 + sparse),
                          ("stream_rms", 1 + sparse, len(got))):
        if hi > lo:
            g, w = np.concatenate(got[lo:hi]), want[cuts[lo]:cuts[hi]]
            out[label] = float(np.abs(g - w).max() / whole)
    out.update(largest=float(whole), stream_rms=w.round(4).tolist())
    return out


def controls(args) -> dict:
    import jax.numpy as jnp
    from benchmark.jobs import common
    from benchmark.references import xing4_0_pretrain as reference
    result = {"mode": "controls", "seed": args.seed, "widths": {}}
    for std in args.bias_std or [None]:
        cell = load_cell(args, **({} if std is None else
                                  {"hc_bias_std": std}))
        s = common.Session(cell, args.seed, say)
        rng = np.random.RandomState(args.seed)
        for _ in range(s.params["ring"]):   # the batch the cell's check draws
            s.builder.batch(s.model, s.params, rng)
        batch = s.builder.batch(s.model, s.params, rng)
        row = {"tolerance": reference.tolerance(s.model)["each"]}
        say(f"hc_bias_std {s.model.get('hc_bias_std')}: as it is:")
        row["as_it_is"] = {**held(s, batch), "parts": parts(s, batch)}
        say("float8 (e4m3) weights in the program's place (must FAIL):")
        row["float8_weights"] = held(s, batch, {
            n: jnp.asarray(v).astype(jnp.float8_e4m3fn).astype(v.dtype)
            for n in s.built["params"] for v in (s.scope.find_var(n),)})
        for control in args.controls or reference.CONTROLS:
            say(f"the reference with {control} (ok: the check cannot see "
                f"it):")
            with departing(control):
                row[control] = {**held(s, batch), "parts": parts(s, batch)}
        result["widths"][str(s.model.get("hc_bias_std"))] = row
        s.close()
    return result


def _products(hlo_text: str) -> set:
    """The instructions of a compiled module that are a ``dot`` or a
    ``convolution``, or a fusion whose computation holds one."""
    from paddle_tpu.observability.attribution import parse_hlo_computations
    comps = parse_hlo_computations(hlo_text)[0]

    def product(i):
        return i.opcode in ("dot", "convolution")
    holds = {name for name, body in comps.items() if any(map(product, body))}
    return {i.name for body in comps.values() for i in body
            if product(i) or holds & set(
                re.findall(r"calls=%?([\w.\-]+)", i.rest))}


def hyper(args) -> dict:
    import tempfile

    import jax
    import jax.numpy as jnp
    from benchmark import trace
    from paddle_tpu.core import registry
    from tools.laguna_probe import _ms
    cell = load_cell(args)
    model, p = cell["model"], cell["params"]
    T, n, C = p["batch"] * p["seq"], model["hc_mult"], model["hidden_size"]
    K = 2 * n + n * n
    attrs = {"streams": n, "iters": model["hc_sinkhorn_iters"],
             "eps": model["hc_eps"],
             "clamp_min": float(model["mhc_h_res_clamp_min"]),
             "clamp_max": float(model["mhc_h_res_clamp_max"])}
    rng = np.random.RandomState(args.seed % (2 ** 31))
    x = jnp.asarray(rng.randn(T, n * C), jnp.bfloat16)
    y = jnp.asarray(rng.randn(T, C), jnp.bfloat16)
    weights = {"Phi": jnp.asarray(rng.randn(n * C, K) * 0.02, jnp.float32),
               "B": jnp.asarray(rng.randn(K) * model.get("hc_bias_std", 0.0),
                                jnp.float32),
               "Alpha": jnp.full((3,), model.get("hc_alpha_init", 0.01),
                                 jnp.float32)}

    def lowered(kind, slots, operands):
        """One lowering alone, compiled for these operands."""
        def fn(*operands):
            out = registry.get(kind).lower(
                registry.LowerCtx(dict(attrs)),
                {s: [v] for s, v in zip(slots, operands)})
            return tuple(v[0] for v in out.values())
        fn.__name__ = kind
        return jax.jit(fn).lower(*operands).compile()
    read = ("X", "Phi", "B", "Alpha")
    u, coef = lowered("hyper_connection_pre", read, (x, *weights.values()))(
        x, *weights.values())
    calls = {
        "pre": ("hyper_connection_pre", read, (x, *weights.values())),
        "post": ("hyper_connection_post", ("X", "Y", "Coef"), (x, y, coef)),
        "post_grad": ("hyper_connection_post_grad",
                      ("X", "Y", "Coef", "Out@GRAD"), (x, y, coef, x)),
        "pre_grad": ("hyper_connection_pre_grad",
                     read + ("U@GRAD", "Coef@GRAD"),
                     (x, *weights.values(), u, coef))}
    result = {"mode": "hyper", "seed": args.seed, "tokens": T, "streams": n,
              "width": C, "iters": attrs["iters"], "calls": args.calls,
              "lowerings": {}}
    for label, (kind, slots, operands) in calls.items():
        fn = lowered(kind, slots, operands)
        products = _products(fn.as_text())
        ms = _ms(fn, *operands)
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                for _ in range(args.calls):
                    out = fn(*operands)
                jax.block_until_ready(out)
            jax.profiler.stop_trace()
            ops = trace.load(trace.newest_xplane(d), args.rehearsal
                             ).first_device()[trace.OPS_LINE]
        by = {}
        for name, a, b in ops:
            name = trace.instruction(name)
            by[name] = by.get(name, 0.0) + (b - a) / 1e6 / args.calls
        inside = {k: v for k, v in by.items() if k in products}
        row = {"ms": ms,
               "device_ms": trace.length(trace.union(trace.spans_of(ops)))
               / 1e6 / args.calls,
               "product_ms": sum(inside.values()),
               "products": {k: round(v, 4) for k, v in sorted(
                   inside.items(), key=lambda kv: -kv[1])},
               "rest": dict([(k, round(v, 4)) for k, v in sorted(
                   by.items(), key=lambda kv: -kv[1])
                   if k not in inside][:8])}
        result["lowerings"][label] = row
        say(f"{label}: {ms:.3f} ms a call; on the device "
            f"{row['device_ms']:.3f} ms, of it {row['product_ms']:.3f} in "
            f"{len(inside)} product fusions {row['products']}; the rest's "
            f"largest {row['rest']}")
    return result


def main(argv=None) -> int:
    def options(ap):
        ap.set_defaults(cell=CELL)
        ap.add_argument("--bias-std", type=float, nargs="*",
                        help="controls: widths of the static biases' "
                             "seeded normal, in hc_bias_std's place")
        ap.add_argument("--controls", nargs="*",
                        help="controls: these of the reference's CONTROLS")
        ap.add_argument("--calls", type=int, default=10,
                        help="hyper: calls of each lowering in its capture")
    laguna_probe.load_cell = load_cell      # its modes load the cell by it
    try:
        return laguna_probe.main(
            argv, modes={"load": laguna_probe.held_shares,
                         "controls": controls, "hyper": hyper},
            doc=__doc__, options=options)
    finally:
        laguna_probe.load_cell = _laguna_load_cell


if __name__ == "__main__":
    sys.exit(main())
