"""What the benchmark's harness does not fetch from the cell
``glm_4_7_flash.pretrain_s4096`` (it reads the loss alone): the builder's
chip readings of PERF.md section 6, PR 48. On a TPU through ``chiprun --
python3 tools/glm_probe.py <mode> ...``; ``--rehearsal`` runs the data
files' rehearsal sizes on the CPU (a debug run: no device number). ``load``
is ``tools/laguna_probe.py``'s on this cell (the held share of the
assignments and the dropped rows over ``--steps`` steps, the module's layer
the last; the check once more on the moved state, selection biases and all);
this file adds:

``parts``     at the cell's own check, the error of each part of what is
              compared (the trunk's block means, the module's mean
              cross-entropy, its block means, the sparse layers' routed
              norms) over the reference's largest entry, and each layer's
              norm.
``controls``  at the cell's own check (seeded weights, before any step),
              every verdict ``benchmark.jobs.common.reference_check``'s own
              and ``parts`` beside each: the program as it is; float8 (e4m3)
              weights in the program's place; and one mechanism of the model
              taken out of the PROGRAM while the reference keeps it -- the
              rotary key head left unrotated, the two latent norms left out,
              the softmax scale 1 / sqrt(192) in place of 1 / sqrt(256), the
              routed scale 1.8 left out, the module's norm of the embedding
              left out, the module trained on the next token in place of the
              one after, an eighth of the row budget. All must fail.
              ``bf16_latent_norms`` is the other way round: the REFERENCE
              computes the two latent norms in bfloat16 and the program is
              as it is -- whether the check can tell a coarser norm from the
              float32 one.
``kernels``   the assembly op and its grad lowering alone at the cell's
              shape (milliseconds a layer, the bytes they must move and the
              share of HBM's rate that is), and the flash kernels at 20
              query = 20 key/value heads of 256 over ``--blocks``, each
              compiled alone; and the expert layers' token sums
              (``tools/laguna_probe.py``'s ``sums``, a mode here too).
"""
from __future__ import annotations

import contextlib
import copy
import functools
import math
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools import laguna_probe  # noqa: E402
from tools.laguna_probe import _ms, say  # noqa: E402
from tools.qwen3_next_probe import flash_by_blocks  # noqa: E402

CELL = "glm_4_7_flash.pretrain_s4096"
MECHANISMS = ("k_r_rotation", "latent_norms", "softmax_scale",
              "routed_scale", "e_norm", "second_label", "row_budget",
              "bf16_latent_norms")


def without(model: dict, mechanism: str) -> dict:
    """The configuration with one mechanism taken out, where a key does it;
    parameters keep their names and shapes, so the program runs on the
    cell's own weights. The others are ``patched``'s."""
    model = copy.deepcopy(model)
    if mechanism == "softmax_scale":
        model["attention_multiplier"] = 1.0 / math.sqrt(
            model["qk_nope_head_dim"])
    elif mechanism == "routed_scale":
        model["routed_scaling_factor"] = 1.0
    elif mechanism == "row_budget":
        # an eighth of the cell's: half an even router's rows (1,024 of
        # 2,048), whole tiles of the grouped products' rows
        model["moe_row_budget"] //= 8
    elif mechanism not in MECHANISMS:
        raise ValueError(mechanism)
    return model


@contextlib.contextmanager
def patched(mechanism: str):
    """What no configuration key takes out, swapped around one program's
    build and check: the rotation of 3-d rows inside ``latent_qkv`` (the one
    key head; q's are 4-d), the builder's norm by its parameter's name, the
    label the module is given, or -- for ``bf16_latent_norms`` -- the
    reference's forward."""
    from paddle_tpu.models import decoder_lm
    from paddle_tpu.ops import decoder_ops
    from benchmark.references import glm_4_7_flash_pretrain as reference
    swaps = []

    def swap(owner, name, new):
        swaps.append((owner, name, getattr(owner, name)))
        setattr(owner, name, new)

    def norm_but(suffixes):
        norm = decoder_lm._norm
        return lambda x, cfg, name: (
            x if name.endswith(suffixes) else norm(x, cfg, name))
    if mechanism == "k_r_rotation":
        rows = decoder_ops._rope_rows
        swap(decoder_ops, "_rope_rows", lambda ctx, x, backward: (
            x if x.ndim == 3 else rows(ctx, x, backward)))
    elif mechanism == "latent_norms":
        swap(decoder_lm, "_norm", norm_but(("_q_a_norm_w", "_kv_a_norm_w")))
    elif mechanism == "e_norm":
        swap(decoder_lm, "_norm", norm_but(("mtp_e_norm_w",)))
    elif mechanism == "second_label":
        build = decoder_lm.build
        swap(decoder_lm, "build", lambda cfg, ids, labels, labels_next:
             build(cfg, ids, labels, labels))
    elif mechanism == "bf16_latent_norms":
        swap(reference, "forward", functools.partial(
            reference.forward, control="bf16_latent_norms"))
    try:
        yield
    finally:
        for owner, name, old in swaps:
            setattr(owner, name, old)


def parts(s, batch, **swapped) -> dict:
    """The check's error by part of what it compares, each over the
    reference's largest entry as ``reference_check`` divides, and beside
    them each sparse layer's routed norm (``tolerance``'s (d)): the
    reference's, and how far off its own value the program's is. ``swapped``: ``laguna_probe.
    checked``'s."""
    from benchmark.references import glm_4_7_flash_pretrain as reference
    built = {**s.built, **swapped}
    got = s.exe.run(s.place(built["test"]), feed=batch,
                    fetch_list=built["check"]["each"], scope=s.scope)
    got = [np.asarray(g, np.float32).reshape(-1) for g in got]
    weights = [s.scope.find_var(n) for n in built["params"]]
    want = np.asarray(reference.loss(weights, batch, s.model, s.params)[
        "each"], np.float32)
    cuts = np.cumsum([0] + [g.size for g in got])
    whole = np.abs(want).max()
    out = {}
    for label, lo, hi in (("trunk_blocks", 0, 1), ("mtp_ce", 1, 2),
                          ("mtp_blocks", 2, 3), ("held_norm", 3, len(got))):
        g, w = np.concatenate(got[lo:hi]), want[cuts[lo]:cuts[hi]]
        out[label] = float(np.abs(g - w).max() / whole)
    out.update(largest=float(whole), held_norms=w.tolist(),
               held_norm_rel=(np.abs(g - w) / w).tolist())
    return out


def checked(s, batch, **swapped) -> dict:
    """``laguna_probe.checked``'s verdict and errors, and ``parts``."""
    return {**laguna_probe.checked(s, batch, **swapped),
            "parts": parts(s, batch, **swapped)}


def check_parts(args) -> dict:
    """``parts`` at the cell's own check: the session and the batch
    ``controls`` checks."""
    from benchmark.jobs import common
    s = common.Session(load_cell(args), args.seed, say)
    rng = np.random.RandomState(args.seed)
    for _ in range(s.params["ring"]):       # the batch the cell's check draws
        s.builder.batch(s.model, s.params, rng)
    result = dict(parts(s, s.builder.batch(s.model, s.params, rng)),
                  mode="parts", seed=args.seed)
    say(f"the check by part, each over the reference's largest entry: "
        f"{result}")
    s.close()
    return result


def controls(args) -> dict:
    return laguna_probe.controls(args, without, MECHANISMS, patched, checked)


_laguna_load_cell = laguna_probe.load_cell


def load_cell(args) -> dict:
    """The cell, with the held experts also under the key ``tools/laguna_
    probe.py`` reads them by."""
    cell = _laguna_load_cell(args)
    cell["model"]["num_experts"] = cell["model"]["n_routed_experts"]
    return cell


def kernels(args) -> dict:
    """The assembly op and its grad lowering alone, and the flash kernels at
    the cell's shape by blocks, each compiled alone."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core import registry
    from paddle_tpu.ops import pallas_mode
    cell = load_cell(args)
    model, p = cell["model"], cell["params"]
    B, S, h = p["batch"], p["seq"], model["num_attention_heads"]
    d_n, d_r = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    d = d_n + d_r
    interpret = pallas_mode.interpret() if args.rehearsal else False
    rng = np.random.RandomState(args.seed % (2 ** 31))
    bf = jnp.bfloat16
    attrs = {"batch": B, "seq": S, "heads": h, "nope_dim": d_n,
             "rope_dim": d_r, "theta": float(model["rope_theta"])}
    q = jnp.asarray(rng.randn(B * S, h * d), bf)
    kv = jnp.asarray(rng.randn(B * S, h * (d_n + d)), bf)
    k_r = jnp.asarray(rng.randn(B * S, d_r), bf)
    gq, gk, gv = (jnp.asarray(rng.randn(B, h, S, d), bf) for _ in range(3))

    @jax.jit
    def forward(q, kv, k_r):
        out = registry.get("latent_qkv").lower(
            registry.LowerCtx(dict(attrs)),
            {"Q": [q], "KV": [kv], "KRope": [k_r]})
        return out["OutQ"][0], out["OutK"][0], out["OutV"][0]

    @jax.jit
    def backward(gq, gk, gv):
        out = registry.get("latent_qkv_grad").lower(
            registry.LowerCtx(dict(attrs)),
            {"Q": [None], "KV": [None], "KRope": [None], "OutQ@GRAD": [gq],
             "OutK@GRAD": [gk], "OutV@GRAD": [gv]})
        return out["Q@GRAD"][0], out["KV@GRAD"][0], out["KRope@GRAD"][0]
    fwd, bwd = _ms(forward, q, kv, k_r), _ms(backward, gq, gk, gv)
    # q, kv and k_r in, q, k, v out; the transposed way in the backward
    moved = (q.size + kv.size + k_r.size + 3 * gq.size) * 2
    result = {"mode": "kernels", "assembly": {
        "fwd_ms": fwd, "bwd_ms": bwd, "bytes": moved}}
    say(f"latent_qkv alone at {B} x {S}, {h} heads of {d_n} + {d_r}: "
        f"forward {fwd:.3f} backward {bwd:.3f} ms a layer for "
        f"{moved / 1e6:.0f} MB each way: {moved / fwd / 1e6:.0f} / "
        f"{moved / bwd / 1e6:.0f} GB/s")
    result["flash"] = flash_by_blocks(B, S, h, h, d, args.blocks, interpret,
                                      rng)
    result["token_sums"] = laguna_probe.token_sums(args)
    return result


def main(argv=None) -> int:
    def options(ap):
        ap.set_defaults(cell=CELL)
    laguna_probe.load_cell = load_cell      # its modes load the cell by it
    try:
        return laguna_probe.main(
            argv, modes={"load": laguna_probe.held_shares,
                         "controls": controls, "parts": check_parts,
                         "kernels": kernels}, doc=__doc__, options=options)
    finally:
        laguna_probe.load_cell = _laguna_load_cell


if __name__ == "__main__":
    sys.exit(main())
