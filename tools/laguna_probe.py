"""What the benchmark's harness does not fetch from the cell
``laguna_s_2_1.pretrain_s4096`` (it reads the loss alone): the builder's and
the reviewer's chip readings of PERF.md section 6, PR 39. On a TPU through
``chiprun -- python3 tools/laguna_probe.py <mode> ...``; ``--rehearsal`` runs
the data files' rehearsal sizes on the CPU (a debug run: no device number).

``load``      the job's own set-up, then ``--steps`` train steps fetching
              every expert layer's load and dropped-row count beside the
              loss: the share of the tokens x top-k assignments held here,
              by windows of 20 steps, the fullest step's held rows against
              the budget, each layer's live rows (what the held experts
              received, at most the buffer: the rows the token sums read)
              against its buffer's, the rows the budget dropped (must stay
              0), the allocator's peak as the harness sums it; the reference
              check once more on the moved state. ``--budget R`` runs it under
              another row budget, ``--budget 0`` under none (the T x k
              worst-case buffers: what the budget saves).
``controls``  at the cell's own check (seeded weights, before any step),
              every verdict ``benchmark.jobs.common.reference_check``'s own:
              the program as it is; float8 (e4m3) weights in the program's
              place; and one mechanism of the model taken out of the PROGRAM
              while the reference keeps it -- the window ignored on the
              sliding layers, the per-head gate left out, the rotary
              embedding over the whole head on full layers, the routed
              scale 2.5 left out, a tenth of the row budget (four tenths
              of what an even router sends: rows are dropped). All must
              fail.
``grads``     one train step at ``--batch`` x ``--seq`` and the published
              widths: every parameter's gradient as the step computes it (the
              window and full flash backward kernels, megablox ``gmm`` /
              ``tgmm`` over the budgeted rows, the gate) against ``jax.grad``
              of the plain float32 reference, by leaf.
``kernels``   the window kernels against the composed lowering and the full
              causal kernels, forward and backward, at the cell's shapes:
              milliseconds a layer over ``--blocks`` (block_q x block_k
              pairs), what the K tiles visit, and the composed lowering's
              time and temporaries; then ``sums``.
``sums``      the expert layers' token sums alone at the cell's shape
              (``--cell``: any cell with expert layers) under a seeded
              router, even or ``--tilt``ed: the kernel of
              ``ops/pallas_moe_rows.py`` against the composed form it
              replaced, milliseconds an op and nanoseconds a live row, over
              ``--sum-plans`` (tokens a block x rows a pass) besides.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import gc
import json
import os
import re
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools.lfm2_probe import leaf_errors, say  # noqa: E402

CELL = "laguna_s_2_1.pretrain_s4096"


def load_cell(args) -> dict:
    from benchmark import run
    cell = run.load_cell(getattr(args, "cell", CELL), args.rehearsal)
    for key in ("batch", "seq", "ring"):
        if getattr(args, key, None):
            cell["params"][key] = getattr(args, key)
    if getattr(args, "lr", None):
        cell["model"]["learning_rate"] = args.lr
    if getattr(args, "budget", None) is not None:
        cell["model"]["moe_row_budget"] = args.budget
        if not args.budget:
            del cell["model"]["moe_row_budget"]
    return cell


def held_shares(args) -> dict:
    from benchmark import probe
    from benchmark.jobs import common, train_feed
    cell = load_cell(args)
    s = train_feed.setup(cell, args.seed, say)
    built, model = s.built, s.model
    tokens = s.params["batch"] * s.params["seq"]
    k, held = model["num_experts_per_tok"], model["num_experts"]
    first, n = model.get("first_expert_held", 0), len(built["expert_load"])
    names = built["expert_load"] + built["expert_dropped"]
    shares, losses, uneven, dropped, load_by_step = [], [], [], None, []
    for i in range(args.steps):
        if i == 1:              # the first step compiled for these fetches
            t0 = time.perf_counter()
        out = s.exe.run(s.program, feed=s.ring[s.step % len(s.ring)],
                        fetch_list=[s.loss] + names, scope=s.scope)
        s.step += 1
        losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        load = np.stack(out[1:1 + n]).astype(np.float64)
        load_by_step.append(load)
        assert (load.sum(1) == tokens * k).all(), load.sum(1)
        shares.append(load[:, first:first + held].sum(1) / (tokens * k))
        uneven.append(load.max(1) / load.mean(1))
        dropped = np.array(out[1 + n:]).reshape(-1)     # summed since startup
    step_ms = (time.perf_counter() - t0) / (args.steps - 1) * 1e3
    shares, uneven = np.array(shares), np.array(uneven)
    load_by_step = np.array(load_by_step)       # [steps, layers, experts]
    budget = model.get("moe_row_budget")        # None: T x k rows a layer
    fullest = float(shares.max() * tokens * k)
    peak_gb = probe.peak_bytes(s.devices) / 1e9
    say(f"ring {len(s.ring)}, lr {model['learning_rate']}, {args.steps} "
        f"steps after the 2 of warm-up; loss every 10th step: "
        + " ".join(f"{v:.3f}" for v in losses[::10]) + f" last {losses[-1]:.4f}")
    say("held share of tokens x top-k, all layers, by windows of 20 steps: "
        + " ".join(f"{shares[i:i + 20].mean():.4f}"
                   for i in range(0, len(shares), 20)))
    say(f"by layer over all steps {shares.mean(0).round(4).tolist()}; single "
        f"step and layer min {shares.min():.4f} max {shares.max():.4f}; "
        f"all {shares.mean():.4f}")
    say(f"the fullest step and layer held {fullest:.0f} rows of a budget of "
        f"{budget}; rows dropped since startup, by layer: {dropped.tolist()}"
        f"; the allocator's peak, as peak_hbm_gb sums it: {peak_gb:.4f} GB; "
        f"{step_ms:.2f} ms a step with these fetches every step (not the "
        f"cell's window)")
    rows = budget or tokens * k
    live = np.minimum(load_by_step[:, :, first:first + held].sum(2), rows)
    say(f"live rows a layer (the held experts' rows, which the token sums "
        f"read) of {rows} buffer rows, mean over the steps "
        f"{live.mean(0).round(0).tolist()}, fullest step "
        f"{live.max(0).tolist()}: {100 * live.mean() / rows:.1f}% of the "
        f"buffer")
    say("max load / mean over the routed experts, mean by windows of 20 "
        "steps: " + " ".join(f"{uneven[i:i + 20].mean():.3f}"
                             for i in range(0, len(uneven), 20)))
    ok = common.reference_check(
        s, s.builder.batch(s.model, s.params, np.random.RandomState(args.seed + 1)))
    result = {"mode": "load", "seed": args.seed, "ring": len(s.ring),
              "lr": model["learning_rate"], "steps": args.steps,
              "share": float(shares.mean()),
              "share_by_20": [float(shares[i:i + 20].mean())
                              for i in range(0, len(shares), 20)],
              "share_min": float(shares.min()),
              "share_max": float(shares.max()),
              "fullest_rows": fullest, "budget": budget,
              "dropped": [int(d) for d in dropped], "peak_gb": peak_gb,
              "buffer_rows": rows, "live_rows_mean": live.mean(0).tolist(),
              "live_rows_max": live.max(0).tolist(),
              "step_ms_fetching": step_ms,
              "loss_first": s.first_loss, "loss_last": losses[-1],
              "reference_after": ok}
    s.close()
    return result


def without(model: dict, mechanism: str) -> dict:
    """The configuration with one mechanism taken out; parameters keep their
    names and shapes, so the program runs on the cell's own weights."""
    model = copy.deepcopy(model)
    if mechanism == "window":
        model["sliding_window"] = 1 << 30       # every key of every query
    elif mechanism == "gate":
        model["gating"] = "none"
    elif mechanism == "partial_rotary":
        model["rope_parameters"]["full_attention"]["partial_rotary_factor"] = 1
    elif mechanism == "routed_scale":
        model["moe_routed_scaling_factor"] = 1.0
    elif mechanism == "row_budget":
        # four tenths of an even router's rows at the cell's 4 x: 512, a
        # whole tile of the grouped products' rows, as the kernels want
        model["moe_row_budget"] //= 10
    else:
        raise ValueError(mechanism)
    return model


MECHANISMS = ("window", "gate", "partial_rotary", "routed_scale",
              "row_budget")


def checked(s, batch, **swapped) -> dict:
    """``common.reference_check`` itself on the session, with ``swapped``
    entries of ``s.built`` in the cell's place (another program's ``test``
    clone and ``check`` variables, other ``params`` for the reference): its
    verdict, and the two errors its line prints."""
    from benchmark.jobs import common
    lines, say_was, built_was = [], s.say, s.built
    s.say = lambda msg: (lines.append(msg), say_was(msg))
    s.built = {**built_was, **swapped}
    try:
        ok = common.reference_check(s, batch)
    finally:
        s.say, s.built = say_was, built_was
    loss, each = re.search(r"of the mean (\S+) \(.* positions (\S+) \(",
                           lines[-1]).groups()
    return {"ok": ok, "loss": float(loss), "each": float(each)}


def controls(args, without=without, mechanisms=MECHANISMS,
             patched=lambda mechanism: contextlib.nullcontext(),
             checked=checked) -> dict:
    """``without`` / ``mechanisms``: another cell's (``tools/qwen3_next_
    probe.py``); ``patched(mechanism)``: a context around the build and the
    check of that mechanism's program, for what no configuration key takes
    out; ``checked``: another cell's reading of one check (``tools/glm_
    probe.py`` adds the error by part)."""
    import jax.numpy as jnp
    from benchmark.jobs import common
    cell = load_cell(args)
    s = common.Session(cell, args.seed, say)
    rng = np.random.RandomState(args.seed)
    for _ in range(s.params["ring"]):       # the batch the cell's check draws
        s.builder.batch(s.model, s.params, rng)
    batch = s.builder.batch(s.model, s.params, rng)
    result = {"mode": "controls", "seed": args.seed}
    result["as_it_is"] = checked(s, batch)
    # float8 weights in the program's place; the reference is handed the
    # originals, kept in the scope under other names
    params = s.built["params"]
    originals = {n: s.scope.find_var(n) for n in params}
    for n, v in originals.items():
        s.scope.set_var(n + "@original", v)
        s.scope.set_var(n, jnp.asarray(v).astype(jnp.float8_e4m3fn)
                        .astype(v.dtype))
    result["float8_weights"] = checked(
        s, batch, params=[n + "@original" for n in params])
    for n, v in originals.items():
        s.scope.set_var(n, v)
    for mechanism in mechanisms:            # a new program each: a compile
        with patched(mechanism):
            other = s.builder.build(without(s.model, mechanism), s.params)
            try:
                result["no_" + mechanism] = checked(
                    s, batch, test=other["test"], check=other["check"])
            except Exception as e:      # noqa: BLE001
                # a control's program that the chip's compiler refuses (the
                # flash forward at d=256 beside other neighbours: PERF.md
                # section 7 (u)) is a reading too; the others still run
                result["no_" + mechanism] = {
                    "ok": None, "error": f"{type(e).__name__}: {e}"[:400]}
    for name, got in result.items():
        if isinstance(got, dict):
            say(f"{name}: {got}" + ("" if name == "as_it_is" else
                                    " (must not be ok)"))
    s.close()
    return result


def gradients(args, reference=None) -> dict:
    import jax
    import jax.numpy as jnp
    from benchmark.jobs import common
    if reference is None:
        from benchmark.references import laguna_pretrain as reference
    cell = load_cell(args)
    s = common.Session(cell, args.seed, say)
    built, model = s.built, s.model
    params, n_sparse = built["params"], len(built["expert_index"])
    batch = s.builder.batch(s.model, s.params,
                            np.random.RandomState(args.seed))
    # host copies: the train step donates its state
    weights = [np.array(s.scope.find_var(n)) for n in params]
    fetch = [s.loss.name] + [n + "@GRAD" for n in params] \
        + built["expert_index"]
    got = s.exe.run(s.program, feed=batch, fetch_list=fetch, scope=s.scope)
    loss = float(np.asarray(got[0], np.float32).reshape(-1)[0])
    grads = [np.asarray(g, np.float32) for g in got[1:1 + len(params)]]
    index = np.stack([np.asarray(i) for i in got[1 + len(params):]])
    index = index.reshape(n_sparse, -1, index.shape[-1])
    tokens = f"{s.params['batch']} x {s.params['seq']}"
    s.close()
    del s, got
    gc.collect()                    # the reference gets the chip to itself
    f32 = [jnp.asarray(w, jnp.float32) for w in weights]

    def loss_of(w, chosen):
        out = reference.forward(w, batch, model, chosen)
        return out["loss"], out["experts"]

    result = {"mode": "grads", "seed": args.seed, "loss": loss}
    grad = jax.jit(jax.value_and_grad(loss_of, has_aux=True))
    # the reference routing by itself, then along the program's own choice:
    # a 10th / 11th expert that flips under bfloat16 moves other rows
    # through an expert, which no rounding explains
    for routing, chosen in (("its_own", None), ("the_programs", index)):
        with jax.default_matmul_precision("highest"):
            (want_loss, experts), want = grad(f32, chosen)
        flips = reference.differing_share(index, np.asarray(experts))
        say(f"{tokens} tokens at the published widths, the reference "
            f"routing by {routing} choice: loss {loss:.6f} against "
            f"{float(want_loss):.6f}; assignments not the reference's "
            f"{flips:.4%}")
        rows = leaf_errors(params, grads, want)
        for row in sorted(rows, key=lambda r: -r["l2"]):
            say(f"  {row['name']:<28} {str(row['shape']):<18} |d|max/|ref|max"
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


def _ms(fn, *args, calls: int = 4, repeats: int = 9) -> float:
    """Median milliseconds of one call of ``fn`` (``calls`` a timing)."""
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / calls * 1e3)
    return float(np.median(times))


def kernels(args) -> dict:
    """Forward / backward milliseconds a layer of the kernels at the cell's
    shapes, by blocks, and of the composed lowering."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_attention as pa, pallas_mode
    cell = load_cell(args)
    model, p = cell["model"], cell["params"]
    B, S, d = p["batch"], p["seq"], model["head_dim"]
    kv, window = model["num_key_value_heads"], model["sliding_window"]
    interpret = pallas_mode.interpret() if args.rehearsal else False
    rng = np.random.RandomState(args.seed % (2 ** 31))
    rows = []

    def operands(heads):
        q, g = (jnp.asarray(rng.randn(B, heads, S, d), jnp.bfloat16)
                for _ in range(2))
        k, v = (jnp.asarray(rng.randn(B, kv, S, d), jnp.bfloat16)
                for _ in range(2))
        return q, k, v, g

    def timed(label, heads, win, blocks):
        q, k, v, g = operands(heads)
        scale, seed = d ** -0.5, jnp.int32(3)
        bq, bk = blocks or pa._blocks(S, True, None, None,
                                      pa.sliding_window(win, S, True))
        win_ = pa.sliding_window(win, S, True)
        try:
            out, lse = pa._fwd_call(q, k, v, None, seed, scale, 0.0, True,
                                    interpret, bq, bk, win_)
            fwd = _ms(lambda: pa._fwd_call(q, k, v, None, seed, scale, 0.0,
                                           True, interpret, bq, bk, win_))
            bwd = _ms(lambda: pa._bwd_call(q, k, v, None, seed, g, lse, scale,
                                           0.0, True, interpret, bq, bk,
                                           win_))
        except Exception as e:      # a pair Mosaic refuses: said, not hidden
            say(f"{label} heads {heads} blocks {bq} x {bk}: "
                f"{type(e).__name__}: {str(e)[:200]}")
            return
        visited, skipped = pa.k_tiles(S, bq, bk, True, win_)
        rows.append({"kernel": label, "heads": heads, "block_q": bq,
                     "block_k": bk, "fwd_ms": fwd, "bwd_ms": bwd,
                     "visited": visited, "skipped": skipped})
        say(f"{label} heads {heads} blocks {bq} x {bk}: forward {fwd:.3f} "
            f"backward {bwd:.3f} ms a layer; K tiles {visited} visited, "
            f"{skipped} skipped")

    heads = dict(zip(model["layer_types"],
                     model["num_attention_heads_per_layer"]))
    pairs = [tuple(int(x) for x in b.split("x")) for b in args.blocks]
    for blocks in pairs:
        timed("window", heads["sliding_attention"], window, blocks)
    timed("full", heads["full_attention"], None, None)
    timed("window_layer_as_full", heads["sliding_attention"], None, None)
    # XLA's composed lowering of the window op, forward + backward
    q, k, v, g = operands(heads["sliding_attention"])

    def composed(q, k, v):
        return pa.composed_attention(q, k, v, None, d ** -0.5, 0.0, True,
                                     None, window=window)
    fwd = jax.jit(composed)
    both = jax.jit(lambda q, k, v, g: jax.vjp(composed, q, k, v)[1](g))
    c_fwd, c_both = _ms(fwd, q, k, v), _ms(both, q, k, v, g)
    temp = both.lower(q, k, v, g).compile().memory_analysis()
    say(f"composed lowering of the window op: forward {c_fwd:.3f}, forward "
        f"+ backward {c_both:.3f} ms a layer; temporaries "
        f"{temp.temp_size_in_bytes / 1e9:.3f} GB")
    return {"mode": "kernels", "rows": rows, "composed_fwd_ms": c_fwd,
            "composed_fwd_bwd_ms": c_both,
            "composed_temp_gb": temp.temp_size_in_bytes / 1e9,
            "token_sums": token_sums(args)}


def token_sums(args) -> dict:
    """An expert layer's token sums at the cell's shape (tokens, top-k,
    hidden width, held of routed experts, row budget) under a seeded even
    router: the kernel of ``ops/pallas_moe_rows.py`` against the composed
    form it replaces (the scatter-add under a budget, the gathered reduce
    without), milliseconds an op (four ops on four buffers inside one jit),
    nanoseconds a live row, and the largest difference between the two."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import decoder_lm
    from paddle_tpu.ops import decoder_ops, pallas_mode, pallas_moe_rows
    cell = load_cell(args)
    model, p = cell["model"], cell["params"]
    T, k, H = p["batch"] * p["seq"], model["num_experts_per_tok"], \
        model["hidden_size"]
    held = decoder_lm._held(model)
    routed = model.get("num_experts_routed", held)
    budget = model.get("moe_row_budget")
    R = budget or T * k
    interpret = pallas_mode.interpret() if args.rehearsal else False
    rng = np.random.RandomState(args.seed % (2 ** 31))
    logits = rng.randn(T, routed).astype(np.float32)
    if args.tilt:       # one held expert draws that share of the tokens
        logits[rng.rand(T) < args.tilt, 0] += 100.0
    index = np.argsort(-logits, axis=1)[:, :k].astype(np.int32)
    flat = index.reshape(-1)
    order = np.argsort(flat, kind="stable").astype(np.int32)
    slot = np.empty_like(order)
    slot[order] = np.arange(order.size, dtype=np.int32)
    bounds = np.minimum(np.concatenate(
        [[0], np.cumsum(np.bincount(flat, minlength=routed)[:held])]), R)
    live = int(bounds[-1])
    buffers = []
    for _ in range(4):
        rows = rng.randn(R, H).astype(np.float32)
        rows[live:] = 0         # what the grouped products leave there
        buffers.append(jnp.asarray(rows, jnp.bfloat16))
    slot, order = jnp.asarray(slot.reshape(T, k)), jnp.asarray(order[:R])
    bounds = jnp.asarray(bounds, jnp.int32)
    composed = decoder_ops._add_rows if budget else decoder_ops._sum_slots
    def kernel():       # a fresh jit: the sweep below changes the plan
        return jax.jit(lambda *rows: tuple(
            pallas_moe_rows.token_sums(r, slot, bounds, interpret)
            for r in rows))
    forms = {"composed": jax.jit(lambda *rows: tuple(
        composed(r, order, slot) for r in rows))}
    if pallas_moe_rows.supports(T, R, H, jnp.bfloat16) \
            and pallas_mode.available():
        forms["kernel"] = kernel()
    was = pallas_moe_rows.BLOCK_TOKENS, pallas_moe_rows.PASS_ROWS
    for plan in args.sum_plans if "kernel" in forms else ():
        pallas_moe_rows.BLOCK_TOKENS, pallas_moe_rows.PASS_ROWS = (
            int(v) for v in plan.split("x"))    # tokens a block x rows a pass
        pallas_moe_rows.token_sums.clear_cache()
        try:
            ms = _ms(kernel(), *buffers, calls=2, repeats=7) / 4
            say(f"token sums, kernel at {plan}: {ms:.3f} ms an op")
        except Exception as e:      # a plan the compiler refuses
            say(f"token sums, kernel at {plan}: {type(e).__name__}: "
                f"{str(e)[:200]}")
    pallas_moe_rows.BLOCK_TOKENS, pallas_moe_rows.PASS_ROWS = was
    pallas_moe_rows.token_sums.clear_cache()
    result = {"mode": "sums", "tokens": T, "k": k, "width": H,
              "held": held, "routed": routed, "buffer_rows": R,
              "live_rows": live, "tilt": args.tilt}
    outs = {}
    for name, fn in forms.items():
        outs[name] = fn(*buffers)[0].astype(jnp.float32)
        result[f"{name}_ms"] = ms = _ms(fn, *buffers, calls=2, repeats=7) / 4
        say(f"token sums, {name}: {ms:.3f} ms an op, {ms * 1e6 / live:.1f} "
            f"ns a live row ({T} tokens x top-{k} of width {H}, {held} of "
            f"{routed} experts held, {live} live of {R} buffer rows)")
    if "kernel" in outs:
        # what a step pays an op beside the kernel: the runs' starts, from
        # the slots and the bounds (four calls share one in the jit above)
        block = pallas_moe_rows.block_tokens_of(T)
        starts = jax.jit(lambda s, b: tuple(pallas_moe_rows.run_starts(
            s + i, b, block) for i in range(4)))
        result["run_starts_ms"] = ms = _ms(starts, slot, bounds, calls=2,
                                           repeats=7) / 4
        say(f"the runs' starts alone ({T // block} blocks x {held} groups): "
            f"{ms:.3f} ms an op")
        result["max_difference"] = float(jnp.max(jnp.abs(
            outs["kernel"] - outs["composed"])))
        say(f"largest difference kernel - composed: "
            f"{result['max_difference']:.3g} (entries up to "
            f"{float(jnp.max(jnp.abs(outs['composed']))):.3g})")
    return result


def main(argv=None, modes=None, doc=__doc__, options=None) -> int:
    """``modes``: another cell's functions by mode, in this file's place,
    and ``options(parser)``: its own arguments and defaults
    (``tools/qwen3_next_probe.py``)."""
    modes = modes or {"load": held_shares, "controls": controls,
                      "grads": gradients, "kernels": kernels}
    modes.setdefault("sums", token_sums)
    ap = argparse.ArgumentParser(description=doc.splitlines()[0])
    ap.add_argument("mode", choices=tuple(modes))
    ap.add_argument("--seed", type=int, default=2147480039)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--seq", type=int)
    ap.add_argument("--ring", type=int)
    ap.add_argument("--lr", type=float)
    ap.add_argument("--budget", type=int,
                    help="another moe_row_budget; 0 for none")
    ap.add_argument("--blocks", nargs="*", default=["512x512"],
                    help="kernels: block_q x block_k pairs, e.g. 256x512")
    ap.add_argument("--cell", default=CELL,
                    help="sums: any cell with expert layers")
    ap.add_argument("--tilt", type=float, default=0.0,
                    help="sums: the share of the tokens one held expert "
                         "draws besides (0: an even router)")
    ap.add_argument("--sum-plans", nargs="*", default=[],
                    help="sums: also time the kernel at these tokens a "
                         "block x rows a pass, e.g. 128x1024 256x2048")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None,
                    help="append the result as one JSON line to this file")
    if options:
        options(ap)
    args = ap.parse_args(argv)
    from paddle_tpu.utils import compile_cache
    compile_cache.arm()
    result = modes[args.mode](args)
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
