"""What the benchmark's harness does not fetch from the cell
``kimi_linear_48b_a3b.pretrain_s4096`` (it reads the loss alone): the
builder's chip readings of PERF.md section 6, PR 51. On a TPU through
``chiprun -- python3 tools/kimi_linear_probe.py <mode> ...``; ``--rehearsal``
runs the data files' rehearsal sizes on the CPU (a debug run: no device
number). ``load`` is ``tools/laguna_probe.py``'s on this cell (the held share
of the assignments and the dropped rows over ``--steps`` steps; the check
once more on the moved state) with the largest ``|G|`` a chunk reaches
beside it; ``grads`` is its gradient comparison against this cell's
reference (``A_log``, ``dt_bias``, ``W_fa``, ``W_fb``, ``W_b`` named). This
file adds:

``parts``     at the cell's own check, the error of each part of what is
              compared (the block means, the sparse layers' routed entries,
              the KDA layers' ``o`` sizes) over the reference's largest
              entry.
``controls``  at the cell's own check (seeded weights, before any step),
              every verdict ``benchmark.jobs.common.reference_check``'s own
              and ``parts`` beside each: the program as it is; float8 (e4m3)
              weights in the program's place; and one mechanism of the model
              taken out of the PROGRAM while the reference keeps it -- the
              decay (``g = 0``), the decay averaged over a head's channels
              (the scalar rule under this model's name), ``beta = 1``, the l2
              norms, ``silu`` for the norm's sigmoid gate, the output gate,
              ``q_r`` / ``k_r`` rotated, the softmax scale 1 / sqrt(128), the
              routed scale 2.446, an eighth of the row budget. All must
              fail. ``bf16_state`` is the other way round: the REFERENCE
              rounds the recurrence's state to bfloat16 every position and
              the program is as it is -- whether the check can tell.
``readings``  the program as it is and float8 weights, by part, a seed
              each of ``--seeds``: what the check's limit is set from.
``kernels``   the rule's kernels alone at the cell's shape by chunk
              (milliseconds a layer forward / backward; ``--packed-from``:
              also by the block length from which a level of the halving
              hands the MXU its lower rows alone, ``pallas_delta.
              PACKED_FROM``; ``--step-heads``: also by the value heads a
              grid step takes at most, ``pallas_delta.STEP_HEADS``, and
              beside each the same grid with empty bodies: a step's fixed
              cost and its DMAs; ``--scalar-reps``: the scalar-decay pair at
              as many value heads by the value heads a key head, each at
              every ``--step-heads`` too: 2 4 8 16 are 1, 2, 4, 8 key heads
              a step at two value heads a key head) against the composed
              form and its temporaries, and the flash kernels at 32 heads
              with q / k 256 (64 zero columns) or 192 wide and v 128.
"""
from __future__ import annotations

import contextlib
import copy
import functools
import itertools
import math
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools import laguna_probe  # noqa: E402
from tools.laguna_probe import _ms, say  # noqa: E402

CELL = "kimi_linear_48b_a3b.pretrain_s4096"
MECHANISMS = ("decay", "channel_decay", "beta", "l2_norm", "sigmoid_gate",
              "out_gate", "nope", "softmax_scale", "routed_scale",
              "row_budget", "bf16_state")


def without(model: dict, mechanism: str) -> dict:
    """The configuration with one mechanism taken out, where a key does it;
    parameters keep their names and shapes, so the program runs on the
    cell's own weights. The others are ``patched``'s."""
    model = copy.deepcopy(model)
    if mechanism == "nope":
        model["mla_use_nope"] = False       # q_r and k_r rotated
    elif mechanism == "softmax_scale":
        model["attention_multiplier"] = 1.0 / math.sqrt(
            model["qk_nope_head_dim"])
    elif mechanism == "routed_scale":
        model["routed_scaling_factor"] = 1.0
    elif mechanism == "row_budget":
        model["moe_row_budget"] //= 8       # half an even router's rows
    elif mechanism not in MECHANISMS:
        raise ValueError(mechanism)
    return model


@contextlib.contextmanager
def patched(mechanism: str):
    """What no configuration key takes out, swapped around one program's
    build and check: the rule's operands as ``kimi_delta`` hands them over,
    the unit norm inside the rule's lowerings, the gated norm's arguments,
    or -- for ``bf16_state`` -- the reference's forward."""
    import jax
    from paddle_tpu import layers
    from paddle_tpu.ops import pallas_delta
    from benchmark.references import kimi_linear_pretrain as reference
    swaps = []

    def swap(owner, name, new):
        swaps.append((owner, name, getattr(owner, name)))
        setattr(owner, name, new)

    rule, norm = layers.gated_delta_rule_packed, layers.rms_norm
    if mechanism == "decay":
        swap(layers, "gated_delta_rule_packed", lambda qkv, g, beta, *a, **k:
             rule(qkv, layers.scale(g, 0.0), beta, *a, **k))
    elif mechanism == "channel_decay":
        swap(layers, "gated_delta_rule_packed", lambda qkv, g, beta, *a, **k:
             rule(qkv, layers.elementwise_add(
                 layers.scale(g, 0.0),
                 layers.reduce_mean(g, dim=3, keep_dim=True)), beta, *a, **k))
    elif mechanism == "beta":
        swap(layers, "gated_delta_rule_packed", lambda qkv, g, beta, *a, **k:
             rule(qkv, g, layers.scale(beta, 0.0, bias=1.0), *a, **k))
    elif mechanism == "l2_norm":
        # the kernels and the composed form trace ``unit`` at lowering time
        jax.clear_caches()
        swap(pallas_delta, "unit",
             lambda x, scale=None: x if scale is None else x * scale)
    elif mechanism == "sigmoid_gate":
        swap(layers, "rms_norm", lambda *a, gate_activation="silu", **k:
             norm(*a, **k))
    elif mechanism == "out_gate":
        swap(layers, "rms_norm", lambda *a, gate=None, gate_activation="silu",
             **k: norm(*a, **k))
    elif mechanism == "bf16_state":
        swap(reference, "forward", functools.partial(
            reference.forward, control="bf16_state"))
    try:
        yield
    finally:
        for owner, name, old in swaps:
            setattr(owner, name, old)
        if mechanism == "l2_norm":
            jax.clear_caches()


def parts(s, batch, **swapped) -> dict:
    """The check's error by part of what it compares, each over the
    reference's largest entry as ``reference_check`` divides; beside them
    the reference's routed entries and ``o`` sizes (scaled) and how far off
    its own value each of the program's is."""
    from benchmark.references import kimi_linear_pretrain as reference
    built = {**s.built, **swapped}
    got = s.exe.run(s.place(built["test"]), feed=batch,
                    fetch_list=built["check"]["each"], scope=s.scope)
    got = [np.asarray(g, np.float32).reshape(-1) for g in got]
    weights = [s.scope.find_var(n) for n in built["params"]]
    want = np.asarray(reference.loss(weights, batch, s.model, s.params)[
        "each"], np.float32)
    n = reference.sparse_layers(s.model)
    cuts = np.cumsum([0] + [g.size for g in got])
    whole = np.abs(want).max()
    out = {"largest": float(whole)}
    for label, lo, hi in (("blocks", 0, 1), ("held_norm", 1, 1 + n),
                          ("o_size", 1 + n, len(got))):
        g, w = np.concatenate(got[lo:hi]), want[cuts[lo]:cuts[hi]]
        out[label] = float(np.abs(g - w).max() / whole)
        if label != "blocks":
            out[label + "s"] = w.tolist()
            out[label + "_rel"] = (np.abs(g - w) / np.abs(w)).tolist()
    return out


def checked(s, batch, **swapped) -> dict:
    """``laguna_probe.checked``'s verdict and errors, and ``parts``."""
    return {**laguna_probe.checked(s, batch, **swapped),
            "parts": parts(s, batch, **swapped)}


def check_parts(args) -> dict:
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


def readings(args) -> dict:
    """The two readings the limit is set from, a seed each of ``--seeds``:
    the program as it is and float8 (e4m3) weights in its place, both by
    part (``controls`` without its mechanisms: no further compile)."""
    rows = []
    for seed in args.seeds:
        args.seed = seed
        got = laguna_probe.controls(args, without, (), patched, checked)
        rows.append({"seed": seed, "as_it_is": got["as_it_is"],
                     "float8_weights": got["float8_weights"]})
    sound = [r["as_it_is"]["each"] for r in rows]
    float8 = [r["float8_weights"]["each"] for r in rows]
    say(f"{len(rows)} seeds: as it is {min(sound):.3e} to {max(sound):.3e}, "
        f"float8 weights {min(float8):.3e} to {max(float8):.3e}")
    return {"mode": "readings", "rows": rows, "as_it_is_max": max(sound),
            "float8_min": min(float8)}


_laguna_load_cell = laguna_probe.load_cell


def load_cell(args) -> dict:
    """The cell, with top-k also under the key ``tools/laguna_probe.py``
    reads it by."""
    cell = _laguna_load_cell(args)
    cell["model"]["num_experts_per_tok"] = cell["model"][
        "num_experts_per_token"]
    return cell


def held_shares(args) -> dict:
    """``laguna_probe.held_shares``, and over the same steps' first and last
    state the largest ``|G|`` a chunk of a KDA layer reaches (the running
    sum of ``g`` inside a chunk: past 88.7 ``exp(-G)`` is not a float32)
    and whether every ``o`` is finite."""
    result = laguna_probe.held_shares(args)
    result["decay"] = largest_sums(args)
    return result


def largest_sums(args) -> dict:
    from benchmark.jobs import train_feed
    s = train_feed.setup(load_cell(args), args.seed, say)
    block = s.built["main"].global_block()
    rules = [op for op in block.ops if op.type == "gated_delta_rule"]
    names = [op.inputs["G"][0] for op in rules] + [
        op.outputs["Out"][0] for op in rules]
    chunk = min(s.model.get("delta_chunk_size", 64), s.params["seq"])
    worst, finite = [], True
    for step in range(args.steps):
        fetch = names if step in (0, args.steps - 1) else []
        out = s.exe.run(s.program, feed=s.ring[s.step % len(s.ring)],
                        fetch_list=[s.loss] + fetch, scope=s.scope)
        s.step += 1
        if not fetch:
            continue
        gs, os_ = out[1:1 + len(rules)], out[1 + len(rules):]
        for g in gs:
            g = np.asarray(g, np.float32)
            sums = np.cumsum(g.reshape(g.shape[0], -1, chunk, *g.shape[2:]),
                             axis=2)
            worst.append(float(np.abs(sums).max()))
        finite = finite and all(
            np.isfinite(np.asarray(o, np.float32)).all() for o in os_)
    say(f"largest |G| inside a chunk of {chunk}, a KDA layer each, at the "
        f"first and the last of {args.steps} steps: {worst}; every o "
        f"finite: {finite}")
    s.close()
    return {"largest_abs_chunk_sum": worst, "finite": bool(finite),
            "chunk": chunk}


def gradients(args) -> dict:
    """``laguna_probe.gradients`` against this cell's reference; the
    selection biases are state, no leaves, and zero at the first step."""
    from benchmark.programs import kimi_linear_pretrain as builder
    from benchmark.references import kimi_linear_pretrain as reference
    build = builder.build

    def leaves_only(model, params):
        built = build(model, params)
        built["params"] = built["params"][:-len(built["expert_bias"])]
        return built
    builder.build = leaves_only
    try:
        result = laguna_probe.gradients(args, reference)
    finally:
        builder.build = build
    for row in result["the_programs"]["leaves"]:
        if row["name"].endswith(("_A_log", "_dt_bias", "_f_a_w", "_f_b_w",
                                 "_kda_b_w")):
            say(f"  along the program's routing: {row['name']:<24} "
                f"|d|/|ref| {row['l2']:.3e} cos {row['cos']:.6f}")
    return result


def flash_by_width(B, S, heads, widths, d_v, scale, interpret, rng) -> list:
    """Forward / backward milliseconds a layer of the causal flash kernels
    at ``heads`` heads with v ``d_v`` wide and q / k each of ``widths``
    wide (zero columns behind the model's 192 where wider), each call
    compiled alone at the default blocks."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_attention as pa
    bf = jnp.bfloat16
    fv, fg = (jnp.asarray(rng.randn(B, heads, S, d_v), bf) for _ in range(2))
    rows = []
    for d in widths:
        fq, fk = (jnp.asarray(rng.randn(B, heads, S, d), bf)
                  for _ in range(2))
        try:
            bq, bk = pa._blocks(S, True, None, None, None)
            out, lse = pa._fwd_call(fq, fk, fv, None, jnp.int32(3), scale,
                                    0.0, True, interpret, bq, bk, None)
            fwd = _ms(lambda: pa._fwd_call(fq, fk, fv, None, jnp.int32(3),
                                           scale, 0.0, True, interpret, bq,
                                           bk, None))
            bwd = _ms(lambda: pa._bwd_call(fq, fk, fv, None, jnp.int32(3), fg,
                                           lse, scale, 0.0, True, interpret,
                                           bq, bk, None))
        except Exception as e:      # noqa: BLE001
            say(f"flash q / k {d}, v {d_v}: {type(e).__name__}: "
                f"{str(e)[:200]}")
            continue
        rows.append({"qk_dim": d, "v_dim": d_v, "fwd_ms": fwd, "bwd_ms": bwd})
        say(f"flash {heads} heads, q / k {d} wide, v {d_v}, blocks {bq} x "
            f"{bk}: forward {fwd:.3f} backward {bwd:.3f} ms a layer")
    return rows


@contextlib.contextmanager
def swapped(owner, **values):
    """``owner``'s attributes set for the block. The delta kernels read
    theirs at a trace, behind ``jax.jit``s: where a value differs the caches
    are cleared, going in and coming out."""
    import jax
    old = {name: getattr(owner, name) for name in values}
    if old != values:
        jax.clear_caches()
    for name, value in values.items():
        setattr(owner, name, value)
    try:
        yield
    finally:
        for name, value in old.items():
            setattr(owner, name, value)
        if old != values:
            jax.clear_caches()


def _no_forward(qn, kn, v, g, bc, s):
    """In ``pallas_delta._channel_forward``'s place: nothing of a chunk."""
    return v.astype("float32"), s, ()


def _no_backward(qn, kn, v, g, bc, s, dsn, do):
    """In ``pallas_delta._channel_backward``'s place: every output from an
    input of its shape (the norms' vjp behind it stays)."""
    return (qn.astype("float32"), kn.astype("float32"), do.astype("float32"),
            g, bc, dsn)


def channel_feeds(B, S, n, d, rng) -> tuple:
    """Seeded q, k, v, do ``[B, S, n, d]`` bfloat16, g (a memory of one to a
    thousand positions a channel) and beta, float32: a KDA layer's rule."""
    import jax
    import jax.numpy as jnp
    q, k, v, do = (jnp.asarray(rng.randn(B, S, n, d), jnp.bfloat16)
                   for _ in range(4))
    g = -jnp.exp(jnp.asarray(rng.uniform(np.log(1e-3), np.log(1.6),
                                         (B, S, n, d)), jnp.float32))
    beta = jax.nn.sigmoid(jnp.asarray(rng.randn(B, S, n), jnp.float32))
    return q, k, v, do, g, beta


def _no_scalar_forward(qn, kn, kk, qk, g, b, v, s):
    """In ``pallas_delta._scalar_forward``'s place: nothing of a value head's
    chunk (its key head's norms, ``k k^T`` and ``q k^T`` stay)."""
    return v.astype("float32"), s


def _no_scalar_backward(qn, kn, kk, qk, g, b, v, s, dsn, do):
    """In ``pallas_delta._scalar_backward``'s place, as ``_no_backward``."""
    qf, kf = qn.astype("float32"), kn.astype("float32")
    return do.astype("float32"), qf, kf, kf, kk, qk, g, b, dsn


def delta_kernels(feeds, chunks, packed_from, step_heads, interpret) -> list:
    """Forward / backward milliseconds a layer of the channel kernels on
    ``channel_feeds``' arrays, a row each chunk, ``packed_from``
    (``pallas_delta.PACKED_FROM``'s place) and ``step_heads`` (``STEP_
    HEADS``'s: the row says what ``pallas_delta.step_heads`` took of it, and
    what the grid costs with empty bodies); an empty list keeps the
    constant."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import decoder_ops, pallas_delta
    q, k, v, do, g, beta = feeds
    B, S, n, d = q.shape
    flat = decoder_ops._flat
    rows = []
    for chunk in chunks:
        if not pallas_delta.supports(S, n, n, d, d, chunk, channel=True):
            say(f"delta kernels: chunk {chunk} at heads of {d} is not theirs")
            continue
        ops = (jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1),
               decoder_ops._chunk_sums(g, chunk), beta)
        sums = _ms(jax.jit(lambda g: decoder_ops._chunk_sums(g, chunk)), g)

        def both():
            o, states = pallas_delta._fwd_call(*ops, chunk, interpret)
            fwd = _ms(lambda: pallas_delta._fwd_call(*ops, chunk, interpret))
            bwd = _ms(lambda: pallas_delta._bwd_call(
                *ops, states, flat(do), chunk, interpret))
            return fwd, bwd, bool(jnp.isfinite(o).all())
        for size, heads in itertools.product(
                packed_from or [pallas_delta.PACKED_FROM],
                step_heads or [pallas_delta.STEP_HEADS]):
            with swapped(pallas_delta, PACKED_FROM=size, STEP_HEADS=heads):
                fwd, bwd, finite = both()
                row = {"chunk": chunk, "packed_from": size,
                       "step_heads": pallas_delta.step_heads(n),
                       "fwd_ms": fwd, "bwd_ms": bwd, "chunk_sums_ms": sums,
                       "finite": finite}
                empty = ""
                if step_heads:
                    with swapped(pallas_delta, _channel_forward=_no_forward,
                                 _channel_backward=_no_backward):
                        row["empty_fwd_ms"], row["empty_bwd_ms"], _ = both()
                    empty = (f"; empty bodies {row['empty_fwd_ms']:.3f} / "
                             f"{row['empty_bwd_ms']:.3f}")
            rows.append(row)
            say(f"KDA kernels, chunk {chunk}, lower rows alone from blocks of "
                f"{size}, {row['step_heads']} heads a grid step: forward "
                f"{fwd:.3f} backward {bwd:.3f} ms a layer ({B} x {S}, {n} "
                f"heads of {d}){empty}; the running sums of g around them "
                f"{sums:.3f} ms")
    return rows


def scalar_kernels(B, S, n, d, chunk, reps, step_heads, interpret,
                   rng) -> list:
    """Forward / backward milliseconds a layer of the scalar-decay kernels at
    ``n`` value heads, by the value heads a key head (``qwen3_next`` has 16
    key heads under 32 value heads) and by ``step_heads`` as
    ``delta_kernels`` takes them: a grid step of theirs is ``pallas_delta.
    step_heads``' key heads and all their value heads' chains, side by
    side."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import decoder_ops, pallas_delta
    bf = jnp.bfloat16
    v, do = (jnp.asarray(rng.randn(B, S, n * d), bf) for _ in range(2))
    g = -jnp.exp(jnp.asarray(rng.uniform(np.log(1e-3), np.log(1.6),
                                         (B, S, n)), jnp.float32))
    beta = jax.nn.sigmoid(jnp.asarray(rng.randn(B, S, n), jnp.float32))
    rows = []
    for rep in reps:
        if n % rep or not pallas_delta.supports(S, n // rep, n, d, d, chunk):
            say(f"scalar kernels: {n} heads over {n // rep} at chunk {chunk} "
                f"are not theirs")
            continue
        q, k = (jnp.asarray(rng.randn(B, S, n // rep * d), bf)
                for _ in range(2))
        ops = ((q, k, v), decoder_ops._chunk_sums(g, chunk), beta)

        def both():
            _, states = pallas_delta._fwd_call(*ops, chunk, interpret)
            return (_ms(lambda: pallas_delta._fwd_call(*ops, chunk,
                                                       interpret)),
                    _ms(lambda: pallas_delta._bwd_call(*ops, states, do,
                                                       chunk, interpret)))
        for heads in step_heads or [pallas_delta.STEP_HEADS]:
            with swapped(pallas_delta, STEP_HEADS=heads):
                step = pallas_delta.step_heads(n // rep, n)
                what = (f"scalar-decay kernels, chunk {chunk}, {n // rep} "
                        f"key heads under {n} value heads, {step} key heads "
                        f"({step * rep} chains) a grid step")
                try:
                    fwd, bwd = both()
                    row = {"chunk": chunk, "key_heads": n // rep, "rep": rep,
                           "step_heads": step, "fwd_ms": fwd, "bwd_ms": bwd}
                    empty = ""
                    if step_heads:
                        with swapped(pallas_delta,
                                     _scalar_forward=_no_scalar_forward,
                                     _scalar_backward=_no_scalar_backward):
                            row["empty_fwd_ms"], row["empty_bwd_ms"] = both()
                        empty = (f"; empty bodies {row['empty_fwd_ms']:.3f} "
                                 f"/ {row['empty_bwd_ms']:.3f}")
                except Exception as e:      # noqa: BLE001  (Mosaic's refusal)
                    say(f"{what}: {type(e).__name__}: {str(e)[:200]}")
                    continue
            rows.append(row)
            say(f"{what}: forward {fwd:.3f} backward {bwd:.3f} ms a layer "
                f"({B} x {S}){empty}")
    return rows


def kernels(args) -> dict:
    """The rule's kernels by chunk, the composed form, the flash kernels by
    q / k width."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import decoder_ops, pallas_mode
    cell = load_cell(args)
    model, p = cell["model"], cell["params"]
    B, S = p["batch"], p["seq"]
    lin = model["linear_attn_config"]
    n, d = lin["num_heads"], lin["head_dim"]
    interpret = pallas_mode.interpret() if args.rehearsal else False
    rng = np.random.RandomState(args.seed % (2 ** 31))
    feeds = q, k, v, do, g, beta = channel_feeds(B, S, n, d, rng)
    result = {"mode": "kernels", "flash": [], "delta": delta_kernels(
        feeds, args.chunks, args.packed_from, args.step_heads, interpret)}
    if args.scalar_reps:
        result["scalar"] = scalar_kernels(
            B, S, n, d, min(args.chunks[0], S), args.scalar_reps,
            args.step_heads, interpret, rng)
    first = min(args.chunks[0], S)

    def composed(q, k, v, g, beta):
        qn, kn, cum = decoder_ops._delta_operands(q, k, g, first,
                                                  jnp.float32)
        return decoder_ops.composed_channel_delta_rule(
            qn, kn, v, cum, beta, first)[0]
    try:
        both = jax.jit(lambda *a: jax.vjp(composed, *a[:5])[1](
            a[5].astype(jnp.float32)))
        temp = both.lower(q, k, v, g, beta, do).compile().memory_analysis()
        c_fwd = _ms(jax.jit(composed), q, k, v, g, beta)
        c_both = _ms(both, q, k, v, g, beta, do)
        say(f"composed chunk form (chunk {first}): forward {c_fwd:.3f}, "
            f"forward + backward {c_both:.3f} ms a layer; temporaries "
            f"{temp.temp_size_in_bytes / 1e9:.3f} GB")
        result.update(composed_fwd_ms=c_fwd, composed_fwd_bwd_ms=c_both,
                      composed_temp_gb=temp.temp_size_in_bytes / 1e9)
    except Exception as e:      # noqa: BLE001
        say(f"composed chunk form: {type(e).__name__}: {str(e)[:300]}")
        result["composed_error"] = f"{type(e).__name__}: {e}"[:300]
    d_qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    widths = sorted({d_qk, -(-d_qk // 128) * 128})   # as published, as written
    result["flash"] = flash_by_width(
        B, S, model["num_attention_heads"], widths, model["v_head_dim"],
        d_qk ** -0.5, interpret, rng)
    return result


def main(argv=None) -> int:
    def options(ap):
        ap.set_defaults(cell=CELL)
        ap.add_argument("--chunks", nargs="*", type=int, default=[64, 128],
                        help="kernels: chunk lengths of the rule")
        ap.add_argument("--packed-from", nargs="*", type=int, default=[],
                        help="kernels: block lengths in pallas_delta."
                             "PACKED_FROM's place, e.g. 4 8 16")
        ap.add_argument("--step-heads", nargs="*", type=int, default=[],
                        help="kernels: value heads a grid step in "
                             "pallas_delta.STEP_HEADS's place, e.g. 1 2 4 8, "
                             "each with the empty bodies' time beside it")
        ap.add_argument("--scalar-reps", nargs="*", type=int, default=[],
                        help="kernels: the scalar-decay pair at as many "
                             "value heads, by value heads a key head, e.g. "
                             "1 2 4, at every --step-heads")
        ap.add_argument("--seeds", nargs="*", type=int, default=[],
                        help="readings: a check each")
    laguna_probe.load_cell = load_cell      # its modes load the cell by it
    try:
        return laguna_probe.main(
            argv, modes={"load": held_shares, "controls": controls,
                         "parts": check_parts, "readings": readings,
                         "grads": gradients,
                         "kernels": kernels}, doc=__doc__, options=options)
    finally:
        laguna_probe.load_cell = _laguna_load_cell


if __name__ == "__main__":
    sys.exit(main())
