"""What the benchmark's harness does not fetch from the cell
``qwen3_next_80b_a3b.pretrain_s4096`` (it reads the loss alone): the
builder's chip readings of PERF.md section 6, PR 41. On a TPU through
``chiprun -- python3 tools/qwen3_next_probe.py <mode> ...``; ``--rehearsal``
runs the data files' rehearsal sizes on the CPU (a debug run: no device
number). ``load`` and ``grads`` are ``tools/laguna_probe.py``'s on this cell
(the held share of the assignments and the dropped rows over ``--steps``
steps; one step's gradients against ``jax.grad`` of the plain reference, by
leaf); this file adds:

``controls``  at the cell's own check (seeded weights, before any step),
              every verdict ``benchmark.jobs.common.reference_check``'s own:
              the program as it is; float8 (e4m3) weights in the program's
              place; and one mechanism of the model taken out of the PROGRAM
              while the reference keeps it -- the decay left out (``g = 0``),
              ``beta = 1``, the l2 norm of q and k left out, the attention
              gate out, the shared expert's gate out, the rotary embedding
              over the whole head, a tenth of the row budget (rows are
              dropped). All must fail. ``bf16_state`` is the other way
              round: the REFERENCE's recurrence carries a bfloat16 state
              and the program is as it is -- whether the check can tell a
              coarser state from the float32 one.
``kernels``   the delta rule's kernels against the composed chunk form at
              the cell's shapes, forward and backward, by chunk length:
              milliseconds a layer and the composed form's temporaries; and
              the flash kernels at the cell's attention shapes (16 query
              over 2 key/value heads of 256, causal) over ``--blocks``; and
              the expert layers' token sums (``tools/laguna_probe.py``'s
              ``sums``, a mode here too: the kernel against the scatter-add
              at 8192 x top-10 of 2048 under the budget of 20480 rows).
``norm``      the gated norm at a DeltaNet mixer's end (``rms_norm`` given
              the gate, ``ops/pallas_norm.py``) at the cell's shapes:
              milliseconds a layer and GB/s of its kernels, forward and
              backward, over ``--norm-blocks`` rows a block and
              ``--norm-chunks`` rows a chunk (0: the defaults), beside the
              two-op form it replaced (``rms_norm`` then ``swiglu``, each
              float32 inside, the backward as ``jax.vjp`` of both).
"""
from __future__ import annotations

import contextlib
import copy
import functools
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools import laguna_probe  # noqa: E402
from tools.laguna_probe import _ms, say  # noqa: E402

CELL = "qwen3_next_80b_a3b.pretrain_s4096"
MECHANISMS = ("decay", "beta", "l2_norm", "attention_gate", "shared_gate",
              "partial_rotary", "row_budget", "bf16_state")


def without(model: dict, mechanism: str) -> dict:
    """The configuration with one mechanism taken out, where a key does it;
    parameters keep their names and shapes, so the program runs on the
    cell's own weights. The others are ``patched``'s."""
    model = copy.deepcopy(model)
    if mechanism == "shared_gate":
        model["shared_expert_gate"] = False
    elif mechanism == "partial_rotary":
        model["partial_rotary_factor"] = 1
    elif mechanism == "row_budget":
        # four tenths of an even router's rows at the cell's 4 x
        model["moe_row_budget"] //= 10
    elif mechanism not in MECHANISMS:
        raise ValueError(mechanism)
    return model


@contextlib.contextmanager
def patched(mechanism: str):
    """What no configuration key takes out, swapped around one program's
    build and check: the layer function a mixer calls (the decay, the step,
    the attention gate), the expression both lowerings normalise q and k by
    (the l2 norm), or -- for ``bf16_state`` -- the reference's recurrence."""
    from paddle_tpu import layers
    from paddle_tpu.ops import pallas_delta
    from benchmark.references import qwen3_next_pretrain as reference
    swaps, stale = [], ()

    def swap(owner, name, new):
        swaps.append((owner, name, getattr(owner, name)))
        setattr(owner, name, new)
    rule = layers.gated_delta_rule_packed
    if mechanism == "decay":
        swap(layers, "gated_delta_rule_packed",
             lambda qkv, g, beta, *a, **kw: rule(
                 qkv, layers.scale(g, 0.0), beta, *a, **kw))
    elif mechanism == "beta":
        swap(layers, "gated_delta_rule_packed",
             lambda qkv, g, beta, *a, **kw: rule(
                 qkv, g, layers.scale(beta, 0.0, bias=1.0), *a, **kw))
    elif mechanism == "attention_gate":
        swap(layers, "attention_gate", lambda x, gate, name=None: x)
    elif mechanism == "l2_norm":
        # one expression for the composed form's operands and the kernels'
        # prologue (whose jits hold a body traced with the real one)
        swap(pallas_delta, "unit",
             lambda x, scale=None: x if scale is None else x * scale)
        stale = (pallas_delta._fwd_call, pallas_delta._bwd_call)
    elif mechanism == "bf16_state":
        import jax.numpy as jnp
        swap(reference, "delta_rule", functools.partial(
            reference.delta_rule, state_dtype=jnp.bfloat16))
    try:
        for call in stale:
            call.clear_cache()
        yield
    finally:
        for owner, name, old in swaps:
            setattr(owner, name, old)
        for call in stale:
            call.clear_cache()


def controls(args) -> dict:
    return laguna_probe.controls(args, without, MECHANISMS, patched)


def gradients(args) -> dict:
    from benchmark.references import qwen3_next_pretrain as reference
    result = laguna_probe.gradients(args, reference)
    for row in result["the_programs"]["leaves"]:
        if row["name"].endswith(("_A_log", "_dt_bias", "_ba_w")):
            say(f"  along the program's routing: {row['name']:<24} "
                f"|d|/|ref| {row['l2']:.3e} cos {row['cos']:.6f}")
    return result


def flash_by_blocks(B, S, heads, kv, d, blocks, interpret, rng) -> list:
    """Forward / backward milliseconds a layer of the causal flash kernels,
    each call compiled alone, at ``heads`` query over ``kv`` key/value heads
    of ``d``: the default blocks, then every ``block_q x block_k`` of
    ``blocks``. A pair Mosaic refuses is said, not hidden."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_attention as pa
    bf = jnp.bfloat16
    fq, fg = (jnp.asarray(rng.randn(B, heads, S, d), bf) for _ in range(2))
    fk, fv = (jnp.asarray(rng.randn(B, kv, S, d), bf) for _ in range(2))
    scale, seed = d ** -0.5, jnp.int32(3)
    rows = []
    for pair in [None] + [tuple(int(x) for x in b.split("x"))
                          for b in blocks]:
        try:
            bq, bk = pair or pa._blocks(S, True, None, None, None)
            out, lse = pa._fwd_call(fq, fk, fv, None, seed, scale, 0.0, True,
                                    interpret, bq, bk, None)
            fwd = _ms(lambda: pa._fwd_call(fq, fk, fv, None, seed, scale, 0.0,
                                           True, interpret, bq, bk, None))
            bwd = _ms(lambda: pa._bwd_call(fq, fk, fv, None, seed, fg, lse,
                                           scale, 0.0, True, interpret, bq,
                                           bk, None))
        except Exception as e:      # noqa: BLE001
            say(f"flash d={d} blocks {pair or 'default'}: "
                f"{type(e).__name__}: {str(e)[:200]}")
            continue
        rows.append({"block_q": bq, "block_k": bk, "fwd_ms": fwd,
                     "bwd_ms": bwd, "default": pair is None})
        say(f"flash {heads} / {kv} heads of {d}, blocks {bq} x {bk}"
            f"{' (the default)' if pair is None else ''}: forward "
            f"{fwd:.3f} backward {bwd:.3f} ms a layer")
    return rows


def kernels(args) -> dict:
    """Forward / backward milliseconds a layer of the delta rule's kernels
    by chunk, of the composed chunk form, and of the flash kernels at
    d=256 by blocks."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import decoder_ops, pallas_delta, pallas_mode
    cell = laguna_probe.load_cell(args)
    model, p = cell["model"], cell["params"]
    B, S = p["batch"], p["seq"]
    n_k, n_v = model["linear_num_key_heads"], model["linear_num_value_heads"]
    d_k, d_v = model["linear_key_head_dim"], model["linear_value_head_dim"]
    interpret = pallas_mode.interpret() if args.rehearsal else False
    rng = np.random.RandomState(args.seed % (2 ** 31))
    bf = jnp.bfloat16
    q, k = (jnp.asarray(rng.randn(B, S, n_k, d_k), bf) for _ in range(2))
    v, do = (jnp.asarray(rng.randn(B, S, n_v, d_v), bf) for _ in range(2))
    g = -jnp.exp(jnp.asarray(rng.uniform(np.log(1e-3), np.log(1.6),
                                         (B, S, n_v)), jnp.float32))
    beta = jax.nn.sigmoid(jnp.asarray(rng.randn(B, S, n_v), jnp.float32))
    flat = decoder_ops._flat
    result = {"mode": "kernels", "delta": [], "flash": []}
    for chunk in args.chunks:
        if not pallas_delta.supports(S, n_k, n_v, d_k, d_v, chunk):
            say(f"delta kernels: chunk {chunk} at heads of {d_k} / {d_v} is "
                f"not theirs")
            continue
        # the cell's operand form: raw q | k | v, one array read in place
        ops = (jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1),
               decoder_ops._chunk_sums(g, chunk), beta)
        o, states = pallas_delta._fwd_call(*ops, chunk, interpret)
        fwd = _ms(lambda: pallas_delta._fwd_call(*ops, chunk, interpret))
        bwd = _ms(lambda: pallas_delta._bwd_call(
            *ops, states, flat(do), chunk, interpret))
        result["delta"].append({"chunk": chunk, "fwd_ms": fwd,
                                "bwd_ms": bwd})
        say(f"delta kernels, chunk {chunk}: forward {fwd:.3f} backward "
            f"{bwd:.3f} ms a layer ({B} x {S}, {n_k} / {n_v} heads)")

    first = min(args.chunks[0], S)      # the rehearsal's sequence is shorter

    def composed(q, k, v, g, beta):
        qn, kn, cum = decoder_ops._delta_operands(q, k, g, first,
                                                  jnp.float32)
        return decoder_ops.composed_gated_delta_rule(
            qn, kn, v, cum, beta, first)[0]
    both = jax.jit(lambda *a: jax.vjp(composed, *a[:5])[1](
        a[5].astype(jnp.float32)))
    c_fwd = _ms(jax.jit(composed), q, k, v, g, beta)
    c_both = _ms(both, q, k, v, g, beta, do)
    temp = both.lower(q, k, v, g, beta, do).compile().memory_analysis()
    say(f"composed chunk form (chunk {first}): forward {c_fwd:.3f}, "
        f"forward + backward {c_both:.3f} ms a layer; temporaries "
        f"{temp.temp_size_in_bytes / 1e9:.3f} GB")
    result.update(composed_fwd_ms=c_fwd, composed_fwd_bwd_ms=c_both,
                  composed_temp_gb=temp.temp_size_in_bytes / 1e9)
    # the flash kernels at the cell's attention shapes
    result["flash"] = flash_by_blocks(
        B, S, model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"], args.blocks, interpret, rng)
    result["token_sums"] = laguna_probe.token_sums(args)
    return result


def norm(args) -> dict:
    """Forward / backward milliseconds a layer of the gated norm's kernels
    by block and chunk, and of the two-op form left to XLA."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_mode, pallas_norm, pallas_rope
    cell = laguna_probe.load_cell(args)
    model, p = cell["model"], cell["params"]
    T = p["batch"] * p["seq"]
    n_v, d_v = model["linear_num_value_heads"], model["linear_value_head_dim"]
    eps = float(model["rms_norm_eps"])
    interpret = pallas_mode.interpret() if args.rehearsal else False
    rng = np.random.RandomState(args.seed % (2 ** 31))
    bf = jnp.bfloat16
    x, dy = (jnp.asarray(rng.randn(T, n_v, d_v), bf) for _ in range(2))
    z = jnp.asarray(rng.randn(T, n_v * d_v), bf)
    w = jnp.asarray(1 + 0.1 * rng.randn(d_v), jnp.float32)
    moved = x.size * 2 / 1e6       # MB of one operand
    default_chunk = pallas_norm.CHUNK_ROWS      # kept while the sweep sets it
    result = {"mode": "norm", "kernels": []}
    sweep = [(rows, chunk) for rows in args.norm_blocks
             for chunk in args.norm_chunks]
    if not (pallas_norm.supports(T, d_v) and pallas_mode.available()):
        say(f"gated norm kernels: rows of {d_v} on "
            f"{jax.default_backend()!r} are not theirs")
        sweep = []
    for rows, chunk in sweep:
        pallas_norm.block_rows_of = (
            (lambda r, d, rows=rows: min(rows, r)) if rows
            else pallas_rope.block_rows_of)
        pallas_norm.CHUNK_ROWS = chunk or default_chunk
        for call in (pallas_norm._fwd_call, pallas_norm._bwd_call):
            call.clear_cache()
        # eight calls chained inside one jit: a call is under a
        # millisecond, and a dispatch from the host is not much less
        def fwd8(x, z, w):
            for _ in range(8):
                x = pallas_norm._fwd_call(x, z, w, eps, interpret)
            return x

        def bwd8(x, z, w, dy):
            for _ in range(8):
                x, dz, dw = pallas_norm._bwd_call(x, z, w, dy, eps,
                                                  interpret)
            return x, dz, dw
        try:
            fwd = _ms(jax.jit(fwd8), x, z, w, calls=2, repeats=5) / 8
            bwd = _ms(jax.jit(bwd8), x, z, w, dy, calls=2, repeats=5) / 8
        except Exception as e:      # a block the compiler refuses
            say(f"gated norm kernels, {rows} rows a block, {chunk} a "
                f"chunk: {type(e).__name__}: {str(e)[:200]}")
            continue
        result["kernels"].append({"block_rows": rows, "chunk_rows": chunk,
                                  "fwd_ms": fwd, "bwd_ms": bwd})
        say(f"gated norm kernels, {rows or 'default'} rows a block, "
            f"{chunk or 'default'} a chunk: forward {fwd:.3f} ms "
            f"({3 * moved / fwd:.0f} GB/s) backward {bwd:.3f} ms "
            f"({5 * moved / bwd:.0f} GB/s) a layer ({T} x {n_v} x {d_v})")

    def two_ops(x, z, w):           # the parent's ops, each float32 inside
        xf = x.astype(jnp.float32)
        o = (xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                                + eps) * w).astype(x.dtype)
        gf = z.astype(jnp.float32)
        return (gf * jax.nn.sigmoid(gf) * o.reshape(z.shape).astype(
            jnp.float32)).astype(z.dtype)
    both = jax.jit(lambda x, z, w, dy: jax.vjp(two_ops, x, z, w)[1](
        dy.reshape(z.shape)))
    t_fwd = _ms(jax.jit(two_ops), x, z, w)
    t_both = _ms(both, x, z, w, dy)
    say(f"two-op form left to XLA: forward {t_fwd:.3f}, forward + backward "
        f"{t_both:.3f} ms a layer")
    result.update(two_op_fwd_ms=t_fwd, two_op_fwd_bwd_ms=t_both)
    return result


def main(argv=None) -> int:
    def options(ap):
        ap.set_defaults(cell=CELL)
        ap.add_argument("--chunks", type=int, nargs="*", default=[64, 128],
                        help="kernels: the chunk lengths to time")
        ap.add_argument("--norm-blocks", type=int, nargs="*", default=[0],
                        help="norm: rows a block (0: the default)")
        ap.add_argument("--norm-chunks", type=int, nargs="*", default=[0],
                        help="norm: rows a chunk (0: the default)")
    return laguna_probe.main(
        argv, {"load": laguna_probe.held_shares, "controls": controls,
               "grads": gradients, "kernels": kernels, "norm": norm},
        __doc__, options)


if __name__ == "__main__":
    sys.exit(main())
