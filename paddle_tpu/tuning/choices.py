"""Tunable choice points + the ``decide()`` front door.

A ``TunableChoice`` names one degree of freedom the op library cannot pick
statically -- ROOFLINE_RESNET.md is the proof: the fused Pallas conv+BN
kernel loses to XLA at every ResNet-50 bottleneck shape while the Pallas
flash kernel wins 1.72x at S=2048, so the right answer is per-shape and
per-device and only measurement finds it. Each choice point declares

- ``bucket(params)``     -- the shape bucket that keys its decisions
                            (batch-like dims round up to powers of two so
                            near-miss batch sizes share one decision);
- ``candidates(params)`` -- the legal candidates for these params;
- ``default(params)``    -- the static heuristic used when tuning is off or
                            no decision is cached (ALWAYS the pre-autotuner
                            behavior, so ``PADDLE_TPU_TUNE=off`` is exactly
                            the old code path);
- ``bench(params, cand)``-- ``(fn, args)`` measured by measure.py, or None
                            when the candidate cannot run on this host;
- ``encode/decode``      -- the stable string form persisted in the JSON
                            decision cache.

The four wired choice points (the ROOFLINE/ISSUE set):

==============================  =============================================
``conv2d_bn_fused.backend``     Pallas fused kernel vs XLA chain for the
                                train-mode 1x1-conv+BN op
``fused_attention.backend``     Pallas flash kernel vs XLA's own fusion
                                (the *auto* policy; its default is the
                                measured AUTO_PALLAS_MIN_S crossover)
``fused_attention.block_sizes`` flash (block_q, block_k), both dividing S;
                                a K tile narrower than S only where the op
                                is causal (default: the kernel's
                                default_block_q / default_block_k)
``conv2d.layout``               run a conv NHWC vs NCHW regardless of the
                                declared data_format (transposing at the op
                                boundary; XLA cancels adjacent transposes)
==============================  =============================================
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

from ..observability.metrics import REGISTRY as _OBS
from . import cache as _cache
from . import measure as _measure


def pow2_bucket(n: int) -> int:
    """Round up to a power of two (1 -> 1, 24 -> 32): batch-like dims vary
    freely across runs and must not each earn a separate search."""
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


@functools.lru_cache(maxsize=None)
def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


@functools.lru_cache(maxsize=None)
def jax_version() -> str:
    import jax
    return jax.__version__


class TunableChoice:
    """Base class; subclasses set ``id`` and implement the hooks."""

    id: str = ""
    doc: str = ""

    def bucket(self, params: dict):
        raise NotImplementedError

    def candidates(self, params: dict) -> List[Any]:
        raise NotImplementedError

    def default(self, params: dict):
        raise NotImplementedError

    def bench(self, params: dict, candidate):
        """(fn, args) for measure.time_callable, or None if unmeasurable."""
        return None

    # decisions persist as strings; keep them stable across versions
    def encode(self, candidate) -> str:
        return str(candidate)

    def decode(self, raw: str):
        return raw

    def key(self, params: dict) -> str:
        return _cache.make_key(self.id, self.bucket(params),
                               str(params.get("dtype", "float32")),
                               device_kind(), jax_version())


_CHOICES: Dict[str, TunableChoice] = {}


def register_choice(choice: TunableChoice) -> TunableChoice:
    if not choice.id:
        raise ValueError("TunableChoice needs a non-empty id")
    if choice.id in _CHOICES:
        raise ValueError(f"duplicate tunable choice id {choice.id!r}")
    _CHOICES[choice.id] = choice
    return choice


def get_choice(choice_id: str) -> TunableChoice:
    try:
        return _CHOICES[choice_id]
    except KeyError:
        raise KeyError(
            f"unknown tunable choice {choice_id!r}; registered: "
            f"{sorted(_CHOICES)}") from None


def list_choices() -> List[str]:
    return sorted(_CHOICES)


def _count(choice_id: str, source: str):
    _OBS.counter("autotune_decisions_total",
                 "autotune decide() answers by choice point and source",
                 choice=choice_id, source=source).inc()


def decide(choice_id: str, params: dict, allow_search: bool = True,
           mode: Optional[str] = None):
    """Answer one tunable choice for ``params``.

    ``mode`` overrides the ``PADDLE_TPU_TUNE`` env gate (the CLI forces
    ``search``). ``allow_search=False`` (abstract/eval_shape lowering) never
    measures even in search mode. The answer is always a legal candidate:
    a stale persisted decision that is no longer in ``candidates(params)``
    (jax upgrade, shape-gate change) falls back to the default rather than
    resurrecting an illegal lowering.
    """
    choice = get_choice(choice_id)
    m = mode if mode is not None else _cache.mode()
    if m == "off":
        return choice.default(params)
    key = choice.key(params)
    rec = _cache.CACHE.get(key)
    if rec is not None:
        try:
            val = choice.decode(rec["winner"])
        except (KeyError, ValueError, TypeError):
            val = None
        if val is not None and val in choice.candidates(params):
            _count(choice_id, "cached")
            return val
    if m == "search" and allow_search:
        rec = _measure.search(choice, params, key)
        _cache.CACHE.put(key, rec)
        _count(choice_id, "search")
        val = choice.decode(rec["winner"])
        if val in choice.candidates(params):
            return val
    _count(choice_id, "default")
    return choice.default(params)


# --------------------------------------------------------------------------------------
# choice point 1: Pallas vs XLA for the fused 1x1-conv+BN op
# --------------------------------------------------------------------------------------


class ConvBnBackend(TunableChoice):
    id = "conv2d_bn_fused.backend"
    doc = ("backend for the train-mode 1x1-conv+BN op: 'pallas' (fused "
           "kernel with the stats epilogue) or 'xla' (dot + separate "
           "mean/var reduces, which XLA fuses itself)")

    def bucket(self, params):
        # M = B*H*W scales with batch: bucket it; K/N are architectural
        return {"m": pow2_bucket(params["m"]), "k": int(params["k"]),
                "n": int(params["n"])}

    def candidates(self, params):
        from ..ops import pallas_mode
        from ..ops.pallas_conv_bn import supports_fused
        out = ["xla"]
        if (pallas_mode.available()
                and supports_fused(params["m"], params["k"], params["n"])):
            out.append("pallas")
        return out

    def default(self, params):
        # pre-autotuner behavior: the fused op (opt-in via the fuse pass)
        # ran its Pallas kernel whenever the shape gate allowed
        return "pallas" if "pallas" in self.candidates(params) else "xla"

    def bench(self, params, candidate):
        import jax
        import jax.numpy as jnp
        m, k, n = params["m"], params["k"], params["n"]
        # inputs are HOST arrays: a search can fire inside an executor trace
        # (decide() runs in op lowerings), where jnp.zeros would return a
        # tracer of the AMBIENT trace and break the isolated measurement jit
        x2 = _np_zeros((m, k), params.get("dtype", "float32"))
        w2 = _np_zeros((k, n), params.get("dtype", "float32"))
        if candidate == "pallas":
            from ..ops import pallas_mode
            from ..ops.pallas_conv_bn import fused_conv1x1_bn_fwd
            if not pallas_mode.available():
                return None
            interpret = pallas_mode.interpret()

            def pallas_fn(x2, w2):
                dummy = jnp.zeros((k,), jnp.float32)
                y2, s, ss = fused_conv1x1_bn_fwd(
                    x2, w2, dummy, jnp.ones((k,), jnp.float32), dummy, dummy,
                    relu_in=False, apply_in_bn=False, interpret=interpret)
                mean = s / m
                var = jnp.maximum(ss / m - mean * mean, 0.0)
                return y2, mean, var

            return pallas_fn, (x2, w2)

        def xla_fn(x2, w2):
            y2 = jax.lax.dot_general(x2, w2, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32
                                     ).astype(x2.dtype)
            yf = y2.astype(jnp.float32)
            mean = jnp.mean(yf, axis=0)
            var = jnp.maximum(jnp.mean(yf * yf, axis=0) - mean * mean, 0.0)
            return y2, mean, var

        return xla_fn, (x2, w2)


# --------------------------------------------------------------------------------------
# choice point 2: Pallas flash vs XLA fusion for fused_attention's auto impl
# --------------------------------------------------------------------------------------


def _np_zeros(shape, dtype):
    """Host-side zeros in any jax dtype (incl. bfloat16 via ml_dtypes):
    bench inputs must be concrete even when a search fires inside an
    executor trace, so they are never built with jnp."""
    import jax
    import numpy as np
    return np.zeros(shape, jax.dtypes.canonicalize_dtype(dtype))


def _attn_inputs(params):
    b, h, s, d = (int(params[k]) for k in ("b", "h", "s", "d"))
    dt = params.get("dtype", "float32")
    q = _np_zeros((b, h, s, d), dt)
    bias = (_np_zeros((b, 1, 1, s), dt)
            if params.get("has_bias") else None)
    return q, bias


def _attn_bucket(params):
    """Shared shape bucket for BOTH attention choice points: bias/causal/
    dropout change the kernel's per-block work, so neither a backend verdict
    nor a block_q measured under one configuration may be reused for
    another."""
    bucket = {"bh": pow2_bucket(int(params["b"]) * int(params["h"])),
              "s": int(params["s"]), "d": int(params["d"]),
              "bias": bool(params.get("has_bias")),
              "causal": bool(params.get("causal")),
              "dropout": round(float(params.get("dropout", 0.0)), 3)}
    if params.get("window"):    # a sliding window visits other tiles
        bucket["window"] = int(params["window"])
    return bucket


class FlashBackend(TunableChoice):
    id = "fused_attention.backend"
    doc = ("impl='auto' backend for fused_attention: 'pallas' (flash "
           "kernel) or 'xla' (composed jnp attention, XLA-fused); default: "
           "the measured S >= AUTO_PALLAS_MIN_S crossover")

    def bucket(self, params):
        return _attn_bucket(params)

    def candidates(self, params):
        from ..ops import pallas_mode
        from ..ops.pallas_attention import supports_pallas
        bias_shape = ((int(params["b"]), 1, 1, int(params["s"]))
                      if params.get("has_bias") else None)
        out = ["xla"]
        if pallas_mode.available() and supports_pallas(
                params["b"], params["h"], params["s"], params["d"],
                bias_shape, float(params.get("dropout", 0.0)),
                pallas_mode.on_tpu()):
            out.append("pallas")
        return out

    def default(self, params):
        from ..ops.pallas_attention import AUTO_PALLAS_MIN_S
        if ("pallas" in self.candidates(params)
                and int(params["s"]) >= AUTO_PALLAS_MIN_S):
            return "pallas"
        return "xla"

    def bench(self, params, candidate):
        if candidate == "pallas":
            return _flash_bench(params, None)
        import jax
        from ..ops.pallas_attention import composed_attention
        q, bias, scale, dropout, causal = _attn_bench_args(params)
        rng = jax.random.PRNGKey(0)

        def xla_fn(q, k, v):
            return _fwd_bwd(lambda q, k, v: composed_attention(
                q, k, v, bias, scale, dropout, causal, rng,
                window=params.get("window")), q, k, v)

        return xla_fn, (q, q, q)


def _attn_bench_args(params):
    import math
    q, bias = _attn_inputs(params)
    scale = float(params.get("scale") or 1.0 / math.sqrt(int(params["d"])))
    return (q, bias, scale, float(params.get("dropout", 0.0)),
            bool(params.get("causal")))


def _fwd_bwd(attend, q, k, v):
    """What a training step pays for the op: the forward and the three
    gradients (the output stands in for its cotangent)."""
    import jax
    out, vjp = jax.vjp(attend, q, k, v)
    return out, vjp(out)


def _flash_bench(params, block_q, block_k=None):
    """(fn, args) timing the flash kernels forward + backward at ``block_q``
    and ``block_k`` (None: the defaults for S), or None where they cannot
    run."""
    from ..ops import pallas_mode
    from ..ops.pallas_attention import _flash
    if not pallas_mode.available():
        return None
    interpret = pallas_mode.interpret()
    q, bias, scale, dropout, causal = _attn_bench_args(params)

    def pallas_fn(q, k, v):
        return _fwd_bwd(lambda q, k, v: _flash(
            q, k, v, bias, 0, scale, dropout, causal, interpret, block_q,
            block_k, params.get("window")),
            q, k, v)

    return pallas_fn, (q, q, q)


# --------------------------------------------------------------------------------------
# choice point 3: flash kernel block sizes
# --------------------------------------------------------------------------------------


class FlashBlockSizes(TunableChoice):
    id = "fused_attention.block_sizes"
    doc = ("(block_q, block_k) of the flash kernels: the Q rows a grid step "
           "and the columns a K tile, every candidate dividing S. One tile "
           "is the row (block_k = S) for every op; a causal op, whose Q "
           "blocks skip the tiles above their diagonal, may also take "
           "narrower tiles. The benches time forward + backward.")

    #: every multiple of the kernel's 128 rows up to here that divides S
    MAX_BLOCK_Q = 1024
    #: the K tiles narrower than S a causal op may take
    CAUSAL_BLOCK_K = (256, 512, 1024)

    def bucket(self, params):
        return _attn_bucket(params)

    def candidates(self, params):
        s = int(params["s"])
        tiles = [s] + [bk for bk in self.CAUSAL_BLOCK_K
                       if params.get("causal") and bk < s and s % bk == 0]
        return [(bq, bk) for bk in tiles
                for bq in range(128, min(s, self.MAX_BLOCK_Q) + 1, 128)
                if s % bq == 0]

    def default(self, params):
        from ..ops.pallas_attention import default_block_k, default_block_q
        s, causal = int(params["s"]), bool(params.get("causal"))
        window = params.get("window")
        return (default_block_q(s, causal, window),
                default_block_k(s, causal, window))

    def encode(self, candidate):
        return f"{int(candidate[0])},{int(candidate[1])}"

    def decode(self, raw):
        bq, bk = str(raw).split(",")
        return (int(bq), int(bk))

    def bench(self, params, candidate):
        return _flash_bench(params, int(candidate[0]), int(candidate[1]))


# --------------------------------------------------------------------------------------
# choice point 4: conv2d compute layout (NHWC vs NCHW)
# --------------------------------------------------------------------------------------


class ConvLayout(TunableChoice):
    id = "conv2d.layout"
    doc = ("activation layout the conv actually computes in, independent of "
           "the declared data_format: 'NHWC' (channels-minor, MXU-friendly "
           "on TPU) or 'NCHW' (the reference default). A decision differing "
           "from the declared format transposes at the op boundary; XLA "
           "cancels adjacent transposes between consecutive convs.")

    def bucket(self, params):
        x = list(int(v) for v in params["x_shape"])
        x[0] = pow2_bucket(x[0])  # batch dim, both layouts
        return {"x": x, "w": [int(v) for v in params["w_shape"]],
                "s": [int(v) for v in params["strides"]],
                "p": [int(v) for v in params["pads"]],
                "d": [int(v) for v in params["dils"]],
                "g": int(params["groups"]), "fmt": params["fmt"]}

    def candidates(self, params):
        return ["NCHW", "NHWC"]

    def default(self, params):
        return params["fmt"]  # pre-autotuner behavior: run as declared

    def bench(self, params, candidate):
        from ..ops.nn_ops import conv_in_layout
        dt = params.get("dtype", "float32")
        x = _np_zeros(tuple(int(v) for v in params["x_shape"]), dt)
        w = _np_zeros(tuple(int(v) for v in params["w_shape"]), dt)
        strides = tuple(int(v) for v in params["strides"])
        pads = [int(v) for v in params["pads"]]
        dils = tuple(int(v) for v in params["dils"])
        groups = int(params["groups"])
        fmt = params["fmt"]

        def fn(x, w):
            return conv_in_layout(x, w, strides, pads, dils, groups, fmt,
                                  candidate)

        return fn, (x, w)


# --------------------------------------------------------------------------------------
# choice point 5: per-tensor gradient-allreduce compression (comm layer)
# --------------------------------------------------------------------------------------


class CommCompress(TunableChoice):
    id = "comm.compress"
    doc = ("per-tensor on/off for the compressed dp gradient allreduce "
           "(DistributedStrategy.comm_compression): 'on' quantizes this "
           "tensor (bf16/int8 + error feedback), 'off' keeps it f32. "
           "Tensors under the min_bytes floor have no 'on' candidate -- "
           "compression there is pure overhead. The "
           "payoff is workload-level (wire time vs quantize arithmetic "
           "on the live step), not isolated-jit measurable: external "
           "measurements persist via tuning.record_decision().")

    def bucket(self, params):
        return {"nbytes": pow2_bucket(int(params["nbytes"])),
                "world": int(params["world"]),
                "mode": str(params["mode"])}

    def candidates(self, params):
        floor = int(params.get("min_bytes", 0))
        if int(params["nbytes"]) < floor or int(params["world"]) <= 1:
            return ["off"]
        return ["off", "on"]

    def default(self, params):
        # the documented heuristic: compress everything the hard gates
        # allow -- the knob was set deliberately, small tensors are
        # already excluded by the floor
        return "on" if "on" in self.candidates(params) else "off"

    def bench(self, params, candidate):
        return None   # measured on the live workload, never isolated


# --------------------------------------------------------------------------------------
# choice point 6: which of the auto-shard planner's top-k plans to run
# --------------------------------------------------------------------------------------


class ShardPlanChoice(TunableChoice):
    id = "shardplan.plan"
    doc = ("which of the static auto-shard planner's top-k plans to run "
           "(DistributedStrategy.auto_shard='measure'): 'top1' is the "
           "cheapest-priced plan, 'topN' the Nth. The static wire-byte "
           "model cannot price overlap or XLA's collective fusion, so "
           "near-ties (PT072) are decided on the live workload; external "
           "measurements persist via tuning.record_decision(). Keyed by "
           "the top plan's digest + the mesh, so a program or mesh change "
           "re-decides.")

    def bucket(self, params):
        return {"plan": str(params["digest"]),
                "mesh": str(params["mesh"]),
                "k": int(params["k"])}

    def candidates(self, params):
        return [f"top{i}" for i in range(1, int(params["k"]) + 1)]

    def default(self, params):
        return "top1"  # the statically cheapest plan

    def bench(self, params, candidate):
        return None   # measured on the live workload, never isolated


register_choice(ConvBnBackend())
register_choice(FlashBackend())
register_choice(FlashBlockSizes())
register_choice(ConvLayout())
register_choice(CommCompress())
register_choice(ShardPlanChoice())
