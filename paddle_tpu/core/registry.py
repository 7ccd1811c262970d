"""Op registry: type -> (lowering, shape inference, grad maker).

TPU-native analog of the reference's OpInfoMap / REGISTER_OPERATOR / kernel registry
(reference: paddle/fluid/framework/op_registry.h:329, op_info.h, operator.cc:861-970).

Design (deliberately different from the reference):
  * A "kernel" is a JAX lowering function: ``lower(ctx, ins) -> outs`` where ins/outs map
    slot name -> list of jax arrays. The same lowering serves every backend (CPU
    interpreter for tests, TPU via jit) -- kernel *choice* (OpKernelType in the
    reference) collapses into XLA's own target lowering. Pallas kernels are just
    alternative lowerings gated by an attr / platform check inside ``lower``.
  * Shape inference (the reference's InferShape, operator.cc:911) is derived
    automatically from the lowering with ``jax.eval_shape`` -- single source of truth.
    -1 (dynamic batch) dims are substituted with a sentinel prime and mapped back.
  * Grad ops (the reference's GradOpDescMakerBase, grad_op_desc_maker.h) are derived
    automatically with ``jax.vjp`` over the forward lowering: an op type T that
    registers nothing else gets a generic "T_grad" whose lowering recomputes T's
    forward under vjp. XLA CSE/fusion dedups the recompute against the forward pass,
    which doubles as free rematerialization -- as long as no output of that second
    forward is read. An op whose backward wants something its forward knew declares
    it as an output (``nondiff_outputs``: a Program variable like any other; the grad
    op desc carries every forward output as an input) and registers a "T_grad"
    lowering of its own with ``register_grad``, which reads it and lowers no forward
    (``fused_attention``'s softmax statistics, ops/pallas_attention.py). Ops may also
    override with a custom grad maker (``grad=callable``) or declare themselves
    non-differentiable (``grad=None``).

Empty-var convention: the name ``@EMPTY@`` in an op's input list means "no tensor here"
(the reference's kEmptyVarName); the executor feeds None and lowerings must cope
(the generic grad lowering substitutes zeros).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ..framework import Block, Operator, convert_dtype, grad_var_name

# Sentinels standing in for -1 (unknown batch) during eval_shape-based
# inference. Inference runs TWICE with two coprime primes; an output dim is
# dynamic iff it differs between the runs -- exact provenance, no collision
# with a real dim that happens to be a multiple of the sentinel (a 7919-wide
# layer stays static). The primes stay small because some lowerings
# materialize real arrays sized by these dims even under eval_shape.
_DYN = 7919
_DYN2 = 7927
EMPTY_VAR = "@EMPTY@"


class LowerCtx:
    """Per-op lowering context: attrs + PRNG access + sub-block runner.

    ``rng()`` returns a PRNGKey unique to (step key, this op). Grad ops reuse the
    forward op's salt so stochastic ops (dropout) see the identical mask in backward.
    ``run_block(idx, env)`` executes a sub-block (control-flow ops); wired by the
    executor, None during shape inference.
    """

    def __init__(self, attrs: dict, base_key=None, salt: int = 0, block_runner=None,
                 program=None, mesh=None, gspmd_mesh=None, abstract=False,
                 data_axis=None, op_idx=None):
        self.attrs = attrs
        self._base_key = base_key
        self._salt = salt
        self.block_runner = block_runner
        self.program = program
        self.mesh = mesh  # set when lowering inside shard_map (SPMD)
        # set when lowering inside a GSPMD jit over a mesh (NOT inside
        # shard_map): ops may open their own shard_map islands over it
        # (ring attention) but must NOT call axis primitives directly
        self.gspmd_mesh = gspmd_mesh
        # beside gspmd_mesh: the name of the strategy's data axis
        # (DistributedStrategy.data_axis, the one data_spec lays feeds over)
        self.data_axis = data_axis
        # True under eval_shape-based inference: the mesh/backend are unknown,
        # so impl choices must not be validated and shape-equivalent fallbacks
        # should be used (e.g. fused_attention lowers its composed path)
        self.abstract = abstract
        # set by pallas_mode.lowers_kernels: this op's lowering asked the
        # kernel-or-composed rule (the executor books its trace seconds
        # under family "kernel", observability/lowerings.py)
        self.asked_kernels = False
        # the op's index in its block (the ``<type>#<idx>`` of its trace
        # scope): a lowering that names a scope of its own inside gives it
        # the same number (``moe_exchange.out#<idx>``)
        self.op_idx = op_idx
        # True on the context a generic grad op lowers its forward again
        # with (under jax.vjp): what that forward reports or names is the
        # backward's
        self.under_grad = False

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def report(self, family: str, amount=1, /, **labels) -> None:
        """Report what this op's lowering chose, as ``amount`` of the metric
        ``family`` under ``labels`` (observability/lowerings.py declares the
        families and publishes them once the compile is made: the executor
        empties the reports before a compile and hands them over after it,
        ``Executor._materialize_miss``). Kept under the op's salt and the
        labels: the forward a grad op lowers again under ``jax.vjp`` lands
        on its forward op's entry. Nothing is kept where no Program is being
        lowered."""
        if self.program is not None:
            from ..observability import lowerings
            lowerings.note(self.program._lowering_notes, self._salt, family,
                           amount, labels)

    def rng(self, offset: int = 0):
        import jax
        key = self._base_key
        if key is None:  # shape-inference / eval path
            key = jax.random.PRNGKey(0)
        return jax.random.fold_in(key, (self._salt + offset) & 0x7FFFFFFF)

    def bernoulli_mask(self, key, keep, shape):
        """``jax.random.bernoulli(key, keep, shape)``: a training op's
        dropout mask. Under a GSPMD mesh whose data axis has n > 1 devices
        dividing ``shape[0]``, each device draws only its shard of the
        leading (batch) dimension, in a ``shard_map`` island over the mesh
        with its index on the data axis folded into ``key``: XLA's SPMD
        partitioner has no rule for ``RngBitGenerator`` and would run it at
        the global shape on every device (PERF.md section 6, PR 31). Such a
        run's masks depend on n, like the explicit-dp step's
        (``Executor._explicit_dp``); forward and re-lowered forward reach
        the same island with the same key. Which way the op drew is
        reported as ``mask_draw_total``."""
        import jax
        mesh, axis = self.gspmd_mesh, self.data_axis
        n = self.data_shards(*shape[:1])
        if n == 1:
            self.report("mask_draw_total", draw="global", shards=1)
            return jax.random.bernoulli(key, keep, shape)
        from jax.sharding import PartitionSpec as P
        self.report("mask_draw_total", draw="shard", shards=n)
        local_shape = (shape[0] // n, *shape[1:])

        def local(k):
            k = jax.random.fold_in(k, jax.lax.axis_index(axis))
            return jax.random.bernoulli(k, keep, local_shape)

        return jax.shard_map(
            local, mesh=mesh, in_specs=P(),
            out_specs=P(axis, *([None] * (len(shape) - 1))))(key)

    def island(self, fn, args, split, shards=None, axis=None):
        """``fn(*args)`` on each device's own rows, in a ``shard_map``
        island over the strategy's data axis: the arguments ``split`` marks
        (one bool an argument) are cut along their leading dimension, the
        others are whole on every device, and every result comes back laid
        over the axis along its leading dimension. The one door through
        which a kernel family calls a Mosaic kernel under a GSPMD mesh (a
        jit over several devices refuses one outside a ``shard_map``):
        ``pallas_mode.lowers_kernels`` answers "kernels" there exactly where
        this opens an island. ``shards`` is ``data_shards`` of the split
        arguments' leading dimensions where the caller has it already; with
        1 -- one device, no mesh, inside another island -- it is
        ``fn(*args)``. ``axis``: another mesh axis than the data axis (an
        expert layer's ``expert_axis``)."""
        import jax
        axis = axis or self.data_axis
        if shards is None:
            shards = self.axis_shards(axis, *(
                a.shape[0] for a, cut in zip(args, split) if cut))
        if shards == 1:
            return fn(*args)
        from jax.sharding import PartitionSpec as P
        return jax.shard_map(
            fn, mesh=self.gspmd_mesh,
            in_specs=tuple(P(axis) if cut else P() for cut in split),
            out_specs=P(axis), check_vma=False)(*args)

    def data_shards(self, *dims) -> int:
        """The devices of the strategy's data axis over which an op may lay
        leading dimensions of sizes ``dims`` in a ``shard_map`` island of its
        own, else 1: a GSPMD mesh whose data axis has n > 1 devices dividing
        every one of ``dims`` (at least one), and no mesh axis manual
        already -- an op lowered inside another op's ``shard_map`` over the
        mesh (the pipeline's stages) sees ``gspmd_mesh`` too, and opens no
        island inside the island."""
        return self.axis_shards(self.data_axis, *dims)

    def axis_shards(self, axis, *dims) -> int:
        """``data_shards`` over the mesh axis ``axis``."""
        import jax
        mesh = self.gspmd_mesh
        n = mesh.shape.get(axis, 1) if mesh is not None else 1
        if (n <= 1 or not dims or any(d % n for d in dims)
                or jax.sharding.get_abstract_mesh().manual_axes):
            return 1
        return n


def stable_salt(name: str) -> int:
    """Deterministic salt from a var name (Python hash() is randomized per process)."""
    h = 2166136261
    for c in name.encode():
        h = ((h ^ c) * 16777619) & 0xFFFFFFFF
    return h & 0x7FFFFFFF


class OpDef:
    def __init__(self, type: str, lower: Callable, infer_shape: Optional[Callable] = None,
                 grad: Any = "auto", nondiff_inputs: Sequence[str] = (),
                 nondiff_outputs: Sequence[str] = ()):
        self.type = type
        self.lower = lower
        self.custom_infer_shape = infer_shape
        self.grad = grad  # "auto" | None (non-differentiable) | callable custom maker
        self.nondiff_inputs = frozenset(nondiff_inputs)
        self.nondiff_outputs = frozenset(nondiff_outputs)


_REGISTRY: Dict[str, OpDef] = {}


def register(type: str, *, infer_shape=None, grad="auto", nondiff_inputs=(),
             nondiff_outputs=()):
    """Decorator: register ``fn(ctx, ins) -> outs`` as the lowering for ``type``."""

    def deco(fn):
        if type in _REGISTRY:
            raise ValueError(f"op type {type!r} already registered")
        _REGISTRY[type] = OpDef(type, fn, infer_shape, grad, nondiff_inputs,
                                nondiff_outputs)
        return fn

    return deco


def simple_op(type: str, *, grad="auto", nondiff_inputs=(), infer_shape=None):
    """Register an op with input slots consumed in sorted-slot order -> single 'Out'.

    The wrapped fn receives ``(ctx, *arrays)`` -- one array per input slot entry, in
    sorted slot order -- and returns the single output array.
    """

    def deco(fn):
        @functools.wraps(fn)
        def lower(ctx, ins):
            args = [v for s in sorted(ins) for v in ins[s]]
            return {"Out": [fn(ctx, *args)]}

        register(type, grad=grad, nondiff_inputs=nondiff_inputs,
                 infer_shape=infer_shape)(lower)
        return fn

    return deco


def get(type: str) -> OpDef:
    d = _REGISTRY.get(type)
    if d is not None:
        return d
    if type.endswith("_grad"):
        base = type[:-5]
        if base in _REGISTRY or base.endswith("_grad"):
            return _grad_opdef(base)
    raise KeyError(
        f"op type {type!r} is not registered in paddle_tpu "
        f"({len(_REGISTRY)} ops registered). If this is a reference op not yet "
        f"ported, add a lowering in paddle_tpu/ops/.")


def register_grad(fwd_type: str):
    """Decorator: register ``fn(ctx, ins, generic) -> outs`` as the lowering
    of ``<fwd_type>_grad`` in place of the generic vjp one. ``ins`` holds
    what the generic grad op's would (the forward's input slots, its output
    slots -- those the op declared for its backward among them -- and the
    "<OutSlot>@GRAD" cotangents; outputs are "<InSlot>@GRAD"), and
    ``generic()`` is the generic lowering on the same ``ctx`` and ``ins``,
    for the cases ``fn`` has no path of its own for. Shapes, the desc maker
    and second-order gradients are the generic grad op's."""

    def deco(fn):
        fwd = _REGISTRY[fwd_type]

        def lower(ctx, ins):
            return fn(ctx, ins, lambda: _generic_grad_lower(fwd, ctx, ins))

        register(fwd_type + "_grad", infer_shape=_grad_infer_shape)(
            functools.wraps(fn)(lower))
        return fn

    return deco


def registered_types() -> List[str]:
    return sorted(_REGISTRY)


def is_registered(type: str) -> bool:
    try:
        get(type)
        return True
    except KeyError:
        return False


# --------------------------------------------------------------------------------------
# Generic vjp-based grad op
# --------------------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _grad_opdef(fwd_type: str) -> OpDef:
    fwd = _REGISTRY.get(fwd_type)
    if fwd is None:
        if fwd_type.endswith("_grad"):   # higher-order: tanh_grad_grad etc.
            fwd = _grad_opdef(fwd_type[:-5])
        else:
            raise KeyError(f"op type {fwd_type!r} is not registered")
    if fwd.grad is None:
        raise KeyError(f"op {fwd_type!r} is non-differentiable; no {fwd_type}_grad")

    def lower(ctx, ins):
        return _generic_grad_lower(fwd, ctx, ins)

    # grad ops are themselves differentiable through the same vjp machinery
    # (jax.vjp of a jax.vjp), which is what Program-level double gradients --
    # reference gradient_checker.py double_grad_check / gradient-penalty
    # training -- lower to. SECOND order only: a *_grad_grad op reuses slot
    # names as both inputs and outputs, which the desc maker rejects with a
    # clear error rather than silently clobbering (third order would need
    # per-level slot namespacing).
    return OpDef(fwd_type + "_grad", lower, infer_shape=_grad_infer_shape,
                 grad="auto")


def _is_float(x) -> bool:
    dt = getattr(x, "dtype", None)
    if dt is None:
        dt = np.asarray(x).dtype
    return np.issubdtype(np.dtype(dt) if str(dt) != "bfloat16" else np.float32,
                         np.floating) or str(dt) == "bfloat16"


def _generic_grad_lower(fwd: OpDef, ctx, ins):
    """Compute input grads of ``fwd`` via jax.vjp of its lowering.

    Grad-op input slots: forward input slots verbatim, forward output slots verbatim
    (listed in attr __fwd_out_slots__), plus "<OutSlot>@GRAD" cotangent slots.
    Output slots: "<InSlot>@GRAD". Missing cotangent entries (None via @EMPTY@) -> zeros.
    """
    import jax
    import jax.numpy as jnp

    fwd_out_slots = set(ctx.attr("__fwd_out_slots__", []))
    # cotangent slots are exactly <fwd out slot>+"@GRAD". When fwd is itself
    # a grad op its INPUT slots also end in "@GRAD" ("Out@GRAD"), so "ends
    # with @GRAD" alone cannot distinguish them -- match against
    # fwd_out_slots instead (second-order support).
    def _is_cot(s):
        return s.endswith("@GRAD") and s[:-5] in fwd_out_slots

    fwd_in_slots = sorted(s for s in ins
                          if s not in fwd_out_slots and not _is_cot(s))
    grad_by_slot = {s[:-5]: ins[s] for s in ins if _is_cot(s)}

    diff_keys, primals = [], []
    for s in fwd_in_slots:
        if s in fwd.nondiff_inputs:
            continue
        for i, v in enumerate(ins[s]):
            if v is not None and _is_float(v):
                diff_keys.append((s, i))
                primals.append(v)

    # the fwd op's own attrs: the nested snapshot when fwd is itself a grad
    # op (its __fwd_* bookkeeping must survive -- the desc maker overwrote
    # the flat keys with this level's), else the flat attrs minus this
    # level's bookkeeping
    fwd_attrs = ctx.attr("__fwd_attrs__", None)
    if fwd_attrs is None:
        fwd_attrs = {k: v for k, v in ctx.attrs.items()
                     if not k.startswith("__fwd_")}
    fwd_ctx = LowerCtx(fwd_attrs, ctx._base_key, ctx._salt, ctx.block_runner,
                       ctx.program, ctx.mesh, gspmd_mesh=ctx.gspmd_mesh,
                       data_axis=ctx.data_axis, op_idx=ctx.op_idx)
    fwd_ctx.under_grad = True

    def f(*diff_vals):
        full = {s: list(ins[s]) for s in fwd_in_slots}
        for (s, i), v in zip(diff_keys, diff_vals):
            full[s][i] = v
        outs = fwd.lower(fwd_ctx, full)
        ctx.asked_kernels |= fwd_ctx.asked_kernels
        # Return only float outputs, keyed (slot, index) for exact cotangent alignment.
        return {s: {i: o for i, o in enumerate(outs[s]) if _is_float(o)}
                for s in outs if s not in fwd.nondiff_outputs}

    primal_outs, vjp = jax.vjp(f, *primals)

    cot = {}
    for s, entries in primal_outs.items():
        provided = grad_by_slot.get(s)
        cot[s] = {}
        for i, o in entries.items():
            g = provided[i] if provided is not None and i < len(provided) else None
            cot[s][i] = (jnp.asarray(g, o.dtype) if g is not None
                         else jnp.zeros(o.shape, o.dtype))
    try:
        grads = vjp(cot)
    except ValueError as e:
        if "while_loop" in str(e):
            raise ValueError(
                "gradient through a dynamic `while` needs a static bound: set "
                "attr max_iters=N on the while op so it lowers to a "
                f"differentiable masked scan ({e})") from e
        raise

    result: Dict[str, List] = {}
    for s in fwd_in_slots:
        if s in fwd.nondiff_inputs:
            continue
        result[s + "@GRAD"] = [None] * len(ins[s])
    for (s, i), g in zip(diff_keys, grads):
        result[s + "@GRAD"][i] = g
    for gs in list(result):
        base = gs[:-5]
        result[gs] = [v if v is not None else
                      (jnp.zeros_like(ins[base][i]) if ins[base][i] is not None else None)
                      for i, v in enumerate(result[gs])]
    return result


def make_grad_op_descs(op: Operator, grad_out_map: Dict[str, str]) -> List[dict]:
    """Generic GradOpDescMaker: one '<type>_grad' op desc for ``op``.

    ``grad_out_map``: forward output var name -> grad var name (only for outputs with
    gradient flow; others get @EMPTY@). Returns op-desc dicts
    {type, inputs, outputs, attrs}; caller (backward.py) appends them and prunes
    unwanted grad outputs.
    """
    fwd = get(op.type)
    if fwd.grad is None:
        return []
    if callable(fwd.grad):
        return fwd.grad(op, grad_out_map)
    return generic_grad_op_descs(op, grad_out_map)


def generic_grad_op_descs(op: Operator,
                          grad_out_map: Dict[str, str]) -> List[dict]:
    """The one '<type>_grad' desc of ``make_grad_op_descs`` for an op without
    a maker of its own; a maker that only prepares its op (``scan``'s marks
    it to keep what its backward reads) ends in this."""
    fwd = get(op.type)
    clash = set(op.inputs) & set(op.outputs)
    if clash:
        # *_grad_grad ops reuse slot names on both sides; building their
        # grad descs would clobber the primal inputs (slots {clash}) --
        # second-order is the supported ceiling
        raise NotImplementedError(
            f"gradients of {op.type!r}: third-order gradients are not "
            f"supported (input/output slot collision on {sorted(clash)})")

    inputs: Dict[str, List[str]] = {s: list(n) for s, n in op.inputs.items()}
    for s, names in op.outputs.items():
        inputs[s] = list(names)
        gnames = [grad_out_map.get(n) for n in names]
        if any(g is not None for g in gnames):
            inputs[s + "@GRAD"] = [g if g is not None else EMPTY_VAR for g in gnames]
    outputs = {}
    for s, names in op.inputs.items():
        if s in fwd.nondiff_inputs:
            continue
        outputs[s + "@GRAD"] = [grad_var_name(n) for n in names]
    attrs = dict(op.attrs)
    # snapshot the op's own attrs BEFORE overwriting the __fwd_* keys with
    # this level's bookkeeping: when ``op`` is itself a grad op, its lowering
    # needs its own __fwd_out_slots__/__fwd_attrs__ back (second order)
    attrs["__fwd_attrs__"] = dict(op.attrs)
    attrs["__fwd_out_slots__"] = sorted(op.outputs)
    first_out = next((ns[0] for ns in op.outputs.values() if ns), "")
    attrs["__fwd_out0__"] = first_out
    return [{"type": op.type + "_grad", "inputs": inputs, "outputs": outputs,
             "attrs": attrs}]


# --------------------------------------------------------------------------------------
# Shape inference
# --------------------------------------------------------------------------------------

def infer_shape(op: Operator, block: Block):
    """Infer & create output variables for ``op`` (reference InferShapeContext,
    shape_inference.h). Uses the registered custom infer fn, else jax.eval_shape of the
    lowering with -1 dims replaced by a sentinel."""
    d = get(op.type)
    if d.custom_infer_shape is not None:
        d.custom_infer_shape(op, block)
        return
    _eval_shape_infer(d, op, block)


def _grad_infer_shape(op: Operator, block: Block):
    """Grad var shapes mirror the corresponding forward input var shapes.

    Grad vars are differentiable (stop_gradient=False): they are functions
    of the forward inputs, and a later backward pass -- double gradients,
    gradient-penalty losses -- must be able to differentiate through them
    (reference gradient_checker.py double_grad_check). append_backward still
    marks the settled PARAM grads it hands to optimizers as stop_gradient.
    """
    for slot, names in op.outputs.items():
        if not slot.endswith("@GRAD"):
            continue
        src = op.inputs.get(slot[:-5], [])
        for i, n in enumerate(names):
            if n == EMPTY_VAR:
                continue
            if i < len(src):
                sv = block.find_var_recursive(src[i])
                if sv is not None:
                    v = block.create_var(n, sv.shape, sv.dtype)
                    v.stop_gradient = False
                    continue
            block.create_var(n, (), "float32").stop_gradient = False


def _eval_shape_infer(d: OpDef, op: Operator, block: Block):
    import jax
    import jax.numpy as jnp

    def build_struct(sentinel):
        has_dyn = False
        ins_struct: Dict[str, List] = {}
        for slot, names in op.inputs.items():
            vals = []
            for n in names:
                if n == EMPTY_VAR:
                    vals.append(None)
                    continue
                v = block.find_var_recursive(n)
                if v is None:
                    raise KeyError(f"op {op.type}: input var {n!r} not found")
                if any(dim == -1 for dim in v.shape):
                    has_dyn = True
                shape = tuple(sentinel if dim == -1 else dim
                              for dim in v.shape)
                dtype = (jnp.bfloat16 if v.dtype == "bfloat16"
                         else np.dtype(v.dtype))
                vals.append(jax.ShapeDtypeStruct(shape, dtype))
            ins_struct[slot] = vals
        return ins_struct, has_dyn

    def run(ins_struct):
        ctx = LowerCtx(op.attrs, abstract=True)
        try:
            return jax.eval_shape(lambda ins: d.lower(ctx, ins), ins_struct)
        except Exception as e:
            raise RuntimeError(
                f"shape inference failed for op {op.type!r} "
                f"(inputs: { {s: [None if v is None else (v.shape, str(v.dtype)) for v in vs] for s, vs in ins_struct.items()} }): {e}"
            ) from e

    ins1, has_dyn = build_struct(_DYN)
    outs = run(ins1)
    # provenance by differencing: rerun with a second sentinel; dims that
    # move are batch-derived -> -1. No collision for real dims that merely
    # equal a multiple of the sentinel.
    outs2 = run(build_struct(_DYN2)[0]) if has_dyn else outs

    for slot, names in op.outputs.items():
        structs = outs.get(slot, [])
        structs2 = outs2.get(slot, [])
        for i, n in enumerate(names):
            if i >= len(structs) or n == EMPTY_VAR or structs[i] is None:
                continue
            st, st2 = structs[i], structs2[i]
            shape = tuple(-1 if d1 != d2 else d1
                          for d1, d2 in zip(st.shape, st2.shape))
            dtype = ("bfloat16" if str(st.dtype) == "bfloat16"
                     else np.dtype(st.dtype).name)
            existing = block.find_var_recursive(n)
            if existing is not None and not existing.is_data:
                existing.shape = shape
                existing.dtype = convert_dtype(dtype)
            elif existing is None:
                block.create_var(n, shape, dtype)
