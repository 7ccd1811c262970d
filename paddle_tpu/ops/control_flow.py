"""Control-flow ops (reference: paddle/fluid/operators/controlflow/:
conditional_block_op, while_op; recurrent_op).

TPU-native: sub-blocks lower through ``ctx.block_runner`` into lax.while_loop /
lax.cond -- XLA-compilable structured control flow instead of the reference's
sub-scope interpreter recursion. Static shapes are required: loop-carried vars must
keep their shapes across iterations.
"""
from __future__ import annotations

from ..core.registry import _grad_infer_shape, _is_float, register


@register("while")
def while_op(ctx, ins):
    """attrs: sub_block (int), cond_name, x_names, out_names, and optionally
    ``max_iters`` (static iteration bound).

    The sub-block must rewrite the condition var and the loop vars each
    iteration. Two lowerings (reference controlflow/while_op.cc + its grad op):

    * ``max_iters`` set -> a masked ``lax.scan`` of exactly max_iters steps:
      inactive steps keep the old carry via jnp.where. This is
      reverse-mode differentiable (the generic vjp works through scan), the
      TPU answer to the reference's StepScope-stack while-grad.
    * no ``max_iters`` -> ``lax.while_loop``: data-dependent trip count, but
      XLA forbids reverse-mode AD through it; requesting a gradient raises at
      vjp-transpose time (registry._generic_grad_lower adds the max_iters
      hint there).
    """
    import jax
    import jax.numpy as jnp

    sub_idx = ctx.attr("sub_block")
    cond_name = ctx.attr("cond_name")
    xs = ins["X"]
    x_names = ctx.attr("x_names", [])
    env0 = dict(zip(x_names, xs))
    max_iters = ctx.attr("max_iters", None)

    if max_iters is not None:
        def body(env, _):
            active = env[cond_name].reshape(()).astype(bool)
            new_env = ctx.block_runner(sub_idx, dict(env))
            merged = {k: jnp.where(active, new_env[k], env[k]) for k in env}
            return merged, None

        env_final, _ = jax.lax.scan(body, env0, None, length=int(max_iters))
        return {"Out": [env_final[n] for n in ctx.attr("out_names", [])]}

    def cond_fn(env):
        return env[cond_name].reshape(())

    def body_fn(env):
        new_env = dict(env)
        new_env = ctx.block_runner(sub_idx, new_env)
        return {k: new_env[k] for k in env}

    env_final = jax.lax.while_loop(cond_fn, body_fn, env0)
    return {"Out": [env_final[n] for n in ctx.attr("out_names", [])]}


@register("conditional_block", grad=None)
def conditional_block(ctx, ins):
    import jax

    sub_idx = ctx.attr("sub_block")
    else_idx = ctx.attr("else_block", -1)
    cond = ins["Cond"][0].reshape(())
    x_names = ctx.attr("x_names", [])
    out_names = ctx.attr("out_names", [])
    env0 = dict(zip(x_names, ins["X"]))

    def then_fn(env):
        e = ctx.block_runner(sub_idx, dict(env))
        return [e[n] for n in out_names]

    def else_fn(env):
        if else_idx >= 0:
            e = ctx.block_runner(else_idx, dict(env))
            return [e[n] for n in out_names]
        return [env[n] for n in out_names]

    outs = jax.lax.cond(cond, then_fn, else_fn, env0)
    return {"Out": list(outs)}


def _scan_grad_descs(op, grad_out_map):
    """``scan``'s grad maker: mark the forward op to keep what its backward
    reads (attr ``keep``, output ``Kept``) and hand ``scan_grad`` the
    generic desc, which carries every forward output, ``Kept`` among them.
    A scan no backward is built over (a test clone taken before
    ``minimize``, a scan inside another op's sub-block) keeps nothing."""
    from ..core import registry
    from ..framework import VarType
    if not op.attr("keep"):
        block = op.block
        kept = block.create_var(op.output("FinalCarry")[0] + "@scan_kept",
                                (), "float32", type=VarType.STEP_SCOPES)
        kept.stop_gradient = True
        op.outputs["Kept"] = [kept.name]
        op.attrs["keep"] = True
        block.program._bump()
    return registry.generic_grad_op_descs(op, grad_out_map)


@register("scan", grad=_scan_grad_descs, nondiff_outputs=("Kept",))
def scan_op(ctx, ins):
    """Structured recurrence: the TPU-native replacement for
    recurrent_op/DynamicRNN, and the loop of a model whose stack of layers
    runs several times a token on weights that exist once (looped /
    recurrent-depth transformers). One ``lax.scan``: the sub-block is
    traced, lowered and compiled once whatever the trip count.

    attrs: sub_block, carry_names (the loop state at an iteration's start, as
    the sub-block reads it), next_names (the sub-block's variables that hold
    it at the iteration's end; ``carry_names`` where absent: the body
    assigns the state back to its name), x_names (per-step inputs scanned
    over the time axis), steps (the trip count where there is no X, else 0),
    out_names (per-step outputs stacked), static_names, time_major, keep.
    Inputs: Init (initial carries, ordered as carry_names), X (sequences
    [T, ...] or [B, T, ...]), Static (loop-invariant outer vars read by the
    body -- params, lengths. They MUST be declared inputs, not
    closure-captured: the backward gives a gradient to the op's declared
    inputs only, to a weight the sum over its uses). Outputs: FinalCarry,
    Out and, under ``keep``, Kept.

    The backward: under ``keep`` (set by the grad maker) the lowering is
    ``jax.vjp`` of the same function, and ``Kept`` is its pullback -- a
    pytree whose leaves are what the forward kept for the backward (a value
    of the trace with no shape of its own in the Program: the reference's
    ``StepScopes``, and declared as that type). ``scan_grad`` calls it on
    the cotangents and lowers no forward. What is kept is JAX's choice of
    residuals for the body as lowered: everything the backward reads, or,
    where the body's ops sit in ``remat_segment`` ops (``RecomputeOptimizer``
    with checkpoints inside the sub-block), the segments' inputs, the
    segments' forwards then running once more inside ``scan_grad``. Its
    bytes beyond the op's own inputs are reported as ``loop_kept_bytes``,
    how often the sub-block was traced as ``loop_stack_lowerings_total``.
    Each weight's gradient is accumulated over the iterations in the
    weight's own dtype (the scan's transpose carries the sum): every term is
    a product accumulated in float32 and rounded once to that dtype.
    """
    import jax
    import jax.numpy as jnp

    sub_idx = ctx.attr("sub_block")
    carry_names = list(ctx.attr("carry_names", []))
    next_names = list(ctx.attr("next_names", carry_names))
    x_names = list(ctx.attr("x_names", []))
    out_names = list(ctx.attr("out_names", []))
    static_names = list(ctx.attr("static_names", []))
    time_major = ctx.attr("time_major", False)
    n_init, n_x = len(ins["Init"]), len(ins.get("X", []))
    length = None if n_x else int(ctx.attr("steps"))
    given = list(ins["Init"]) + list(ins.get("X", [])) \
        + list(ins.get("Static", []))
    diff = [i for i, v in enumerate(given) if _is_float(v)]
    traced = [0]

    def by_time(x):
        return x if time_major else jnp.swapaxes(x, 0, 1)

    def body(statics, carry, xt):
        traced[0] += 1
        env = dict(zip(static_names, statics))
        env.update(zip(carry_names, carry))
        env.update(zip(x_names, xt))
        env = ctx.block_runner(sub_idx, env)
        return [env[n] for n in next_names], [env[n] for n in out_names]

    def run(values):
        full = list(given)
        for i, v in zip(diff, values):
            full[i] = v
        init, seqs = full[:n_init], full[n_init:n_init + n_x]
        statics = full[n_init + n_x:]
        final, stacked = jax.lax.scan(
            lambda c, xt: body(statics, c, xt), init,
            [by_time(s) for s in seqs], length=length)
        return final, [by_time(o) for o in stacked]

    values = [given[i] for i in diff]
    if ctx.attr("keep", False) and not ctx.under_grad:
        (final, outs), pullback = jax.vjp(run, values)
        own = {id(v) for v in given}
        ctx.report("loop_kept_bytes", sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(pullback)
            if id(leaf) not in own))
        kept = {"Kept": [pullback]}
    else:
        (final, outs), kept = run(values), {}
    ctx.report("loop_stack_lowerings_total", traced[0])
    return {"Out": list(outs), "FinalCarry": list(final), **kept}


@register("scan_grad", infer_shape=_grad_infer_shape,
          nondiff_inputs=("Kept",))
def scan_grad(ctx, ins):
    """dInit, dX and dStatic from ``Kept``, the pullback the forward op left
    (``scan``): called on the cotangents of FinalCarry and Out, zeros where
    none flows. No forward is lowered here -- but for a double gradient
    (``scan_grad_grad``, the generic grad op, lowers this op again under
    ``jax.vjp``): what the pullback closed over would be constants to that,
    so there the gradient is computed from the inputs, as the generic grad
    op does."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..core import registry
    if ctx.under_grad:
        return registry._generic_grad_lower(
            registry.get("scan"), ctx,
            {slot: v for slot, v in ins.items() if slot != "Kept"})
    pullback = ins.get("Kept", [None])[0]
    if pullback is None:
        raise ValueError("scan_grad: the forward scan op kept nothing "
                         "(built by another maker than the op's own?)")

    def cotangents(slot):
        outs = ins.get(slot, [])
        flowing = ins.get(slot + "@GRAD") or [None] * len(outs)
        return [np.zeros(o.shape, jax.dtypes.float0) if not _is_float(o)
                else jnp.zeros_like(o) if g is None else g.astype(o.dtype)
                for o, g in zip(outs, flowing)]

    grads = iter(pullback((cotangents("FinalCarry"), cotangents("Out")))[0])
    return {slot + "@GRAD": [next(grads) if _is_float(v)
                             else jnp.zeros_like(v) for v in ins.get(slot, [])]
            for slot in ("Init", "X", "Static")}


@register("remat_segment")
def remat_segment(ctx, ins):
    """Rematerialized forward segment (the RecomputeOptimizer unit,
    reference optimizer.py:3278 + backward.py:576).

    The segment's ops live in a sub-block; the lowering wraps its execution in
    jax.checkpoint, so the generic vjp grad recomputes the segment's
    intermediates in backward instead of storing them -- true rematerialization
    (XLA cannot CSE across the checkpoint barrier).
    """
    import jax

    sub_idx = ctx.attr("sub_block")
    in_names = list(ctx.attr("in_names", []))
    out_names = list(ctx.attr("out_names", []))

    def f(xs):
        env = dict(zip(in_names, xs))
        env = ctx.block_runner(sub_idx, env)
        return [env[n] for n in out_names]

    outs = jax.checkpoint(f)(list(ins["X"]))
    return {"Out": list(outs)}


@register("array_write", nondiff_inputs=("I", "ALen"))
def array_write_op(ctx, ins):
    """TensorArray write (reference lod_array_ops/array_write). TPU-native: the
    array is a fixed-capacity stacked buffer [cap, *elem]; write is a
    dynamic_update_slice at index i (differentiable wrt Array and X, so arrays
    built inside a bounded While train end-to-end)."""
    import jax
    import jax.numpy as jnp
    arr, x, i = ins["Array"][0], ins["X"][0], ins["I"][0]
    alen = ins["ALen"][0]
    idx = i.reshape(()).astype(jnp.int32)
    new = jax.lax.dynamic_update_slice_in_dim(arr, x[None], idx, axis=0)
    newlen = jnp.maximum(alen, (idx + 1).astype(alen.dtype).reshape(alen.shape))
    return {"Out": [new], "OutLen": [newlen]}


@register("array_read", nondiff_inputs=("I",))
def array_read_op(ctx, ins):
    """TensorArray read: dynamic_index_in_dim at i (reference array_read op)."""
    import jax
    import jax.numpy as jnp
    arr, i = ins["Array"][0], ins["I"][0]
    idx = i.reshape(()).astype(jnp.int32)
    return {"Out": [jax.lax.dynamic_index_in_dim(arr, idx, axis=0,
                                                 keepdims=False)]}


@register("is_empty", grad=None)
def is_empty_op(ctx, ins):
    """numel == 0 is a static fact at lowering (controlflow/is_empty_op)."""
    import jax.numpy as jnp
    x = ins["X"][0]
    return {"Out": [jnp.full((1,), x.size == 0, bool)]}


@register("print", grad="auto")
def print_op(ctx, ins):
    """Debug print (reference print_op.cc / lodtensor_printer): host callback."""
    import jax
    x = ins["In"][0]
    msg = ctx.attr("message", "")
    jax.debug.print(msg + "{x}", x=x)
    return {"Out": [x]}


@register("assert", grad=None)
def assert_op(ctx, ins):
    return {}
