"""Control-flow DSL (reference: python/paddle/fluid/layers/control_flow.py:
While:763, Switch:1678, IfElse:1827, StaticRNN:478, DynamicRNN:1999, array ops).

TPU-native: sub-blocks become lax.while_loop / lax.scan / lax.cond bodies (see
ops/control_flow.py); loop-carried vars must keep static shapes. Writes to
outer vars inside a While/Switch body are detected automatically and become
the op's functional carries/outputs -- the DSL reads like the reference's
in-place mutation style but lowers to pure XLA control flow. TensorArrays are
fixed-capacity stacked buffers (capacity = the loop's max_iters).
"""
from __future__ import annotations

from .. import unique_name
from ..framework import convert_dtype, default_main_program
from ..layer_helper import LayerHelper
from . import tensor

__all__ = ["increment", "array_write", "array_read", "array_length",
           "create_array", "less_than", "equal", "greater_than",
           "greater_equal", "less_equal", "not_equal", "is_empty", "Print",
           "Scan", "StaticRNN", "While", "Switch", "IfElse", "DynamicRNN",
           "reorder_lod_tensor_by_rank"]


def _outer_writes(program, root_idx, parent):
    """Var names written (transitively) inside block ``root_idx`` that resolve
    to ``parent`` or its ancestors -- i.e. the loop-carried state of a
    While/Switch body. Names shadowed by a var local to the body don't count."""
    order, seen = [], set()

    def walk(idx, local):
        blk = program.blocks[idx]
        local = local | set(blk.vars)
        for op in blk.ops:
            for a in ("sub_block", "else_block"):
                si = op.attr(a, -1)
                if isinstance(si, int) and 0 <= si < len(program.blocks) \
                        and si != idx:
                    walk(si, local)
            for n in op.output_arg_names():
                if n in local or n in seen or n == "@EMPTY@":
                    continue
                if parent.find_var_recursive(n) is not None:
                    seen.add(n)
                    order.append(n)

    walk(root_idx, set())
    return order


def _outer_reads(program, root_idx, parent, exclude=()):
    """Outer vars read inside block ``root_idx``. These must be declared as
    inputs of the enclosing while op (not closure-captured) so jax.vjp sees
    them and gradients flow to params/activations used in the body."""
    order, seen = [], set(exclude)

    def walk(idx, local):
        blk = program.blocks[idx]
        local = local | set(blk.vars)
        for op in blk.ops:
            for n in op.input_arg_names():
                if n in local or n in seen or n == "@EMPTY@":
                    continue
                if parent.find_var_recursive(n) is not None:
                    seen.add(n)
                    order.append(n)
            for a in ("sub_block", "else_block"):
                si = op.attr(a, -1)
                if isinstance(si, int) and 0 <= si < len(program.blocks) \
                        and si != idx:
                    walk(si, local)

    walk(root_idx, set())
    return order


def increment(x, value=1.0, in_place=True):
    helper = LayerHelper("increment")
    if in_place:
        out = x
    else:
        out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("increment", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"step": float(value)})
    return helper.main_program.current_block().var(out.name)


def less_than(x, y, force_cpu=None, cond=None):
    helper = LayerHelper("less_than")
    if cond is None:
        cond = helper.create_variable_for_type_inference("bool",
                                                         stop_gradient=True)
    helper.append_op("less_than", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [cond]})
    return helper.main_program.current_block().var(cond.name)


def equal(x, y, cond=None):
    helper = LayerHelper("equal")
    if cond is None:
        cond = helper.create_variable_for_type_inference("bool",
                                                         stop_gradient=True)
    helper.append_op("equal", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [cond]})
    return helper.main_program.current_block().var(cond.name)


def create_array(dtype, capacity=None, like=None):
    """TensorArray (reference LoDTensorArray via create_array). TPU-native: a
    fixed-capacity stacked buffer [capacity, *elem] -- XLA requires static
    shapes, so pass ``capacity`` (use the enclosing While's max_iters). The
    element shape is fixed by the first array_write; when that first write
    happens inside a loop body with a dynamic batch dim, pass ``like`` (an
    outer var sharing the batch dim) so the zero-init can size it."""
    block = default_main_program().current_block()
    name = unique_name.generate("tensor_array")
    arr = block.create_var(name, (), convert_dtype(dtype))
    arr.persistable = False
    arr.stop_gradient = False
    arr._ta_capacity = capacity
    arr._ta_like = like
    arr._ta_block = block
    arr._ta_initialized = False
    arr._ta_len_name = name + "@alen"
    alen = block.create_var(arr._ta_len_name, (1,), "int32")
    alen.stop_gradient = True
    block.append_op("fill_constant", outputs={"Out": [alen.name]},
                    attrs={"shape": [1], "dtype": "int32", "value": 0.0},
                    infer_shape=False)
    return arr


def _init_tensor_array(array, x):
    """First write fixes the element shape: emit the zero-init op into the
    array's creation block (before any enclosing While captures it)."""
    cap = getattr(array, "_ta_capacity", None)
    if cap is None:
        raise ValueError(
            f"TensorArray {array.name!r} needs a static capacity on TPU: "
            f"create it with layers.create_array(dtype, capacity=N) where N "
            f"bounds the writes (e.g. the While's max_iters)")
    blk = array._ta_block
    shape = (int(cap),) + tuple(x.shape)
    array.shape = shape
    dyn = [i for i, s in enumerate(x.shape) if s == -1]
    if dyn:
        # the init op lives in the array's creation block, so its batch-size
        # reference must be visible there -- a value computed inside the loop
        # body is not; fall back to the `like=` var from create_array
        ref = x
        if blk.find_var_recursive(x.name) is None:
            ref = getattr(array, "_ta_like", None)
            if ref is None:
                raise ValueError(
                    f"TensorArray {array.name!r}: first array_write value "
                    f"{x.name!r} has a dynamic batch dim but is computed "
                    f"inside a sub-block, so the array's zero-init (in the "
                    f"creation block) cannot size it. Pass a batch reference "
                    f"at creation: layers.create_array(dtype, capacity=N, "
                    f"like=some_outer_var)")
        blk.append_op("fill_constant_batch_size_like",
                      inputs={"Input": [ref.name]},
                      outputs={"Out": [array.name]},
                      attrs={"shape": list(shape), "dtype": array.dtype,
                             "value": 0.0, "input_dim_idx": dyn[0],
                             "output_dim_idx": dyn[0] + 1},
                      infer_shape=False)
    else:
        blk.append_op("fill_constant", outputs={"Out": [array.name]},
                      attrs={"shape": list(shape), "dtype": array.dtype,
                             "value": 0.0},
                      infer_shape=False)
    array._ta_initialized = True


def array_write(x, i, array=None):
    """Write x at index i (reference control_flow.py:array_write). Inside a
    While body the array becomes a loop carry automatically."""
    if array is None:
        array = create_array(x.dtype)   # raises with capacity guidance
    if not getattr(array, "_ta_initialized", False):
        _init_tensor_array(array, x)
    block = default_main_program().current_block()
    block.append_op("array_write",
                    inputs={"Array": [array.name], "X": [x.name],
                            "I": [i.name], "ALen": [array._ta_len_name]},
                    outputs={"Out": [array.name],
                             "OutLen": [array._ta_len_name]},
                    infer_shape=False)
    return array


def array_read(array, i):
    """Read element i (reference control_flow.py:array_read)."""
    block = default_main_program().current_block()
    out = block.create_var(unique_name.generate(array.name + "@read"),
                           tuple(array.shape[1:]), array.dtype)
    block.append_op("array_read",
                    inputs={"Array": [array.name], "I": [i.name]},
                    outputs={"Out": [out.name]}, infer_shape=False)
    return out


def array_length(array):
    """Number of elements written (reference control_flow.py:array_length)."""
    root = array._ta_block
    blk = default_main_program().current_block()
    alen = (blk.find_var_recursive(array._ta_len_name) or
            root.var(array._ta_len_name))
    return tensor.cast(alen, "int64")


class While:
    """While loop DSL (reference control_flow.py:763). Usage::

        i = layers.fill_constant([1], "float32", 0)
        limit = layers.fill_constant([1], "float32", 10)
        cond = layers.less_than(i, limit)
        w = layers.While(cond, max_iters=10)
        with w.block():
            ...                                   # body writes loop vars in place
            layers.increment(i, in_place=True)
            layers.less_than(i, limit, cond=cond) # body must rewrite cond

    Outer vars written in the body (detected automatically, including through
    nested sub-blocks) become the loop carries; after the loop their names hold
    the final values -- reference in-place semantics over a pure lax loop.
    ``max_iters`` gives the static bound that makes the loop reverse-mode
    differentiable (masked lax.scan); without it, lowering uses
    lax.while_loop (forward-only, data-dependent trip count).
    """

    def __init__(self, cond, is_test=False, name=None, max_iters=None):
        if cond.dtype != "bool":
            raise TypeError(f"While cond must be bool, got {cond.dtype}")
        if tuple(cond.shape) not in ((1,), ()):
            raise TypeError(f"While cond must be scalar [1], got {cond.shape}")
        self.cond = cond
        self.max_iters = max_iters

    def block(self):
        w = self

        class _Guard:
            def __enter__(self):
                prog = default_main_program()
                w._parent = prog.current_block()
                w._sub = prog._create_block()
                return self

            def __exit__(self, exc_type, *exc):
                default_main_program()._rollback()
                if exc_type is None:
                    w._finalize()
                return False

        return _Guard()

    def _finalize(self):
        parent, sub = self._parent, self._sub
        carries = _outer_writes(parent.program, sub.idx, parent)
        if self.cond.name not in carries:
            raise ValueError(
                "While body never rewrites the condition var -- the loop would "
                "never terminate. End the body with e.g. "
                "layers.less_than(i, limit, cond=cond).")
        reads = _outer_reads(parent.program, sub.idx, parent, exclude=carries)
        # The op writes the carries' own names (reference in-place semantics),
        # so its *inputs* must be SSA snapshots: the grad op re-runs the loop
        # from its declared inputs, and reading the clobbered names would
        # recompute from the final state (cond already False -> zero grads).
        snaps = []
        for n in carries:
            v = parent.find_var_recursive(n)
            # after the loop these names are the loop's outputs: clear the
            # stop_gradient their constant initializers set, or backward
            # prunes the path from loss to the loop body
            if v is not None and v.dtype in ("float32", "float64", "bfloat16",
                                             "float16"):
                v.stop_gradient = False
            sv = parent.create_var(unique_name.generate(n + "@while_in"),
                                   tuple(v.shape) if v is not None else (),
                                   v.dtype if v is not None else "float32")
            sv.stop_gradient = False
            parent.append_op("assign", inputs={"X": [n]},
                             outputs={"Out": [sv.name]}, infer_shape=False)
            snaps.append(sv.name)
        attrs = {"sub_block": sub.idx, "cond_name": self.cond.name,
                 "x_names": list(carries) + reads,
                 "out_names": list(carries)}
        if self.max_iters is not None:
            attrs["max_iters"] = int(self.max_iters)
        parent.append_op("while", inputs={"X": snaps + reads},
                         outputs={"Out": list(carries)}, attrs=attrs,
                         infer_shape=False)


class Switch:
    """First-match-wins case chain (reference control_flow.py:1678); the
    standard vehicle for piecewise LR schedules. Usage::

        with layers.Switch() as switch:
            with switch.case(cond1):
                layers.assign(v1, lr)
            with switch.default():
                layers.assign(v2, lr)

    Lowers to a chain of lax.cond blocks; vars assigned in any branch keep
    their pre-Switch value when no branch fires. Non-differentiable (use
    IfElse for gradients)."""

    def __init__(self, name=None):
        self._cases = []
        self._default = None
        self._inside = False

    def __enter__(self):
        self._parent = default_main_program().current_block()
        self._inside = True
        return self

    def __exit__(self, exc_type, *exc):
        self._inside = False
        if exc_type is None:
            self._finalize()
        return False

    def _branch(self, condition):
        sw = self

        class _Guard:
            def __enter__(self):
                if not sw._inside:
                    raise ValueError("Switch.case/default must be used inside "
                                     "'with Switch() as switch:'")
                sub = default_main_program()._create_block()
                if condition is None:
                    if sw._default is not None:
                        raise ValueError("Switch allows one default() only")
                    sw._default = sub
                else:
                    sw._cases.append((condition, sub))
                return self

            def __exit__(self, *exc):
                default_main_program()._rollback()
                return False

        return _Guard()

    def case(self, condition):
        if condition.dtype != "bool":
            raise TypeError(f"Switch.case cond must be bool, "
                            f"got {condition.dtype}")
        return self._branch(condition)

    def default(self):
        return self._branch(None)

    def _finalize(self):
        if not self._cases:
            raise ValueError("Switch needs at least one case()")
        parent = self._parent
        prog = parent.program
        outs = []
        branches = [b for _, b in self._cases]
        if self._default is not None:
            branches.append(self._default)
        for b in branches:
            for n in _outer_writes(prog, b.idx, parent):
                if n not in outs:
                    outs.append(n)
        # Nested levels only see declared inputs (the executor's block_runner
        # merges the TOP-level env, not an enclosing loop body's), so every
        # deeper case condition and every var any branch reads must ride the
        # X slot -- otherwise a Switch inside a While body can't resolve them.
        xs = list(outs)
        for cond, _ in self._cases[1:]:
            if cond.name not in xs:
                xs.append(cond.name)
        for b in branches:
            for n in _outer_reads(prog, b.idx, parent, exclude=xs):
                xs.append(n)
        next_else = self._default.idx if self._default is not None else -1
        for cond, blk in reversed(self._cases[1:]):
            wrapper = prog._create_block(parent_idx=parent.idx)
            wrapper.append_op(
                "conditional_block",
                inputs={"Cond": [cond.name], "X": list(xs)},
                outputs={"Out": list(outs)},
                attrs={"sub_block": blk.idx, "else_block": next_else,
                       "x_names": list(xs), "out_names": list(outs)},
                infer_shape=False)
            prog._rollback()
            next_else = wrapper.idx
        cond0, blk0 = self._cases[0]
        parent.append_op(
            "conditional_block",
            inputs={"Cond": [cond0.name], "X": list(xs)},
            outputs={"Out": list(outs)},
            attrs={"sub_block": blk0.idx, "else_block": next_else,
                   "x_names": list(xs), "out_names": list(outs)},
            infer_shape=False)


class IfElse:
    """Branch-on-mask (reference control_flow.py:1827). TPU-native semantics:
    BOTH branches execute over the full batch and each output pair merges
    elementwise with ``where(cond, true, false)`` -- XLA has no per-row
    divergence, and computing both sides then selecting is the hardware-native
    form (identical results for rowwise computation, fully differentiable).
    ``input(x)`` therefore returns x unsplit. cond shape [B, 1] (rowwise) or
    [1] (scalar)::

        ie = layers.IfElse(cond)
        with ie.true_block():
            ie.output(ie.input(x) + 1)
        with ie.false_block():
            ie.output(ie.input(x) - 1)
        out, = ie()
    """

    def __init__(self, cond, name=None):
        if cond.dtype != "bool":
            raise TypeError(f"IfElse cond must be bool, got {cond.dtype}")
        self.cond = cond
        self._outs = {True: [], False: []}
        self._branch = None

    def _guard(self, val):
        ie = self

        class _Guard:
            def __enter__(self):
                ie._branch = val
                return self

            def __exit__(self, *exc):
                ie._branch = None
                return False

        return _Guard()

    def true_block(self):
        return self._guard(True)

    def false_block(self):
        return self._guard(False)

    def input(self, x):
        if self._branch is None:
            raise ValueError("IfElse.input() outside a true_block/false_block")
        return x

    def output(self, *outs):
        if self._branch is None:
            raise ValueError("IfElse.output() outside a true_block/false_block")
        self._outs[self._branch].extend(outs)

    def __call__(self):
        t, f = self._outs[True], self._outs[False]
        if len(t) != len(f):
            raise ValueError(f"IfElse branches produced {len(t)} vs {len(f)} "
                             f"outputs; they must match pairwise")
        from . import nn as _nn
        return [_nn.where(self.cond, a, b) for a, b in zip(t, f)]


class Scan:
    """Structured recurrence builder lowering to lax.scan (the TPU-native
    StaticRNN/DynamicRNN analog, reference control_flow.py StaticRNN:478).

    Usage::

        scan = Scan()
        with scan.step():
            x_t = scan.step_input(x_seq)          # [B, T, D] -> [B, D] per step
            h_prev = scan.memory(init=h0)         # loop state
            h = some_layers(x_t, h_prev)
            scan.update_memory(h_prev, h)
            scan.step_output(h)
        outs = scan()                              # [B, T, H]

    ``steps``: the trip count of a scan over no sequence -- a body applied
    ``steps`` times to its memories on weights that exist once, as the stack
    of a looped language model is (``models/decoder_lm.py``): memories and
    outputs alone, outputs stacked ``[steps, ...]`` under ``time_major``.
    The Program holds the body's ops once whatever ``steps`` is (an
    attribute). ``finals`` (after the call): the memories after the last
    step, in ``memory`` order.
    """

    def __init__(self, time_major=False, steps=None):
        if steps is not None and int(steps) < 1:
            raise ValueError(f"Scan needs steps >= 1, got {steps}")
        self.time_major, self.steps = time_major, steps
        self._seq_inputs = []   # (outer var, inner name)
        self._memories = []     # (init outer var, inner name, update name)
        self._outputs = []      # inner names
        self._sub_block_idx = None

    def step(self):
        scan = self

        class _Guard:
            def __enter__(self):
                prog = default_main_program()
                scan._parent_block = prog.current_block()
                scan._sub = prog._create_block()
                return scan

            def __exit__(self, *exc):
                default_main_program()._rollback()
                return False

        return _Guard()

    def step_input(self, x):
        sub = default_main_program().current_block()
        inner = sub.create_var(x.name + "@step", tuple(
            s for i, s in enumerate(x.shape) if i != (0 if self.time_major else 1)),
            x.dtype)
        self._seq_inputs.append((x, inner.name))
        return inner

    def memory(self, init):
        sub = default_main_program().current_block()
        inner = sub.create_var(init.name + "@mem", init.shape, init.dtype)
        self._memories.append([init, inner.name, None])
        return inner

    def update_memory(self, mem, new_val):
        for m in self._memories:
            if m[1] == mem.name:
                m[2] = new_val.name
                return
        raise ValueError(f"{mem.name} is not a Scan memory")

    def step_output(self, o):
        self._outputs.append(o.name)

    def __call__(self):
        prog = default_main_program()
        parent = self._parent_block
        sub = self._sub
        # The scan op carries memories: what an iteration hands the next is
        # the update value (the op's ``next_names``).
        for init, inner, update in self._memories:
            if update is None:
                raise ValueError(f"memory {inner} never updated")
        if self._seq_inputs:
            T = self._seq_inputs[0][0].shape[0 if self.time_major else 1]
        elif self.steps is not None:
            T = int(self.steps)
        else:
            raise ValueError("Scan requires at least one step_input, or "
                             "steps, to determine the sequence length")
        outs = []
        for n in self._outputs:
            sv = sub.var(n)
            step_shape = tuple(sv.shape)
            if self.time_major:
                shape = (T,) + step_shape
            else:
                shape = step_shape[:1] + (T,) + step_shape[1:]
            outs.append(parent.create_var(n + "@scan_out", shape, sv.dtype))
        finals = [parent.create_var(m[1] + "@final",
                                    parent.program.blocks[sub.idx].var(m[1]).shape,
                                    parent.program.blocks[sub.idx].var(m[1]).dtype)
                  for m in self._memories]
        # final carry values, in memory() declaration order (see final_memory())
        self.finals = [parent.var(f.name) for f in finals]
        # Outer vars the body reads (params, lengths) must be DECLARED inputs:
        # the scan op's grad is jax.vjp over its lowering, and a var reaching
        # the body only through closure capture would get no gradient.
        already = {m[0].name for m in self._memories} | \
            {si[0].name for si in self._seq_inputs}
        statics = _outer_reads(parent.program, sub.idx, parent,
                               exclude=already)
        parent.append_op(
            "scan",
            inputs={"Init": [m[0] for m in self._memories],
                    "X": [si[0] for si in self._seq_inputs],
                    "Static": list(statics)},
            outputs={"Out": outs, "FinalCarry": finals},
            attrs={"sub_block": sub.idx,
                   "steps": 0 if self._seq_inputs else T,
                   "carry_names": [m[1] for m in self._memories],
                   "next_names": [m[2] for m in self._memories],
                   "x_names": [si[1] for si in self._seq_inputs],
                   "out_names": list(self._outputs),
                   "static_names": list(statics),
                   "time_major": self.time_major},
            infer_shape=False)
        blk = parent
        if len(outs) == 1:
            return blk.var(outs[0].name)
        return [blk.var(o.name) for o in outs]


class DynamicRNN:
    """Variable-length RNN DSL (reference control_flow.py:1999).

    TPU-native: where the reference shrinks the batch as sequences finish
    (LoD-sorted dynamic batching -- dynamic shapes XLA can't compile), this
    runs a fixed [B, T] lax.scan with a per-step validity mask: memories
    freeze and outputs zero once ``t >= length``. Padded [B, T, D] input +
    a ``lengths`` [B] int tensor replace the LoD (SURVEY.md §5.7 design)::

        drnn = layers.DynamicRNN()
        with drnn.block():
            w = drnn.step_input(x_padded, lengths=seq_len)   # [B, D] per step
            prev = drnn.memory(shape=[H], value=0.0)
            h = layers.fc(w, H) + layers.fc(prev, H)
            drnn.update_memory(prev, h)
            drnn.output(h)
        hs = drnn()                                           # [B, T, H]
    """

    def __init__(self, name=None):
        self._scan = Scan(time_major=False)
        self._lengths = None
        self._mask = None
        self._t = None
        self._first_outer_x = None

    def block(self):
        rnn = self
        inner = self._scan.step()

        class _Guard:
            def __enter__(self):
                inner.__enter__()
                return rnn

            def __exit__(self, exc_type, *exc):
                if exc_type is None and rnn._t is not None:
                    nxt = increment(rnn._t, value=1.0, in_place=False)
                    rnn._scan.update_memory(rnn._t, nxt)
                return inner.__exit__(exc_type, *exc)

        return _Guard()

    def step_input(self, x, lengths=None):
        """x: padded [B, T, ...] sequence; returns the per-step [B, ...] slice.
        Pass ``lengths`` ([B] int) once to activate masking."""
        if self._first_outer_x is None:
            self._first_outer_x = x
        inner = self._scan.step_input(x)
        if lengths is not None:
            if self._lengths is not None:
                raise ValueError("DynamicRNN lengths already set")
            self._lengths = lengths
            self._build_mask()
        return inner

    def static_input(self, x):
        """Non-sequence input visible at every step (closure capture)."""
        return x

    def _build_mask(self):
        parent = self._scan._parent_block
        t0 = unique_name.generate("drnn_t0")
        parent.create_var(t0, (1,), "float32").stop_gradient = True
        parent.append_op("fill_constant", outputs={"Out": [t0]},
                         attrs={"shape": [1], "dtype": "float32",
                                "value": 0.0},
                         infer_shape=False)
        self._t = self._scan.memory(parent.var(t0))
        from . import nn as _nn
        lens_f = _nn.reshape(tensor.cast(self._lengths, "float32"), [-1])
        self._mask = less_than(self._t, lens_f)   # [1] < [B] -> [B] bool

    def _masked(self, new, old):
        if self._mask is None:
            return new
        from . import nn as _nn
        cond = self._mask
        rank = len(new.shape)
        if rank > 1:
            cond = _nn.unsqueeze(cond, list(range(1, rank)))
        return _nn.where(cond, new, old)

    def memory(self, init=None, shape=None, value=0.0, dtype="float32",
               need_reorder=False):
        """Loop state: pass ``init`` (a [B, ...] var) or ``shape``+``value``
        for a zero/constant batch-sized init (reference :2090)."""
        if init is None:
            if self._first_outer_x is None:
                raise ValueError(
                    "DynamicRNN.memory(shape=...) needs a prior step_input to "
                    "size the batch dim")
            parent = self._scan._parent_block
            name = unique_name.generate("drnn_mem_init")
            full = [-1] + [int(s) for s in (shape or [])]
            parent.create_var(name, tuple(full), convert_dtype(dtype))
            parent.append_op(
                "fill_constant_batch_size_like",
                inputs={"Input": [self._first_outer_x.name]},
                outputs={"Out": [name]},
                attrs={"shape": full, "dtype": convert_dtype(dtype),
                       "value": float(value), "input_dim_idx": 0,
                       "output_dim_idx": 0},
                infer_shape=False)
            init = parent.var(name)
        return self._scan.memory(init)

    def update_memory(self, mem, new):
        """Masked: finished sequences keep their last state."""
        self._scan.update_memory(mem, self._masked(new, mem))

    def output(self, *outputs):
        """Per-step outputs, zeroed past each sequence's length."""
        for o in outputs:
            if self._mask is not None:
                o = self._masked(o, tensor.zeros_like(o))
            self._scan.step_output(o)

    def __call__(self):
        return self._scan()


def _cmp_layer(op_type):
    def layer(x, y, cond=None):
        helper = LayerHelper(op_type)
        if cond is None:
            cond = helper.create_variable_for_type_inference(
                "bool", stop_gradient=True)
        helper.append_op(op_type, inputs={"X": [x], "Y": [y]},
                         outputs={"Out": [cond]})
        return helper.main_program.current_block().var(cond.name)
    layer.__name__ = op_type
    return layer


greater_than = _cmp_layer("greater_than")
greater_equal = _cmp_layer("greater_equal")
less_equal = _cmp_layer("less_equal")
not_equal = _cmp_layer("not_equal")


def is_empty(x, cond=None):
    """Reference control_flow.py:is_empty. Decided at LOWERING time, where
    every dim (including the batch, concrete once the feed arrives) is
    static -- so feed vars with a -1 build-time dim work, unlike a
    build-time constant which would bake in the wrong answer."""
    helper = LayerHelper("is_empty")
    out = cond or helper.create_variable_for_type_inference(
        "bool", stop_gradient=True)
    helper.append_op("is_empty", inputs={"X": [x]}, outputs={"Out": [out]})
    return helper.main_program.current_block().var(out.name)


def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=False,
          print_phase="both"):
    """Reference control_flow.py:Print -- host-side debug print via the
    print op (jax.debug.print under jit)."""
    helper = LayerHelper("print")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("print", inputs={"In": [input]},
                     outputs={"Out": [out]},
                     attrs={"message": message or (input.name + ": ")})
    return helper.main_program.current_block().var(out.name)


def reorder_lod_tensor_by_rank(x, rank_table):
    raise NotImplementedError(
        "reorder_lod_tensor_by_rank reorders ragged LoD rows by a rank "
        "table; the TPU representation is padded+lengths (SCOPE.md LoD row) "
        "-- sort/gather the padded batch with argsort + gather instead")


# StaticRNN: Scan was designed as its TPU-native analog -- same
# step_input/memory/update_memory/step_output protocol over lax.scan
# (reference control_flow.py:478). The alias keeps ported code working.
StaticRNN = Scan
