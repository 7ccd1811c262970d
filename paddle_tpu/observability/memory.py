"""Device-memory telemetry: what a compiled step's peak is made of.

**Always on, set at every compile miss** (``Executor._post_compile_telemetry``;
no device synchronization, no proto, no walk of the HLO):

- ``program_argument_bytes`` / ``program_temp_bytes`` /
  ``program_output_bytes`` / ``program_alias_bytes`` /
  ``program_peak_bytes{program}`` from the executable's
  ``memory_analysis()`` (peak = arg + out + temp - alias, the XLA-exact
  answer to "does this step fit"), ``program_xla_peak_bytes`` where jaxlib
  gives a non-zero ``peak_memory_in_bytes`` (the compiler's own simulated
  peak: libtpu fills it, the CPU backend only with its arguments);
- ``program_static_peak_bytes`` / ``program_static_peak_ratio`` from
  ``analysis/memplan.py`` (liveness over the IR) beside XLA's answer;
- ``program_state_bytes{program, class}``: bytes on one device of what the
  step takes in, by what the Program says each is (``parameter``,
  ``optimizer`` = accumulators of its optimizer ops, ``other`` persistable
  state, ``feed``), after the executable's own input shardings;
- ``program_allocator_bytes{program, stat}``: the fullest device's allocator
  marks (``in_use``, ``peak_in_use``, ``peak_reserved``) BEFORE the fresh
  program first runs -- taken from the one ``sample_device_memory("compile")``
  read. The peaks are monotone, so the marks of successive compiles bracket
  the phases between them (what start-up reached, what the step added);
- ``program_compile_seq{program}``: the order of compile misses in this
  process, so a reader can pick the last compiled program (the train step);
- runtime occupancy: ``device_memory_bytes_in_use`` /
  ``device_memory_peak_bytes`` from ``memory_stats()`` (TPU/GPU); the CPU
  test backend has none, so a ``jax.live_arrays()`` fallback sums committed
  bytes per device -- coarser, and without a reserved pool:
  ``program_allocator_bytes`` then has no ``peak_reserved``. Beyond compile
  time the executor samples every K steps (``PADDLE_TPU_OBS_MEM_INTERVAL``,
  default 10) only while ``PADDLE_TPU_OBS`` is on.

**On demand, never on the run's path**: ``peak_live_set(label)`` -- the
buffers live where the step's temporaries are highest, each with the
``<op_type>#<idx>`` scope of the instruction that defines it. It parses the
step's optimized, scheduled HLO text (seconds for a large step) and is called
by whoever asks (``benchmark/reducers/live_set.py``, a debugging session),
never by ``Executor.run``. ``compiled_step(label)`` is the handle it works
from: label, ``hlo_text()``, ``memory()``.
"""
from __future__ import annotations

import itertools
import os
import re
import weakref
from typing import Dict, List, Optional

from .attribution import (_CALLEE_RE, _DTYPE_BYTES, _IR_TOKEN, _split_tuple,
                          parse_hlo_computations)
from .metrics import REGISTRY, MetricsRegistry

DEFAULT_INTERVAL = 10


def sample_interval() -> int:
    raw = os.environ.get("PADDLE_TPU_OBS_MEM_INTERVAL", "")
    try:
        k = int(raw) if raw else DEFAULT_INTERVAL
    except ValueError:
        k = DEFAULT_INTERVAL
    return max(1, k)


def _live_bytes_by_device() -> Dict[str, int]:
    """Fallback accounting: committed live jax.Array bytes per device."""
    import jax
    out: Dict[str, int] = {}
    for arr in jax.live_arrays():
        try:
            nbytes = arr.nbytes
            devs = arr.devices()
        except Exception:
            continue
        for d in devs:
            key = f"{d.platform}:{d.id}"
            out[key] = out.get(key, 0) + nbytes // max(1, len(devs))
    return out


def sample_device_memory(reason: str = "step",
                         registry: Optional[MetricsRegistry] = None,
                         ) -> Dict[str, Dict[str, float]]:
    """Take one memory sample; set gauges + counter track; return the
    {device: {bytes_in_use, peak_bytes[, peak_bytes_reserved]}} snapshot
    (``update_allocator_gauges``, tests, obs_report)."""
    import jax

    registry = registry or REGISTRY
    snapshot: Dict[str, Dict[str, float]] = {}
    fallback = None
    for d in jax.local_devices():
        key = f"{d.platform}:{d.id}"
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        reserved = None
        if stats:
            in_use = float(stats.get("bytes_in_use", 0.0))
            peak = float(stats.get("peak_bytes_in_use", in_use))
            if "peak_bytes_reserved" in stats:
                reserved = float(stats["peak_bytes_reserved"])
        else:
            if fallback is None:
                fallback = _live_bytes_by_device()
            in_use = float(fallback.get(key, 0))
            # no allocator high-water mark without memory_stats(): track the
            # max this process has observed so the gauge is still monotone
            g = registry.gauge("device_memory_peak_bytes",
                               "peak device bytes (allocator high-water "
                               "mark, or max observed sample)", device=key)
            peak = max(g.value, in_use)
        snapshot[key] = {"bytes_in_use": in_use, "peak_bytes": peak}
        if reserved is not None:
            snapshot[key]["peak_bytes_reserved"] = reserved
        registry.gauge("device_memory_bytes_in_use",
                       "device bytes in use at last sample",
                       device=key).set(in_use)
        registry.gauge("device_memory_peak_bytes",
                       "peak device bytes (allocator high-water mark, or "
                       "max observed sample)", device=key).set(peak)
    registry.counter("memory_samples_total",
                     "device-memory telemetry samples by reason",
                     reason=reason).inc()
    from . import timeline as _timeline
    _timeline.counter_sample(
        "device_memory_bytes",
        {k: v["bytes_in_use"] for k, v in snapshot.items()})
    return snapshot


def update_program_memory_gauges(compiled_step, program: str,
                                 registry: Optional[MetricsRegistry] = None,
                                 ) -> Optional[Dict[str, float]]:
    """Set per-program footprint gauges from the executable's
    ``memory_analysis()``.  Returns the byte decomposition, or None when the
    step holds no executable (lazy-jit fallback) or the backend lacks the
    analysis."""
    registry = registry or REGISTRY
    exe = getattr(compiled_step, "executable", None)
    if exe is None:
        return None
    try:
        ma = exe.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    parts = {
        "argument_bytes": float(getattr(ma, "argument_size_in_bytes", 0) or 0),
        "output_bytes": float(getattr(ma, "output_size_in_bytes", 0) or 0),
        "temp_bytes": float(getattr(ma, "temp_size_in_bytes", 0) or 0),
        "alias_bytes": float(getattr(ma, "alias_size_in_bytes", 0) or 0),
        "code_bytes": float(getattr(ma, "generated_code_size_in_bytes", 0)
                            or 0),
    }
    # aliased (donated) buffers are counted inside argument_bytes and reused
    # for outputs -- subtract so peak is not double-counted
    parts["peak_bytes"] = max(
        0.0, parts["argument_bytes"] + parts["output_bytes"] +
        parts["temp_bytes"] - parts["alias_bytes"])
    g = registry.gauge
    g("program_peak_bytes", "XLA memory_analysis arg+out+temp-alias bytes "
      "for the compiled step", program=program).set(parts["peak_bytes"])
    g("program_temp_bytes", "XLA scratch bytes for the compiled step",
      program=program).set(parts["temp_bytes"])
    g("program_argument_bytes", "input (incl. donated state) bytes",
      program=program).set(parts["argument_bytes"])
    g("program_output_bytes", "output bytes", program=program).set(
        parts["output_bytes"])
    g("program_alias_bytes", "output bytes that reuse a donated argument's "
      "buffer", program=program).set(parts["alias_bytes"])
    xla_peak = float(getattr(ma, "peak_memory_in_bytes", 0) or 0)
    if xla_peak > 0:
        parts["xla_peak_bytes"] = xla_peak
        g("program_xla_peak_bytes", "the compiler's own simulated peak "
          "(CompiledMemoryStats.peak_memory_in_bytes)",
          program=program).set(xla_peak)
    return parts


def update_static_memory_gauges(program_ir, feed_shapes, feed_names,
                                fetch_names, strategy, program: str,
                                xla_parts: Optional[Dict[str, float]] = None,
                                registry: Optional[MetricsRegistry] = None):
    """Set the *static* peak-memory estimate gauge (analysis/memplan.py:
    liveness over the IR, sharding divisors + donation applied) next to
    XLA's exact ``memory_analysis()`` answer, plus their ratio when both
    exist -- the planner's accuracy is itself observable, per compile.
    Returns the MemEstimate, or None when the estimate fails (never
    raises into the compile path)."""
    registry = registry or REGISTRY
    try:
        from ..analysis import memplan
        batch = (memplan.infer_batch(program_ir, feed_shapes)
                 if feed_shapes else None)
        est = memplan.estimate_program_memory(
            program_ir, feed_names=feed_names, fetch_names=fetch_names,
            strategy=strategy, batch=batch)
    except Exception:
        return None
    registry.gauge("program_static_peak_bytes",
                   "static liveness-based peak-memory estimate for the "
                   "compiled step (analysis/memplan.py)",
                   program=program).set(float(est.peak_bytes))
    xla_peak = (xla_parts or {}).get("peak_bytes") or 0.0
    if xla_peak > 0:
        registry.gauge("program_static_peak_ratio",
                       "static estimate / XLA memory_analysis peak (1.0 = "
                       "planner exact; the planner's accuracy gauge)",
                       program=program).set(float(est.peak_bytes) / xla_peak)
    return est


# ------------------------------------------- compile-miss bookkeeping --

#: gauge families of this module that carry a label besides ``program``
#: (exact-label removal cannot reach them: ``retire_program`` drops them)
_LABELLED_FAMILIES = ("program_allocator_bytes", "program_state_bytes")

_ALLOCATOR_STATS = (("in_use", "bytes_in_use"), ("peak_in_use", "peak_bytes"),
                    ("peak_reserved", "peak_bytes_reserved"))

#: label -> weak reference to the compiled step: what ``peak_live_set`` works
#: from, dropped with the gauges
_STEPS: Dict[str, weakref.ref] = {}
_COMPILE_SEQ = itertools.count(1)


def update_allocator_gauges(snapshot, program: str,
                            registry: Optional[MetricsRegistry] = None):
    """``program_allocator_bytes{program, stat}`` from one
    ``sample_device_memory`` snapshot taken before the program's first run:
    the marks of the fullest device (by ``peak_in_use + peak_reserved``).
    ``peak_reserved`` is left out where the allocator keeps none (the CPU's
    ``live_arrays`` fallback)."""
    registry = registry or REGISTRY
    if not snapshot:
        return None
    fullest = max(snapshot.values(), key=lambda v: v["peak_bytes"]
                  + v.get("peak_bytes_reserved", 0.0))
    for stat, key in _ALLOCATOR_STATS:
        if key in fullest:
            registry.gauge("program_allocator_bytes",
                           "allocator marks of the fullest device before "
                           "the program's first run", program=program,
                           stat=stat).set(fullest[key])
    return fullest


def state_classes(program_ir) -> Dict[str, str]:
    """{persistable variable: ``parameter`` | ``optimizer``} as the Program
    itself says: a ``Parameter``, or what an optimizer op (one that takes a
    ``Param`` and its ``Grad``) reads besides them and the learning rate --
    moments, beta powers. Whatever else a step keeps is ``other``."""
    from ..framework import Parameter
    gb = program_ir.global_block()
    out = {n: "parameter" for n, v in gb.vars.items()
           if isinstance(v, Parameter)}
    for op in gb.ops:
        if "Param" in op.inputs and "Grad" in op.inputs:
            for slot, names in op.inputs.items():
                if slot not in ("Param", "Grad", "LearningRate"):
                    for n in names:
                        out.setdefault(n, "optimizer")
    return out


def update_state_gauges(compiled_step, program_ir, exe_args, program: str,
                        registry: Optional[MetricsRegistry] = None
                        ) -> Optional[Dict[str, float]]:
    """``program_state_bytes{program, class}``: bytes on ONE device of what
    the step takes in (``exe_args`` = mutable state, read-only state, feeds,
    run counter), each array cut by the executable's own input sharding.
    Like its siblings it never raises into the compile path: where the walk
    fails the gauges stay unset and None is returned."""
    import numpy as np
    registry = registry or REGISTRY
    totals = dict.fromkeys(("parameter", "optimizer", "other", "feed"), 0.0)
    try:
        shardings = compiled_step.executable.input_shardings[0]
        classes = state_classes(program_ir)
        for vals, cut, fixed in zip(exe_args[:3], shardings,
                                    (None, None, "feed")):
            for n, v in vals.items():
                shape = tuple(np.shape(v))
                if cut.get(n) is not None:
                    shape = cut[n].shard_shape(shape)
                dtype = v.dtype if hasattr(v, "dtype") \
                    else np.asarray(v).dtype
                totals[fixed or classes.get(n, "other")] += float(
                    np.prod(shape, dtype=np.float64)
                    * np.dtype(dtype).itemsize)
    except Exception:
        return None
    for cls, nbytes in totals.items():
        registry.gauge("program_state_bytes",
                       "bytes on one device of what the step takes in, by "
                       "what the Program says it is", program=program,
                       **{"class": cls}).set(nbytes)
    return totals


def note_compiled_step(compiled_step, program_ir, program: str, exe_args,
                       marks,
                       registry: Optional[MetricsRegistry] = None) -> int:
    """What a compile miss records of a step that holds an executable,
    beyond XLA's and the planner's totals: its place among the process's
    compile misses (``program_compile_seq``), what it takes in by class,
    the allocator's ``marks`` (one ``sample_device_memory`` snapshot) before
    it first runs, and a weak reference to the step for ``peak_live_set``."""
    registry = registry or REGISTRY
    seq = next(_COMPILE_SEQ)
    registry.gauge("program_compile_seq",
                   "order of this program's latest compile miss among the "
                   "process's", program=program).set(float(seq))

    def gone(ref):      # an executor collected without close(): no leak
        if _STEPS.get(program) is ref:
            del _STEPS[program]
    _STEPS[program] = weakref.ref(compiled_step, gone)
    update_state_gauges(compiled_step, program_ir, exe_args, program,
                        registry)
    update_allocator_gauges(marks, program, registry)
    return seq


def retire_program(label: str,
                   registry: Optional[MetricsRegistry] = None) -> None:
    """Drop what this module keeps for a program label that no live executor
    caches any more: the weak step and the gauges with a second label."""
    registry = registry or REGISTRY
    _STEPS.pop(label, None)
    for fname in _LABELLED_FAMILIES:
        fam = registry.get(fname)
        if fam is None:
            continue
        with fam._lock:
            for key in [k for k in fam.children
                        if ("program", label) in k]:
                fam.children.pop(key, None)


class StepHandle:
    """The public face of one compiled step: ``label``, ``hlo_text()``,
    ``memory()``. ``compiled_step(label)`` returns it; nothing here reaches
    into an executor's cache."""

    def __init__(self, label: str, step):
        self.label, self._step = label, step

    def hlo_text(self) -> str:
        """Optimized, scheduled HLO text of the step."""
        text = self._step.executable.as_text()
        return "\n".join(map(str, text)) if isinstance(
            text, (list, tuple)) else str(text)

    def memory(self) -> Dict[str, int]:
        """XLA's memory analysis of the step, bytes: ``argument``,
        ``output``, ``temp``, ``alias``, and ``xla_peak`` (0 where the
        backend gives none)."""
        m = self._step.executable.memory_analysis()
        return {"argument": int(m.argument_size_in_bytes),
                "output": int(m.output_size_in_bytes),
                "temp": int(m.temp_size_in_bytes),
                "alias": int(m.alias_size_in_bytes),
                "xla_peak": int(getattr(m, "peak_memory_in_bytes", 0) or 0)}


def compiled_step(label: str) -> Optional[StepHandle]:
    """The handle of the program label's latest compiled step, or None once
    it is gone (evicted, executor closed) or holds no executable."""
    ref = _STEPS.get(label)
    step = ref() if ref else None
    if step is None or getattr(step, "executable", None) is None:
        return None
    return StepHandle(label, step)


# ------------------------------------- on demand: the live set at the peak --

_LEAF = re.compile(r"^([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{([^}]*)\})?")
_TILE = re.compile(r"T\(([0-9,]+)\)")
_SPACE = re.compile(r"S\((\d+)\)")
_GTE_INDEX = re.compile(r"\bindex=(\d+)")
_OUT_ALIAS = re.compile(r"\{(\d*)\}:\s*\((\d+),")
_TPU_ALIAS = '"aliasing_operands":{"lists":['
_TPU_GROUP = re.compile(r'"indices":\[([^\]]*)\]')
#: a result that shares its operand's buffer and defines none
_IN_PLACE = frozenset(("bitcast", "while", "dynamic-update-slice",
                       "optimization-barrier"))
_CALLERS = frozenset(("while", "conditional", "call"))


def _elements(shape: str) -> List[str]:
    """The top-level elements of an HLO shape string (itself, if no tuple)."""
    shape = shape.strip()
    if not shape.startswith("("):
        return [shape]
    depth = 0
    for i, ch in enumerate(shape):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return _split_tuple(shape[1:i])
    return [shape]


def hbm_bytes(shape: str) -> float:
    """Bytes a value of this HLO shape takes in HBM: tuples sum their leaves,
    a tiled layout pads the minor dimensions up to its first tile
    (``T(8,128)``), and a leaf the compiler placed in another memory space
    (``S(1)``: on-chip memory on a TPU) takes none."""
    parts = _elements(shape)
    if len(parts) > 1 or parts[0] != shape.strip():
        return sum(hbm_bytes(p) for p in parts)
    m = _LEAF.match(parts[0])
    if not m:
        return 0.0
    dtype, dims, layout = m.group(1), m.group(2), m.group(3) or ""
    space = _SPACE.search(layout)
    if space and space.group(1) != "0":
        return 0.0
    extents = [int(d) for d in dims.split(",") if d]
    tile = _TILE.search(layout)
    if tile and extents:
        order = [int(x) for x in layout.split(":")[0].split(",")
                 if x.strip().isdigit()]
        minor_first = [extents[i] for i in order] \
            if len(order) == len(extents) else extents[::-1]
        for k, t in enumerate(reversed(
                [int(x) for x in tile.group(1).split(",")])):
            if k < len(minor_first):
                minor_first[k] = -(-minor_first[k] // t) * t
        extents = minor_first
    n = 1.0
    for d in extents:
        n *= d
    return n * _DTYPE_BYTES.get(dtype, 4)


def _shared_with_operand(attrs: str, n_operands: int):
    """(output element, operand) pairs that share one buffer, as the
    instruction's attributes say: HLO's ``output_to_operand_aliasing``, and
    the TPU compiler's ``aliasing_operands`` (groups over the operands
    followed by the outputs, counted on)."""
    pairs = []
    if "output_to_operand_aliasing" in attrs:
        tail = attrs.split("output_to_operand_aliasing", 1)[1]
        pairs += [(int(j or 0), int(k)) for j, k in
                  _OUT_ALIAS.findall(tail.split("}}", 1)[0] + "}")]
    tail = attrs.split(_TPU_ALIAS, 1)[1] if _TPU_ALIAS in attrs else "]"
    if not tail.startswith("]"):
        for group in _TPU_GROUP.findall(tail[:tail.find("]}]") + 2]):
            idx = [int(x) for x in re.findall(r"\d+", group)]
            taken = [i for i in idx if i < n_operands]
            pairs += [(i - n_operands, taken[0]) for i in idx
                      if i >= n_operands and taken]
    return pairs


def _scopes(op_name: str):
    """(innermost ``<op_type>#<idx>``, the outermost one's idx) of an
    instruction's ``op_name``: the scope, and the op's place in the Program's
    global block. (None, None) where the instruction has no IR scope."""
    toks = _IR_TOKEN.findall(op_name)
    if not toks:
        return None, None
    return toks[-1], int(toks[0].rsplit("#", 1)[1])


def _scope_table(instrs) -> Dict[str, tuple]:
    """{instruction: ``_scopes`` of its ``op_name``} over one computation. A
    copy or an eviction the compiler put in carries no scope of its own: it
    holds its operand's data, so it takes its operand's scope. Nor does a
    buffer the compiler makes for a loop to fill or to add into (the stack
    a scan op keeps for its backward, the sums of the weights' gradients
    its backward carries): it takes the scope of the ``while`` it enters."""
    named, by_name = {}, {ins.name: ins for ins in instrs}
    for ins in instrs:
        named[ins.name] = _scopes(ins.op_name)
        if named[ins.name][0] is None:
            named[ins.name] = next(
                (named[o] for o in ins.operands
                 if named.get(o, (None,))[0] is not None), (None, None))
    for ins in instrs:
        if ins.opcode != "while" or named[ins.name][0] is None:
            continue
        entering = list(ins.operands)
        while entering:
            o = entering.pop()
            if o not in named:
                continue
            if named[o][0] is None:
                named[o] = named[ins.name]
            if by_name[o].opcode == "tuple":
                entering += by_name[o].operands
    return named


def _computation_peak(cname: str, comps, memo) -> dict:
    """Liveness over one scheduled computation: ``{"bytes", "position", "n",
    "instruction", "live": [(instruction, bytes, scope, idx)]}`` at the point
    where the buffers it defines (not its parameters, not what leaves through
    its root) are largest. A fusion is one buffer; a view (tuple, bitcast,
    get-tuple-element, a ``-done``) and an in-place result share their
    operand's; a called computation's own peak stands at its call."""
    if cname in memo:
        return memo[cname]
    memo[cname] = {"bytes": 0.0, "position": 0, "n": 0, "instruction": None,
                   "live": []}          # a cycle would be a malformed module
    instrs = comps.get(cname, [])
    elems, shape_of, named = {}, {}, _scope_table(instrs)
    size, born, last, scope = {}, {}, {}, {}
    calls = {}                           # position -> the callee's peak
    none = frozenset()
    root = instrs[-1] if instrs else None
    for pos, ins in enumerate(instrs):
        if ins.is_root:
            root = ins
        shape_of[ins.name] = ins.shape
        ops = [elems.get(o, [none]) for o in ins.operands]
        for o in ops:
            for e in o:
                for b in e:
                    last[b] = pos
        op, parts = ins.opcode, _elements(ins.shape)
        whole = [none.union(*o) for o in ops]     # per operand, all of it

        def fresh(j, part):
            b = (ins.name, j)
            size[b], born[b], last[b] = hbm_bytes(part), pos, pos
            scope[b] = named[ins.name]
            return frozenset((b,))

        if op in ("parameter", "constant"):
            out = [none] * len(parts)
        elif op == "get-tuple-element":
            m = _GTE_INDEX.search(ins.rest)
            i = int(m.group(1)) if m else 0
            out = [ops[0][i] if ops and i < len(ops[0]) else
                   (whole[0] if whole else none)]
        elif op == "tuple":
            out = whole or [none]
        elif op in _IN_PLACE:
            out = (ops[0] if ops and len(ops[0]) == len(parts)
                   else [whole[0] if whole else none] * len(parts))
        elif op.endswith("-done"):
            start = _elements(shape_of.get(ins.operands[0], "")) \
                if ins.operands else []
            hit = [j for j, p in enumerate(start) if p == ins.shape.strip()]
            out = [ops[0][hit[0]] if hit and hit[0] < len(ops[0])
                   else (whole[0] if whole else none)]
        elif op.endswith("-start") and len(parts) > 1:
            # (operands.., result, context): an element that is a nested
            # tuple, copy-start's second, or a first one shaped like the
            # operand is the operand again
            first = shape_of.get(ins.operands[0], "").strip() \
                if ins.operands else ""
            out = []
            for j, part in enumerate(parts):
                again = part.startswith("(") or (
                    j == 1 if op == "copy-start" else
                    j == 0 and part == first)
                out.append((whole[0] if whole else none) if again
                           else fresh(j, part))
        else:
            out = [fresh(j, part) for j, part in enumerate(parts)]
            for j, k in _shared_with_operand(ins.rest, len(ops)):
                if j < len(out) and k < len(whole):
                    size.pop((ins.name, j), None)
                    out[j] = whole[k]
        elems[ins.name] = out
        if op in _CALLERS or (op.endswith("-start") and "calls=" in ins.rest):
            callees = [c for _, c in _CALLEE_RE.findall(ins.rest)]
            m = re.search(r"branch_computations=\{([^}]*)\}", ins.rest)
            if m:
                callees += re.findall(r"%?([\w.\-]+)", m.group(1))
            peaks = [_computation_peak(c, comps, memo) for c in callees
                     if c in comps]
            if peaks:
                calls[pos] = max(peaks, key=lambda p: p["bytes"])
    leaves = none.union(*elems.get(root.name, [none])) if root else none
    temps = [b for b, n in size.items() if n > 0 and b not in leaves]
    delta = [0.0] * (len(instrs) + 1)
    for b in temps:
        delta[born[b]] += size[b]
        delta[last[b] + 1] -= size[b]
    best, at, cur = 0.0, 0, 0.0
    for pos in range(len(instrs)):
        cur += delta[pos]
        here = cur + (calls[pos]["bytes"] if pos in calls else 0.0)
        if here > best:
            best, at = here, pos
    live = [(b[0], size[b]) + scope[b] for b in temps
            if born[b] <= at <= last[b]]
    if at in calls:
        outer = named[instrs[at].name]
        live += [(i, n, s or outer[0], outer[1] if outer[1] is not None
                  else x) for i, n, s, x in calls[at]["live"]]
    memo[cname] = {"bytes": best, "position": at, "n": len(instrs),
                   "instruction": instrs[at].name if instrs else None,
                   "live": live}
    return memo[cname]


def _first_backward(instrs) -> Optional[int]:
    """Where the backward begins in the Program's global block: the least
    index among the ``*_grad`` ops that left an instruction."""
    grads = [x for ins in instrs for s, x in (_scopes(ins.op_name),)
             if s and s.split("#")[0].endswith("_grad")]
    return min(grads) if grads else None


def live_set_from_hlo(comps, entry) -> dict:
    """Liveness over the scheduled optimized HLO
    (``attribution.parse_hlo_computations`` of ``as_text()``, or of an
    offline compile's text). The entry computation's order is the schedule
    (``is_scheduled=true``)."""
    return dict(_computation_peak(entry, comps, {}))


#: the live set is taken as the peak's where what it lists adds up to XLA's
#: temporaries
RECONCILED = (0.8, 1.25)


def _reserved_high_mark() -> Optional[float]:
    """The largest ``peak_bytes_reserved`` of this process's devices: what
    the runtime has reserved for a program's temporaries at most, so far.
    None where the allocator keeps none (the CPU)."""
    import jax
    marks = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        if "peak_bytes_reserved" in stats:
            marks.append(float(stats["peak_bytes_reserved"]))
    return max(marks, default=None)


def peak_live_set(label: str) -> Optional[dict]:
    """The buffers live where the step's temporaries are highest.

    ``label`` is the program label of a step this process compiled and still
    holds; None where there is no such step. Else ``{"program", "source",
    "temp_bytes", "peak_bytes", "coverage", "reconciled", "position":
    {"index", "of", "instruction"}, "first_backward", "buffers":
    [{"instruction", "bytes", "scope", "phase"}]}``, buffers largest first.
    ``scope`` is the innermost ``<op_type>#<idx>`` of the defining
    instruction's ``op_name``; ``phase`` is ``forward`` or ``backward`` by the
    op's place in the global block against the first ``*_grad`` op
    (``backward`` takes the gradient sums and the optimizer's ops), None
    without a scope. The one ``source`` is liveness over the scheduled HLO
    (``scheduled_hlo``: libtpu's ``memory_analysis()`` carries no
    buffer-assignment proto and the CPU backend's no heap trace, jaxlib
    0.9.0). ``coverage`` = listed bytes over XLA's ``temp_size_in_bytes``;
    ``reconciled`` says whether it lies in ``RECONCILED``, or, where it
    does not, whether the listed bytes over ``reserved_bytes`` do: the
    device's high mark of its reserved pool at the time of the call (None
    on the CPU), what the runtime did reserve for the largest program so
    far, which the train step is in every cell. XLA's own count stands
    above it where a step's work sits in ``while``s (a scan op's forward
    and backward: 4.842 GB counted, 3.887 reserved, 3.872 listed, PERF.md
    PR 57); a step that is not the process's largest reads low against the
    mark and stays unreconciled. Never called on a run's path: seconds for
    a large step."""
    handle = compiled_step(label)
    if handle is None:
        return None
    temp = float(handle.memory()["temp"])
    comps, entry, _ = parse_hlo_computations(handle.hlo_text())
    if not entry or not temp:
        return None
    first_backward = _first_backward(comps.get(entry, []))
    found = live_set_from_hlo(comps, entry)
    cover = found["bytes"] / temp
    reserved = _reserved_high_mark()

    def adds_up(share):
        return RECONCILED[0] <= share <= RECONCILED[1]

    def phase(idx):
        if idx is None or first_backward is None:
            return None if idx is None else "forward"
        return "backward" if idx >= first_backward else "forward"
    buffers = sorted(
        ({"instruction": i, "bytes": n, "scope": s, "phase": phase(x)}
         for i, n, s, x in found["live"]), key=lambda b: -b["bytes"])
    return {"program": label, "source": "scheduled_hlo",
            "temp_bytes": temp, "peak_bytes": found["bytes"],
            "coverage": cover, "reserved_bytes": reserved,
            "reconciled": adds_up(cover) or bool(reserved) and adds_up(
                found["bytes"] / reserved),
            "position": {"index": found["position"], "of": found["n"],
                         "instruction": found["instruction"]},
            "first_backward": first_backward, "buffers": buffers}
