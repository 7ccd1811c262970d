"""IR->HLO attribution: per-op cost breakdown of a compiled program.

The executor's ``trace_block`` wraps every op lowering in
``jax.named_scope("<op_type>#<op_idx>")``, so each instruction of the
optimized HLO module carries Program-IR identity in its ``op_name``
metadata (nested for control-flow sub-blocks; the innermost token is the
most precise).  This module walks ``executable.as_text()`` and buckets a
byte/FLOP/instruction-count model per IR op and per category:

- ``fusion``        -- fused loops/outputs (operand + output traffic, the
  same model XLA's cost analysis uses: fusion internals are free);
- ``layout``        -- copy / transpose / bitcast-convert churn inserted
  by layout assignment (the ROOFLINE copy-done tax, now attributable);
- ``collective``    -- all-reduce / all-gather / reduce-scatter / ...;
- ``dynamic-slice`` -- dynamic-(update-)slice gather/scatter traffic;
- ``compute``       -- dot / convolution;
- ``elementwise``   -- everything else that moves bytes;
- ``plumbing``      -- parameter/constant/tuple/bitcast (zero-byte).

Per-instruction bytes are modeled as operand sizes + output size (XLA's
``cost_analysis()`` on this jax is aggregate-only, so the per-instruction
split must come from the text); the aggregate is kept beside the model so
the model's own coverage is observable.  Copy/transpose bytes are blamed
on the (producer IR op, consumer IR op) pair that forced the round trip,
feeding the opt-in ``layout_churn`` analysis pass (PT060).

Everything here runs once per compile miss and only when armed
(``PADDLE_TPU_OBS=1``, ``PADDLE_TPU_OBS_ATTRIB=1``, or an armed
``bench.py --emit-hlo`` capture); obs-off means zero extra work on the
executor path, guard-tested.  ``python -m paddle_tpu.observability.
attribution A B`` (= ``tools/hlo_diff.py``) diffs two captured programs.
"""
from __future__ import annotations

import collections
import json
import os
import re
import weakref
from typing import Dict, List, Optional, Tuple

from .metrics import REGISTRY, MetricsRegistry

#: env override: arm the attribution walk without the full obs toggle
ATTRIB_ENV = "PADDLE_TPU_OBS_ATTRIB"

#: metric families owned by this module (per-program, category-labeled)
GAUGE_FAMILIES = ("hlo_op_bytes", "hlo_op_instructions",
                  "hlo_attributed_bytes_fraction")

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0, "tuple": 0,
}

#: opcodes whose bytes are modeled as zero (no memory traffic of their own)
_FREE_OPCODES = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "add-dependency"))

_LAYOUT_OPCODES = frozenset((
    "copy", "copy-start", "copy-done", "transpose", "bitcast-convert"))

_DSLICE_OPCODES = frozenset(("dynamic-slice", "dynamic-update-slice"))

_COMPUTE_OPCODES = frozenset(("dot", "convolution", "cholesky",
                              "triangular-solve"))

#: computations whose instructions ride their caller's cost (fusion bodies,
#: reduce/scatter/sort regions) are excluded from per-instruction counting
_SUBSUMING_REFS = ("calls", "to_apply")

_IR_TOKEN = re.compile(r"([A-Za-z0-9_.]+#\d+)")
_SHAPE_RE = re.compile(r"^([a-zA-Z0-9]+)\[([0-9,]*)\](?:\{[^}]*\})?")
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLEE_RE = re.compile(r"(calls|to_apply|body|condition)=\{?%?([\w.\-]+)")


def _category(opcode: str) -> str:
    if opcode == "fusion":
        return "fusion"
    if opcode in _LAYOUT_OPCODES:
        return "layout"
    if opcode.startswith("all-") or opcode.startswith("collective-") \
            or opcode.startswith("reduce-scatter"):
        return "collective"
    if opcode in _DSLICE_OPCODES:
        return "dynamic-slice"
    if opcode in _COMPUTE_OPCODES:
        return "compute"
    if opcode in _FREE_OPCODES:
        return "plumbing"
    return "elementwise"


def _shape_elems_bytes(shape: str) -> Tuple[float, float]:
    """(element count, byte size) of one non-tuple HLO shape string."""
    m = _SHAPE_RE.match(shape)
    if not m:
        return 0.0, 0.0
    dtype, dims = m.group(1), m.group(2)
    n = 1.0
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n, n * _DTYPE_BYTES.get(dtype, 4)


def _split_tuple(s: str) -> List[str]:
    """Top-level comma split of a parenthesized tuple body."""
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur).strip())
    return out


def shape_bytes(shape: str) -> float:
    """Byte size of an HLO shape string (tuples sum their leaves)."""
    shape = shape.strip()
    if shape.startswith("("):
        depth, end = 0, len(shape)
        for i, ch in enumerate(shape):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                end = i
                break
        return sum(shape_bytes(p) for p in _split_tuple(shape[1:end]))
    return _shape_elems_bytes(shape)[1]


def shape_elems(shape: str) -> float:
    shape = shape.strip()
    if shape.startswith("("):
        return 0.0
    return _shape_elems_bytes(shape)[0]


class HloInstruction:
    """One parsed instruction line of an HLO text dump."""

    __slots__ = ("name", "opcode", "shape", "operands", "rest", "op_name",
                 "is_root")

    def __init__(self, name, opcode, shape, operands, rest, op_name,
                 is_root):
        self.name = name
        self.opcode = opcode
        self.shape = shape          # output shape string
        self.operands = operands    # operand instruction names (same comp)
        self.rest = rest            # attrs after the operand list
        self.op_name = op_name      # metadata op_name ("" when absent)
        self.is_root = is_root

    def ir_op(self) -> Optional[str]:
        """Innermost ``<op_type>#<op_idx>`` token of the op_name scope."""
        toks = _IR_TOKEN.findall(self.op_name)
        return toks[-1] if toks else None


#: the Program ops that run a sub-block several times: an instruction
#: inside one carries its scope before its own op's
_LOOP_OPS = ("scan", "scan_grad")


def op_phase(op_name: str) -> Optional[Tuple[Optional[str], str]]:
    """``(loop, phase)`` of an instruction from its ``op_name`` metadata,
    None for one with no Program op's scope. ``loop``: the
    ``scan#<idx>`` / ``scan_grad#<idx>`` scope of the scan op the
    instruction was traced inside (its own op's ``<op_type>#<op_idx>`` is
    the innermost token still, which the per-op readers take), else None.
    ``phase``: ``recompute`` -- a forward op run again for the backward,
    inside a ``remat_segment``'s ``jax.checkpoint`` (JAX names the re-run
    ``rematted_computation``); ``backward`` -- inside a grad op, or the
    transpose of a loop's body (``transpose(jvp(...))``); else
    ``forward``. So the forward, the recomputed forward and the backward of
    one Program op inside a loop's sub-block are told apart in a device
    trace, where all three carry that op's scope."""
    tokens = _IR_TOKEN.findall(op_name)
    if not tokens:
        return None
    loop = next((t for t in tokens if t.split("#")[0] in _LOOP_OPS), None)
    if "rematted_computation" in op_name:
        return loop, "recompute"
    if "transpose(" in op_name or any(
            t.split("#")[0].endswith("_grad") for t in tokens):
        return loop, "backward"
    return loop, "forward"


def instruction_phases(text: str) -> Dict[str, Tuple[Optional[str], str]]:
    """``{instruction name: op_phase(its op_name)}`` over every computation
    of an HLO text dump, for the instructions that carry a Program op's
    scope."""
    out = {}
    for line in text.splitlines():
        m = _INSTR_RE.match(line)
        meta = _OPNAME_RE.search(line) if m else None
        found = op_phase(meta.group(1)) if meta else None
        if found is not None:
            out[m.group(1)] = found
    return out


def _parse_shape_prefix(rhs: str) -> Tuple[str, str]:
    """Split an instruction RHS into (output shape, remainder)."""
    rhs = rhs.strip()
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rhs[:i + 1], rhs[i + 1:].strip()
        return rhs, ""
    m = _SHAPE_RE.match(rhs)
    if not m:
        return "", rhs
    return rhs[:m.end()], rhs[m.end():].strip()


def _parse_call(rest: str) -> Tuple[str, str, str]:
    """(opcode, operand string, trailing attrs) of an instruction tail."""
    m = re.match(r"^([\w\-]+)\s*\(", rest)
    if not m:
        return rest.split(" ", 1)[0] if rest else "", "", ""
    opcode = m.group(1)
    depth, start = 0, m.end() - 1
    for i in range(start, len(rest)):
        depth += rest[i] == "("
        depth -= rest[i] == ")"
        if depth == 0:
            return opcode, rest[start + 1:i], rest[i + 1:]
    return opcode, rest[start + 1:], ""


def parse_hlo_computations(text: str) -> Tuple[
        Dict[str, List[HloInstruction]], Optional[str], Dict[str, set]]:
    """HLO text -> ({computation: [instructions]}, entry name,
    {computation: set of (caller opcode, ref kind) that reference it})."""
    comps: Dict[str, List[HloInstruction]] = {}
    refs: Dict[str, set] = {}
    entry = None
    cur: Optional[str] = None
    for line in text.splitlines():
        if cur is None:
            h = _HEADER_RE.match(line)
            if h:
                cur = h.group(2)
                comps[cur] = []
                if h.group(1):
                    entry = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        is_root = bool(re.match(r"^\s+ROOT\s", line))
        shape, rest = _parse_shape_prefix(rhs)
        opcode, operand_str, tail = _parse_call(rest)
        operands = re.findall(r"%([\w.\-]+)", operand_str)
        if not operands and operand_str:
            # newer dumps may omit the % sigil; resolve bare ids later
            # against the computation's instruction table
            operands = [tok for tok in
                        re.findall(r"(?<![\w.\-])([A-Za-z_][\w.\-]*)",
                                   operand_str)]
        mo = _OPNAME_RE.search(tail)
        comps[cur].append(HloInstruction(
            name, opcode, shape, operands, tail,
            mo.group(1) if mo else "", is_root))
        for kind, callee in _CALLEE_RE.findall(tail):
            refs.setdefault(callee, set()).add((opcode, kind))
        bm = re.search(r"branch_computations=\{([^}]*)\}", tail)
        if bm:
            for callee in re.findall(r"%?([\w.\-]+)", bm.group(1)):
                refs.setdefault(callee, set()).add((opcode, "branch"))
    return comps, entry, refs


def _counted_computations(comps, entry, refs) -> List[str]:
    """Computations whose instructions are accounted directly: the entry,
    while bodies/conditions and conditional branches -- NOT fusion bodies
    or reduce/scatter/sort regions (their cost rides the caller)."""
    out = []
    for name in comps:
        ref = refs.get(name)
        if name == entry or ref is None:
            if name == entry:
                out.append(name)
            continue
        if any(kind in _SUBSUMING_REFS for _, kind in ref):
            continue
        out.append(name)
    return out


def _model_flops(instr: HloInstruction, resolve) -> float:
    """Best-effort FLOP model per instruction (dot exact, convolution via
    dim_labels, reduce = input elems, elementwise = output elems)."""
    if instr.opcode == "dot":
        lhs = resolve(instr.operands[0]) if instr.operands else None
        if lhs is None:
            return 0.0
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", instr.rest)
        sm = _SHAPE_RE.match(lhs.shape)
        if not m or not sm:
            return 0.0
        dims = [int(d) for d in sm.group(2).split(",") if d]
        k = 1.0
        for idx in (int(i) for i in m.group(1).split(",") if i):
            if idx < len(dims):
                k *= dims[idx]
        return 2.0 * shape_elems(instr.shape) * k
    if instr.opcode == "convolution":
        ker = resolve(instr.operands[1]) if len(instr.operands) > 1 else None
        dm = re.search(r"dim_labels=[\w?]+_([\w?]+)->", instr.rest)
        if ker is None or not dm or "o" not in dm.group(1):
            return 0.0
        sm = _SHAPE_RE.match(ker.shape)
        if not sm:
            return 0.0
        dims = [int(d) for d in sm.group(2).split(",") if d]
        o_idx = dm.group(1).index("o")
        if o_idx >= len(dims) or not dims[o_idx]:
            return 0.0
        kprod = 1.0
        for d in dims:
            kprod *= d
        return 2.0 * shape_elems(instr.shape) * kprod / dims[o_idx]
    if instr.opcode in ("reduce", "reduce-window"):
        src = resolve(instr.operands[0]) if instr.operands else None
        return shape_elems(src.shape) if src is not None else 0.0
    if instr.opcode in _FREE_OPCODES or instr.opcode in _LAYOUT_OPCODES:
        return 0.0
    return shape_elems(instr.shape)


class ProgramAttribution:
    """Attribution result for one compiled program."""

    def __init__(self, label: str):
        self.label = label
        #: ir key ("conv2d#12" or the synthetic "<unattributed>") ->
        #: {"bytes", "flops", "instructions", "categories": {cat: bytes}}
        self.per_ir: Dict[str, dict] = {}
        #: category -> {"bytes", "instructions"}
        self.per_category: Dict[str, dict] = {}
        #: (producer ir, consumer ir) -> {"bytes", "instructions"}
        self.copy_pairs: Dict[Tuple[str, str], dict] = {}
        self.total_bytes = 0.0        # model total over counted instructions
        self.attributed_bytes = 0.0   # model bytes carrying an IR token
        self.model_flops = 0.0
        self.instruction_count = 0
        #: XLA cost_analysis() aggregate (None when unavailable)
        self.cost_bytes: Optional[float] = None
        self.cost_flops: Optional[float] = None

    @property
    def coverage(self) -> float:
        """Fraction of modeled bytes attributed to a named IR op."""
        return (self.attributed_bytes / self.total_bytes
                if self.total_bytes else 0.0)

    def top_ops(self, k: int = 10) -> List[Tuple[str, dict]]:
        return sorted(self.per_ir.items(),
                      key=lambda kv: -kv[1]["bytes"])[:k]

    def top_copy_pairs(self, k: int = 10) -> List[Tuple[Tuple[str, str],
                                                        dict]]:
        return sorted(self.copy_pairs.items(),
                      key=lambda kv: -kv[1]["bytes"])[:k]

    def to_dict(self) -> dict:
        return {
            "label": self.label,
            "total_bytes": self.total_bytes,
            "attributed_bytes": self.attributed_bytes,
            "coverage": self.coverage,
            "model_flops": self.model_flops,
            "instruction_count": self.instruction_count,
            "cost_bytes": self.cost_bytes,
            "cost_flops": self.cost_flops,
            "per_category": self.per_category,
            "per_ir": self.per_ir,
            "copy_pairs": [{"producer": p, "consumer": c, **v}
                           for (p, c), v in self.top_copy_pairs(64)],
        }

    @staticmethod
    def from_dict(d: dict) -> "ProgramAttribution":
        a = ProgramAttribution(d.get("label", "?"))
        a.total_bytes = float(d.get("total_bytes", 0.0))
        a.attributed_bytes = float(d.get("attributed_bytes", 0.0))
        a.model_flops = float(d.get("model_flops", 0.0))
        a.instruction_count = int(d.get("instruction_count", 0))
        a.cost_bytes = d.get("cost_bytes")
        a.cost_flops = d.get("cost_flops")
        a.per_category = dict(d.get("per_category", {}))
        a.per_ir = dict(d.get("per_ir", {}))
        for p in d.get("copy_pairs", []):
            a.copy_pairs[(p["producer"], p["consumer"])] = {
                "bytes": p["bytes"], "instructions": p["instructions"]}
        return a

    def summary_lines(self, top: int = 8) -> List[str]:
        lines = [f"program {self.label}: {self.instruction_count} "
                 f"instruction(s), model {_fmt_bytes(self.total_bytes)}"
                 + (f" (XLA cost_analysis "
                    f"{_fmt_bytes(self.cost_bytes)})"
                    if self.cost_bytes else "")
                 + f", {self.coverage:.1%} attributed to IR ops"]
        for cat, v in sorted(self.per_category.items(),
                             key=lambda kv: -kv[1]["bytes"]):
            lines.append(f"  {cat}: {_fmt_bytes(v['bytes'])} over "
                         f"{v['instructions']} instruction(s)")
        for ir, v in self.top_ops(top):
            cats = ",".join(sorted(v["categories"]))
            lines.append(f"  op {ir}: {_fmt_bytes(v['bytes'])} [{cats}]")
        for (p, c), v in self.top_copy_pairs(3):
            lines.append(f"  layout round-trip {p} -> {c}: "
                         f"{_fmt_bytes(v['bytes'])} in "
                         f"{v['instructions']} copy/transpose(s)")
        return lines


def _fmt_bytes(v: Optional[float]) -> str:
    if v is None:
        return "?"
    return (f"{v / 1e9:.3f} GB" if v >= 1e9 else
            f"{v / 1e6:.3f} MB" if v >= 1e6 else
            f"{v / 1e3:.1f} KB" if v >= 1e3 else f"{v:.0f} B")


def _chase_up(instr: Optional[HloInstruction], table,
              depth: int = 8) -> Optional[str]:
    """Nearest IR token upstream of ``instr`` (BFS over operands --
    metadata-stripped rewrites inherit from their producers); "input"
    when every path dead-ends in parameters, None when nothing named is
    reachable."""
    if instr is None:
        return None
    seen, frontier, all_params = set(), [instr], True
    while frontier and depth:
        nxt = []
        for x in frontier:
            ir = x.ir_op()
            if ir:
                return ir
            if x.opcode != "parameter":
                all_params = False
            for o in x.operands:
                if o in table and o not in seen:
                    seen.add(o)
                    nxt.append(table[o])
        frontier = nxt
        depth -= 1
    return "input" if all_params else None


def _chase_down(instr: Optional[HloInstruction], users, depth: int = 4
                ) -> Optional[str]:
    """Nearest IR token downstream (BFS over users); "output" when the
    instruction feeds only the ROOT, None otherwise."""
    if instr is None:
        return None
    seen, frontier = set(), [instr]
    at_root = instr.is_root
    while frontier and depth:
        nxt = []
        for x in frontier:
            ir = x.ir_op()
            if ir:
                return ir
            at_root = at_root or x.is_root
            for u in users.get(x.name, []):
                if u.name not in seen:
                    seen.add(u.name)
                    nxt.append(u)
        frontier = nxt
        depth -= 1
    return "output" if at_root else None


def _chase_down_users_only(instr: HloInstruction, users,
                           depth: int = 4) -> Optional[str]:
    """_chase_down starting below ``instr`` -- used when the layout copy
    itself inherited the producer's metadata and would otherwise name
    itself as its own consumer."""
    for u in users.get(instr.name, []):
        got = _chase_down(u, users, depth)
        if got is not None:
            return got
    return "output" if instr.is_root else None


def attribute_hlo_text(text: str, label: str = "program"
                       ) -> ProgramAttribution:
    """Walk one HLO text dump into a ProgramAttribution (pure, no jax)."""
    comps, entry, refs = parse_hlo_computations(text)
    attrib = ProgramAttribution(label)
    for comp_name in _counted_computations(comps, entry, refs):
        instrs = comps[comp_name]
        table = {i.name: i for i in instrs}
        users: Dict[str, List[HloInstruction]] = {}
        for i in instrs:
            for opnd in i.operands:
                if opnd in table:
                    users.setdefault(opnd, []).append(i)

        def resolve(name):
            return table.get(name)

        for i in instrs:
            cat = _category(i.opcode)
            out_b = shape_bytes(i.shape)
            if i.opcode in _FREE_OPCODES:
                nbytes = 0.0
            else:
                nbytes = out_b + sum(
                    shape_bytes(table[o].shape) for o in i.operands
                    if o in table)
            flops = _model_flops(i, resolve)
            attrib.instruction_count += 1
            attrib.total_bytes += nbytes
            attrib.model_flops += flops
            c = attrib.per_category.setdefault(
                cat, {"bytes": 0.0, "instructions": 0})
            c["bytes"] += nbytes
            c["instructions"] += 1
            ir = i.ir_op()
            if ir is None:
                # metadata-stripped rewrite (layout copies, simplified
                # convs, ...): inherit the nearest named neighbour
                chased = _chase_up(i, table) or _chase_down(i, users)
                if chased not in (None, "input", "output"):
                    ir = chased
            if ir is not None:
                attrib.attributed_bytes += nbytes
            key = ir or "<unattributed>"
            e = attrib.per_ir.setdefault(
                key, {"bytes": 0.0, "flops": 0.0, "instructions": 0,
                      "categories": {}})
            e["bytes"] += nbytes
            e["flops"] += flops
            e["instructions"] += 1
            e["categories"][cat] = e["categories"].get(cat, 0.0) + nbytes

            if cat == "layout" and nbytes > 0:
                # blame the round trip on the (producer, consumer) IR op
                # pair; the copy's own inherited metadata is skipped so
                # the pair names the ops on either side of it
                producer = _chase_up(table.get(i.operands[0])
                                     if i.operands else None,
                                     table) or "<unattributed>"
                consumer = _chase_down(i, users) if i.ir_op() is None \
                    else (_chase_down_users_only(i, users)
                          or ("output" if i.is_root else "<unattributed>"))
                if consumer is None:
                    consumer = "<unattributed>"
                p = attrib.copy_pairs.setdefault(
                    (producer, consumer), {"bytes": 0.0, "instructions": 0})
                p["bytes"] += nbytes
                p["instructions"] += 1
    return attrib


# ------------------------------------------------------------- executor --
# Compile-time hook: gauges + IR store + optional artifact capture.

#: (id(program), version) -> (weakref to program, ProgramAttribution);
#: read by the PT060 layout_churn analysis pass (bounded, insertion LRU)
_IR_STORE: "collections.OrderedDict" = collections.OrderedDict()
_IR_STORE_CAP = 64

#: armed --emit-hlo capture directory (None = disarmed)
_capture_dir: Optional[str] = None
_warned_labels: set = set()


def attribution_enabled() -> bool:
    """Is the compile-time attribution walk armed?  True under the obs
    toggle, the dedicated PADDLE_TPU_OBS_ATTRIB toggle, or an armed
    --emit-hlo capture."""
    from . import journal as _journal
    if _capture_dir is not None:
        return True
    if _journal.env_truthy(ATTRIB_ENV):
        return True
    return _journal.enabled()


def arm_capture(directory: Optional[str]) -> None:
    """Arm (or disarm with None) HLO artifact capture: every subsequent
    compile miss writes ``hlo_<label>.json`` (HLO text + attribution) into
    ``directory`` -- what ``bench.py --emit-hlo`` turns on."""
    global _capture_dir
    if directory is not None:
        os.makedirs(directory, exist_ok=True)
    _capture_dir = directory


def capture_dir() -> Optional[str]:
    return _capture_dir


def _safe_label(label: str) -> str:
    return re.sub(r"[^\w.\-]+", "_", label)


def signature_digest(sig) -> str:
    """Stable 8-hex digest of a feed signature -- gauge labels must be
    reproducible across processes (``hash()`` is salted per run)."""
    import hashlib
    return hashlib.md5(repr(sig).encode()).hexdigest()[:8]


def record_program(program_ir, attrib: ProgramAttribution) -> None:
    if program_ir is None:
        return
    key = (id(program_ir), getattr(program_ir, "_version", 0))
    try:
        ref = weakref.ref(program_ir)
    except TypeError:
        ref = (lambda p: (lambda: p))(program_ir)
    _IR_STORE[key] = (ref, attrib)
    while len(_IR_STORE) > _IR_STORE_CAP:
        _IR_STORE.popitem(last=False)


def lookup_program(program_ir) -> Optional[ProgramAttribution]:
    """Attribution recorded at compile time for this exact Program object
    (identity + version checked; None when it was never compiled with
    attribution armed)."""
    key = (id(program_ir), getattr(program_ir, "_version", 0))
    ent = _IR_STORE.get(key)
    if ent is None:
        return None
    ref, attrib = ent
    return attrib if ref() is program_ir else None


def update_attribution_gauges(attrib: ProgramAttribution,
                              registry: Optional[MetricsRegistry] = None
                              ) -> None:
    """Export one attribution as per-category gauges under its label."""
    registry = registry or REGISTRY
    for cat, v in attrib.per_category.items():
        registry.gauge("hlo_op_bytes",
                       "modeled HLO bytes per step by instruction category "
                       "(operand+output traffic; fusion internals free)",
                       program=attrib.label, category=cat
                       ).set(v["bytes"])
        registry.gauge("hlo_op_instructions",
                       "optimized-HLO instruction count by category",
                       program=attrib.label, category=cat
                       ).set(v["instructions"])
    registry.gauge("hlo_attributed_bytes_fraction",
                   "fraction of modeled HLO bytes attributed to a named "
                   "Program-IR op (named_scope metadata coverage)",
                   program=attrib.label).set(attrib.coverage)


def retire_program(label: str,
                   registry: Optional[MetricsRegistry] = None) -> None:
    """Drop every attribution series for one program label (cache eviction
    / executor close -- mirrors the PR-1 cost-gauge retirement, but
    label-subset-aware because of the extra ``category`` label)."""
    registry = registry or REGISTRY
    for fname in GAUGE_FAMILIES:
        fam = registry.get(fname)
        if fam is None:
            continue
        with fam._lock:
            for key in [k for k in fam.children
                        if ("program", label) in k]:
                fam.children.pop(key, None)


def compute(compiled, label: str = "program"
            ) -> Optional[ProgramAttribution]:
    """Attribution for a compiled step / jax executable; None (with a
    one-shot warning) when the backend can't dump HLO text."""
    exe = getattr(compiled, "executable", None)
    if exe is None and hasattr(compiled, "as_text"):
        exe = compiled
    if exe is None:
        return None
    try:
        texts = exe.as_text()
    except Exception as e:
        if label not in _warned_labels:
            _warned_labels.add(label)
            import warnings
            warnings.warn(
                f"HLO attribution unavailable for {label}: as_text() "
                f"failed on this backend ({e!r}); hlo_op_bytes gauges and "
                f"--emit-hlo artifacts are skipped", RuntimeWarning)
        return None
    if isinstance(texts, (list, tuple)):
        texts = "\n".join(str(t) for t in texts)
    attrib = attribute_hlo_text(str(texts), label=label)
    try:
        from .cost import normalize_cost
        ca = normalize_cost(exe.cost_analysis())
        if ca is not None:
            attrib.cost_bytes = ca["bytes_accessed"]
            attrib.cost_flops = ca["flops"]
    except Exception:
        pass
    attrib._hlo_text = str(texts)
    return attrib


def on_compile(compiled, program_ir, label: str,
               registry: Optional[MetricsRegistry] = None
               ) -> Optional[ProgramAttribution]:
    """Executor/Predictor compile-miss hook.  Computes the attribution walk
    once (cached on the compiled object), exports gauges, records the IR
    store for the PT060 pass, journals a summary, and writes the capture
    artifact when armed.  No-op when disarmed; never raises."""
    try:
        if not attribution_enabled():
            return None
        attrib = getattr(compiled, "_attribution", False)
        if attrib is False:
            attrib = compute(compiled, label)
            try:
                compiled._attribution = attrib
            except Exception:
                pass
        if attrib is None:
            return None
        update_attribution_gauges(attrib, registry)
        record_program(program_ir, attrib)
        from . import journal as _journal
        _journal.emit({
            "event": "attribution", "program": label,
            "instructions": attrib.instruction_count,
            "model_bytes": attrib.total_bytes,
            "cost_bytes": attrib.cost_bytes,
            "coverage": round(attrib.coverage, 4),
            "categories": {c: v["bytes"]
                           for c, v in attrib.per_category.items()},
            "top_ops": [{"ir": k, "bytes": v["bytes"]}
                        for k, v in attrib.top_ops(5)],
            "copy_pairs": [{"producer": p, "consumer": c,
                            "bytes": v["bytes"], "n": v["instructions"]}
                           for (p, c), v in attrib.top_copy_pairs(3)],
        })
        if _capture_dir is not None:
            path = os.path.join(_capture_dir,
                                f"hlo_{_safe_label(label)}.json")
            with open(path, "w") as f:
                json.dump({"label": label,
                           "hlo": getattr(attrib, "_hlo_text", ""),
                           "attribution": attrib.to_dict()}, f)
        return attrib
    except Exception:
        return None


# ----------------------------------------------------------------- diff --

def diff_attributions(a: ProgramAttribution, b: ProgramAttribution) -> dict:
    """Structural delta B - A: per-category instruction/byte deltas plus
    the top grown/new/removed IR ops (what hlo_diff renders)."""
    cats = sorted(set(a.per_category) | set(b.per_category))
    cat_rows = []
    for c in cats:
        va = a.per_category.get(c, {"bytes": 0.0, "instructions": 0})
        vb = b.per_category.get(c, {"bytes": 0.0, "instructions": 0})
        cat_rows.append({
            "category": c,
            "instructions_a": va["instructions"],
            "instructions_b": vb["instructions"],
            "instructions_delta": vb["instructions"] - va["instructions"],
            "bytes_a": va["bytes"], "bytes_b": vb["bytes"],
            "bytes_delta": vb["bytes"] - va["bytes"]})
    grown = []
    for ir in set(a.per_ir) | set(b.per_ir):
        ba = a.per_ir.get(ir, {}).get("bytes", 0.0)
        bb = b.per_ir.get(ir, {}).get("bytes", 0.0)
        if bb != ba:
            grown.append({"ir": ir, "bytes_a": ba, "bytes_b": bb,
                          "delta": bb - ba,
                          "status": ("new" if ir not in a.per_ir else
                                     "removed" if ir not in b.per_ir
                                     else "changed")})
    grown.sort(key=lambda g: -abs(g["delta"]))
    return {"a": a.label, "b": b.label,
            "total_bytes_a": a.total_bytes, "total_bytes_b": b.total_bytes,
            "instructions_a": a.instruction_count,
            "instructions_b": b.instruction_count,
            "categories": cat_rows, "ops": grown}


def format_diff(d: dict, top: int = 8) -> str:
    lines = [f"hlo_diff: {d['a']} -> {d['b']}",
             f"  instructions {d['instructions_a']} -> "
             f"{d['instructions_b']} "
             f"({d['instructions_b'] - d['instructions_a']:+d}), "
             f"model bytes {_fmt_bytes(d['total_bytes_a'])} -> "
             f"{_fmt_bytes(d['total_bytes_b'])}",
             "  per category (instr a->b, bytes a->b):"]
    for r in d["categories"]:
        lines.append(
            f"    {r['category']:<13} {r['instructions_a']:>5} -> "
            f"{r['instructions_b']:<5} ({r['instructions_delta']:+d})   "
            f"{_fmt_bytes(r['bytes_a'])} -> {_fmt_bytes(r['bytes_b'])} "
            f"({'+' if r['bytes_delta'] >= 0 else '-'}"
            f"{_fmt_bytes(abs(r['bytes_delta']))})")
    shown = [g for g in d["ops"]][:top]
    if shown:
        lines.append(f"  top {len(shown)} changed IR ops by |byte delta|:")
        for g in shown:
            lines.append(
                f"    {g['ir']:<28} {_fmt_bytes(g['bytes_a'])} -> "
                f"{_fmt_bytes(g['bytes_b'])} [{g['status']}]")
    else:
        lines.append("  no per-op byte deltas (structurally identical "
                     "under the model)")
    return "\n".join(lines)


def load_artifact(path: str) -> ProgramAttribution:
    """Load one comparand: a ``--emit-hlo`` JSON artifact or a raw HLO
    text dump (auto-detected)."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except ValueError:
        return attribute_hlo_text(text, label=os.path.basename(path))
    if isinstance(doc, dict) and doc.get("hlo"):
        a = attribute_hlo_text(doc["hlo"],
                               label=doc.get("label",
                                             os.path.basename(path)))
        return a
    if isinstance(doc, dict) and "attribution" in doc:
        return ProgramAttribution.from_dict(doc["attribution"])
    raise ValueError(f"{path}: neither an HLO text dump nor an "
                     f"--emit-hlo artifact")


# ------------------------------------------------------------- selftest --

_SELFTEST_HLO_A = """\
HloModule selftest_a

ENTRY %main.1 (Arg_0.1: f32[64,128], Arg_1.2: f32[128,256]) -> f32[64,256] {
  %Arg_0.1 = f32[64,128]{1,0} parameter(0)
  %Arg_1.2 = f32[128,256]{1,0} parameter(1)
  %dot.3 = f32[64,256]{1,0} dot(f32[64,128]{1,0} %Arg_0.1, f32[128,256]{1,0} %Arg_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jit(main)/matmul#0/dot_general"}
  ROOT %exp.4 = f32[64,256]{1,0} exponential(f32[64,256]{1,0} %dot.3), metadata={op_name="jit(f)/jit(main)/exp#1/exp"}
}
"""

_SELFTEST_HLO_B = """\
HloModule selftest_b

ENTRY %main.1 (Arg_0.1: f32[64,128], Arg_1.2: f32[128,256]) -> f32[256,64] {
  %Arg_0.1 = f32[64,128]{1,0} parameter(0)
  %Arg_1.2 = f32[128,256]{1,0} parameter(1)
  %dot.3 = f32[64,256]{1,0} dot(f32[64,128]{1,0} %Arg_0.1, f32[128,256]{1,0} %Arg_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jit(main)/matmul#0/dot_general"}
  %exp.4 = f32[64,256]{1,0} exponential(f32[64,256]{1,0} %dot.3), metadata={op_name="jit(f)/jit(main)/exp#1/exp"}
  %transpose.5 = f32[256,64]{0,1} transpose(f32[64,256]{1,0} %exp.4), dimensions={1,0}, metadata={op_name="jit(f)/jit(main)/transpose2#2/transpose"}
  ROOT %copy.6 = f32[256,64]{1,0} copy(f32[256,64]{0,1} %transpose.5), metadata={op_name="jit(f)/jit(main)/transpose2#2/transpose"}
}
"""


def selftest() -> int:
    """Pin the parser + diff on two synthetic programs whose only delta is
    an injected transpose->copy layout round-trip (the smoke CI gate;
    hermetic, no jax)."""
    a = attribute_hlo_text(_SELFTEST_HLO_A, "A")
    b = attribute_hlo_text(_SELFTEST_HLO_B, "B")
    assert a.per_category.get("compute", {}).get("bytes", 0) > 0, \
        "selftest: dot not counted"
    assert a.coverage > 0.99, f"selftest: coverage {a.coverage} on A"
    assert "layout" not in a.per_category, "selftest: phantom layout in A"
    lb = b.per_category.get("layout", {})
    # transpose + copy, each 2 * 64*256*4 bytes of operand+output traffic
    assert lb.get("instructions") == 2 and lb.get("bytes") == 4 * 65536, \
        f"selftest: layout bucket wrong: {lb}"
    assert ("transpose2#2", "output") in b.copy_pairs and \
        ("exp#1", "transpose2#2") in b.copy_pairs, \
        f"selftest: copy blame wrong: {b.copy_pairs}"
    d = diff_attributions(a, b)
    cat = {r["category"]: r for r in d["categories"]}
    assert cat["layout"]["instructions_delta"] == 2 and \
        cat["layout"]["bytes_delta"] == 4 * 65536, \
        f"selftest: diff layout delta wrong: {cat['layout']}"
    top = d["ops"][0]
    assert top["ir"] == "transpose2#2" and top["status"] == "new", \
        f"selftest: top grown op wrong: {top}"
    text = format_diff(d)
    assert "transpose2#2" in text and "layout" in text
    # dot flop model: 2 * 64 * 256 * 128
    assert a.model_flops >= 2 * 64 * 256 * 128, \
        f"selftest: flops model {a.model_flops}"
    print("hlo_diff selftest: OK")
    return 0


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.observability.attribution",
        description="diff two captured HLO programs (bench.py --emit-hlo "
                    "artifacts or raw as_text() dumps): per-category "
                    "instruction/byte deltas with IR-op attribution")
    ap.add_argument("a", nargs="?", help="baseline artifact / HLO text")
    ap.add_argument("b", nargs="?", help="candidate artifact / HLO text")
    ap.add_argument("--top", type=int, default=8,
                    help="changed IR ops to show (default 8)")
    ap.add_argument("--json", action="store_true",
                    help="emit the raw diff dict as JSON")
    ap.add_argument("--summary", action="store_true",
                    help="also print each side's per-op summary")
    ap.add_argument("--selftest", action="store_true")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.a or not args.b:
        ap.error("need two artifacts to diff (or --selftest)")
    try:
        a, b = load_artifact(args.a), load_artifact(args.b)
    except (OSError, ValueError) as e:
        print(f"error: {e}")
        return 2
    d = diff_attributions(a, b)
    if args.json:
        print(json.dumps(d, indent=2, sort_keys=True))
        return 0
    if args.summary:
        for side in (a, b):
            print("\n".join(side.summary_lines()))
    print(format_diff(d, top=args.top))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
