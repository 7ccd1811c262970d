"""XLA's own cost estimate of a compiled step, by Program op.

    JAX_PLATFORMS=cpu python3 benchmark/offline_compile.py --hlo-dir DIR <cell>
    python -m tools.hlo_cycles DIR/<cell>.hlo.txt [--top N] [--by type|scope] [--json]

Reads optimized TPU HLO text (``offline_compile.py --hlo-dir``'s, or a
compiled step's ``as_text()``) and sums the ``estimated_cycles`` that the TPU
backend writes into each fusion's ``backend_config``, by the innermost
``<op_type>#<idx>`` scope of the instruction's ``op_name``: first by op type
or by scope, then the N largest instructions with their ``iteration_bounds``
and output shape. A ``while`` is the sum of its body times its
trip count (``known_trip_count``, else the bound its condition compares a
counter with, else 1), under the ``while``'s own
scope where a body instruction has none; a ``call`` / ``conditional`` counts
each callee once.

Costs no chip time and is no chip number: Mosaic kernels (``custom-call``)
carry no estimate, and the estimate is in cycles of XLA's cost model, not
milliseconds. It ranks forms of one program as the chip does (PERF.md
section 6, PR 36 and PR 40: 0.46-0.66 ms a million cycles by op type on a
v5e), which is what it is for: sizing a change before a chip call.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional

from paddle_tpu.observability.attribution import parse_hlo_computations
from paddle_tpu.observability.memory import _scopes

_CYCLES_RE = re.compile(r'"estimated_cycles":"(\d+)"')
_BOUNDS_RE = re.compile(r'"iteration_bounds":\[([^\]]*)\]')
_TRIPS_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_INT_CONSTANT_RE = re.compile(
    r"%?([\w.\-]+) = [su]\d+\[\]\S* constant\((\d+)\)")
_CALLEES_RE = re.compile(
    r"(?:body|to_apply|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


class Costed(NamedTuple):
    cycles: int          # estimated_cycles x the trips of the loops around it
    name: str            # instruction
    computation: str
    scope: Optional[str]     # innermost "<op_type>#<idx>", None without one
    trips: int
    bounds: str          # iteration_bounds, "" where the backend gave none
    shape: str           # output shape, layout and tiling stripped


def _plain_shape(shape: str) -> str:
    return re.sub(r"\{[^{}]*\}", "", shape)


def _trip_count(loop, comps, constants) -> int:
    """Trips of a ``while``: its ``known_trip_count`` where the text states
    one, else the constant its condition's root compares a counter below
    (a ``fori_loop`` from 0, as the TPU backend prints it), else 1."""
    n = _TRIPS_RE.search(loop.rest)
    if n:
        return int(n.group(1))
    cond = re.search(r"condition=%?([\w.\-]+)", loop.rest)
    root = next((i for i in comps.get(cond.group(1), []) if i.is_root),
                None) if cond else None
    if root is None or root.opcode != "compare" \
            or "direction=LT" not in root.rest:
        return 1
    return next((constants[o] for o in root.operands if o in constants), 1)


def costed_instructions(text: str) -> List[Costed]:
    """Every instruction of ``text`` that carries an ``estimated_cycles``,
    reached from the entry computation through ``while`` bodies (times their
    trip count), calls and conditional branches."""
    comps, entry, _ = parse_hlo_computations(text)
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")
    # the parser keeps operand names, not literals: scalar integer constants
    constants = {name: int(v) for name, v in _INT_CONSTANT_RE.findall(text)}
    out: List[Costed] = []

    def walk(cname: str, trips: int, outer_scope: Optional[str]):
        for ins in comps.get(cname, []):
            scope = _scopes(ins.op_name)[0] or outer_scope
            if ins.opcode == "while":
                body = re.search(r"body=%?([\w.\-]+)", ins.rest)
                if body:
                    walk(body.group(1), trips * _trip_count(ins, comps, constants), scope)
                continue
            if ins.opcode in ("call", "conditional"):
                callees = _CALLEES_RE.findall(ins.rest)
                for group in _BRANCHES_RE.findall(ins.rest):
                    callees += re.findall(r"%?([\w.\-]+)", group)
                for callee in callees:
                    walk(callee, trips, scope)
                continue
            m = _CYCLES_RE.search(ins.rest)
            if not m:
                continue
            b = _BOUNDS_RE.search(ins.rest)
            out.append(Costed(
                int(m.group(1)) * trips, ins.name, cname, scope, trips,
                "[" + b.group(1).replace('"', "") + "]" if b else "",
                _plain_shape(ins.shape)))

    walk(entry, 1, None)
    return out


def summarize(text: str, by: str = "type", top: int = 10) -> dict:
    """``{"total", "by": {key: cycles}, "top": [Costed as dict]}``; ``by`` is
    ``"type"`` (``mul_grad``) or ``"scope"`` (``mul_grad#60``), an
    instruction without a scope under ``"(no scope)"``."""
    items = costed_instructions(text)
    sums: Dict[str, int] = defaultdict(int)
    for it in items:
        key = it.scope or "(no scope)"
        sums[key.split("#")[0] if by == "type" else key] += it.cycles
    ranked = sorted(items, key=lambda it: -it.cycles)[:top]
    return {"total": sum(sums.values()),
            "by": dict(sorted(sums.items(), key=lambda kv: -kv[1])),
            "top": [it._asdict() for it in ranked]}


def render(summary: dict, rows: int = 20) -> str:
    total = summary["total"] or 1
    lines = [f"estimated_cycles, entry computation: "
             f"{summary['total'] / 1e6:.2f} M"]
    for key, c in list(summary["by"].items())[:rows]:
        lines.append(f"  {c / 1e6:9.2f} M  {100 * c / total:5.1f}%  {key}")
    lines.append("largest instructions:")
    for it in summary["top"]:
        trips = f" x{it['trips']} trips" if it["trips"] > 1 else ""
        lines.append(
            f"  {it['cycles'] / 1e6:9.2f} M  {it['name']} "
            f"({it['scope'] or 'no scope'}){trips} {it['bounds']} "
            f"{it['shape']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("hlo", help="optimized HLO text of one compiled step")
    ap.add_argument("--top", type=int, default=10,
                    help="largest instructions to list")
    ap.add_argument("--by", choices=("type", "scope"), default="type")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    with open(args.hlo) as f:
        summary = summarize(f.read(), args.by, args.top)
    print(json.dumps(summary) if args.json else render(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
