"""The collectives of ONE kind of op on one device's plane: those whose
trace scope matches one of the spec's ``match`` globs (an expert layer's
exchange: ``moe_exchange*#*``), read as ``reducers/collective.py`` reads
all of them -- the collective ops of the op line and the collective spans
of the async line, a start-done pair from start to done.

``what: exposed_ms_per_step``: the part of their time in flight during
which no other op ran, in milliseconds for each step of the traced window.
``what: wire_share``: the bytes the spec's ``needs`` function
(``<module>:<function>`` under ``benchmark/``, the whole step's, all
chips') gives one chip, over the peak ``peak`` names in ``peaks.json``,
over their time in flight: a share of the interconnect's roofline. A share
above 100 is an error, not a result. None where there is no trace, on a
program without such a scope (a parent commit), and, for the share, without
a chip's peaks.
"""
import importlib
from fnmatch import fnmatchcase

from benchmark import trace as tr


#: benchmark/trace.py's list plus the opcode of jax.lax.ragged_all_to_all
OPCODES = tr.COLLECTIVE_OPCODES + ("ragged-all-to-all",)


def _collective_in_scopes(spec, ev, name) -> bool:
    ins = ev.hlo.get(tr.instruction(name))
    if ins is None or not any(fnmatchcase(ins.scope or "", g)
                              for g in spec["match"]):
        return False
    op = ins.opcode or ""
    for suffix in ("-start", "-done"):
        if op.endswith(suffix):
            op = op[:-len(suffix)]
    return op in OPCODES


def reduce(spec, ev):
    if ev.trace is None:
        return None
    lines = ev.trace.first_device()
    flight = tr.union(tr.spans_of(
        e for ln in (tr.OPS_LINE, tr.ASYNC_LINE) for e in lines.get(ln, [])
        if _collective_in_scopes(spec, ev, e[0])))
    if not tr.length(flight):
        return None
    if spec["what"] == "exposed_ms_per_step":
        others = tr.union(tr.spans_of(
            e for e in lines.get(tr.OPS_LINE, [])
            if not tr.is_collective(e[0], ev.hlo)
            and not _collective_in_scopes(spec, ev, e[0])))
        return tr.length(tr.subtract(flight, others)) / 1e6 / ev.traced_steps
    if ev.peaks is None:
        return None
    module, function = spec["needs"].split(":")
    need = getattr(importlib.import_module(f"benchmark.{module}"), function)(
        ev.cell["model"], ev.cell["params"])
    least = need["bytes"] / ev.cell["chips"] / ev.peaks[spec["peak"]]
    spent = tr.length(flight) / 1e9
    share = 100.0 * least * ev.traced_steps / spent
    ev.say(f"{spec['name']}: {spent * 1e3 / ev.traced_steps:.3f} ms in "
           f"flight a step, least possible {least * 1e3:.3f} ms at "
           f"{spec['peak']}")
    if share > 100.0:
        raise ValueError(f"{spec['name']} = {share:.1f}%: the need "
                         f"({spec['needs']}) or the match is wrong")
    return share
