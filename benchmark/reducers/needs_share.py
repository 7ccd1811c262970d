"""A share of a roofline or of the chip's peak whose need is a closed form
outside ``benchmark/flops.py``: ``needs`` names it as ``<module>:<function>``
under ``benchmark/`` (``needs_olmoe:moe_expert_matmul``). ``over`` says what
the need is held against:

- ``scope_events``: the device time of the op events inside the scopes
  ``match`` names -- only those whose ``custom_call_target`` is the spec's,
  where it gives one; every event, kernel or XLA alike, where it does not.
  The least time is the larger of operations over peak FLOP/s and bytes over
  peak bytes/s, as ``roofline_share`` has it.
- ``step_module``: the train step's median device time (``module_ms``); the
  least time is the need's FLOPs over peak FLOP/s.

A share above 100 is an error, not a result. None where there is no trace,
no such event, or no chip (a rehearsal has no peaks).
"""
import importlib
import statistics
from fnmatch import fnmatchcase

from benchmark import flops
from benchmark import trace as tr


def _spent_in_scopes(spec, ev) -> float:
    target = spec.get("custom_call_target")
    spent = 0.0
    for name, a, b in ev.trace.first_device().get(tr.OPS_LINE, []):
        ins = ev.hlo.get(tr.instruction(name))
        if ins is None or (target and ins.target != target):
            continue
        if any(fnmatchcase(ins.scope or "", g) for g in spec["match"]):
            spent += b - a
    return spent / 1e9


def reduce(spec, ev):
    if ev.trace is None or ev.peaks is None:    # no chip: no peak to hold
        return None
    module, function = spec["needs"].split(":")
    need = getattr(importlib.import_module(f"benchmark.{module}"), function)(
        ev.cell["model"], ev.cell["params"])
    # one device's plane is read: under any layout it does its share
    need = {k: v / ev.cell["chips"] for k, v in need.items()}
    if spec["over"] == "step_module":
        _, runs = tr.step_module(ev.trace.first_device())
        if not runs:
            return None
        spent = statistics.median(runs) / 1e9 * ev.traced_steps
        least, bound = need["flops"] / ev.peaks["bf16_flops_per_s"], "flops"
    else:
        spent = _spent_in_scopes(spec, ev)
        least, bound = flops.roofline_seconds(need, ev.peaks)
    if not spent:
        return None
    share = 100.0 * least * ev.traced_steps / spent
    ev.say(f"{spec['name']}: {spent * 1e3 / ev.traced_steps:.3f} ms a step, "
           f"least possible {least * 1e3:.3f} ms, bound by {bound}")
    if share > 100.0:
        raise ValueError(f"{spec['name']} = {share:.1f}%: the need "
                         f"({spec['needs']}) or the match is wrong")
    return share
