"""A kernel's share of its roofline: the least time the chip could take for
what the algorithm needs (``benchmark/flops.py:<need>``: the larger of
operations over peak FLOP/s and bytes over peak bytes/s) over the device time
of the kernel's events -- the ``custom_call_target`` events inside the scopes
``match`` names. A share above 100 is an error, not a result."""
from fnmatch import fnmatchcase

from benchmark import flops
from benchmark import trace as tr


def reduce(spec, ev):
    if ev.trace is None:
        return None
    spent = 0.0
    for name, a, b in ev.trace.first_device().get(tr.OPS_LINE, []):
        ins = ev.hlo.get(tr.instruction(name))
        if ins is not None and ins.target == spec["custom_call_target"] \
                and any(fnmatchcase(ins.scope or "", g)
                        for g in spec["match"]):
            spent += b - a
    if not spent:
        return None
    need = getattr(flops, spec["need"])(ev.cell["model"], ev.cell["params"])
    # one device's plane is read: under any layout it does its share
    need = {k: v / ev.cell["chips"] for k, v in need.items()}
    least, bound = flops.roofline_seconds(need, ev.peaks)
    share = 100.0 * least * ev.traced_steps / (spent / 1e9)
    ev.say(f"{spec['name']}: kernel time {spent / 1e6 / ev.traced_steps:.3f} "
           f"ms a step, least possible {least * 1e3:.3f} ms, bound by "
           f"{bound}")
    if share > 100.0:
        raise ValueError(f"{spec['name']} = {share:.1f}% of the roofline: "
                         f"the need in benchmark/flops.py or the match is "
                         f"wrong")
    return share
