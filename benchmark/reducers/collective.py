"""Collectives on one device's plane. ``what: time_share``: the time a
collective was in flight as a percentage of device busy time;
``what: exposed_ms_per_step``: the part of it during which no other op ran,
in milliseconds for each step of the traced window. None on one chip, where
there is no collective to read."""
from benchmark import trace as tr


def reduce(spec, ev):
    if ev.trace is None:
        return None
    lines = ev.trace.first_device()
    flight = tr.length(tr.collective_intervals(lines, ev.hlo))
    if not flight:
        return None
    if spec["what"] == "time_share":
        return 100.0 * flight / tr.busy_ns(lines)
    return tr.exposed_collective_ns(lines, ev.hlo) / 1e6 / ev.traced_steps
