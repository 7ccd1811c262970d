"""Device time of the op events whose named scope matches one of the spec's
``match`` globs (``fused_attention*#*``), as a percentage of device busy
time. ``unattributed`` matches the events with no IR scope."""
from fnmatch import fnmatchcase

from benchmark import trace as tr


def reduce(spec, ev):
    if ev.trace is None:
        return None
    lines = ev.trace.first_device()
    busy = tr.busy_ns(lines)
    if not busy:
        return None
    hit = sum(ns for scope, ns in ev.trace.time_by_scope(ev.hlo).items()
              if any(fnmatchcase(scope, g) for g in spec["match"]))
    return 100.0 * hit / busy
