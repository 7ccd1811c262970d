"""Median device duration of the train step's module (the module with the
most time on the ``XLA Modules`` line of the traced window), milliseconds."""
import statistics

from benchmark import trace as tr


def reduce(spec, ev):
    if ev.trace is None:
        return None
    _, runs = tr.step_module(ev.trace.first_device())
    return statistics.median(runs) / 1e6 if runs else None
