"""Seconds the program spent, since the process began, in the phases
``match`` names (category ``cat``): the sum its ``phase_seconds`` histogram
holds at the end of the run, which survives a wrapped span ring. For phases
of the compile path that is their time during set-up, because ``correct``
forbids a compile inside the windows. None where the program records no
such phase."""
from benchmark import probe_spans


def reduce(spec, ev):
    return probe_spans.phase_seconds(spec["match"], spec["cat"])
