"""A counter of set-up: ``match`` names a key of the compile watch
(``backend_s``, ``backend_compiles``, ``cache_hits``, ``cache_misses``)."""


def reduce(spec, ev):
    return float(ev.setup_counters[spec["match"]])
