"""A labelled counter of the program's metrics registry, summed over the
children whose labels include the spec's ``labels`` (every compiled program
of the run: the counters of ``observability/`` are added at each compile).
None where the program has no such counter, as a parent commit that lacks
the op has not."""


def reduce(spec, ev):
    from paddle_tpu.observability.metrics import REGISTRY
    family = REGISTRY.get(spec["match"])
    if family is None:
        return None
    want = set(spec.get("labels", {}).items())
    found = [child.value for labels, child in family.items()
             if want <= set(labels)]
    return float(sum(found)) if found else None
