"""``registry_count`` over every counter family whose name matches the
spec's ``match`` glob (``*_lowering_total``): the children whose labels
include the spec's ``labels``, summed over the families and the run's
compiled programs. None where no family has such a child, as on a parent
commit whose lowerings do not give the label."""
from fnmatch import fnmatchcase


def reduce(spec, ev):
    from paddle_tpu.observability.metrics import REGISTRY
    want = set(spec.get("labels", {}).items())
    found = [child.value
             for family in REGISTRY.collect()
             if fnmatchcase(family.name, spec["match"])
             for labels, child in family.items() if want <= set(labels)]
    return float(sum(found)) if found else None
