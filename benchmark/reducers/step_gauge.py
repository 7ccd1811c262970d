"""Gauges the program sets for ONE compiled program, the train step: the
program whose ``program_compile_seq`` is highest, i.e. the last compile miss
of the process (``correct`` forbids a compile inside the windows, and every
job compiles its train step last: startup program, the check's test clone,
the train step). ``registry_count`` sums over the run's programs; memory must
pick one.

``sum`` lists the terms, each ``{"match": <gauge>, "labels": {...}}``; a term
the program did not set is left out (the CPU backend keeps no reserved pool),
and where none of them is set ``else`` is tried. ``scale`` multiplies the sum
(1e-9: bytes to GB). None where the program numbers no compiles, as a parent
commit without the gauge does not, and where the program picked takes in no
optimizer state: a train step always has accumulators, so that one is
another program (an eval clone or a second feed shape compiled later). The
pick is said once a run, with its ``program_temp_bytes``, to be held against
the line ``XLA's analysis of the train step``.
"""

_SAID = set()


def train_step_label(say=None):
    """The ``program`` label of the last compile miss; None without one, or
    where that program holds no optimizer state."""
    from paddle_tpu.observability.metrics import REGISTRY
    family = REGISTRY.get("program_compile_seq")
    if family is None:
        return None
    seqs = [(child.value, dict(labels)["program"])
            for labels, child in family.items()]
    if not seqs:
        return None
    seq, label = max(seqs)
    optimizer = gauge("program_state_bytes", program=label,
                      **{"class": "optimizer"})
    if say is not None and label not in _SAID:
        _SAID.add(label)
        say(f"memory.*: the train step is taken to be program {label}, "
            f"compile miss {seq:g} of the process and its last: "
            f"program_temp_bytes {gauge('program_temp_bytes', program=label)}"
            f", optimizer state {optimizer} bytes"
            + ("" if optimizer else ": no train step, nothing is reported"))
    return label if optimizer else None


def gauge(name: str, **labels):
    """The value of one labelled gauge, or None where it was never set."""
    from paddle_tpu.observability.metrics import REGISTRY
    family = REGISTRY.get(name)
    if family is None:
        return None
    want = set(labels.items())
    found = [child.value for key, child in family.items()
             if want == set(key)]
    return float(found[0]) if found else None


def reduce(spec, ev):
    label = train_step_label(ev.say)
    if label is None:
        return None
    for terms in (spec["sum"], spec.get("else", [])):
        found = [gauge(t["match"], program=label, **t.get("labels", {}))
                 for t in terms]
        found = [v for v in found if v is not None]
        if found:
            return sum(found) * spec.get("scale", 1.0)
    return None
