"""What is live where the train step's temporaries are highest:
``paddle_tpu.observability.memory.peak_live_set`` of the train step (the last
compile miss, ``step_gauge.train_step_label``), summed over the buffers whose
``phase`` is the spec's (``forward``: activations held for the backward;
``backward``: gradients, cotangents, the optimizer's), in GB. Computed once a
run, on the first metric that asks, and never on the run's path.

The first call also says, on earlier lines: the source used and its coverage
(listed bytes over XLA's ``temp_size_in_bytes``), the peak's place in the
schedule, the live bytes by op type (top 12 and the rest), the scopes of the
five largest buffers, the step's state by class with the allocator's marks
before its first run, and the static planner's ratio. None where the coverage
does not reconcile (under 80%: the line says what it was), and for a program
without the call, as a parent commit is.
"""
from benchmark.reducers import step_gauge

_FOUND = {}


def _state(label, cls) -> float:
    return step_gauge.gauge("program_state_bytes", program=label,
                            **{"class": cls}) or 0.0


def _describe(found, say) -> None:
    gb = lambda n: f"{n / 1e9:.3f}"                         # noqa: E731
    at = found["position"]
    say(f"live set at the train step's peak: source {found['source']}, "
        f"{gb(found['peak_bytes'])} GB listed of {gb(found['temp_bytes'])} "
        f"GB of temporaries (coverage {found['coverage']:.3f}), at "
        f"instruction {at['index']} of {at['of']} "
        f"({at['instruction']}); the backward begins at op "
        f"{found['first_backward']}")
    by_type, by_phase = {}, {}
    for b in found["buffers"]:
        kind = b["scope"].split("#")[0] if b["scope"] else "no scope"
        by_type[kind] = by_type.get(kind, 0.0) + b["bytes"]
        by_phase[b["phase"]] = by_phase.get(b["phase"], 0.0) + b["bytes"]
    ranked = sorted(by_type.items(), key=lambda kv: -kv[1])
    say("live GB at the peak by op type: " + ", ".join(
        f"{k} {gb(v)}" for k, v in ranked[:12])
        + f"; {len(ranked[12:])} others {gb(sum(v for _, v in ranked[12:]))}")
    say("live GB at the peak by phase: " + ", ".join(
        f"{k or 'no scope'} {gb(v)}" for k, v in sorted(
            by_phase.items(), key=lambda kv: -kv[1])))
    say("five largest buffers: " + "; ".join(
        f"{b['scope'] or 'no scope'} {b['instruction']} {gb(b['bytes'])}"
        for b in found["buffers"][:5]))
    label = found["program"]
    say("the train step takes in, GB a device: " + ", ".join(
        f"{c} {gb(_state(label, c))}"
        for c in ("parameter", "optimizer", "other", "feed"))
        + "; the allocator before its first run: " + ", ".join(
        f"{c} {gb(v)}" for c in ("in_use", "peak_in_use", "peak_reserved")
        for v in (step_gauge.gauge("program_allocator_bytes", program=label,
                                   stat=c),) if v is not None))
    ratio = step_gauge.gauge("program_static_peak_ratio", program=label)
    say("program_static_peak_ratio (memplan's estimate over XLA's "
        f"arg + out + temp - alias): {ratio}")


def live_set(ev):
    """``peak_live_set`` of the train step, once a run; None without it."""
    from paddle_tpu.observability import memory
    label = step_gauge.train_step_label(ev.say)
    find = getattr(memory, "peak_live_set", None)
    if label is None or find is None:
        return None
    if label not in _FOUND:
        _FOUND[label] = find(label)
        if _FOUND[label] is not None:
            _describe(_FOUND[label], ev.say)
    return _FOUND[label]


def reduce(spec, ev):
    found = live_set(ev)
    if found is None:
        return None
    if not found["reconciled"]:
        ev.say(f"{spec['name']}: {found['source']} lists "
               f"{found['coverage']:.3f} of XLA's temporaries, which does "
               f"not reconcile: left out")
        return None
    return sum(b["bytes"] for b in found["buffers"]
               if b["phase"] == spec["phase"]) / 1e9
