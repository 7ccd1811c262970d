"""Device time of the op events of one phase of the step -- ``phase``:
``forward``, ``recompute`` (forward ops run again for the backward) or
``backward`` -- as a percentage of device busy time. The phase of an
instruction is the program's to say
(``paddle_tpu.observability.attribution.instruction_phases`` over the train
step's HLO text, which ``observability.memory.compiled_step`` hands out):
None for a program without it, as a parent commit is, and where there is no
trace."""
from benchmark import trace as tr
from benchmark.reducers import step_gauge

_PHASES = {}


def phases(ev):
    """``instruction_phases`` of the train step, once a run; None without."""
    from paddle_tpu.observability import attribution, memory
    read = getattr(attribution, "instruction_phases", None)
    label = step_gauge.train_step_label(ev.say)
    if read is None or label is None:
        return None
    if label not in _PHASES:
        step = memory.compiled_step(label)
        _PHASES[label] = read(step.hlo_text()) if step is not None else None
    return _PHASES[label]


def reduce(spec, ev):
    if ev.trace is None:
        return None
    found = phases(ev)
    lines = ev.trace.first_device()
    busy = tr.busy_ns(lines)
    if found is None or not busy:
        return None
    hit = 0.0
    for name, a, b in lines.get(tr.OPS_LINE, []):
        if found.get(tr.instruction(name), (None, None))[1] == spec["phase"]:
            hit += b - a
    return 100.0 * hit / busy
