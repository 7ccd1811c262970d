"""Sum of the program's flight-recorder spans named in ``match`` over the
untraced window, in milliseconds for each step."""


def reduce(spec, ev):
    if not ev.steps:
        return None
    total = sum(d for name, _, d in ev.spans if name in spec["match"])
    return 1e3 * total / ev.steps
