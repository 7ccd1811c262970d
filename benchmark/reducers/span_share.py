"""Sum of the program's flight-recorder spans named in ``match`` as a
percentage of the untraced window (0 where the layer never waited)."""


def reduce(spec, ev):
    if not ev.window_s:
        return None
    total = sum(d for name, _, d in ev.spans if name in spec["match"])
    return 100.0 * total / ev.window_s
