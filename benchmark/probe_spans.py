"""What the span readers take from the program's flight recorder beyond
``probe.spans``: the span tree and the lifetime phase sums. Beside
``probe.py`` and for the same reason -- every accessor of program internals
in one place -- in a file of its own because a PR that adds readers may not
edit a file the benchmark has. Both return nothing, and do not raise, for a
program whose recorder has no tree or no such phase: the reader then leaves
its metric out.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence


class Node(NamedTuple):
    """One span of the program's ring with its place in the tree: ``t0`` and
    ``dur`` in ``time.perf_counter`` seconds, ``tid`` the recording thread,
    ``parent`` the id of the span open on that thread when this one began
    (0: a root)."""
    name: str
    t0: float
    dur: float
    tid: int
    id: int
    parent: int


def tree() -> List[Node]:
    """Every span in the ring, in the order they ended; [] where the ring's
    entries carry no id and parent."""
    from paddle_tpu.observability import timeline
    return [Node(s[0], s[2], s[3], s[5], s[6], s[7])
            for s in timeline.spans() if len(s) >= 8]


def phase_seconds(phases: Sequence[str], cat: str) -> Optional[float]:
    """Seconds the registry's ``phase_seconds`` histogram has summed since
    the process began, over the named phases of one category; None where it
    has observed none of them. Unlike the ring it never wraps."""
    from paddle_tpu.observability.metrics import REGISTRY
    family = REGISTRY.get("phase_seconds")
    if family is None:
        return None
    found = [child.sum for labels, child in family.items()
             if child.count and ("cat", cat) in labels
             and any(("phase", p) in labels for p in phases)]
    return float(sum(found)) if found else None
