"""Host time the device waited on: the time inside the union of the
program's spans named in ``match`` (a nested one counts once) during which
the first device ran no op, over the traced window, in milliseconds for each
step. Only gaps of at least ``trace.HOST_GAP_NS`` count: a shorter one is
the device between two ops of one program, not the host holding it back.

The spans live on ``time.perf_counter``, the trace on the capture's own
nanoseconds. The offset comes from pairs the evidence already holds: every
``pair`` annotation of the benchmark (``bench.exe_run``) wraps exactly one
root span of the program on the calling thread (``run`` under
``train_feed``, ``train_from_dataset`` under ``train_dataset``), so the last
n root spans of this thread pair with the window's n annotations in order,
and the offset is the median of (annotation start - span start). The reader
checks itself and raises instead of reporting: the offsets may spread (the
distance between their quartiles; the whole range under four pairs, so that
one pair the scheduler tore apart does not condemn a run) no wider than
``MAX_OFFSET_SPREAD_NS``, and every paired span, once shifted, must lie
inside its annotation to within the same figure.

What the checks cannot see is what all pairs share: a span starts a few
microseconds after its annotation, so every span lands that much early on
the trace's axis. The lead is at most the pair's slack (annotation length
minus span length), whose median the reader prints beside the spread. With
one pair (a window that is one epoch under ``train_dataset``) the spread is
0 by construction and only the span's end is checked: the offset then rests
on annotation and span starting together, to within that slack.

With ``idle_table`` the reader also says, on an earlier line, the window's
idle seconds by the innermost program span open on the calling thread
(``run(self)``: in ``run`` and in none of its children; ``none``: no span
open; ``between_ops``: the gaps under ``HOST_GAP_NS``), largest first, with
their sum against the first device's idle time.

None where there is no trace or the program's spans form no tree.
"""
import bisect
import json
import statistics
import threading

from benchmark import probe_spans
from benchmark import trace as tr

#: the widest the pairs' offsets may spread: the shortest gap that counts,
#: beyond which a gap could change hands between two spans. Found: 1.9 to
#: 4.8 us over 20 pairs on the v5e machine (PERF.md, PR 23), 7.0 to 9.6 us
#: in the CPU rehearsal that the tests run, eight at once on eight cores. A
#: limit of 10 us would refuse that rehearsal at random; a wrong pairing
#: puts the offsets a step apart, milliseconds, and is caught either way.
MAX_OFFSET_SPREAD_NS = float(tr.HOST_GAP_NS)


def clock_offset(roots, notes, limit_ns=MAX_OFFSET_SPREAD_NS):
    """(offset, spread, slack) in ns: ``t0 * 1e9 + offset`` is a root span's
    start on the trace's axis, ``slack`` the median of (annotation length -
    span length), which bounds how early that places it; raises where the
    pairs disagree."""
    offsets = [a - r.t0 * 1e9 for r, (_, a, _) in zip(roots, notes)]
    slack = statistics.median(b - a - r.dur * 1e9
                              for r, (_, a, b) in zip(roots, notes))
    offset = statistics.median(offsets)
    if len(offsets) >= 4:
        q1, _, q3 = statistics.quantiles(offsets, n=4, method="inclusive")
        spread = q3 - q1
    else:
        spread = max(offsets) - min(offsets)
    if spread > limit_ns:
        raise ValueError(
            f"span_idle_overlap: the {len(offsets)} pairs of root span and "
            f"annotation put the clock offset {spread:.0f} ns apart, more "
            f"than MAX_OFFSET_SPREAD_NS = {limit_ns:.0f}: the pairing is "
            f"wrong or the two clocks do not keep step")
    for r, (name, a, b) in zip(roots, notes):
        lo = r.t0 * 1e9 + offset
        hi = lo + r.dur * 1e9
        if lo < a - limit_ns or hi > b + limit_ns:
            raise ValueError(
                f"span_idle_overlap: root span {r.name!r} [{lo:.0f}, "
                f"{hi:.0f}] ns does not lie inside its {name} annotation "
                f"[{a:.0f}, {b:.0f}] once shifted by {offset:.0f} ns")
    return offset, spread, slack


def within(intervals, sorted_gaps, starts):
    """Length of ``intervals`` (a union) inside the sorted, disjoint gaps."""
    total = 0.0
    for lo, hi in intervals:
        k = max(bisect.bisect_right(starts, lo) - 1, 0)
        while k < len(sorted_gaps) and sorted_gaps[k][0] < hi:
            total += max(0.0, min(hi, sorted_gaps[k][1])
                         - max(lo, sorted_gaps[k][0]))
            k += 1
    return total


def idle_by_innermost(spans, long_gaps, starts):
    """{label: ns of the long gaps} by the innermost of ``spans`` (one
    thread's, as (name, lo, hi, id, parent) on the trace's axis)."""
    children = {}
    for _, lo, hi, _, parent in spans:
        children.setdefault(parent, []).append((lo, hi))
    out = {}
    for name, lo, hi, sid, _ in spans:
        label = f"{name}(self)" if sid in children else name
        own = tr.subtract([(lo, hi)], tr.union(children.get(sid, [])))
        out[label] = out.get(label, 0.0) + within(own, long_gaps, starts)
    covered = within(tr.union((lo, hi) for _, lo, hi, _, _ in spans),
                     long_gaps, starts)
    out["none"] = tr.length(long_gaps) - covered
    return out


def reduce(spec, ev):
    if ev.trace is None:
        return None
    me = threading.get_ident()
    mine = [s for s in probe_spans.tree() if s.tid == me]
    notes = [e for e in ev.trace.host if e[0] == spec["pair"]]
    if not mine or not notes or not ev.traced_steps:
        return None
    roots = [s for s in mine if s.parent == 0][-len(notes):]
    if len(roots) < len(notes):
        raise ValueError(
            f"span_idle_overlap: {len(notes)} {spec['pair']} annotations in "
            f"the window but {len(roots)} root spans on the calling thread")
    offset, spread, slack = clock_offset(roots, notes)
    w_lo, w_hi = ev.trace.window
    shifted = []
    for s in mine:
        lo = max(s.t0 * 1e9 + offset, w_lo)
        hi = min((s.t0 + s.dur) * 1e9 + offset, w_hi)
        if hi > lo:
            shifted.append((s.name, lo, hi, s.id, s.parent))
    gaps = tr.subtract([ev.trace.window], tr.union(tr.spans_of(
        ev.trace.first_device().get(tr.OPS_LINE, []))))
    long_gaps = [g for g in gaps if g[1] - g[0] >= tr.HOST_GAP_NS]
    starts = [g[0] for g in long_gaps]
    if spec.get("idle_table"):
        by = idle_by_innermost(shifted, long_gaps, starts)
        by["between_ops"] = tr.length(gaps) - tr.length(long_gaps)
        table = {k: round(v / 1e9, 6) for k, v in
                 sorted(by.items(), key=lambda kv: -kv[1]) if v > 0}
        ev.say(f"idle by innermost program span, s: {json.dumps(table)}; "
               f"together {sum(by.values()) / 1e9:.6f}s of the first "
               f"device's {tr.length(gaps) / 1e9:.6f}s idle; clock offset "
               f"from {len(notes)} pairs, spread {spread:.0f} ns, slack "
               f"{slack:.0f} ns")
    matched = tr.union((lo, hi) for name, lo, hi, _, _ in shifted
                       if name in spec["match"])
    return within(matched, long_gaps, starts) / 1e6 / ev.traced_steps
