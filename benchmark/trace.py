"""From a profiler trace to numbers: the benchmark's own reduction.

``load`` turns the ``.xplane.pb`` the JAX profiler wrote into a plain
``Trace`` (lists of ``(name, start_ns, end_ns)`` per device line, plus the
benchmark's own host annotations), clipped to the ``bench.window``
annotation. Everything after that is arithmetic on those lists, so it can be
checked on a recorded or hand-made trace without a device:

- device busy time: the union of the intervals of the ``XLA Ops`` line;
- the named-scope join: an op event is named by its HLO instruction
  (``%fusion.12 = ...``); the instruction's ``op_name`` metadata in the
  compiled step's HLO text carries the ``<op_type>#<idx>`` scope the
  executor opened around the Program op that produced it;
- collectives: events whose HLO opcode is a collective (``-start`` /
  ``-done`` halves and the async line included), and the part of their time
  during which no other op runs on that device;
- idle gaps, labelled by the benchmark's host annotation that covers them.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # name, start_ns, end_ns

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
UNATTRIBUTED = "unattributed"

COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute",
                      "collective-broadcast")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"[A-Za-z0-9_.]+#\d+")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


class Instr:
    """What the compiled step's HLO text says of one instruction."""

    __slots__ = ("scope", "opcode", "target")

    def __init__(self, scope, opcode, target):
        self.scope, self.opcode, self.target = scope, opcode, target


class Trace:
    """``devices``: {plane name: {line name: [Event]}} clipped to the window;
    ``host``: the benchmark's annotations as Events; ``window``: (start, end)
    in the trace's nanoseconds."""

    def __init__(self, devices: Dict[str, Dict[str, List[Event]]],
                 host: List[Event], window: Interval):
        self.devices, self.host, self.window = devices, host, window
        self._by_scope = None

    def time_by_scope(self, hlo) -> Dict[str, float]:
        """``time_by_scope`` of the first device, one pass for all readers."""
        if self._by_scope is None:
            self._by_scope = time_by_scope(self.first_device(), hlo)
        return self._by_scope

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def first_device(self) -> Dict[str, List[Event]]:
        return self.devices[sorted(self.devices)[0]]

    def to_json(self) -> dict:
        return {"devices": self.devices, "host": self.host,
                "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        ev = lambda es: [(n, float(a), float(b)) for n, a, b in es]  # noqa
        return cls({p: {ln: ev(es) for ln, es in lines.items()}
                    for p, lines in d["devices"].items()},
                   ev(d["host"]), tuple(d["window"]))


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, rehearsal: bool = False) -> Trace:
    """Read an xplane file with nothing but JAX. A device is a
    ``/device:TPU:n`` plane. In a CPU rehearsal there is none: the CPU
    client's worker threads then stand in as one device whose op line holds
    their thunk events (named by HLO instruction), so that the join and the
    readers run end to end -- on numbers that mean nothing."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)

    def events(line, keep=lambda name: True):
        return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                for e in line.events if keep(e.name)]
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = {ln.name: events(ln) for ln in plane.lines}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += events(ln, lambda n: n.startswith("bench."))
                if rehearsal and ln.name.startswith("tf_XLA"):
                    devices.setdefault(plane.name, {OPS_LINE: []})[
                        OPS_LINE] += events(ln, lambda n: not n.startswith(
                            ("end: ", "Thread", "Thunk")))
    if not devices:
        raise ValueError(f"no device plane in {path}: planes "
                         f"{[p.name for p in data.planes]}")
    windows = [e for e in host if e[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW!r} annotations in {path}")
    return clip(Trace(devices, sorted(host, key=lambda e: e[1]),
                      (windows[0][1], windows[0][2])))


def clip(trace: Trace) -> Trace:
    """Keep what overlaps the window, cut to it."""
    lo, hi = trace.window

    def cut(events):
        return [(n, max(a, lo), min(b, hi)) for n, a, b in events
                if b > lo and a < hi]
    return Trace({p: {ln: cut(es) for ln, es in lines.items()}
                  for p, lines in trace.devices.items()},
                 [e for e in cut(trace.host) if e[0] != WINDOW],
                 trace.window)


# ------------------------------------------------------------- intervals --

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of union ``a`` that union ``b`` does not cover."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def spans_of(events: Iterable[Event]) -> List[Interval]:
    return [(a, b) for _, a, b in events]


# ------------------------------------------------------------ the reads --

def busy_ns(lines: Dict[str, List[Event]]) -> float:
    """Nanoseconds in which an operation ran on this device."""
    return length(union(spans_of(lines.get(OPS_LINE, []))))


def busy_s(trace: Trace) -> float:
    """Device busy seconds, averaged over the devices in the trace."""
    per = [busy_ns(lines) for lines in trace.devices.values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def instruction(event_name: str) -> str:
    """The HLO instruction an op event stands for: events are named by the
    instruction's text (``%fusion.12 = bf16[..] fusion(..)``) or its name."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.strip().lstrip("%").split(" ")[0]


def parse_hlo(hlo_text: str) -> Dict[str, Instr]:
    """{instruction name: Instr} over every computation of the module. The
    scope is the innermost ``<op_type>#<idx>`` of the ``op_name`` metadata."""
    out = {}
    for ln in hlo_text.splitlines():
        m = _INSTR.match(ln)
        if not m:
            continue
        meta = _OP_NAME.search(ln)
        toks = _SCOPE.findall(meta.group(1)) if meta else []
        op = _OPCODE.search(ln, m.end())
        tgt = _TARGET.search(ln)
        out[m.group(1)] = Instr(toks[-1] if toks else None,
                                op.group(1) if op else None,
                                tgt.group(1) if tgt else None)
    return out


def opcode(event_name: str, hlo: Dict[str, Instr]) -> str:
    """The event's HLO opcode: from the step's HLO where the instruction is
    there, else the stem of its name (XLA names instructions by opcode)."""
    name = instruction(event_name)
    ins = hlo.get(name)
    if ins is not None and ins.opcode:
        return ins.opcode
    return re.sub(r"[.\d]+$", "", name)


def scope(event_name: str, hlo: Dict[str, Instr]) -> str:
    ins = hlo.get(instruction(event_name))
    return ins.scope if ins is not None and ins.scope else UNATTRIBUTED


def op_type(scope_name: str) -> str:
    """``fused_attention_grad#412`` -> ``fused_attention_grad``."""
    return scope_name.split("#", 1)[0]


def is_collective(event_name: str, hlo: Dict[str, Instr]) -> bool:
    op = opcode(event_name, hlo)
    for suffix in ("-start", "-done"):
        if op.endswith(suffix):
            op = op[:-len(suffix)]
    return op in COLLECTIVE_OPCODES


def time_by_scope(lines, hlo) -> Dict[str, float]:
    """Device nanoseconds by ``<op_type>#<idx>`` scope; events with no IR
    scope -- copies, parameter handling -- land in ``UNATTRIBUTED``."""
    out: Dict[str, float] = {}
    for name, a, b in lines.get(OPS_LINE, []):
        key = scope(name, hlo)
        out[key] = out.get(key, 0.0) + (b - a)
    return out


def time_by_op_type(by_scope: Dict[str, float]) -> Dict[str, float]:
    """``time_by_scope`` summed by ``<op_type>`` (index stripped)."""
    out: Dict[str, float] = {}
    for key, ns in by_scope.items():
        out[op_type(key)] = out.get(op_type(key), 0.0) + ns
    return out


def collective_intervals(lines, hlo) -> List[Interval]:
    """When a collective was in flight on this device: the collective ops of
    the op line, and the collective spans of the async line."""
    return union(spans_of(
        e for ln in (OPS_LINE, ASYNC_LINE) for e in lines.get(ln, [])
        if is_collective(e[0], hlo)))


def exposed_collective_ns(lines, hlo) -> float:
    """Collective time during which no other op ran on the device."""
    others = union(spans_of(e for e in lines.get(OPS_LINE, [])
                            if not is_collective(e[0], hlo)))
    return length(subtract(collective_intervals(lines, hlo), others))


def step_module(lines) -> Tuple[Optional[str], List[float]]:
    """The module that took most device time in the window (the train step)
    and the durations of its runs, ns."""
    by: Dict[str, List[float]] = {}
    for n, a, b in lines.get(MODULES_LINE, []):
        by.setdefault(n, []).append(b - a)
    if not by:
        return None, []
    name = max(by, key=lambda k: sum(by[k]))
    return name, by[name]


#: a gap shorter than this is the device between two ops of one program, not
#: the host holding it back
HOST_GAP_NS = 20e3


def idle_gaps(trace: Trace, top: int = 10,
              host_gap_ns: float = HOST_GAP_NS) -> List[Tuple[str, float]]:
    """Idle seconds of the first device by what the host was doing: every gap
    of at least ``host_gap_ns`` goes to the benchmark annotation that covers
    most of it (``none``: no annotation was open), the shorter ones together
    to ``between_ops``. Largest first."""
    lines = trace.first_device()
    gaps = subtract([trace.window], union(spans_of(lines.get(OPS_LINE, []))))
    labelled: Dict[str, float] = {}
    # the annotations are sequential on one thread: sorted by start, their
    # ends are sorted too
    ends = [e[2] for e in trace.host]
    for lo, hi in gaps:
        best, cover = "between_ops", 0.0
        if hi - lo >= host_gap_ns:
            best = "none"
            k = bisect.bisect_right(ends, lo)
            while k < len(trace.host) and trace.host[k][1] < hi:
                n, a, b = trace.host[k]
                if min(b, hi) - max(a, lo) > cover:
                    best, cover = n, min(b, hi) - max(a, lo)
                k += 1
        labelled[best] = labelled.get(best, 0.0) + (hi - lo) / 1e9
    return sorted(labelled.items(), key=lambda kv: -kv[1])[:top]


def breakdown(trace: Trace, hlo: Dict[str, Instr], top: int = 10) -> dict:
    by = time_by_op_type(trace.time_by_scope(hlo))
    ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps(trace, top)]}
