"""Flight-recorder timeline: a tree of phase spans, on the profiler's clock.

The reference framework answered "where did step N's time go" with
platform/profiler RecordEvent push/pop plus tools/timeline.py (Chrome
trace).  Here every hot path (Executor.run and its feed-prep / h2d /
dispatch / fetch, the compile path, train_from_dataset and its prefetch
worker, the dataset's file parse, Predictor.run, the GPipe schedule trace)
records ``phase(...)`` spans into ONE bounded in-process ring.

A span is a :class:`Span`: besides name, category, start and duration on
``time.perf_counter`` it carries an ``id`` and the id of the span that was
open on the same thread when it began (``parent``, 0 for a root), so a
reader gets self time (a span's duration minus its children's) without
guessing from overlaps.  ``step=`` is the identifier one step's spans
share.

``phase()`` also opens ``jax.profiler.TraceAnnotation("paddle_tpu.<cat>.
<name>")``.  That is the shared clock: in any profiler capture, whoever
started it, the program's phases lie on the host plane beside the device
lines, and a device gap can be read against the phase that was open.  The
annotation carries the span's id (``span_id``), so an export from a
capture finds the ring's entry again.  The ring keeps ``perf_counter``
seconds; a capture's timestamps count from its own start, and one paired
event fixes the offset between the two (measured on the CPU, jax 0.9.0:
five annotations over 240 ms disagreed by 4.3 us).

Set-up has the same tree.  ``spanned(name, cat)`` wraps an entry point in
one phase (``Executor.run``; category ``build``: ``append_backward``,
``minimize``, ``Program.clone(for_test=True)``, ``with_strategy``), and JAX's
own compile events become child spans of the executor's compile that caused
them (category ``jax``: ``jaxpr_trace`` and ``mlir_lower`` under
``trace_lower``, ``backend_compile`` under ``compile`` with a ``cache_load``
child on a persistent-cache hit): ``_on_jax_event`` holds what fires on a
thread while an executor ``compile`` phase is open there, and
``settle_jax_events`` writes it once the compile has succeeded.
``mark_uptime`` sets ``process_uptime_seconds{at}`` against the process's
start as the OS gives it.

Cost, always on: a ``phase()`` is two ``perf_counter`` calls, an idle
``TraceAnnotation``, a thread-local stack push/pop, a lock'd deque append
and one histogram observe through a handle cached per (name, category) --
no import and no label lookup per span: about 4.5 us on the v5e machine's
host, about 5 us under a capture, where it was 6.2 before the handle was
cached (PERF.md, PR 23, has the readings).  Nothing is written to disk
until ``export_chrome_trace`` is called (``bench.py --emit-trace``), so
with ``PADDLE_TPU_OBS`` unset the hot path still performs zero file I/O.

The exporter writes one trace-event-format timeline from

- flight-recorder phase spans (this module's ring),
- legacy ``profiler.record_event`` host spans (``profiler._agg.spans``),
- counter samples (device-memory telemetry from ``observability.memory``)
  as Chrome counter ("C") tracks.

Given the ``trace_dir`` of a finished profiler capture it starts from the
capture instead: phases and RecordEvent spans are already in it as
annotations, so only the counter tracks are spliced in, and each phase
gets the args and parent link the ring holds for its ``span_id``.  Load
the output in chrome://tracing or https://ui.perfetto.dev.
"""
from __future__ import annotations

import collections
import functools
import itertools
import json
import os
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

from .metrics import REGISTRY

# pids for the synthesized process tracks; chosen above the xplane capture's
# pid range and distinct from profiler._host_span_events' 90000 default
PID_PHASES = 90001
PID_COUNTERS = 90002

_SPAN_CAP = 65536


class Span(NamedTuple):
    """One ring entry.  The first six fields are the historical tuple
    (readers index them); ``id`` / ``parent`` were appended for the tree."""
    name: str
    cat: str
    t0: float               # time.perf_counter seconds
    dur: float
    args: Optional[dict]
    tid: int                # threading.get_ident() of the recording thread
    id: int
    parent: int             # id of the enclosing span on that thread, 0: root


_lock = threading.Lock()
_spans: "collections.deque" = collections.deque(maxlen=_SPAN_CAP)
_ids = itertools.count(1)
# (track_name, t_seconds, {series: value})
_counters: "collections.deque" = collections.deque(maxlen=_SPAN_CAP)
# [earliest span start, latest span end] over WINDOW_PHASES, for the WHOLE
# process -- the ring above is bounded (~13k steps), so anything deriving a
# run window from ring contents alone (the goodput ledger) would silently
# shrink its wall-clock once the ring wraps while the cumulative
# phase_seconds sums keep growing
_window = [None, None]
#: the phases whose extent is that window: the ones the goodput ledger gives
#: a cause (``goodput._PHASE_CAUSE``; a test holds the two equal).  The
#: tree's containers and the prefetch worker's spans stay out: ``run``
#: opens before ``feed_prep``, and
#: ``parse_file`` also runs in ``load_into_memory()``, long before training;
#: either would stretch the wall the ledger divides by.
WINDOW_PHASES = frozenset(
    [(n, "executor") for n in ("feed_prep", "dispatch", "fetch_sync",
                               "journal", "compile", "warm_restore",
                               "verify")]
    + [("feed_wait", "dataset")])


class _Open(threading.local):
    """The phases open on this thread, outermost first."""

    def __init__(self):
        self.stack: List["phase"] = []


_open = _Open()
# (name, cat) -> (annotation name, phase_seconds child, in WINDOW_PHASES),
# valid for one generation of the registry: reset() / remove_labeled()
# start another, or a span after a reset would feed a histogram nobody can
# read
_handles: Dict[Tuple[str, str], tuple] = {}
_handles_generation = -1


def _handle(name: str, cat: str) -> tuple:
    global _handles_generation
    if REGISTRY.generation != _handles_generation:
        _handles.clear()
        _handles_generation = REGISTRY.generation
    h = _handles.get((name, cat))
    if h is None:
        # labeled by phase AND category: executor and Predictor both record
        # dispatch/feed_prep/fetch_sync and their durations differ by orders
        # of magnitude -- one merged series would describe neither workload
        h = _handles[(name, cat)] = (
            f"paddle_tpu.{cat}.{name}",
            REGISTRY.histogram("phase_seconds",
                               "flight-recorder phase durations by phase "
                               "and category", phase=name, cat=cat),
            (name, cat) in WINDOW_PHASES)
    return h


def _record(name, cat, t0, dur, args, span_id, parent, hist, in_window):
    with _lock:
        # recording thread rides along: concurrent spans must land on
        # separate trace tracks, not garble one tid-0 line
        _spans.append(Span._make((name, cat, t0, dur, args or None,
                                  threading.get_ident(), span_id, parent)))
        if in_window:
            if _window[0] is None or t0 < _window[0]:
                _window[0] = t0
            end = t0 + max(dur, 0.0)
            if _window[1] is None or end > _window[1]:
                _window[1] = end
    hist.observe(dur)


class phase:
    """``with phase(name, cat, **args):`` records one flight-recorder span
    around the body, child of the phase open on this thread, and opens the
    ``paddle_tpu.<cat>.<name>`` profiler annotation for its duration.  The
    annotation carries the span's id (``span_id``), by which an export from
    a capture finds the ring's entry again (args, parent).

    Never hold one open across a ``yield``: the stack is per thread, and a
    span left on it while another frame runs adopts children that are not
    its own.  Wrap the call, not the loop body."""

    __slots__ = ("name", "cat", "args", "id", "_t0", "_h", "_note")

    def __init__(self, name: str, cat: str = "executor", **args):
        self.name, self.cat, self.args = name, cat, args

    def __enter__(self):
        self._h = _handle(self.name, self.cat)
        self.id = next(_ids)
        self._note = TraceAnnotation(self._h[0], span_id=self.id)
        self._note.__enter__()
        _open.stack.append(self)
        self._t0 = time.perf_counter()

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        stack = _open.stack
        stack.pop()
        self._note.__exit__(*exc)
        h = self._h
        if h is not None:               # None: discard()ed
            _record(self.name, self.cat, self._t0, dur, self.args, self.id,
                    stack[-1].id if stack else 0, h[1], h[2])
        return False


def annotate(**args):
    """Add ``args`` to the innermost phase open on this thread (what is
    known only once the work is under way: a step index, bytes moved)."""
    stack = _open.stack
    if stack:
        stack[-1].args.update(args)


def discard():
    """Let the innermost phase open on this thread end without a span: the
    work turned out not to be what the phase names (a warm-store consult
    that missed, a compile that raised), and a reader that sums the phase
    must not count it."""
    stack = _open.stack
    if stack:
        stack[-1]._h = None


def record_span(name: str, t0: float, dur: float, cat: str = "executor",
                **args):
    """Append an already-timed span (t0 from time.perf_counter), child of
    the phase open on this thread now; mirrors into ``phase_seconds``."""
    stack = _open.stack
    h = _handle(name, cat)
    _record(name, cat, t0, dur, args, next(_ids),
            stack[-1].id if stack else 0, h[1], h[2])


def spanned(name: str, cat: str = "executor", nested: bool = True):
    """Run the decorated entry point inside one phase, from its first
    statement to its return, exceptions included.  The phase is a container:
    its self time is what its children leave (in ``run``: the cache key, the
    scope write-back, the bookkeeping after dispatch).  ``nested=False``
    opens it only where no phase of ``cat`` is open on the thread already:
    ``minimize`` calls ``append_backward`` and a wrapping optimizer the inner
    one's ``minimize``, and one span an outermost call keeps the category's
    ``phase_seconds`` a sum of disjoint times."""
    def deco(fn):
        @functools.wraps(fn)
        def entry(*args, **kwargs):
            if not nested and any(p.cat == cat for p in _open.stack):
                return fn(*args, **kwargs)
            with phase(name, cat=cat):
                return fn(*args, **kwargs)
        return entry
    return deco


def find(span_id: int) -> Optional[Span]:
    """The ring's span of that id, looked for from the newest end (a caller
    asks for a phase it has just closed); None where the phase was
    ``discard()``ed or the ring has wrapped past it."""
    with _lock:
        for s in reversed(_spans):
            if s.id == span_id:
                return s
    return None


# ------------------------------------------------ JAX's own compile events --

#: the events of jax/_src/dispatch.py and compiler.py that become spans of
#: category ``jax``, each with the executor phase under which it counts
_JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": ("jaxpr_trace", "trace_lower"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("mlir_lower", "trace_lower"),
    "/jax/core/compile/backend_compile_duration":
        ("backend_compile", "compile"),
    "/jax/compilation_cache/cache_retrieval_time_sec":
        ("cache_load", "compile"),
}


class _HeldSpan:
    """One JAX event of the executor compile open on a thread, not yet
    written."""
    __slots__ = ("name", "t0", "end", "id", "parent")

    def __init__(self, name, t0, end, span_id, parent):
        self.name, self.t0, self.end = name, t0, end
        self.id, self.parent = span_id, parent


class _Held(threading.local):
    def __init__(self):
        self.compile = 0        # id of the compile phase the spans belong to
        self.spans: List[_HeldSpan] = []


_held = _Held()


def _on_jax_event(event: str, secs: float, **_):
    """JAX's duration listener.  An event counts where the innermost phase
    open on the thread is the executor's ``trace_lower`` (trace, lowering) or
    ``compile`` (backend compile, cache read) of an executor ``compile``;
    every other is dropped: eager ``jax.numpy`` calls, a reference the caller
    jits, an autotune candidate compiled while the step is traced -- they are
    not the program's compile.  JAX fires the trace event for every inner
    ``jit`` and ``custom_vjp`` it traces, over a thousand a decoder step, each
    inside the next, and again for what a lowering rule traces while the
    module is lowered: one is kept, the one that began first, which is the
    step's own.  The cache read fires inside the backend compile that then
    fires around it, and becomes its child."""
    name, under = _JAX_SPANS.get(event, (None, None))
    stack = _open.stack
    if name is None or not stack:
        return
    top = stack[-1]
    owner = next((p for p in reversed(stack)
                  if p.name == "compile" and p.cat == "executor"), None)
    if owner is None or top.cat != "executor" or top.name != under:
        return
    now = time.perf_counter()
    t0 = max(now - secs, top._t0)
    if _held.compile != owner.id:       # the last compile raised: drop it
        _held.compile, _held.spans = owner.id, []
    held = _held.spans
    if name == "jaxpr_trace":
        for h in held:
            if h.name == name:
                if t0 < h.t0:
                    h.t0, h.end = t0, now
                return
    span_id = next(_ids)
    if name == "backend_compile":
        for h in held:
            if h.name == "cache_load" and h.t0 >= t0:
                h.parent = span_id
    held.append(_HeldSpan(name, t0, now, span_id, top.id))


def settle_jax_events() -> Dict[str, float]:
    """Write what ``_on_jax_event`` holds for the executor ``compile`` phase
    open on this thread, in the order the spans ended, and return their
    seconds by name.  The executor calls it once lower and compile have
    returned: a compile that raises leaves none of them.  A lowering that
    fired inside the kept trace (an autotune candidate's) is the trace's
    time and is left out."""
    stack = _open.stack
    held, _held.spans = _held.spans, []
    if not stack or stack[-1].id != _held.compile:
        return {}
    traced = max((h.end for h in held if h.name == "jaxpr_trace"),
                 default=0.0)
    parts: Dict[str, float] = {}
    for h in sorted(held, key=lambda h: h.end):
        if h.name == "mlir_lower" and h.t0 < traced:
            continue
        _, hist, in_window = _handle(h.name, "jax")
        _record(h.name, "jax", h.t0, h.end - h.t0, None, h.id, h.parent,
                hist, in_window)
        parts[h.name] = parts.get(h.name, 0.0) + h.end - h.t0
    return parts


def _listen_to_jax():
    """Register ``_on_jax_event`` with JAX, once a process: a module executed
    again (``importlib.reload``) keeps its namespace, and with it the mark
    that the first execution registered."""
    global _LISTENING
    if not _LISTENING:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_jax_event)
        _LISTENING = True


_LISTENING = globals().get("_LISTENING", False)
_listen_to_jax()


# ------------------------------------------------------- process uptime --

def _process_start() -> Optional[float]:
    """The ``perf_counter`` reading at which the OS started this process:
    ``/proc/self/stat``'s start time (field 22, clock ticks since boot)
    against ``CLOCK_BOOTTIME`` now.  None where either cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - age
    except (OSError, ValueError, IndexError, AttributeError):
        return None


_PROCESS_START = _process_start()


def mark_uptime(at: str, t: Optional[float] = None) -> None:
    """Set ``process_uptime_seconds{at}`` to the seconds from the process's
    start to the ``perf_counter`` reading ``t`` (now): before ``at`` lie the
    interpreter, the imports and whatever the caller did until then.  Not
    set where the OS does not say when the process began."""
    if _PROCESS_START is not None:
        REGISTRY.gauge(
            "process_uptime_seconds",
            "seconds from the process's start (the OS's) to a point of "
            "set-up: import_start / import_end of paddle_tpu, the first "
            "Executor", at=at).set(
                (time.perf_counter() if t is None else t) - _PROCESS_START)


def counter_sample(track: str, values: Dict[str, float],
                   t: Optional[float] = None):
    """Record one sample of a counter track (e.g. device memory bytes)."""
    with _lock:
        _counters.append((track, time.perf_counter() if t is None else t,
                          dict(values)))


def spans(name: Optional[str] = None) -> List[tuple]:
    with _lock:
        out = list(_spans)
    if name is not None:
        out = [s for s in out if s[0] == name]
    return out


def counters(track: Optional[str] = None) -> List[tuple]:
    with _lock:
        out = list(_counters)
    if track is not None:
        out = [c for c in out if c[0] == track]
    return out


def span_window():
    """(earliest start, latest end) perf_counter pair over every span of
    ``WINDOW_PHASES`` this process EVER recorded -- survives ring wrap,
    unlike reading the ring.  (None, None) before the first such span."""
    with _lock:
        return (_window[0], _window[1])


def clear():
    with _lock:
        _spans.clear()
        _counters.clear()
        _window[0] = _window[1] = None


def _trace_events(host_pid: int = PID_PHASES,
                  phases: bool = True) -> List[dict]:
    """The ring contents as trace-event dicts (ts/dur in microseconds);
    ``phases=False`` leaves the spans out and keeps the counter tracks.

    Under a multi-rank job the process tracks are rank-tagged, so
    per-rank exports merged by ``profiler.merge_chrome_traces`` keep
    distinct, attributable track names instead of N identical lines."""
    from .journal import current_rank
    r = current_rank()
    tag = "" if r is None else f" [rank {r}]"
    events: List[dict] = [
        {"ph": "M", "pid": PID_COUNTERS, "name": "process_name",
         "args": {"name": f"paddle_tpu telemetry (counters){tag}"}},
    ]
    with _lock:
        span_list = list(_spans) if phases else []
        counter_list = list(_counters)
    if phases:
        events.insert(0, {
            "ph": "M", "pid": host_pid, "name": "process_name",
            "args": {"name": f"paddle_tpu flight recorder (phases){tag}"}})
    tid_map = {t: i for i, t in enumerate(
        sorted({s[5] for s in span_list if len(s) > 5}))}
    for s in span_list:
        name, cat, t0, dur, args = s[:5]
        # small stable tids (enumerate recording threads), not raw idents
        tid = tid_map[s[5]] if len(s) > 5 else 0
        ev = {"ph": "X", "pid": host_pid, "tid": tid, "name": name,
              "cat": cat, "ts": max(t0, 0.0) * 1e6, "dur": max(dur, 0.0) * 1e6}
        if args:
            ev["args"] = args
        if len(s) > 7:
            # the tree, for readers of the file (obs_report's self time)
            ev["span_id"], ev["parent_id"] = s[6], s[7]
        events.append(ev)
    for track, t, values in counter_list:
        events.append({"ph": "C", "pid": PID_COUNTERS, "name": track,
                       "ts": max(t, 0.0) * 1e6, "args": values})
    return events


def _shift_onto_xplane(perf_events: List[dict], xplane_events: List[dict],
                       trace_dir: Optional[str] = None) -> List[dict]:
    """Re-clock perf_counter-domain events (the counter tracks; phases ride
    the capture itself) onto the xplane trace's epoch.

    The two sources tick different clocks: our spans carry raw
    ``time.perf_counter()*1e6`` (epoch ~system boot) while the xplane
    chrome trace is normalized to its own capture start -- naively merged,
    every device event lands hours away from the host phases.  Anchor:
    ``profiler._agg.trace_anchor`` (perf_counter at ``start_trace``, keyed
    by the capture's trace_dir so a stale anchor from an earlier capture
    never re-clocks a different one) maps to the xplane events' minimum ts;
    without a matching one (capture not started through our profiler) fall
    back to aligning the two minima.  Spans that began before the capture
    clamp to ts 0.
    """
    base = min((float(e.get("ts", 0.0)) for e in xplane_events
                if e.get("ph") != "M"), default=None)
    if base is None:
        return perf_events
    from .. import profiler as _profiler
    anchor = getattr(_profiler._agg, "trace_anchor", None)
    # abspath-normalized compare: './tb' vs 'tb' vs 'tb/' is the same
    # capture and must not silently discard the anchor
    t0 = (anchor[1] if anchor is not None and anchor[0] is not None
          and trace_dir is not None
          and os.path.abspath(anchor[0]) == os.path.abspath(trace_dir)
          else None)
    if t0 is None:
        t0 = min((float(e.get("ts", 0.0)) for e in perf_events
                  if e.get("ph") != "M"), default=None)
        if t0 is None:
            return perf_events
    delta = base - t0
    out = []
    for e in perf_events:
        if e.get("ph") != "M":
            e = dict(e)
            e["ts"] = max(float(e.get("ts", 0.0)) + delta, 0.0)
        out.append(e)
    return out


def export_chrome_trace(output_path: str = "timeline.json",
                        trace_dir: Optional[str] = None,
                        include_profiler: bool = True) -> str:
    """Write the unified Chrome-trace/Perfetto JSON timeline.

    Host-only (``trace_dir=None``): the flight-recorder phase spans and
    counter tracks with the legacy profiler RecordEvent spans (same
    perf_counter clock -> same timeline).  With the ``trace_dir`` of a
    finished capture: the capture's own chrome trace -- device events,
    and the phases and RecordEvent spans that rode it as annotations --
    plus the counter tracks.  Returns ``output_path``.
    """
    from .. import profiler as _profiler
    src = (_profiler._find_xplane_chrome_trace(trace_dir)
           if trace_dir is not None else None)
    if trace_dir is not None and src is None:
        # same contract as profiler.export_chrome_tracing: a trace_dir with
        # no capture is a caller error (typo, capture never flushed) -- a
        # silent host-only file would masquerade as the device timeline
        raise FileNotFoundError(
            f"no xplane chrome trace (*.trace.json.gz) under {trace_dir!r};"
            f" pass the directory given to profiler(trace_dir=...) after "
            f"the capture stopped, or trace_dir=None for a host-only "
            f"timeline")
    if src is not None:
        return splice_into_xplane(src, trace_dir, output_path)
    events = _trace_events()
    if include_profiler:
        host = _profiler._host_span_events()
        # skip the metadata record when there are no spans behind it
        if len(host) > 1:
            events.extend(host)
    trace = {"traceEvents": events, "displayTimeUnit": "ms"}
    trace["traceEvents"].sort(key=lambda e: (e.get("ph") != "M",
                                             e.get("ts", 0.0)))
    with open(output_path, "w") as f:
        json.dump(trace, f)
    return output_path


def splice_into_xplane(src: str, trace_dir: Optional[str],
                       output_path: str) -> str:
    """Write the xplane chrome trace at ``src`` (gzip JSON) with this
    module's counter tracks re-clocked onto the capture's epoch; the file's
    own top-level keys (displayTimeUnit, metadata) are kept.  Phases and
    RecordEvent spans are NOT synthesized: both ride the capture as
    ``TraceAnnotation`` s, and a second copy would double-count every span
    in obs_report.  A phase's annotation carries its ``span_id``; where the
    ring still holds that span, the event gets what the host-only export
    gives it: the span's args and its ``span_id`` / ``parent_id``.  The
    single splice implementation behind both
    ``export_chrome_trace(trace_dir=...)`` and
    ``profiler.export_chrome_tracing``."""
    import gzip
    with gzip.open(src, "rt") as f:
        trace = json.load(f)
    # the profiler closes its event list with an empty object, which is no
    # trace event (validate_trace, rightly, refuses one)
    trace["traceEvents"] = [e for e in trace.get("traceEvents", []) if e]
    ring = {s.id: s for s in spans()}
    for e in trace["traceEvents"]:
        if e.get("name", "").startswith("paddle_tpu."):
            s = ring.get(int((e.get("args") or {}).get("span_id", 0)))
            if s is not None:
                e["args"] = dict(s.args or {})
                e["span_id"], e["parent_id"] = s.id, s.parent
    trace["traceEvents"].extend(_shift_onto_xplane(
        _trace_events(phases=False), trace["traceEvents"], trace_dir))
    trace["traceEvents"].sort(key=lambda e: (e.get("ph") != "M",
                                             e.get("ts", 0.0)))
    with open(output_path, "w") as f:
        json.dump(trace, f)
    return output_path


def validate_trace(path: str) -> List[dict]:
    """Load ``path`` and assert it is well-formed trace-event JSON with
    monotone-sortable, non-negative ts/dur.  Returns the event list (tests
    and obs_report use this instead of re-implementing the checks)."""
    with open(path) as f:
        trace = json.load(f)
    if isinstance(trace, dict):
        events = trace.get("traceEvents")
        if events is None:
            raise ValueError(
                f"{path}: no 'traceEvents' key -- not a Chrome trace "
                f"(a metrics dump? pass this file to --metrics instead)")
    else:
        events = trace
    if not isinstance(events, list):
        raise ValueError(f"{path}: traceEvents is not a list")
    last_ts = 0.0
    for e in events:
        if "ph" not in e:
            raise ValueError(f"{path}: event missing 'ph': {e}")
        if e["ph"] == "M":
            continue
        ts = float(e.get("ts", 0.0))
        if ts < 0 or float(e.get("dur", 0.0)) < 0:
            raise ValueError(f"{path}: negative ts/dur: {e}")
        if ts < last_ts:
            raise ValueError(f"{path}: events not sorted by ts at {e}")
        last_ts = ts
    return events
