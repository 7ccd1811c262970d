"""Profiler: JAX/XLA trace capture + host-side op aggregate table.

Reference: platform/profiler.{h,cc} (RecordEvent push/pop, EnableProfiler states),
platform/device_tracer.* (CUPTI kernel records), tools/timeline.py (Chrome trace).

TPU-native mapping (SURVEY.md §5.1): device-side timing comes from the JAX/XLA
profiler (xplane traces, viewable in TensorBoard/Perfetto -- the chrome://tracing
analog); host-side RecordEvent annotations use jax.profiler.TraceAnnotation so they
appear on the same timeline; and an aggregate per-label table mirrors the reference's
printed op-time summary.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Optional


class _Agg(threading.local):
    def __init__(self):
        # plain dict, NOT defaultdict: a read (summary/report on a name that
        # never fired) must not materialize an empty row as a side effect
        self.times: Dict[str, list] = {}
        self.spans: list = []   # (name, start_s, dur_s) for timeline export
        self.enabled = False


_agg = _Agg()


@contextlib.contextmanager
def record_event(name: str):
    """RAII host annotation (reference RecordEvent, profiler.h:81)."""
    import jax
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    if _agg.enabled:
        dt = time.perf_counter() - t0
        _agg.times.setdefault(name, []).append(dt)
        _agg.spans.append((name, t0, dt))
        # mirror every span into the metrics registry (one histogram per
        # event label) so the aggregate table and the registry cannot
        # disagree -- both are fed from this single append site
        from .observability.metrics import REGISTRY
        REGISTRY.histogram("profiler_event_seconds",
                           "RecordEvent span durations by event label",
                           event=name).observe(dt)


class RecordEvent:
    def __init__(self, name):
        self.name = name
        self._cm = None

    def __enter__(self):
        self._cm = record_event(self.name)
        return self._cm.__enter__()

    def __exit__(self, *exc):
        return self._cm.__exit__(*exc)


def start_profiler(state: str = "All", trace_dir: Optional[str] = None):
    """Reference EnableProfiler. state kept for parity (CPU/GPU/All); the XLA
    trace always captures both host and device."""
    import jax
    _agg.enabled = True
    _agg.times.clear()
    # spans too: they feed every timeline export now, and a second session
    # must not carry the previous one's RecordEvent spans (pre-capture
    # spans would delta-shift negative and pile up clamped at ts 0)
    _agg.spans.clear()
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        _agg.trace_dir = trace_dir
        # capture start on the host perf_counter clock, keyed by trace_dir:
        # the xplane chrome trace uses its own ts epoch, and this anchor is
        # what lets the flight-recorder spans be shifted onto it at export
        # time (kept past stop_profiler -- export happens after stop -- but
        # only ever applied to THIS capture's directory)
        _agg.trace_anchor = (trace_dir, time.perf_counter() * 1e6)
    else:
        _agg.trace_dir = None


def stop_profiler(sorted_key: str = "total", profile_path: Optional[str] = None):
    """Reference DisableProfiler: stop + emit the aggregate table.

    With ``profile_path`` the table goes to that file and is returned --
    not printed (a profiler(profile_path=...) context must not spam
    stdout); without a path it prints, as the reference did."""
    import jax
    if getattr(_agg, "trace_dir", None):
        jax.profiler.stop_trace()
        _agg.trace_dir = None  # capture is finished; a later stop/reset
        #                        must not touch the (now idle) tracer
    _agg.enabled = False
    table = summary(sorted_key)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(table)
    else:
        print(table)
    return table


def summary(sorted_key: str = "total") -> str:
    """Aggregate table; on an empty/never-enabled aggregate, a well-formed
    header + explicit empty marker (never a KeyError or a defaultdict
    side-effect row)."""
    rows = []
    for name, ts in _agg.times.items():
        if not ts:
            continue
        rows.append((name, len(ts), sum(ts), sum(ts) / len(ts), min(ts),
                     max(ts)))
    key_idx = {"calls": 1, "total": 2, "ave": 3, "min": 4, "max": 5}.get(
        sorted_key, 2)
    rows.sort(key=lambda r: r[key_idx], reverse=True)
    lines = [f"{'Event':<40}{'Calls':>8}{'Total(s)':>12}{'Avg(s)':>12}"
             f"{'Min(s)':>12}{'Max(s)':>12}"]
    for r in rows:
        lines.append(f"{r[0]:<40}{r[1]:>8}{r[2]:>12.6f}{r[3]:>12.6f}"
                     f"{r[4]:>12.6f}{r[5]:>12.6f}")
    if not rows:
        lines.append("(no events recorded)")
    return "\n".join(lines)


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: str = "total",
             profile_path: Optional[str] = None, trace_dir: Optional[str] = None):
    """``with profiler.profiler():`` context (reference fluid/profiler.py)."""
    start_profiler(state, trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


def reset_profiler():
    _agg.times.clear()
    _agg.spans.clear()
    if getattr(_agg, "trace_dir", None):
        # a trace is still ACTIVE: stop (discard) it before clearing, else
        # the tracer is leaked and the next start_profiler(trace_dir=...)
        # raises "profiler has already been started"
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        _agg.trace_dir = None


# --------------------------------------------------------------------------
# chrome://tracing export (reference tools/timeline.py:36 Timeline)
# --------------------------------------------------------------------------

def _find_xplane_chrome_trace(trace_dir: str) -> Optional[str]:
    import glob
    paths = glob.glob(f"{trace_dir}/**/*.trace.json.gz", recursive=True)
    return sorted(paths)[-1] if paths else None


def _host_span_events(pid: int = 90000):
    """Our RecordEvent spans as chrome trace events (used when no xplane
    capture exists; with one, the same spans already ride the timeline via
    TraceAnnotation)."""
    events = [
        {"ph": "M", "pid": pid, "name": "process_name",
         "args": {"name": "paddle_tpu host (RecordEvent)"}},
    ]
    # spans append at scope EXIT (inner before outer): sort by start so the
    # exported timeline is monotone in ts
    for name, t0, dt in sorted(_agg.spans, key=lambda s: s[1]):
        events.append({"ph": "X", "pid": pid, "tid": 0, "name": name,
                       "ts": max(t0, 0.0) * 1e6, "dur": max(dt, 0.0) * 1e6,
                       "cat": "host"})
    return events


def export_chrome_tracing(trace_dir: Optional[str] = None,
                          output_path: str = "timeline.json") -> str:
    """Write a plain chrome://tracing / Perfetto-loadable JSON timeline.

    With ``trace_dir`` (a directory passed to start_profiler/profiler):
    decompresses the newest xplane chrome trace -- host TraceAnnotation
    spans and device (TPU) op events share that timeline. Without one:
    synthesizes the timeline from the host RecordEvent spans alone.
    Returns output_path (reference tools/timeline.py converted the profiler
    proto the same way).
    """
    src = _find_xplane_chrome_trace(trace_dir) if trace_dir else None
    if trace_dir and src is None:
        raise FileNotFoundError(
            f"no xplane chrome trace (*.trace.json.gz) under {trace_dir!r}; "
            f"pass the directory given to profiler(trace_dir=...) after the "
            f"capture stopped, or call with trace_dir=None for a host-only "
            f"timeline")
    from .observability import timeline as _obs_timeline
    if src is not None:
        # the flight recorder's counter tracks ride along on their own pid
        # (its phases, like the RecordEvent spans, already appear in the
        # xplane capture via TraceAnnotation -- not re-synthesized here)
        return _obs_timeline.splice_into_xplane(src, trace_dir, output_path)
    if not _agg.spans and not _obs_timeline.spans():
        raise ValueError(
            "nothing to export: pass the trace_dir used with "
            "profiler()/start_profiler, or record host events first "
            "(FLAGS_profile_executor=1 records one span per "
            "executor run)")
    # host-only synthesis: RecordEvent spans + flight-recorder phase spans
    # share one timeline (observability.timeline merges both rings)
    return _obs_timeline.export_chrome_trace(output_path, trace_dir=None,
                                             include_profiler=True)


def merge_chrome_traces(paths, output_path: str = "timeline.json") -> str:
    """Merge per-process chrome traces into one timeline with disjoint pids
    (the reference tools/timeline.py multi-process merge: each input's pids
    are offset and labeled with the source index)."""
    import gzip
    import json

    merged = {"traceEvents": []}
    # cumulative offsets: each input's range starts past the previous input's
    # max pid, so re-merging an already-merged timeline (pids >= 100000)
    # cannot collide with a later input's range.
    offset = 0
    for i, p in enumerate(paths):
        try:
            op = gzip.open(p, "rt") if str(p).endswith(".gz") else open(p)
        except OSError as e:
            raise FileNotFoundError(
                f"merge_chrome_traces: input {i} ({p!r}) cannot be opened: "
                f"{e}") from e
        with op as f:
            try:
                t = json.load(f)
            except (ValueError, EOFError, OSError) as e:
                # EOFError/BadGzipFile: a .gz capture truncated mid-write
                # surfaces during json.load's reads, not at open
                raise ValueError(
                    f"merge_chrome_traces: input {i} ({p!r}) is not valid "
                    f"trace JSON (empty or truncated capture?): {e}") from e
        events = t.get("traceEvents", [])
        pids = [int(e["pid"]) for e in events if "pid" in e]
        base = offset - min(pids) if pids else offset
        for e in events:
            e = dict(e)
            if "pid" in e:
                e["pid"] = base + int(e["pid"])
            if e.get("ph") == "M" and e.get("name") == "process_name":
                e.setdefault("args", {})
                e["args"]["name"] = (f"proc{i}: "
                                     f"{e['args'].get('name', '')}")
            merged["traceEvents"].append(e)
        offset = base + (max(pids) if pids else 0) + 1
    # inputs are each internally sorted but their ts ranges overlap (per-
    # process captures of the same run), so the concatenation drops back at
    # every file boundary -- re-sort or validate_trace / obs_report --trace
    # reject the merged file as unsorted
    merged["traceEvents"].sort(key=lambda e: (e.get("ph") != "M",
                                              float(e.get("ts", 0.0))))
    with open(output_path, "w") as f:
        json.dump(merged, f)
    return output_path


import contextlib as _contextlib


@_contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """Reference profiler.py:cuda_profiler (nvprof hooks). There is no CUDA
    here; the xplane trace (profiler()/start_profiler) covers the TPU. Kept
    as a no-op context so ported scripts run."""
    yield
