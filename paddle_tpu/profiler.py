"""Profiler front-end: host event recording + device trace + Chrome export.

Reference: /root/reference/paddle/fluid/platform/profiler.{h,cc}
(EnableProfiler/DisableProfiler :209-213, RAII RecordEvent :127, summary
tables), python/paddle/fluid/profiler.py (profiler context manager,
start_profiler/stop_profiler/reset_profiler) and tools/timeline.py (profile
→ chrome://tracing JSON).

TPU-native: host events are recorded here; DEVICE profiling delegates to
jax.profiler (XPlane → TensorBoard/perfetto — the CUPTI analog,
platform/device_tracer.h), started/stopped alongside the host profiler when
a trace dir is given.

`RecordEvent` is the program's ONE span mechanism (docs/observability.md
"Spans").  It enters a `jax.profiler.TraceAnnotation` under whatever
profiler session is live — started here, by a benchmark, by TensorBoard —
so a span lands on the host plane of that trace, on the device trace's
clock, with its fields as event stats.  A live session is the only switch.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional

__all__ = ["profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "RecordEvent", "record_event", "cuda_profiler",
           "npu_profiler", "export_chrome_tracing"]


class _Event:
    __slots__ = ("name", "start", "end", "thread", "fields", "parent")

    def __init__(self, name, start, end, thread, fields=None, parent=None):
        self.name = name
        self.start = start
        self.end = end
        self.thread = thread
        self.fields = fields or {}
        self.parent = parent    # name of the innermost span open on the
        # same thread when this one began (None at the top)


class _ProfilerState:
    def __init__(self):
        self.enabled = False
        self.events: List[_Event] = []
        self.lock = threading.Lock()
        self.t0 = 0.0
        self.jax_trace_dir: Optional[str] = None


_state = _ProfilerState()


def start_profiler(state="All", tracer_option="Default", trace_dir=None):
    """profiler.py start_profiler parity.  state: CPU/GPU/All (GPU/All also
    start the jax device profiler when trace_dir is given)."""
    with _state.lock:
        _state.enabled = True
        _state.events = []
        _state.t0 = time.perf_counter()
        if trace_dir and state in ("GPU", "All"):
            try:
                import jax
                jax.profiler.start_trace(trace_dir)
                _state.jax_trace_dir = trace_dir
            except (ImportError, RuntimeError):
                _state.jax_trace_dir = None


def stop_profiler(sorted_key="total", profile_path="/tmp/profile"):
    """profiler.py stop_profiler: stop, print the summary table, write the
    chrome trace next to profile_path."""
    with _state.lock:
        _state.enabled = False
        if _state.jax_trace_dir:
            try:
                import jax
                jax.profiler.stop_trace()
            except (ImportError, RuntimeError):
                pass
        _state.jax_trace_dir = None
        events = list(_state.events)
    _print_summary(events, sorted_key)
    if profile_path:
        export_chrome_tracing(profile_path + ".json", events)


def reset_profiler():
    with _state.lock:
        _state.events = []
        _state.t0 = time.perf_counter()


def _print_summary(events: List[_Event], sorted_key):
    agg: Dict[str, List[float]] = {}
    for e in events:
        agg.setdefault(e.name, []).append(e.end - e.start)
    rows = []
    for name, ds in agg.items():
        rows.append((name, len(ds), sum(ds), sum(ds) / len(ds),
                     min(ds), max(ds)))
    key_idx = {"calls": 1, "total": 2, "ave": 3, "min": 4, "max": 5}.get(
        str(sorted_key), 2)
    rows.sort(key=lambda r: r[key_idx], reverse=True)
    print(f"{'Event':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>10}"
          f"{'Min(ms)':>10}{'Max(ms)':>10}")
    print("-" * 90)
    for name, calls, tot, ave, mn, mx in rows:
        print(f"{name:<40}{calls:>8}{tot * 1e3:>12.3f}{ave * 1e3:>10.3f}"
              f"{mn * 1e3:>10.3f}{mx * 1e3:>10.3f}")


def export_chrome_tracing(path: str, events: Optional[List[_Event]] = None):
    """tools/timeline.py analog: chrome://tracing JSON."""
    events = events if events is not None else list(_state.events)
    trace = {"traceEvents": [
        {"name": e.name, "cat": "host", "ph": "X",
         "ts": e.start * 1e6, "dur": (e.end - e.start) * 1e6,
         "pid": 0, "tid": e.thread,
         "args": dict(e.fields, parent=e.parent)}
        for e in events]}
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


# jax.profiler cached ONCE (None = not yet resolved, False = absent):
# RecordEvent.__enter__ sits inside Executor.run and the engine's decode
# loop, and re-running the import machinery on every span cost real
# hot-path time
_jax_profiler = None
# the spans open on this thread while the in-memory profiler is on
_open = threading.local()


def _resolve_jax_profiler():
    global _jax_profiler
    if _jax_profiler is None:
        try:
            import jax
            _jax_profiler = jax.profiler
        except (ImportError, AttributeError):
            _jax_profiler = False
    return _jax_profiler


class RecordEvent:
    """RAII host span (platform/profiler.h:127) with optional fields:
    ``RecordEvent("engine/prefill", req=7, prompt=96)``.

    Enters a `jax.profiler.TraceAnnotation` whenever a profiler session
    is live — whoever started it: the span lands on the host plane of the
    device trace with its fields as event stats.  With none live that is
    one C++ flag check (`is_enabled`) and nothing is built or recorded.
    Under `start_profiler()` it is also kept in memory (summary table,
    Chrome export) with its fields and its `parent`: the innermost span
    open on the same thread.  Cause across threads travels in a field (`req`),
    not in a pointer."""

    __slots__ = ("name", "fields", "_t", "_parent", "_jax_ctx")

    def __init__(self, name: str, **fields):
        self.name = name
        self.fields = fields
        self._t = None
        self._parent = None
        self._jax_ctx = None

    def set(self, **fields):
        """Fields known only once the work is under way (bytes fetched,
        the bucket chosen); call before the span closes."""
        self.fields.update(fields)
        if self._jax_ctx is not None:
            self._jax_ctx.set_metadata(**fields)

    def __enter__(self):
        prof = _jax_profiler or _resolve_jax_profiler()
        if prof and prof.TraceAnnotation.is_enabled():
            self._jax_ctx = prof.TraceAnnotation(self.name, **self.fields)
            self._jax_ctx.__enter__()
        if _state.enabled:
            stack = _open.__dict__.setdefault("stack", [])
            self._parent = stack[-1] if stack else None
            stack.append(self.name)
            self._t = time.perf_counter() - _state.t0
        return self

    def __exit__(self, *a):
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(*a)
        if self._t is not None:
            end = time.perf_counter() - _state.t0
            stack = _open.__dict__.get("stack")
            if stack:
                stack.pop()
            with _state.lock:
                _state.events.append(_Event(
                    self.name, self._t, end, threading.get_ident(),
                    self.fields, self._parent))
        return False


record_event = RecordEvent


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path="/tmp/profile",
             tracer_option="Default", trace_dir=None):
    """fluid.profiler.profiler context manager parity."""
    start_profiler(state, tracer_option, trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*a, **kw):
    """No CUDA on TPU; kept for API parity (wraps the jax trace)."""
    yield


npu_profiler = cuda_profiler
