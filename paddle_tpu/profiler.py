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

`Phase` is a `RecordEvent` whose record is ALWAYS kept in memory, session
or none: the account of start-up (import, weights, programs obtained).  A
phase site runs once a process, a model, an engine or a program obtained,
never a step, a dispatch of a cached program, a request, an op or a
parameter; the steady state executes no phase code.  JAX's own stages
(trace, lower, compile, cache load) are booked beside them by one pair
of `jax.monitoring` listeners, each under the phase open on its thread.

ONE clock: every time in this module is `time.perf_counter()`, absolute.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional

__all__ = ["profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "RecordEvent", "record_event", "Phase", "record_phase", "phases",
           "phases_dropped", "PHASES_MAX", "NO_PHASE", "cuda_profiler",
           "npu_profiler", "export_chrome_tracing"]

# the kept records' bound: a process books a few hundred to ~1,500 (an
# eager route's per-op executables are three JAX stages and a cache load
# each); what does not fit is counted (`phases_dropped`), the oldest stay
PHASES_MAX = 4096


class _Event:
    __slots__ = ("name", "start", "end", "thread", "fields", "parent")

    def __init__(self, name, start, end, thread, fields=None, parent=None):
        self.name = name
        self.start = start
        self.end = end
        self.thread = thread
        self.fields = {} if fields is None else fields
        # the innermost span open on the same thread when this one began
        # (None at the top): its NAME for a span kept by `start_profiler`,
        # the parent's own record for a kept `Phase` record
        self.parent = parent


class _ProfilerState:
    def __init__(self):
        self.enabled = False
        self.events: List[_Event] = []
        self.lock = threading.Lock()
        self.jax_trace_dir: Optional[str] = None
        # `Phase` records, kept with or without a session
        self.kept: List[_Event] = []
        self.dropped = 0


_state = _ProfilerState()


def start_profiler(state="All", tracer_option="Default", trace_dir=None):
    """profiler.py start_profiler parity.  state: CPU/GPU/All (GPU/All also
    start the jax device profiler when trace_dir is given)."""
    with _state.lock:
        _state.enabled = True
        _state.events = []
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
    """Forget every recorded span, the kept `Phase` records among them."""
    with _state.lock:
        _state.events = []
        _state.kept = []
        _state.dropped = 0


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
    """tools/timeline.py analog: chrome://tracing JSON, times from the
    first span's start."""
    events = events if events is not None else list(_state.events)
    t0 = min((e.start for e in events), default=0.0)
    trace = {"traceEvents": [
        {"name": e.name, "cat": "host", "ph": "X",
         "ts": (e.start - t0) * 1e6, "dur": (e.end - e.start) * 1e6,
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
            self._t = time.perf_counter()
        return self

    def __exit__(self, *a):
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(*a)
        if self._t is not None:
            end = time.perf_counter()
            stack = _open.__dict__.get("stack")
            if stack:
                stack.pop()
            with _state.lock:
                _state.events.append(_Event(
                    self.name, self._t, end, threading.get_ident(),
                    self.fields, self._parent))
        return False


record_event = RecordEvent


# ---------------------------------------------------------------------------
# kept spans: the account of start-up
# ---------------------------------------------------------------------------
def _keep(rec: _Event):
    """Store a closed kept record (or count it as dropped) and add it to
    `core/monitor`: `phase.<name>.us` / `phase.<name>.calls`, so `/stats`
    and `/metrics` say what a replica's start-up went on with no trace."""
    from .core.monitor import stat_add
    with _state.lock:
        if len(_state.kept) < PHASES_MAX:
            _state.kept.append(rec)
        else:
            _state.dropped += 1
    stat_add(f"phase.{rec.name}.us", int((rec.end - rec.start) * 1e6))
    stat_add(f"phase.{rec.name}.calls")


def _kept_top() -> Optional[_Event]:
    """The innermost kept span open on this thread."""
    kept = _open.__dict__.get("kept")
    return kept[-1] if kept else None


def _open_kept(name, fields) -> _Event:
    """A kept record begun now on this thread, on its stack of open ones."""
    rec = _Event(name, time.perf_counter(), None, threading.get_ident(),
                 fields, _kept_top())
    _open.__dict__.setdefault("kept", []).append(rec)
    return rec


def _close_kept(rec: _Event):
    """End `rec` now and take it off its thread's stack, with whatever
    was left open above it (a stage JAX began and never ended)."""
    rec.end = time.perf_counter()
    kept = _open.kept
    while kept and kept.pop() is not rec:
        pass


class Phase(RecordEvent):
    """A `RecordEvent` whose record is ALWAYS kept, session or none
    (`phases()`): name, start and end on `time.perf_counter()`, thread,
    fields, and `parent` — the record of the innermost kept span open on
    the same thread.  Under a live profiler session it annotates as any
    span does.  For work done once a process, a model, an engine or a
    program obtained (docs/observability.md §6 has the sites); never on a
    path that runs again with everything warm."""

    __slots__ = ("_rec",)

    def __enter__(self):
        _listen_to_jax()
        super().__enter__()
        self._rec = _open_kept(self.name, self.fields)
        return self

    def __exit__(self, *a):
        _close_kept(self._rec)
        _keep(self._rec)
        return super().__exit__(*a)


# what a site holds in place of a `Phase` on the path that obtained nothing
# new (`with RecordEvent(...), first_launch:`): no record, no phase code
NO_PHASE = contextlib.nullcontext()


def record_phase(name: str, start: float, end: float, **fields) -> _Event:
    """Book a kept record whose interval (`time.perf_counter()` seconds) is
    known only afterwards; its parent is the kept span open on this thread
    now.  No annotation: a profiler session takes no event after the fact."""
    _listen_to_jax()
    rec = _Event(name, start, end, threading.get_ident(), fields,
                 _kept_top())
    _keep(rec)
    _into_session(rec)
    return rec


def _into_session(rec: _Event):
    """Under `start_profiler()` a kept record that no `RecordEvent` wrote
    joins the in-memory spans, its parent there a name like theirs."""
    if _state.enabled:
        stack = _open.__dict__.get("stack")
        with _state.lock:
            _state.events.append(_Event(
                rec.name, rec.start, rec.end, rec.thread, rec.fields,
                stack[-1] if stack else None))


def phases() -> List[_Event]:
    """The kept records closed so far, in the order they closed."""
    with _state.lock:
        return list(_state.kept)


def phases_dropped() -> int:
    """Kept records that found the store full (`PHASES_MAX`)."""
    return _state.dropped


# JAX's stages of obtaining an executable, as kept spans: JAX reports each
# stage's start (a scalar) and its duration at its end, on the thread that
# does the work, so a stage lands under the phase that asked for the
# program, a cache load under its `jax/compile`
_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax/lower",
    "/jax/core/compile/backend_compile_duration": "jax/compile",
}
# A `jax/trace` is kept when it is a program's.  One made inside another
# JAX stage (`jnp`'s own jitted helpers traced inside a program's trace,
# functions lowered as calls) is that stage's work.  One shorter than this
# is an op's shape inference while Program IR is built (~2 ms each, 2,700
# of them for BERT-base) or an eager op's: its time stays in its parent's
# self time, which says how much of that it was (`short_traces`,
# `short_trace_s`); a program's trace takes tenths of a second to seconds.
_TRACE_MIN_S = 0.02
_JAX_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAX_TIME_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_listening = False


def _on_jax_stage_start(event, _value, fun_name="", **_):
    name = _JAX_STAGES.get(event)
    if name is not None:
        _open_kept(name, {"fun": fun_name})


def _on_jax_duration(event, secs, fun_name="", **_):
    if event == _JAX_TIME_SAVED:    # reported just before its retrieval
        _open.saved_s = secs
    elif event == _JAX_CACHE_LOAD:
        end = time.perf_counter()
        record_phase("jax/cache_load", end - secs, end,
                     saved_s=_open.__dict__.pop("saved_s", 0.0))
    elif event in _JAX_STAGES:
        name = _JAX_STAGES[event]
        rec = _kept_top()
        if rec is None or rec.name != name or rec.fields.get("fun") \
                != fun_name:
            # an end whose start this listener never saw
            end = time.perf_counter()
            record_phase(name, end - secs, end, fun=fun_name)
            return
        _close_kept(rec)
        if name == "jax/compile":
            # the phase that asked counts what it obtained: a site that
            # waits for a program to settle reads `executables`
            asked = rec.parent
            while asked is not None and asked.name.startswith("jax/"):
                asked = asked.parent
            if asked is not None:
                asked.fields["executables"] = \
                    asked.fields.get("executables", 0) + 1
        if name == "jax/trace" and rec.parent is not None \
                and rec.parent.name.startswith("jax/"):
            return      # that stage's own work
        if name == "jax/trace" and rec.end - rec.start < _TRACE_MIN_S:
            if rec.parent is not None:      # its parent's own time, named
                f = rec.parent.fields
                f["short_traces"] = f.get("short_traces", 0) + 1
                f["short_trace_s"] = round(
                    f.get("short_trace_s", 0.0) + rec.end - rec.start, 6)
            return
        _keep(rec)
        _into_session(rec)


def _listen_to_jax():
    """Register the one pair of `jax.monitoring` listeners (a stage's
    start, a stage's end), on the first phase."""
    global _listening
    if _listening:
        return
    with _state.lock:
        if _listening:
            return
        _listening = True
    try:
        import jax.monitoring as monitoring
    except ImportError:
        return
    monitoring.register_scalar_listener(_on_jax_stage_start)
    monitoring.register_event_duration_secs_listener(_on_jax_duration)


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
