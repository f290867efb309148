"""The harness: one cell, one run, one process, driven by data.

`run_cell` finds everything by name — the cell in `BENCHMARK.json`, its
files `cells/<cell>.json`, `traffic/<traffic>.json`,
`configs/<config>.json`, its driver `drivers/<driver>.py` and each
per-layer metric's reader `layer_metrics/<reader>.py` — so a later PR
adds a configuration, a traffic mix, a cell or a per-layer metric by
adding files and manifest entries, never by editing this file.

A per-layer metric's name in `BENCHMARK.json` is `<reader>` or
`<group>.<reader>`: the group only tells entries apart that share a reader
but move different end-to-end metrics (`train.device_idle_share`,
`chat.device_idle_share`).  A cell reports the per-layer metrics whose
`moves` it reports itself and whose optional `workloads` list names it.

A cell file with a `held_back` block is a cell that cannot be admitted to
`BENCHMARK.json` yet (the block says why, and when it can): the block
carries the manifest entries the cell will need, and the harness takes them
from there when the cell is run by hand.
"""
import contextlib
import importlib.util
import json
import os
import sys
import time

from benchmark import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME_OK = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
              "0123456789_.-")
TRACE_SLICE_S = 5.0        # a few seconds inside the window, not all of it
TRACE_START_SHARE = 0.25   # the slice begins a quarter into the window


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
def _read_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _check_name(name):
    if not name or not set(name) <= NAME_OK or not name[0].isalnum():
        raise ValueError(f"bad name {name!r}")
    return name


def load_module(root, kind, name):
    """`benchmark/<kind>/<name>.py`, imported from its path."""
    path = os.path.join(root, "benchmark", kind, _check_name(name) + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of `workloads` with its files read in."""

    def __init__(self, root, name):
        self.root = root
        self.name = _check_name(name)
        manifest = load_manifest(root)
        bdir = os.path.join(root, "benchmark")
        cell_path = os.path.join(bdir, "cells", name + ".json")
        self.cell = _read_json(cell_path) if os.path.isfile(cell_path) else {}
        entries = [w for w in manifest["workloads"] if w["name"] == name]
        # a cell that is held back (its file says why) has no entries in
        # BENCHMARK.json yet and brings them itself, to be run by hand
        self.held_back = self.cell.get("held_back") if not entries else None
        if self.held_back:
            entries = [self.held_back["workload"]]
            for key in ("end_to_end", "per_layer"):
                manifest[key] = manifest[key] + self.held_back[key]
        if not entries:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json (has: "
                f"{[w['name'] for w in manifest['workloads']]})")
        self.entry = entries[0]
        self.chips = int(self.entry["chips"])
        self.traffic = _read_json(os.path.join(
            bdir, "traffic", _check_name(self.entry["traffic"]) + ".json"))
        cfg = [c for c in manifest["configs"]
               if c["name"] == self.entry["config"]][0]
        self.config = _read_json(os.path.join(root, cfg["file"]))
        self.driver = self.traffic["driver"]
        # what the cell reports: its own end-to-end metrics and set-up ...
        self.reports = list(self.cell["reports"])
        if "setup_s" not in self.reports:
            self.reports.append("setup_s")
        self.end_to_end = {m["name"]: m for m in manifest["end_to_end"]
                           if m["name"] in self.reports}
        # ... and the per-layer metrics that move one of them
        self.per_layer = [
            m for m in manifest["per_layer"]
            if m["moves"] in self.reports
            and name in m.get("workloads", [name])]


# ---------------------------------------------------------------------------
# compile accounting (copied from chip_smoke.py's CompileClock)
# ---------------------------------------------------------------------------
class CompileClock:
    """Seconds JAX spent obtaining executables (XLA compile, or the load
    from the persistent cache), how many it obtained, and persistent-cache
    hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return {"seconds": self.seconds, "compiles": self.compiles,
                "hits": self.hits}


# ---------------------------------------------------------------------------
# the traced slice
# ---------------------------------------------------------------------------
class TraceSlice:
    """Starts the JAX profiler a quarter into the window and stops it a few
    seconds later.  Drivers call `poll(elapsed)` at a boundary of their loop
    (between train dispatches; in the load generator's tick) and get back
    "start" / "stop" at the two boundaries so they can note what their own
    counters read there.  The slice is wrapped in a `bench/slice`
    annotation: the reduction takes the traced window from it."""

    def __init__(self, enabled, log_dir, seconds):
        self.log_dir = log_dir
        self.start_at = seconds * TRACE_START_SHARE
        self.length = min(TRACE_SLICE_S, seconds / 3.0)
        self.state = "idle" if enabled else "done"
        self.t_start = None
        self._span = None

    def poll(self, elapsed):
        import jax
        if self.state == "idle" and elapsed >= self.start_at:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # host TraceMes, no Python
            jax.profiler.start_trace(self.log_dir, profiler_options=options)
            self._span = jax.profiler.TraceAnnotation("bench/slice")
            self._span.__enter__()
            self.t_start = time.perf_counter()
            self.state = "tracing"
            return "start"
        if self.state == "tracing" and \
                time.perf_counter() - self.t_start >= self.length:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"
            return "stop"
        return None

    def close(self):
        """Stop a slice the window ended inside of."""
        if self.state == "tracing":
            self.length = 0.0
            self.poll(0.0)

    def xplane_path(self):
        newest = None
        for dirpath, _, files in os.walk(self.log_dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    p = os.path.join(dirpath, f)
                    if newest is None or \
                            os.path.getmtime(p) > os.path.getmtime(newest):
                        newest = p
        return newest


# ---------------------------------------------------------------------------
# what a driver is handed, and what it fills in
# ---------------------------------------------------------------------------
class Run:
    """The state of one run.  The harness fills in the identity (cell,
    seed, devices) and the clocks; the driver calls `begin_window()` when
    set-up is over, measures, and leaves on this object what the metric
    readers take: `end_to_end` (name -> value), `counters`, `spans`,
    `samples`, `work`, `attempted`, `failed`, `correct`."""

    def __init__(self, cell, seed, seconds, trace, devices, t_process,
                 clock, log):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.config, self.traffic = cell.config, cell.traffic
        self.chips = cell.chips
        self.devices = list(devices)[:cell.chips]
        self.t_process = t_process
        self.clock = clock
        self.log = log
        trace_dir = os.path.join(cell.root, ".bench_trace", cell.name)
        self.slice = TraceSlice(bool(trace), trace_dir, self.seconds)
        self.setup_s = None
        self.setup_compile = None
        self.window_compile = None
        self.window_s = None
        self.end_to_end = {}
        self.counters = {}
        self.spans = []          # (name, t0, t1) on time.perf_counter()
        self.samples = {}
        self.work = None         # (flops, bytes) required inside the slice
        self.slice_units = None  # train steps / engine forwards in the slice
        self.attempted = self.failed = 0
        self.correct = False
        self.checks = {}
        self.trace = None
        self._t_window = None
        self._retraces0 = None

    @contextlib.contextmanager
    def span(self, name):
        """A host span of the benchmark's own, kept in memory and written
        into the profiler's trace as `bench/<name>` when one is running."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/" + name):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def begin_window(self):
        from paddle_tpu.core import compile_cache
        self.setup_compile = self.clock.snapshot()
        self._retraces0 = compile_cache.cache_stats()["traces"]
        self._t_window = time.perf_counter()
        self.setup_s = self._t_window - self.t_process
        return self._t_window

    def end_window(self, measured_s=None):
        """Close the window: `measured_s` where the driver measured a
        stretch of its own (the sending period, before a drain), else the
        time since `begin_window`.  Compilations count up to here."""
        from paddle_tpu.core import compile_cache
        self.slice.close()
        self.window_s = measured_s or time.perf_counter() - self._t_window
        now = self.clock.snapshot()
        self.window_compile = {
            "compiles": now["compiles"] - self.setup_compile["compiles"],
            "retraces": compile_cache.cache_stats()["traces"]
            - self._retraces0}


def memory_peak_bytes(devices):
    """The peak on the fullest chip.  On the v5e `peak_bytes_in_use` counts
    live arrays only and `peak_bytes_reserved` an executable's temporaries
    (PR 21), so the larger of the two is the best lower bound JAX gives."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, stats.get("peak_bytes_in_use") or 0,
                   stats.get("peak_bytes_reserved") or 0)
    return int(peak)


def run_cell(name, seed, seconds, trace, root=ROOT, require_tpu=True,
             t_process=None, log=print):
    """Run one cell once and return the result object (the last line).
    Raises, and returns nothing, when the device is not what the cell
    needs or the driver fails."""
    t_process = time.perf_counter() if t_process is None else t_process
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        raise SystemExit("benchmark: the program (paddle_tpu/) is not in "
                         "this checkout — nothing to measure")
    cell = Cell(root, name)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    log(f"device: platform={platform} kind={devices[0].device_kind!r} "
        f"count={len(devices)}; cell {name} needs {cell.chips}"
        + ("; held back, not in BENCHMARK.json" if cell.held_back else ""))
    if require_tpu and platform != "tpu":
        raise SystemExit(f"benchmark: platform is {platform!r}, not 'tpu' "
                         "— nothing was run")
    if len(devices) < cell.chips:
        raise SystemExit(f"benchmark: cell {name} needs {cell.chips} "
                         f"chips, JAX sees {len(devices)}")
    from paddle_tpu.core import compile_cache
    cache_dir = compile_cache.initialize()
    entries0 = compile_cache.persistent_entries()
    clock = CompileClock()

    run = Run(cell, seed, seconds, trace, devices, t_process, clock, log)
    driver = load_module(root, "drivers", cell.driver)
    driver.run(run)
    if run.setup_s is None or run.window_s is None:
        raise RuntimeError(f"driver {cell.driver} never opened or closed "
                           "its window")
    setup = run.setup_compile
    log(f"set-up {run.setup_s:.2f} s (compile {setup['seconds']:.2f} s, "
        f"{setup['compiles']} executables, {setup['hits']} cache hits); "
        f"cache {cache_dir} entries {entries0} -> "
        f"{compile_cache.persistent_entries()}")
    log(f"window {run.window_s:.2f} s: compilations inside "
        f"{run.window_compile['compiles']}, retraces "
        f"{run.window_compile['retraces']}")
    run.checks["no_compile_in_window"] = \
        run.window_compile["compiles"] == 0 and \
        run.window_compile["retraces"] == 0
    run.correct = bool(run.correct and all(run.checks.values()))
    log(f"checks: {run.checks}")

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak_bytes(run.devices)}
    metrics = {}
    result = {"correct": run.correct, "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": metrics,
              "device": device}
    if not trace:
        run.end_to_end["setup_s"] = run.setup_s
        for mname, m in cell.end_to_end.items():
            metrics[mname] = {"value": float(run.end_to_end[mname]),
                              "unit": m["unit"]}
        return result

    path = run.slice.xplane_path()
    if path is None:
        raise RuntimeError("the traced run left no .xplane.pb")
    run.trace = trace_reduce.summarize(path, n_devices=cell.chips)
    log(f"trace: {path} ({os.path.getsize(path)} bytes), window "
        f"{run.trace['window_s']:.3f} s, busy "
        f"{[round(b, 4) for b in run.trace['busy_s_per_device']]}")
    device["busy_s"] = run.trace["busy_s"]
    device["window_s"] = run.trace["window_s"]
    for m in cell.per_layer:
        reader = load_module(root, "layer_metrics", m["name"].split(".")[-1])
        value = reader.reduce(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["breakdown"] = {
        "device_ops": run.trace["top_ops"][:10],
        "idle_gaps": run.trace["idle_gaps"][:10]}
    return result


def result_line(result):
    return json.dumps(result)
