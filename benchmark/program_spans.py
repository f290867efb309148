"""The program's own spans, read back from a profiler trace.

`paddle_tpu.profiler.RecordEvent` writes a `jax.profiler.TraceAnnotation`
whenever a profiler session is live, so the benchmark's traced slice holds
the program's spans on the host plane, on the device trace's clock, one
line per thread, with their fields (`req`, `bytes`, `waited_ms`, ...) as
event stats.  This module turns them into what the per-layer readers take:

- `Span`s clipped to the `bench/slice` window, each with its `parent` (the
  innermost span enclosing it on the same thread) and its self time
  (duration less its children's);
- totals by name, per thread;
- the thread that feeds the device (the engine's decode loop when serving,
  the dispatching thread when training);
- the idle-gap attribution of `trace_reduce.attribute`, run again over the
  feeding thread with the program's spans added to the benchmark's.

A program without spans (the parent of the PR that added them) gives empty
lists and every reader over them returns None.  The interval arithmetic is
`trace_reduce`'s; everything works on plain `Span` lists so tests drive it
with hand-built events.
"""
import gzip

from benchmark import trace_reduce

PREFIXES = ("Executor::", "executor/", "mesh/", "prefetcher/", "engine/",
            "server/")
ENGINE_LOOP = ("engine/idle", "engine/admit", "engine/prefill",
               "engine/step")
DISPATCH = ("Executor::Run", "Executor::RunSteps")
OPEN_AT_START = "(an engine span open when the slice began)"
OPEN_AT_END = "(an engine span open when the slice ended)"


class Span:
    """One host span: times in ns on the trace's clock, `line` the index
    of its thread's line on the host plane, `fields` its event stats.
    `nest` fills in `parent` (a Span or None) and `self_ns`."""
    __slots__ = ("name", "start", "end", "line", "fields", "parent",
                 "self_ns")

    def __init__(self, name, start, end, line=0, fields=None):
        self.name, self.start, self.end = name, start, end
        self.line, self.fields = line, fields or {}
        self.parent, self.self_ns = None, end - start

    @property
    def ns(self):
        return self.end - self.start

    def __repr__(self):
        return f"Span({self.name!r}, {self.start}, {self.end}, " \
               f"line={self.line}, {self.fields})"


def nest(spans):
    """Set each span's parent — the innermost span that encloses it on the
    same line — and its self time; returns the spans sorted by line and
    start."""
    spans = sorted(spans, key=lambda sp: (sp.line, sp.start, -sp.end))
    stack = []
    for sp in spans:
        sp.parent, sp.self_ns = None, sp.ns
        while stack and (stack[-1].line != sp.line
                         or stack[-1].end <= sp.start):
            stack.pop()
        if stack:
            sp.parent = stack[-1]
            stack[-1].self_ns -= min(sp.end, stack[-1].end) - sp.start
        stack.append(sp)
    return spans


def clip(spans, window):
    """The parts of `spans` inside `window`, as new Spans."""
    lo, hi = window
    return [Span(sp.name, max(sp.start, lo), min(sp.end, hi), sp.line,
                 sp.fields) for sp in spans
            if min(sp.end, hi) > max(sp.start, lo)]


def totals(spans, line=None):
    """{name: {"count", "ns", "self_ns"}} of nested spans, on one line or
    on all."""
    out = {}
    for sp in spans:
        if line is not None and sp.line != line:
            continue
        t = out.setdefault(sp.name, {"count": 0, "ns": 0, "self_ns": 0})
        t["count"] += 1
        t["ns"] += sp.ns
        t["self_ns"] += sp.self_ns
    return out


def feeding_line(spans):
    """(line, loop names) of the thread that feeds the device: the one
    that spent longest in the engine's loop where there is an engine, else
    in `Executor::Run*`; (None, ()) without either."""
    for names in (ENGINE_LOOP, DISPATCH):
        by_line = {}
        for sp in spans:
            if sp.name in names:
                by_line[sp.line] = by_line.get(sp.line, 0) + sp.ns
        if by_line:
            return max(by_line, key=by_line.get), names
    return None, ()


def assemble(spans, window, events=(), busy=()):
    """What `load` returns, from the program's Spans and the window:
    {"window", "spans": nested, clipped to the window; "whole": those that
    began inside it, unclipped; "line": the feeding thread's; "extent":
    from the start of that thread's first whole loop span to the end of
    its last; "loop": that thread's spans nested and clipped to the
    extent; "engine": that loop is an engine's; "host": `events` (other
    host events, as (name, start, end, line)) and the spans alike; "busy"
    as given}.

    Why an extent: a TraceMe is recorded only if it begins and ends inside
    the profiler session, so the span the thread was in when the slice
    began, and the one it was in when the slice ended, are missing (their
    finished children are there, orphaned).  The engine's thread is always
    inside a loop span, so between the first and the last whole one every
    instant is accounted for; shares of the thread's time are taken over
    that stretch."""
    whole = nest([sp for sp in spans if window[0] <= sp.start < window[1]])
    line, names = feeding_line(whole)
    tops = [sp for sp in whole if sp.line == line and sp.name in names]
    extent = (tops[0].start, min(window[1], max(sp.end for sp in tops))) \
        if tops else None
    return {"window": window, "spans": nest(clip(spans, window)),
            "whole": whole, "line": line, "extent": extent,
            "loop": nest(clip([sp for sp in spans if sp.line == line],
                              extent)) if extent else [],
            "engine": names == ENGINE_LOOP,
            "host": list(events) + [(sp.name, sp.start, sp.end, sp.line)
                                    for sp in spans],
            "busy": list(busy)}


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------
def load(path):
    """`assemble` of one pass over a `.xplane.pb`: the window is the
    `bench/slice` annotation, "host" every host event of the benchmark's
    filter or the program's as (name, start, end, line), "busy" chip 0's
    merged busy intervals inside the window."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    spans, events, window, ops = [], [], None, []
    first_device = None
    for plane in data.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m and (first_device is None or int(m.group(1)) < first_device):
            first_device = int(m.group(1))
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                name = e.name
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if name == trace_reduce.SLICE_SPAN:
                    window = window or (s, t)
                elif name.startswith(PREFIXES):
                    spans.append(Span(name, s, t, i, dict(e.stats)))
                else:
                    events.append((name, s, t, i))
    if window is None:
        edges = [x for sp in spans for x in (sp.start, sp.end)] + \
            [x for _, s, t in ops for x in (s, t)]
        window = (min(edges), max(edges)) if edges else (0, 0)
    busy = trace_reduce.reduce_device(
        {"ops": ops, "modules": []}, window)["busy"] if ops else []
    return assemble(spans, window, trace_reduce._host_spans(events), busy)


def of(run):
    """The run's trace, parsed once and kept on `run` for every reader."""
    if getattr(run, "program_spans", None) is None:
        run.program_spans = load(run.slice.xplane_path())
        report(run, run.program_spans)
    return run.program_spans


def reattribute(parsed):
    """Chip 0's idle time inside the window by what the feeding thread was
    doing: ({span: ns} over the program's spans alone, {span: ns} with the
    benchmark's own filter — `bench/...` and JAX's TraceMes — added).
    Both empty where the program has no spans.  The two stretches at the
    window's edges in which an engine's thread sat in a span that was not
    recorded (`assemble`) are named for what they are."""
    line = parsed["line"]
    if line is None:
        return {}, {}
    (lo, hi), idle = parsed["window"], [
        g for g in trace_reduce.gaps(parsed["busy"], parsed["window"])
        if g[1] - g[0] >= trace_reduce.MIN_GAP_NS]
    on_line = [h[:3] for h in parsed["host"] if h[3] == line]
    mine = [h for h in on_line if h[0].startswith(PREFIXES)]
    edges = [(OPEN_AT_START, lo, parsed["extent"][0]),
             (OPEN_AT_END, parsed["extent"][1], hi)] \
        if parsed["engine"] else []
    return (trace_reduce.attribute(idle, mine + edges),
            trace_reduce.attribute(idle, on_line + edges))


def split(parsed, parent):
    """How the spans named `parent` that began in the window divide among
    their children: (count, mean ms, {child or "(self)": (mean ms, mean
    bytes moved)}); None where there is none."""
    whole = parsed["whole"]
    parents = [sp for sp in whole if sp.name == parent]
    if not parents:
        return None
    n = float(len(parents))
    by_child = {"(self)": [sum(sp.self_ns for sp in parents), 0]}
    for sp in whole:
        if sp.parent is not None and sp.parent.name == parent:
            t = by_child.setdefault(sp.name, [0, 0])
            t[0] += sp.ns
            t[1] += int(sp.fields.get("bytes", 0))
    return (len(parents), sum(sp.ns for sp in parents) / n / 1e6,
            {name: (ns / n / 1e6, b / n) for name, (ns, b)
             in by_child.items()})


def report(run, parsed, top=12):
    """Print through `run.log` the program's spans by name and the
    re-attribution of the device's idle time."""
    if not parsed["spans"]:
        run.log("program_spans: the trace holds no span of the program's")
        return
    lo, hi = parsed["window"]
    a, b = parsed["extent"] or (lo, lo)
    run.log(f"program_spans: window {(hi - lo) / 1e9:.3f} s, feeding "
            f"thread is host line {parsed['line']}, its whole loop spans "
            f"cover {(b - a) / 1e9:.3f} s of it")
    rows = sorted(totals(parsed["spans"]).items(),
                  key=lambda kv: -kv[1]["ns"])
    for name, t in rows[:2 * top]:
        run.log(f"  span {name}: n={t['count']} total "
                f"{t['ns'] / 1e6:.2f} ms self {t['self_ns'] / 1e6:.2f} ms")
    for parent in ("engine/prefill", "engine/step", "Executor::Run",
                   "Executor::RunSteps"):
        got = split(parsed, parent)
        if got:
            run.log(f"program_spans: a {parent} (n={got[0]}) takes "
                    f"{got[1]:.2f} ms: " + ", ".join(
                        f"{name} {ms:.2f} ms" + (f" [{b / 1e6:.2f} MB]"
                                                 if b else "")
                        for name, (ms, b) in sorted(
                            got[2].items(), key=lambda kv: -kv[1][0])))
    for label, by_name in zip(("program spans", "program + benchmark "
                               "filter"), reattribute(parsed)):
        rows = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        run.log(f"program_spans: idle gaps of chip 0 by {label} on the "
                f"feeding thread: "
                + ", ".join(f"{n} {v / 1e9:.4f} s" for n, v in rows))


# ---------------------------------------------------------------------------
# what the readers share
# ---------------------------------------------------------------------------
def share_of_loop(run, pick, self_time=False):
    """Per cent of the feeding thread's accounted stretch of the slice
    (`assemble`'s extent) spent in the spans `pick` accepts (by name), as
    whole durations or self times; None where the program has no spans."""
    parsed = of(run)
    if not parsed["extent"]:
        return None
    lo, hi = parsed["extent"]
    spent = sum(sp.self_ns if self_time else sp.ns
                for sp in parsed["loop"] if pick(sp.name))
    return 100.0 * spent / (hi - lo)


def total_ms(run, names):
    """Milliseconds inside spans named in `names`, on every thread, inside
    the slice; None where there is no such span."""
    picked = [sp.ns for sp in of(run)["spans"] if sp.name in names]
    return sum(picked) / 1e6 if picked else None


def median(values):
    values = sorted(values)
    if not values:
        return None
    mid = len(values) // 2
    return values[mid] if len(values) % 2 else \
        (values[mid - 1] + values[mid]) / 2.0


if __name__ == "__main__":
    # python -m benchmark.program_spans <trace.xplane.pb[.gz]>: the report
    # for a trace taken by hand (a held-back cell, a TensorBoard capture)
    import sys
    import types

    from benchmark import device_scopes
    _run = types.SimpleNamespace(
        log=print, slice=types.SimpleNamespace(
            xplane_path=lambda: sys.argv[1]))
    of(_run)
    device_scopes.of(_run)
