"""The account of set-up, from the program's kept start-up records.

`paddle_tpu.profiler.Phase` keeps a record of every piece of start-up work
in memory, session or none, on `time.perf_counter()` — the clock of
`benchmark/run.py`'s `T_PROCESS` and of `Run.begin_window()` — and books
JAX's own stages (`jax/trace`, `jax/lower`, `jax/compile`,
`jax/cache_load`) beside them, each on the thread that did the work.  This
module clips those records to the run's set-up, `[t_process, t_process +
setup_s]`, nests them per thread and takes self times (a record's duration
less what the records inside it cover: `program_spans.nest`, on `Span`s
whose `line` is the thread and whose times are ns), and holds set-up to
one identity:

    setup_s = before + Σ self times + unattributed - overlap

`before`: `t_process` to the first record's start (interpreter, `import
jax`, the TPU client); `unattributed`: time after that with no record open
on ANY thread; `overlap`: time two threads' records cover at once (the
engine thread obtains a program while the caller's `server/start` is still
open), counted twice in the sum.  The `setup.*` readers
(`layer_metrics/`) are a few lines each over `self_s` / `parsed`.

A program that keeps no such records (the parent of the PR that added
them) gives None and every reader over it returns None; a cell with no
phase of a reader's kind reads 0.0.
"""
from benchmark import program_spans, trace_reduce

# the phases that are one program being obtained, first run included
PROGRAMS = ("jit/program", "executor/first_launch")


def records():
    """The program's kept records as `program_spans.Span`s (ns, `line` =
    thread id); None where the program keeps none."""
    from paddle_tpu import profiler
    kept = getattr(profiler, "phases", None)
    if kept is None:
        return None
    return [program_spans.Span(p.name, int(round(p.start * 1e9)),
                               int(round(p.end * 1e9)), p.thread,
                               dict(p.fields)) for p in kept()]


def assemble(spans, t_process, setup_s):
    """The account of `[t_process, t_process + setup_s]` (seconds, the
    records' clock) from `spans`: {"spans": clipped to it and nested;
    "before_s", "covered_s" (on any thread), "unattributed_s", "self_s"
    (Σ self times, all threads), "overlap_s", "residual_s" (the identity's:
    0 but for rounding and records that cross on one thread)}."""
    lo = int(round(t_process * 1e9))
    hi = lo + int(round(setup_s * 1e9))
    spans = program_spans.nest(program_spans.clip(spans, (lo, hi)))
    first = min((sp.start for sp in spans), default=hi)
    covered = trace_reduce.total(trace_reduce.union(
        [(sp.start, sp.end) for sp in spans]))
    self_ns = sum(sp.self_ns for sp in spans)
    by_thread = {}
    for sp in spans:
        by_thread.setdefault(sp.line, []).append((sp.start, sp.end))
    overlap = sum(trace_reduce.total(trace_reduce.union(v))
                  for v in by_thread.values()) - covered
    unattributed = (hi - first) - covered
    return {"spans": spans, "window": (lo, hi),
            "before_s": (first - lo) / 1e9,
            "covered_s": covered / 1e9, "self_s": self_ns / 1e9,
            "unattributed_s": unattributed / 1e9, "overlap_s": overlap / 1e9,
            "residual_s": ((first - lo) + self_ns + unattributed - overlap
                           - (hi - lo)) / 1e9}


def of(run):
    """The run's account, made once and kept on `run`; None where the
    program keeps no records."""
    if not hasattr(run, "setup_phases"):
        spans = records()
        run.setup_phases = None if spans is None else \
            assemble(spans, run.t_process, run.setup_s)
        report(run, run.setup_phases)
    return run.setup_phases


def self_s(run, pick):
    """Seconds of self time, inside set-up, of the records `pick` accepts
    (a Span): 0.0 where there is none, None without the records."""
    parsed = of(run)
    if parsed is None:
        return None
    return sum(sp.self_ns for sp in parsed["spans"] if pick(sp)) / 1e9


def named(*names):
    """A `pick` by name, for `self_s`."""
    return lambda sp: sp.name in names


def describe(sp):
    return f"{sp.name} {sp.ns / 1e9:.3f} s " + " ".join(
        f"{k}={v}" for k, v in sorted(sp.fields.items()))


def gaps(parsed):
    """What `unattributed_s` is made of: the stretches after the first
    record with no record open on any thread, longest first, as (start,
    end, the record that closed last before it, the one that opened next)."""
    spans, (_, hi) = parsed["spans"], parsed["window"]
    covered = trace_reduce.union([(sp.start, sp.end) for sp in spans])
    out = []
    for a, b in trace_reduce.gaps(covered, (covered[0][0], hi)) \
            if covered else []:
        before = max((sp for sp in spans if sp.end <= a),
                     key=lambda sp: sp.end)
        after = min((sp for sp in spans if sp.start >= b),
                    key=lambda sp: sp.start, default=None)
        out.append((a, b, describe(before),
                    describe(after) if after else "the window"))
    return sorted(out, key=lambda g: g[0] - g[1])


def report(run, parsed, top=3):
    """One `setup_phases:` line through `run.log` with every phase's
    count, total and self time, the identity's residual and the overlap;
    then the longest phases with their fields, the longest stretches no
    record covers, and what JAX obtained under no phase."""
    if parsed is None:
        run.log("setup_phases: the program keeps no start-up records")
        return
    rows = sorted(program_spans.totals(parsed["spans"]).items(),
                  key=lambda kv: -kv[1]["self_ns"])
    from paddle_tpu import profiler
    dropped = profiler.phases_dropped()
    if dropped:
        run.log(f"setup_phases: THE STORE WAS FULL: {dropped} records "
                "were dropped, the account below misses them")
    run.log(
        f"setup_phases: set-up {run.setup_s:.3f} s = before the program "
        f"{parsed['before_s']:.3f} + self times {parsed['self_s']:.3f} + "
        f"unattributed {parsed['unattributed_s']:.3f} - overlap "
        f"{parsed['overlap_s']:.3f} (residual {parsed['residual_s']:.4f}); "
        + "; ".join(f"{name} n={t['count']} total {t['ns'] / 1e9:.3f} self "
                    f"{t['self_ns'] / 1e9:.3f}" for name, t in rows))
    longest = sorted((sp for sp in parsed["spans"]
                      if not sp.name.startswith("jax/")),
                     key=lambda sp: -sp.ns)[:top]
    run.log("setup_phases: longest: " + " | ".join(map(describe, longest)))
    run.log("setup_phases: longest stretches with no record open: "
            + " | ".join(f"{(b - a) / 1e9:.3f} s after {before} before "
                         f"{after}" for a, b, before, after in gaps(parsed)
                         [:top]))
    orphans = [sp for sp in parsed["spans"]
               if sp.name.startswith("jax/") and sp.parent is None]
    by_fun = {}
    for sp in orphans:
        by_fun[sp.fields.get("fun", "")] = \
            by_fun.get(sp.fields.get("fun", ""), 0) + sp.ns
    run.log(f"setup_phases: {len(orphans)} JAX stages under no phase, "
            f"{sum(sp.ns for sp in orphans) / 1e9:.3f} s"
            + "".join(f"; {fun} {ns / 1e9:.3f}" for fun, ns in sorted(
                by_fun.items(), key=lambda kv: -kv[1])[:top]))
