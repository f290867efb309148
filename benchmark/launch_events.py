"""Single launches of the engine's compiled step programs, paired with
the span that asked for each.

The roofline shares of the hybrid cells are RATIOS PER EVENT: the seconds
one launch requires (`work_hybrid`, at the bucket, rows and lengths its
own span carries) over the duration of that launch's own device event —
never a sum of work over `trace["busy_s"]`.  A share built from the same
event on both sides cannot pass what the chip did because other events
were lost from the trace or forwards were miscounted (PR 25 read 165%
that way).

A launch: the `engine/forward` span gives when the program was asked for
and its `bucket` / `rows` fields; its parent (`engine/prefill` or
`engine/step`) gives `prompt` or `active` / `context`; the device event is
the longest `XLA Modules` event of chip 0 that starts between this
forward span's start and the next one's (the compiled step dwarfs the
uploads' and the state install's programs); `scoped` sums, by IR scope,
the `XLA Ops` events of chip 0 inside that module event
(`device_scopes`).  A program without these spans or fields (the parent of
the PR that added them) gives no launches and every reader returns None.
"""
from benchmark import device_scopes, program_spans, trace_reduce


def pair(forwards, modules, ops):
    """[{"span", "module": (start, end), "scoped": {scope: ns}, "n_ops"}]
    for the forward spans that have a device event.  `forwards`: Spans
    sorted by start; `modules`: (name, start, end); `ops`: (scope or
    None, start, end), any order; "n_ops" counts every op event inside
    the module event, scoped or not."""
    modules = sorted(modules, key=lambda m: m[1])
    ops = sorted(ops, key=lambda o: o[1])
    out, j = [], 0
    for i, sp in enumerate(forwards):
        until = forwards[i + 1].start if i + 1 < len(forwards) \
            else float("inf")
        while j < len(modules) and modules[j][1] < sp.start:
            j += 1
        best = None
        while j < len(modules) and modules[j][1] < until:
            if best is None or modules[j][2] - modules[j][1] > \
                    best[1] - best[0]:
                best = (modules[j][1], modules[j][2])
            j += 1
        if best is None:
            continue
        scoped, n_ops = {}, 0
        for scope, s, e in ops:
            if s >= best[1]:
                break
            if s >= best[0]:
                n_ops += 1
                if scope:
                    scoped[scope] = scoped.get(scope, 0) + (e - s)
        out.append({"span": sp, "module": best, "scoped": scoped,
                    "n_ops": n_ops})
    return out


def of(run):
    """The run's launches, parsed once and kept on `run`."""
    if getattr(run, "launch_events", None) is None:
        run.launch_events = []
        parsed = program_spans.of(run)
        forwards = sorted(
            (sp for sp in parsed["whole"] if sp.name == "engine/forward"
             and "bucket" in sp.fields and sp.parent is not None),
            key=lambda sp: sp.start)
        path = run.slice.xplane_path()
        plane = device_scopes.read_device(path) if forwards else None
        if plane is not None:
            scope = {}
            for md_id, md in plane["metadata"].items():
                got = device_scopes.scope_of(md.get("tf_op", ""))
                # a `while` encloses its body's events: count the body
                enclosing = trace_reduce.parse_op_name(
                    md.get("name", ""))[1] in trace_reduce.CONTROL_FLOW
                scope[md_id] = "/".join(got) if got and not enclosing \
                    else None
            chip0 = trace_reduce.load(path)["devices"]
            modules = chip0[min(chip0)]["modules"]
            run.launch_events = pair(
                forwards, modules, [(scope.get(md_id), s, e)
                                    for md_id, s, e in plane["events"]])
            lo, hi = parsed["window"]
            by_phase = {}
            for launch in run.launch_events:
                by_phase.setdefault(launch["span"].parent.name, []).append(
                    launch["n_ops"])
            run.log(
                f"launch_events: in the slice {len(forwards)} engine/forward "
                f"spans with a bucket, {len(run.launch_events)} paired with "
                f"a device event; {sum(lo <= m[1] < hi for m in modules)} "
                f"XLA Modules events and "
                f"{sum(lo <= e[1] < hi for e in plane['events'])} XLA Ops "
                "events begin in it; ops inside a paired module event, "
                "median by phase: " + ", ".join(
                    f"{k} {program_spans.median(v)} (n={len(v)})"
                    for k, v in sorted(by_phase.items())))
        else:
            run.log(f"launch_events: {len(forwards)} engine/forward spans "
                    "with a bucket, no device plane read")
    return run.launch_events


def shares(run, phase, required_s, device_ns):
    """Median over the launches of `phase` ("engine/prefill" or
    "engine/step") of 100 * required_s(launch) / device_ns(launch) * 1e9;
    None where there is none.  Either function may return None to leave a
    launch out."""
    got = []
    for launch in of(run):
        if launch["span"].parent.name != phase:
            continue
        need, took = required_s(launch), device_ns(launch)
        if need is not None and took:
            got.append(100.0 * need * 1e9 / took)
    return program_spans.median(got)
