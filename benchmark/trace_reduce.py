"""From a profiler trace (`.xplane.pb`) to numbers.

Read with `jax.profiler.ProfileData` and nothing else.  What an
un-annotated v5e trace gives (looked at by hand, PR 22, see
tests/benchmark/data/): one plane `/device:TPU:<n>` per chip whose lines
`XLA Modules` (one event per executable launch, named `jit_<fn>(<hash>)`)
and `XLA Ops` (one event per HLO instruction executed, named with the
instruction's full text, `%fusion.12 = bf16[...] fusion(...)`) carry device
times; `while` / `conditional` / `call` events enclose their bodies' events
on the same line.  `/host:CPU` holds one line per host thread with JAX's
own TraceMes (`PjitFunction(step)`, `np.asarray(jax.Array)`, `DevicePut`)
and the benchmark's `bench/...` annotations.  Host and device events share
one clock.

The interval arithmetic (union, subtraction, gap attribution) works on
plain (start, end) tuples so tests can drive it with hand-built lists.
"""
import gzip
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
CONTROL_FLOW = {"while", "conditional", "call"}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
SLICE_SPAN = "bench/slice"
MIN_GAP_NS = 10_000     # shorter device gaps are back-to-back ops, not idle
_HLO = re.compile(r"^%(?P<instr>\S+) = (?P<shape>.*?)\s*"
                  r"(?P<opcode>[a-z][a-z0-9\-]*)\(")


def parse_op_name(text):
    """(instruction name, opcode, result shape) of an `XLA Ops` event
    name; events that are not HLO text come back as (text, "", "")."""
    m = _HLO.match(text)
    if not m:
        return text, "", ""
    shape = m.group("shape")
    if len(shape) > 48:                # a tuple of hundreds of gradients
        shape = shape[:45] + "..."
    return m.group("instr"), m.group("opcode"), shape


def is_collective(opcode):
    return opcode.startswith(COLLECTIVES)


# ---------------------------------------------------------------------------
# interval arithmetic on (start, end) tuples
# ---------------------------------------------------------------------------
def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, window):
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The parts of merged intervals `a` that merged intervals `b` do not
    cover."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, window):
    """The idle intervals of a window, given its merged busy intervals."""
    return subtract([tuple(window)], busy)


def attribute(idle, spans):
    """Seconds (in the intervals' unit) of `idle` by what the host was
    doing: each instant goes to the span covering it that started last
    (the innermost), `unattributed` where there is none.  `spans` are
    (name, start, end)."""
    spans = sorted(spans, key=lambda sp: sp[1])
    by_name, live, nxt = {}, [], 0
    for s, e in sorted(idle):
        # one sweep: spans enter when they start before the gap ends and
        # leave for good once they end before a gap starts
        while nxt < len(spans) and spans[nxt][1] < e:
            live.append(spans[nxt])
            nxt += 1
        live = [sp for sp in live if sp[2] > s]
        cuts = sorted({s, e} | {min(max(c, s), e)
                                for sp in live for c in (sp[1], sp[2])})
        for a, b in zip(cuts, cuts[1:]):
            covering = [sp for sp in live if sp[1] <= a and sp[2] >= b]
            name = max(covering, key=lambda sp: sp[1])[0] if covering \
                else "unattributed"
            by_name[name] = by_name.get(name, 0) + (b - a)
    return by_name


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------
def load(path):
    """{"devices": {n: {"ops": [(text, s, e)], "modules": [(name, s, e)]}},
    "host": [(name, s, e)]} with times in nanoseconds on the trace's
    clock."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):            # the recorded traces of the tests
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.start_ns +
                                 e.duration_ns) for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    return {"devices": devices, "host": host}


def _host_spans(host):
    """The host spans idle time is attributed to: the benchmark's own
    annotations and JAX's TraceMes that say what a Python thread asked
    the runtime for."""
    keep = ("bench/", "PjitFunction(", "np.asarray(", "DevicePut",
            "shard_args")
    return [sp for sp in host
            if sp[0].startswith(keep) and sp[0] != SLICE_SPAN]


def reduce_device(dev, window):
    """One chip's numbers inside `window` (ns)."""
    ops = []
    for text, s, e in dev["ops"]:
        instr, opcode, shape = parse_op_name(text)
        if opcode not in CONTROL_FLOW:
            ops.append((instr, opcode, shape, s, e))
    busy = clip(union((s, e) for *_, s, e in ops), window)
    coll = clip(union((s, e) for _, oc, _, s, e in ops
                      if is_collective(oc)), window)
    other = clip(union((s, e) for _, oc, _, s, e in ops
                       if not is_collective(oc)), window)
    by_op = {}
    for instr, opcode, shape, s, e in ops:
        s, e = max(s, window[0]), min(e, window[1])
        if e > s:
            key = f"{instr} {opcode} {shape}".strip()
            by_op[key] = by_op.get(key, 0) + (e - s)
    modules = [(n, s, e) for n, s, e in dev["modules"]
               if s >= window[0] and s < window[1]]
    return {"busy": busy, "busy_ns": total(busy),
            "collective_ns": total(coll),
            "collective_exposed_ns": total(subtract(coll, other)),
            "by_op": by_op, "modules": modules}


def summarize(path, n_devices=1):
    """Everything the per-layer readers and the breakdown take from one
    trace.  The window is the `bench/slice` annotation where the trace has
    one, else the extent of the device events.  Busy time is averaged over
    the first `n_devices` chips; everything else is chip 0's."""
    trace = load(path)
    if not trace["devices"]:
        raise ValueError(f"{path}: no /device:TPU:<n> plane — nothing ran "
                         "on a TPU while this trace was taken")
    slices = [sp for sp in trace["host"] if sp[0] == SLICE_SPAN]
    if slices:
        window = (slices[0][1], slices[0][2])
    else:
        evs = [ev for d in trace["devices"].values() for ev in d["ops"]]
        window = (min(e[1] for e in evs), max(e[2] for e in evs))
    ids = sorted(trace["devices"])[:n_devices]
    per_dev = [reduce_device(trace["devices"][i], window) for i in ids]
    first = per_dev[0]
    window_ns = window[1] - window[0]
    idle = [g for g in gaps(first["busy"], window)
            if g[1] - g[0] >= MIN_GAP_NS]
    by_host = attribute(idle, _host_spans(trace["host"]))
    module_names = {}
    for n, _, _ in first["modules"]:
        n = re.sub(r"\(\d+\)$", "", n)
        module_names[n] = module_names.get(n, 0) + 1
    ns = 1e-9
    return {
        "window_s": window_ns * ns,
        "busy_s_per_device": [d["busy_ns"] * ns for d in per_dev],
        "busy_s": sum(d["busy_ns"] for d in per_dev) * ns / len(per_dev),
        "idle_share": 1.0 - first["busy_ns"] / window_ns,
        "collective_s": first["collective_ns"] * ns,
        "collective_exposed_s": first["collective_exposed_ns"] * ns,
        "launches": len(first["modules"]),
        "modules": sorted(module_names.items(), key=lambda kv: -kv[1]),
        "top_ops": [[k, v * ns] for k, v in sorted(
            first["by_op"].items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[k, v * ns] for k, v in sorted(
            by_host.items(), key=lambda kv: -kv[1])],
    }
