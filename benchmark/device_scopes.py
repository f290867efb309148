"""Device time by what the Program asked for.

`BlockTracer.run_op` wraps every kernel in `jax.named_scope("<role>/<op
type>")`, with the role from the op's IR `op_role`; XLA keeps that path as
each lowered instruction's `op_name`, and the profiler writes it — with
the instruction's `hlo_category`, `flops`, `bytes_accessed` and `source` —
as stats of the instruction's *event metadata*, e.g.

    tf_op = jit(step)/backward/mul_grad/transpose(jvp())/dot_general:

`jax.profiler.ProfileData` exposes only an event's own stats, so the
`XSpace` bytes are read here with a small protobuf wire-format decoder of
the fields needed (tsl/profiler/protobuf/xplane.proto; field numbers
below), with no dependency beyond the standard library.

A fused instruction carries the `op_name` of one of the ops fused into it
(XLA's choice), and a `.remat` clone keeps its original's: rematerialized
forward work counts under `forward/`.  A program without scopes (the
parent of the PR that added them) has no role in any `tf_op` and every
reader over this module returns None.
"""
import gzip
import re

from benchmark import trace_reduce

ROLES = ("forward", "backward", "optimize", "lr_sched", "rpc", "dist")
_ROLE = re.compile(r"/(" + "|".join(ROLES) + r")/([A-Za-z0-9_]+)")

# xplane.proto field numbers
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_MD_NAME, _MD_STATS = 2, 5                     # XEventMetadata
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7    # XStat
_MAP_KEY, _MAP_VALUE = 1, 2


# ---------------------------------------------------------------------------
# protobuf wire format
# ---------------------------------------------------------------------------
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf, start=0, end=None):
    """(field number, value) of the message in `buf[start:end]`: a varint
    as an int, a length-delimited field as its (start, end) in `buf`,
    fixed-width fields as None."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield tag >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """(key, value span) of one `map<int64, message>` entry."""
    key, value = 0, None
    for f, v in fields(buf, *span):
        if f == _MAP_KEY:
            key = v
        elif f == _MAP_VALUE:
            value = v
    return key, value


# ---------------------------------------------------------------------------
# one device plane
# ---------------------------------------------------------------------------
def read_plane(buf, span, line_name="XLA Ops"):
    """{"metadata": {id: {"name", "tf_op", "hlo_category"}}, "events":
    [(metadata id, start ns, end ns)] of the line `line_name`} of the
    XPlane in `buf[span]`."""
    stat_names, raw_md, lines = {}, [], []
    for f, v in fields(buf, *span):
        if f == _PLANE_STAT_MD:
            key, value = _map_entry(buf, v)
            for g, w in fields(buf, *value):
                if g == _MD_NAME:
                    stat_names[key] = _text(buf, w)
        elif f == _PLANE_EVENT_MD:
            raw_md.append(_map_entry(buf, v))
        elif f == _PLANE_LINES:
            lines.append(v)
    wanted = {i: n for i, n in stat_names.items()
              if n in ("tf_op", "hlo_category")}
    metadata = {}
    for key, value in raw_md:
        md = {"name": "", "tf_op": "", "hlo_category": ""}
        for f, v in fields(buf, *value):
            if f == _MD_NAME:
                md["name"] = _text(buf, v)
            elif f == _MD_STATS:
                stat = dict(fields(buf, *v))
                name = wanted.get(stat.get(_STAT_MD_ID))
                if name and _STAT_STR in stat:
                    md[name] = _text(buf, stat[_STAT_STR])
                elif name and _STAT_REF in stat:   # a string held once
                    md[name] = stat_names.get(stat[_STAT_REF], "")
        metadata[key] = md
    events = []
    for span_ in lines:
        line = {}
        evs = []
        for f, v in fields(buf, *span_):
            if f == _LINE_EVENTS:
                evs.append(v)
            else:
                line[f] = v
        if _LINE_NAME not in line or \
                _text(buf, line[_LINE_NAME]) != line_name:
            continue
        t0_ps = line.get(_LINE_TIMESTAMP_NS, 0) * 1000
        for ev in evs:
            e = dict(fields(buf, *ev))
            start_ps = t0_ps + e.get(_EVENT_OFFSET_PS, 0)
            events.append((e.get(_EVENT_MD_ID, 0), start_ps / 1000.0,
                           (start_ps + e.get(_EVENT_DURATION_PS, 0))
                           / 1000.0))
    return {"metadata": metadata, "events": events}


def read_device(path, chip=0):
    """`read_plane` of `/device:TPU:<chip>` in an `.xplane.pb[.gz]`, or
    None where the trace has no such plane."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = f.read()
    want = f"/device:TPU:{chip}"
    for f, v in fields(buf):
        if f != _SPACE_PLANES:
            continue
        for g, w in fields(buf, *v):
            if g == _PLANE_NAME:
                if _text(buf, w) == want:
                    return read_plane(buf, v)
                break       # another plane: its name comes before its lines
    return None


# ---------------------------------------------------------------------------
# from instructions to scopes
# ---------------------------------------------------------------------------
def scope_of(tf_op):
    """(role, op type) where `tf_op` has `<role>/<op type>` as path
    components — the outermost such pair: a sub-block's ops sit inside
    their parent op's scope — else None."""
    m = _ROLE.search("/" + tf_op)
    return (m.group(1), m.group(2)) if m else None


def by_scope(plane, window):
    """Chip time inside `window` (ns) by scope: {"busy_ns", "roles":
    {role: ns}, "ops": {"role/op type": ns}, "unscoped": {opcode or
    hlo_category: ns}}.  Control-flow instructions enclose their bodies'
    events and are left out, as in `trace_reduce.reduce_device`."""
    ops, unscoped, per_role, all_ivals = {}, {}, {}, []
    kinds = {}      # metadata id -> (opcode, scope, hlo_category), once
    for md_id, s, e in plane["events"]:
        if md_id not in kinds:
            md = plane["metadata"].get(md_id, {})
            kinds[md_id] = (
                trace_reduce.parse_op_name(md.get("name", ""))[1],
                scope_of(md.get("tf_op", "")), md.get("hlo_category"))
        opcode, scope, category = kinds[md_id]
        if opcode in trace_reduce.CONTROL_FLOW:
            continue
        s, e = max(s, window[0]), min(e, window[1])
        if e <= s:
            continue
        all_ivals.append((s, e))
        if scope:
            per_role.setdefault(scope[0], []).append((s, e))
            key = "/".join(scope)
            ops[key] = ops.get(key, 0) + (e - s)
        else:
            key = opcode or category or "?"
            unscoped[key] = unscoped.get(key, 0) + (e - s)
    return {"busy_ns": trace_reduce.total(trace_reduce.union(all_ivals)),
            "roles": {role: trace_reduce.total(trace_reduce.union(ivals))
                      for role, ivals in per_role.items()},
            "ops": ops, "unscoped": unscoped}


def of(run):
    """The run's chip-0 scopes inside the slice, parsed once and kept on
    `run`; None where there is no device plane or no scoped instruction."""
    if not hasattr(run, "device_scopes"):
        from benchmark import program_spans
        run.device_scopes = None
        plane = read_device(run.slice.xplane_path())
        if plane is not None:
            got = by_scope(plane, program_spans.of(run)["window"])
            report(run, got)
            if got["roles"] and got["busy_ns"]:
                run.device_scopes = got
    return run.device_scopes


def report(run, got, top=12):
    busy = got["busy_ns"] or 1
    if not got["roles"]:
        run.log("device_scopes: no instruction carries a role scope (device "
                "work that is no Program's ops, such as the dygraph "
                "forward; a program from before the scopes; or an "
                "executable from a compile cache that predates them)")
        return
    for label, table in (("role", got["roles"]), ("op", got["ops"]),
                         ("unscoped, by opcode", got["unscoped"])):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        run.log(f"device_scopes: chip 0 busy {busy / 1e9:.4f} s by {label}"
                ": " + ", ".join(f"{n} {100.0 * v / busy:.2f}%"
                                 for n, v in rows))


def role_share(run, role):
    """Per cent of chip 0's busy time in the slice under `/<role>/`."""
    got = of(run)
    if got is None:
        return None
    return 100.0 * got["roles"].get(role, 0) / got["busy_ns"]
