"""Device time by IR scope: the wire-format decoder on hand-built bytes and
on the traces recorded on the v5e (tests/benchmark/data/README.md and
tiny_scoped_v5e.README.md), the scope pattern, the reduction on hand-built
planes, and — on the CPU — the scopes themselves in the lowered text of a
train step and a scanned window."""
import importlib.util
import os
import re

import numpy as np
import pytest

from benchmark import device_scopes as ds
from benchmark import program_spans as ps
from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
UNSCOPED = os.path.join(DATA, "tiny_train_v5e.xplane.pb")      # PR 22
SCOPED = os.path.join(DATA, "tiny_scoped_v5e.xplane.pb.gz")    # PR 24


# ---------------------------------------------------------------------------
# protobuf wire format, by hand
# ---------------------------------------------------------------------------
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _stat(md_id, text=None, ref=None):
    return _field(1, md_id) + (_field(5, text.encode()) if text is not None
                               else _field(7, ref))


def _plane(name, stat_names, event_md, lines):
    """An XPlane: `stat_names` {id: name}, `event_md` {id: (name, [XStat
    bytes])}, `lines` [(name, timestamp_ns, [(md id, offset ps, dur ps)])]."""
    out = _field(1, 7) + _field(2, name.encode())
    for lname, t0, events in lines:
        line = _field(1, 3) + _field(2, lname.encode()) + _field(3, t0)
        for md_id, off, dur in events:
            line += _field(4, _field(1, md_id) + _field(2, off)
                           + _field(3, dur))
        out += _field(3, line)
    for key, (ename, stats) in event_md.items():
        md = _field(1, key) + _field(2, ename.encode())
        for st in stats:
            md += _field(5, st)
        out += _field(4, _field(1, key) + _field(2, md))
    for key, sname in stat_names.items():
        out += _field(5, _field(1, key) + _field(
            2, _field(1, key) + _field(2, sname.encode())))
    return out


def test_wire_decoder_reads_varints_lengths_and_skips_fixed_width():
    msg = (_field(1, 300) + _field(2, b"abc")
           + _varint(3 << 3 | 1) + b"\x00" * 8        # a double, skipped
           + _varint(4 << 3 | 5) + b"\x00" * 4        # a float, skipped
           + _field(5, 1 << 40))
    got = list(ds.fields(msg))
    assert got[0] == (1, 300) and got[4] == (5, 1 << 40)
    assert msg[got[1][1][0]:got[1][1][1]] == b"abc"
    assert got[2] == (3, None) and got[3] == (4, None)
    with pytest.raises(ValueError, match="wire type"):
        list(ds.fields(_varint(1 << 3 | 3)))


def test_hand_built_plane_reads_back_with_str_and_ref_stats(tmp_path):
    stats = {1: "tf_op", 2: "hlo_category", 3: "flops",
             9: "jit(step)/optimize/adam/mul:"}      # a string held once
    md = {
        10: ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
             [_stat(1, "jit(step)/forward/mul/dot_general:"),
              _stat(2, "convolution fusion"), _stat(3, ref=4)]),
        11: ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)",
             [_stat(1, ref=9), _stat(2, "loop fusion")]),
        12: ("%while.3 = (f32[8]{0}) while((f32[8]{0}) %t)", []),
        13: ("%copy-done.4 = f32[8]{0} copy-done(f32[8]{0} %c)", [])}
    ops = [(12, 0, 900_000), (10, 0, 400_000), (11, 400_000, 100_000),
           (13, 600_000, 200_000)]
    space = (_field(1, _plane("/host:CPU", {}, {}, []))
             + _field(1, _plane("/device:TPU:0", stats, md, [
                 ("XLA Modules", 5, [(10, 0, 900_000)]),
                 ("XLA Ops", 5, ops)]))
             + _field(1, _plane("/device:TPU:1", stats, md, [
                 ("XLA Ops", 5, ops[:1])])))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(space)
    plane = ds.read_device(str(path))
    assert plane["metadata"][10] == {
        "name": md[10][0], "tf_op": "jit(step)/forward/mul/dot_general:",
        "hlo_category": "convolution fusion"}
    assert plane["metadata"][11]["tf_op"] == "jit(step)/optimize/adam/mul:"
    assert plane["metadata"][13]["tf_op"] == ""
    # times are ns on the line's clock: timestamp_ns + offset_ps / 1000
    assert plane["events"] == [(12, 5.0, 905.0), (10, 5.0, 405.0),
                               (11, 405.0, 505.0), (13, 605.0, 805.0)]
    assert len(ds.read_device(str(path), chip=1)["events"]) == 1
    assert ds.read_device(str(path), chip=2) is None
    got = ds.by_scope(plane, (0.0, 705.0))
    # the while encloses its body and is left out; copy-done is clipped
    assert got["busy_ns"] == pytest.approx(600.0)
    assert got["roles"] == {"forward": pytest.approx(400.0),
                            "optimize": pytest.approx(100.0)}
    assert got["ops"] == {"forward/mul": pytest.approx(400.0),
                          "optimize/adam": pytest.approx(100.0)}
    assert got["unscoped"] == {"copy-done": pytest.approx(100.0)}


@pytest.mark.parametrize("tf_op,want", [
    ("jit(step)/backward/mul_grad/transpose(jvp())/dot_general:",
     ("backward", "mul_grad")),
    ("jit(multi)/while/body/forward/softmax/reduce_max:",
     ("forward", "softmax")),
    ("jit(step)/forward/while/forward/mul/dot_general:",
     ("forward", "while")),                 # the outermost: the parent op
    ("jit(step)/shard_map/optimize/adam/sqrt:", ("optimize", "adam")),
    ("jit(step)/lr_sched/increment/add:", ("lr_sched", "increment")),
    ("jit(step)/transpose(jvp())/neg:", None),      # before the scopes
    ("jit(forward)/dot_general:", None),    # a function that shares a name
    ("", None),
])
def test_a_role_is_matched_as_a_path_component(tf_op, want):
    assert ds.scope_of(tf_op) == want


# ---------------------------------------------------------------------------
# the traces recorded on the v5e
# ---------------------------------------------------------------------------
def test_decoder_reads_tf_op_and_hlo_category_of_the_recorded_trace():
    plane = ds.read_device(UNSCOPED)
    by_instr = {trace_reduce.parse_op_name(md["name"])[0]: md
                for md in plane["metadata"].values()}
    assert by_instr["fusion.304"]["tf_op"] == \
        "jit(step)/transpose(jvp())/neg:"
    assert by_instr["fusion.304"]["hlo_category"] == "loop fusion"
    assert {md["hlo_category"] for md in plane["metadata"].values()} >= \
        {"loop fusion", "convolution fusion", "data formatting"}
    # every event resolves, and the decoder's clock is ProfileData's
    assert all(e[0] in plane["metadata"] for e in plane["events"])
    loaded = trace_reduce.load(UNSCOPED)["devices"][0]["ops"]
    assert len(loaded) == len(plane["events"]) == 6309
    for (_, s, e), (_, s2, e2) in zip(loaded[:200], plane["events"][:200]):
        assert s == pytest.approx(s2, abs=2.0)     # whole ns there
        assert e == pytest.approx(e2, abs=2.0)
    # a trace from before the scopes: instructions have a tf_op (most of
    # the device time), no role in it, and the readers say nothing
    window = ps.load(UNSCOPED)["window"]
    got = ds.by_scope(plane, window)
    assert got["roles"] == {} and got["ops"] == {}
    assert sum(got["unscoped"].values()) >= got["busy_ns"]
    named = sum(e - s for i, s, e in plane["events"]
                if plane["metadata"][i]["tf_op"])
    assert named > 0.8 * sum(e - s for _, s, e in plane["events"]
                             if " while(" not in plane["metadata"][_]["name"])


class RecordedRun:
    """What a reader is handed, over a recorded trace."""

    def __init__(self, path):
        self.slice = self
        self._path = path
        self.lines = []

    def xplane_path(self):
        return self._path

    def log(self, line):
        self.lines.append(line)


def _reader(name):
    path = os.path.join(REPO, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_unscoped_trace_gives_no_scope_metric_and_says_why():
    run = RecordedRun(UNSCOPED)
    for role in ("forward", "backward", "optimize"):
        assert _reader(f"scope_{role}_share").reduce(run) is None
    assert sum("no instruction carries a role scope" in ln
               for ln in run.lines) == 1        # parsed once, said once


def test_scope_shares_of_the_recorded_scoped_trace():
    """The tiny BERT again, brought back from the chip after
    `BlockTracer.run_op` stamped its scopes: two `Executor.run` and one
    scanned window of four steps inside one `bench/slice`."""
    run = RecordedRun(SCOPED)
    shares = {role: _reader(f"scope_{role}_share").reduce(run)
              for role in ("forward", "backward", "optimize")}
    assert all(v is not None and v > 0 for v in shares.values()), shares
    # backward is the largest; the three cover nearly all the chip did
    # (the rest: copies and the step seed's convert, listed by opcode)
    assert shares["backward"] > shares["forward"] > shares["optimize"]
    assert 85.0 < sum(shares.values()) <= 100.0 + 1e-6, shares
    got = run.device_scopes
    assert got is ds.of(run)                    # kept on the run
    ops = got["ops"]
    # attention against FFN is told by op type, not by a block's name
    assert {"forward/softmax", "forward/matmul", "forward/mul",
            "backward/mul_grad", "backward/matmul_grad",
            "backward/softmax_grad", "optimize/adam"} <= set(ops), ops
    assert any(k.startswith("forward/") and "cross_entropy" in k
               for k in ops), ops
    assert got["unscoped"] and "while" not in got["unscoped"]
    assert sum(got["roles"].values()) + sum(got["unscoped"].values()) == \
        pytest.approx(got["busy_ns"], rel=0.02)
    assert any(ln.startswith("device_scopes: chip 0 busy") and " by op: "
               in ln for ln in run.lines)
    # the scanned window's body carries the same scopes as the single step
    plane = ds.read_device(SCOPED)
    scanned = [md["tf_op"] for md in plane["metadata"].values()
               if md["tf_op"].startswith("jit(multi)/")]
    assert any("/while/body/" in t and ds.scope_of(t) for t in scanned)


# ---------------------------------------------------------------------------
# on the CPU: the scopes in what the Executor lowers
# ---------------------------------------------------------------------------
def test_lowered_step_and_scanned_window_carry_the_ir_roles():
    import jax.numpy as jnp
    import paddle_tpu.static as static
    from paddle_tpu.static import layers
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 8])
        y = layers.data("y", [-1, 1])
        loss = layers.mean(layers.square(layers.fc(x, 1) - y))
        static.Adam(1e-3).minimize(loss)
    main.random_seed = startup.random_seed = 3
    exe, scope = static.Executor(), static.Scope()
    feed = {"x": np.ones((4, 8), np.float32),
            "y": np.ones((4, 1), np.float32)}
    stacked = {n: np.stack([v] * 3) for n, v in feed.items()}
    with static.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        exe.run_steps(main, feed=stacked, fetch_list=[loss])
        for key, fn in exe._cache.items():
            steps = key[0] == "run_steps"
            state = {n: scope.get(n) for n in key[-1]}
            seed = jnp.zeros(3, jnp.uint32) if steps else jnp.uint32(0)
            text = fn.lower(state, {n: jnp.asarray(v) for n, v in (
                stacked if steps else feed).items()}, seed).as_text(
                    debug_info=True)
            # op names as `loc("...")`s (a scan's body is outlined, so its
            # names are relative to `jit(multi)/while/body/`); file paths
            # are locs too and start with a slash
            paths = {p for p in re.findall(r'loc\("([^"]+)"', text)
                     if not p.startswith("/")}
            scopes = {ds.scope_of(p) for p in paths} - {None}
            assert {("forward", "mul"), ("forward", "mean"),
                    ("backward", "mul_grad"), ("backward", "mean_grad"),
                    ("optimize", "adam")} <= scopes, (key[0], scopes)
            # the loss op is Forward | Loss: it stays under forward/
            assert {role for role, _ in scopes} == \
                {"forward", "backward", "optimize"}
            assert any(p.startswith("jit(step)/") != steps
                       for p in paths if ds.scope_of(p))
