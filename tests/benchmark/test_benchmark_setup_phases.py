"""Set-up's account (`benchmark/setup_phases.py`) on hand-built records;
the ten `setup.*` readers on a hand-built run; their manifest entries; and
a CPU rehearsal of a training and a serving cell that reports every one and
closes the identity."""
import importlib.util
import json
import os
import re

import pytest

import benchmark_tiny_root as tiny
from benchmark import harness, program_spans as ps, setup_phases, work
from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000      # ns
READERS = ["before_program_s", "import_s", "weights_s", "program_build_s",
           "jax_trace_s", "lower_s", "cache_load_s", "first_run_s",
           "slowest_program_s", "unattributed_s"]
LAYERS = ["process", "process", "model", "executor", "executor",
          "compile_cache", "compile_cache", "engine", "compile_cache",
          "process"]
MAIN, ENGINE = 1, 2     # threads


def S(name, start_ms, end_ms, line=MAIN, **fields):
    return ps.Span(name, start_ms * MS, end_ms * MS, line, fields)


# A process started at t = 100 s whose set-up lasts 10 s.  Main thread:
# the import 1.0-3.0 s in; a model 3.0-5.0 with one executable loaded for
# its draws (trace 0.1, lower 0.2, compile 0.5 of which 0.3 from the
# cache); the pool 5.0-5.5; the server 5.0-6.0 around it.  Engine thread:
# a prefill program 5.8-8.8 (record 1.0 with the abstract trace 0.4 in it;
# trace 0.5, lower 0.8, compile 0.4 all loaded) — 0.2 s of it while the
# server's phase is still open on the main thread — and an install program
# that began before the window's end and straddles it (9.5-10.5).  A decode
# program obtained after the window began (11-12) is no part of set-up; a
# start-up phase that began before the process's clock (a record from an
# earlier run in one process) is clipped at it.
T0, SETUP = 100.0, 10.0
RECORDS = [
    S("import/paddle_tpu", 101_000, 103_000),
    S("model/build", 103_000, 105_000, params=10, bytes=40),
    S("jax/trace", 103_100, 103_200, fun="_normal"),
    S("jax/lower", 103_200, 103_400, fun="jit(_normal)"),
    S("jax/compile", 103_400, 103_900, fun="jit(_normal)"),
    S("jax/cache_load", 103_500, 103_800, saved_s=1.5),
    S("server/start", 105_000, 106_000),
    S("kv_pool/allocate", 105_000, 105_500, slots=2, pages=0, bytes=64),
    S("jit/program", 105_800, 108_800, ENGINE, kind="prefill", bucket=64,
      rows=1, ops=90),
    S("jit/record", 105_900, 106_900, ENGINE, ops=90),
    S("jax/trace", 106_000, 106_400, ENGINE, fun="body"),
    S("jax/trace", 107_000, 107_500, ENGINE, fun="fn"),
    S("jax/lower", 107_500, 108_300, ENGINE, fun="jit(fn)"),
    S("jax/compile", 108_300, 108_700, ENGINE, fun="jit(fn)"),
    S("jax/cache_load", 108_300, 108_700, ENGINE, saved_s=20.0),
    S("jit/program", 109_500, 110_500, ENGINE, kind="install", bucket=64,
      rows=1),
    S("jit/program", 111_000, 112_000, ENGINE, kind="decode", columns=128,
      rows=2, ops=95),
    S("compile_cache/initialize", 99_000, 100_400, entries=3),
]
WANT = {
    "before_program_s": 0.0,    # the stale record starts at the clock's 0
    "import_s": 2.0,
    "weights_s": (2.0 - 0.8) + 0.5,
    "program_build_s": 1.0 - 0.4,
    "jax_trace_s": 0.1 + 0.4 + 0.5,
    "lower_s": 0.2 + 0.8,
    "cache_load_s": 0.3 + 0.4,
    "first_run_s": (3.0 - 1.0 - 0.5 - 0.8 - 0.4) + 0.5,
    "slowest_program_s": 3.0,
    # 0.4-1.0 and 8.8-9.5 hold no record on either thread
    "unattributed_s": 0.6 + 0.7,
}


class FakeRun:
    def __init__(self, records=RECORDS):
        self.t_process, self.setup_s = T0, SETUP
        self.lines = []
        self.log = self.lines.append
        if records is not None:
            self.setup_phases = setup_phases.assemble(records, T0, SETUP)


def _reader(name):
    path = os.path.join(tiny.REPO, "benchmark", "layer_metrics",
                        name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# hand-built records
# ---------------------------------------------------------------------------
def test_self_time_and_nesting_per_thread():
    parsed = setup_phases.assemble(RECORDS, T0, SETUP)
    by = {}
    for sp in parsed["spans"]:
        by.setdefault((sp.name, sp.line), []).append(sp)
    model, = by["model/build", MAIN]
    compile_, = by["jax/compile", MAIN]
    load, = by["jax/cache_load", MAIN]
    assert compile_.parent is model and load.parent is compile_
    assert model.self_ns == (2000 - 100 - 200 - 500) * MS
    assert compile_.self_ns == 200 * MS and load.self_ns == 300 * MS
    # the pool is inside the server's phase by its interval alone (that
    # phase is booked once it is over, `record_phase`)
    pool, = by["kv_pool/allocate", MAIN]
    server, = by["server/start", MAIN]
    assert pool.parent is server and server.self_ns == 500 * MS
    # a phase on another thread is nobody's child, whatever it overlaps
    prefill = by["jit/program", ENGINE][0]
    assert prefill.parent is None and prefill.fields["kind"] == "prefill"
    record, = by["jit/record", ENGINE]
    assert record.parent is prefill and record.self_ns == 600 * MS
    assert by["jax/trace", ENGINE][0].parent is record
    assert by["jax/trace", ENGINE][1].parent is prefill


def test_clipping_at_both_ends_of_set_up():
    parsed = setup_phases.assemble(RECORDS, T0, SETUP)
    names = [(sp.name, sp.fields.get("kind")) for sp in parsed["spans"]]
    assert ("jit/program", "decode") not in names   # after the window began
    stale, = [sp for sp in parsed["spans"]
              if sp.name == "compile_cache/initialize"]
    assert (stale.start, stale.end) == (100_000 * MS, 100_400 * MS)
    straddling = [sp for sp in parsed["spans"]
                  if sp.fields.get("kind") == "install"]
    assert [(sp.start, sp.end) for sp in straddling] == [
        (109_500 * MS, 110_000 * MS)]
    # nothing at all inside: the whole of set-up is before the program
    empty = setup_phases.assemble(RECORDS[-2:-1], T0, SETUP)
    assert empty["spans"] == [] and empty["before_s"] == SETUP
    assert empty["unattributed_s"] == 0.0 and empty["residual_s"] == 0.0


def test_the_identity_and_the_overlap_of_two_threads():
    parsed = setup_phases.assemble(RECORDS, T0, SETUP)
    # the engine's program began 0.2 s before the server's phase ended
    assert parsed["overlap_s"] == pytest.approx(0.2)
    assert parsed["before_s"] == 0.0
    assert parsed["unattributed_s"] == pytest.approx(1.3)
    assert parsed["covered_s"] == pytest.approx(SETUP - 1.3)
    assert parsed["self_s"] == pytest.approx(SETUP - 1.3 + 0.2)
    assert parsed["before_s"] + parsed["self_s"] + \
        parsed["unattributed_s"] - parsed["overlap_s"] == \
        pytest.approx(SETUP)
    assert parsed["residual_s"] == pytest.approx(0.0, abs=1e-9)
    # without the stale record, set-up begins before the program does
    later = setup_phases.assemble(RECORDS[:-1], T0, SETUP)
    assert later["before_s"] == pytest.approx(1.0)
    assert later["unattributed_s"] == pytest.approx(0.7)
    assert later["residual_s"] == pytest.approx(0.0, abs=1e-9)


def test_the_report_is_one_line_with_every_phase():
    run = FakeRun(None)
    run.setup_phases = setup_phases.assemble(RECORDS, T0, SETUP)
    setup_phases.report(run, run.setup_phases)
    head = [ln for ln in run.lines if ln.startswith("setup_phases: set-up")]
    assert len(head) == 1
    for part in ("set-up 10.000 s = before the program 0.000 + self times "
                 "8.900 + unattributed 1.300 - overlap 0.200 (residual "
                 "0.0000)", "jit/program n=2 total 3.500 self 0.800",
                 "jax/lower n=2 total 1.000 self 1.000",
                 "jax/cache_load n=2 total 0.700 self 0.700",
                 "import/paddle_tpu n=1 total 2.000 self 2.000"):
        assert part in head[0], (part, head[0])
    assert any(ln.startswith("setup_phases: longest: jit/program 3.000 s "
                             "bucket=64 kind=prefill ops=90 rows=1")
               for ln in run.lines)
    assert any("0 JAX stages under no phase" in ln for ln in run.lines)
    # what `unattributed_s` is made of, by the records on either side
    assert [(round((b - a) / 1e9, 3), before.split()[0], after.split()[0])
            for a, b, before, after in setup_phases.gaps(
                run.setup_phases)] == [
        (0.7, "jit/program", "jit/program"),
        (0.6, "compile_cache/initialize", "import/paddle_tpu")]
    assert any(ln.startswith("setup_phases: longest stretches with no "
                             "record open: 0.700 s after jit/program 3.000 "
                             "s bucket=64 kind=prefill") for ln in run.lines)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_built_run(name, monkeypatch):
    mod = _reader(name)
    run = FakeRun()
    assert mod.reduce(run) == pytest.approx(WANT[name])
    if name == "slowest_program_s":
        assert run.lines == ["setup.slowest_program_s: jit/program 3.000 s "
                             "bucket=64 kind=prefill ops=90 rows=1"]
    # a cell with no phase of the reader's kind reads 0.0, so every cell's
    # line carries all ten (the eager GPT route builds no Program)
    bare = FakeRun([S("server/start", 100_000, 100_001)])
    assert mod.reduce(bare) == pytest.approx(
        SETUP - 0.001 if name == "unattributed_s" else 0.0)
    # a program from before the phases (this PR's parent, which the driver
    # runs these same files against): nothing, and no raise
    from paddle_tpu import profiler
    monkeypatch.delattr(profiler, "phases")
    old = FakeRun(None)
    assert mod.reduce(old) is None
    assert old.lines == ["setup_phases: the program keeps no start-up "
                         "records"]


def test_the_ten_entries_are_in_the_manifest_in_order_and_contiguous():
    """Order and contiguity, not the manifest's tail: the next PR appends
    after these as the contract orders."""
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]][:6]
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("setup." + READERS[0])
    mine = manifest["per_layer"][at:at + len(READERS)]
    assert [m["name"] for m in mine] == ["setup." + r for r in READERS]
    assert not [n for n in names[:at] + names[at + len(READERS):]
                if n.startswith("setup.")]
    for m, reader, layer in zip(mine, READERS, LAYERS):
        assert m == {"name": "setup." + reader, "unit": "s",
                     "better": "lower", "source": "program_span",
                     "layer": layer, "moves": "setup_s",
                     "workloads": cells}
        mod = _reader(reader)
        assert (mod.LAYER, mod.SOURCE, mod.UNIT, mod.BETTER) == \
            (layer, "program_span", "s", "lower")
    # what they stand beside is left as it was
    assert {"compile_s", "cache_hits"} <= set(names[:at])
    assert len(manifest["per_layer"]) <= 128


# ---------------------------------------------------------------------------
# a rehearsal on the CPU, through the function `benchmark/run.py` calls
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench_setup"))


@pytest.mark.parametrize("cell", ["bert-tiny.tiny_scan",
                                  "gpt-tiny.tiny_score"])
def test_rehearsal_reports_every_setup_metric_and_closes_the_identity(
        root, cell, monkeypatch):
    import time
    from paddle_tpu import profiler
    real = work.peaks
    monkeypatch.setattr(
        work, "peaks",
        lambda kind: real("TPU v5 lite" if kind == "cpu" else kind))
    recorded = trace_reduce.summarize
    monkeypatch.setattr(
        trace_reduce, "summarize", lambda path, n_devices=1: recorded(
            os.path.join(DATA, "tiny_train_v5e.xplane.pb"), 1))
    # one process runs many tests: the store starts empty and the run's
    # clock here, as a process's does (the import is long over)
    profiler.reset_profiler()
    lines = []
    result = harness.run_cell(cell, 5, 1.5, 1, root=root, require_tpu=False,
                              t_process=time.perf_counter(),
                              log=lines.append)
    got = {name: m["value"] for name, m in result["metrics"].items()
           if name.startswith("setup.")}
    assert sorted(got) == sorted("setup." + r for r in READERS), lines
    assert all(m["unit"] == "s" for name, m in result["metrics"].items()
               if name.startswith("setup."))
    head, = [ln for ln in lines if ln.startswith("setup_phases: set-up")]
    numbers = dict(zip(
        ("setup", "before", "self", "unattributed", "overlap", "residual"),
        map(float, re.match(
            r"setup_phases: set-up ([\d.]+) s = before the program ([\d.]+) "
            r"\+ self times ([\d.]+) \+ unattributed ([\d.]+) - overlap "
            r"([\d.]+) \(residual (-?[\d.]+)\)", head).groups())))
    setup = numbers["setup"]
    assert setup > 0 and got["setup.before_program_s"] >= 0
    assert numbers["before"] + numbers["self"] + numbers["unattributed"] \
        - numbers["overlap"] == pytest.approx(setup, rel=0.02)
    assert abs(numbers["residual"]) <= 0.02 * setup
    assert 0 <= got["setup.unattributed_s"] < setup
    assert got["setup.cache_load_s"] <= \
        result["metrics"]["compile_s"]["value"] + 1e-6
    assert got["setup.import_s"] == 0.0         # imported long before
    if cell.startswith("bert"):
        # the trainer builds a Program, draws its weights in a start-up
        # run, and obtains its scanned step under a phase that names it
        for name in ("weights_s", "program_build_s", "jax_trace_s",
                     "lower_s", "first_run_s", "slowest_program_s"):
            assert got["setup." + name] > 0, (name, lines)
        assert any(ln.startswith("setup.slowest_program_s: "
                                 "executor/first_launch") and
                   "mode=run_steps" in ln for ln in lines)
    else:
        # the eager GPT route builds no Program and obtains its per-op
        # executables under no phase: the model's weights are all it names
        assert got["setup.weights_s"] > 0
        assert got["setup.program_build_s"] == 0.0
