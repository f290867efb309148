"""The program's spans read back from a trace: nesting, self time and
per-thread shares on hand-built events; the twelve readers on hand-built
runs; and an engine, an Executor and a Prefetcher run on the CPU under a
`jax.profiler` session they did not start, whose host plane must hold every
span with its fields on the thread that did the work."""
import importlib.util
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import benchmark_tiny_root as tiny
from benchmark import device_scopes, harness, program_spans as ps
from benchmark import trace_reduce, work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "tiny_scoped_v5e.xplane.pb.gz")
MS = 1_000_000      # ns
# a per-layer metric over the program's own spans, dropped into the
# throw-away root as a new file and a manifest entry like any other
SPAN_METRIC = {"name": "extra.program_span_count", "unit": "count",
               "better": "lower", "source": "program_span",
               "layer": "executor", "moves": "setup_s"}
SPAN_READER = '''"""The program's spans that began in the traced slice."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "executor", "program_span", "count", "lower"


def reduce(run):
    return float(len(program_spans.of(run)["whole"])) or None
'''


def S(name, start_ms, end_ms, line=0, **fields):
    return ps.Span(name, start_ms * MS, end_ms * MS, line, fields)


# ---------------------------------------------------------------------------
# hand-built events
# ---------------------------------------------------------------------------
def test_parent_is_the_innermost_enclosing_span_on_the_same_line():
    spans = ps.nest([
        S("engine/forward", 12, 80), S("engine/prefill", 10, 100, req=1),
        S("engine/fetch", 80, 95), S("engine/build", 10, 12),
        S("server/generate", 0, 120, line=3, req=1),
        S("server/wait", 5, 118, line=3), S("engine/admit", 100, 101)])
    by = {sp.name: sp for sp in spans}
    assert by["engine/prefill"].parent is None
    for child in ("engine/build", "engine/forward", "engine/fetch"):
        assert by[child].parent is by["engine/prefill"]
    # the handler's span covers every instant and is nobody's parent on
    # the engine's line
    assert by["server/wait"].parent is by["server/generate"]
    assert by["engine/admit"].parent is None
    # self time = duration - children (10..100 less 2 + 68 + 15)
    assert by["engine/prefill"].self_ns == 5 * MS
    assert by["engine/forward"].self_ns == 68 * MS
    assert by["server/generate"].self_ns == 7 * MS
    assert [sp.name for sp in spans if sp.line == 0][:2] == \
        ["engine/prefill", "engine/build"]


def test_clip_totals_and_the_thread_that_feeds_the_device():
    spans = [S("engine/prefill", 0, 60), S("engine/forward", 5, 50),
             S("engine/idle", 60, 100), S("engine/prefill", 100, 160),
             S("engine/forward", 110, 150),
             S("server/wait", 0, 160, line=1),
             S("Executor::Run", 0, 10, line=2)]
    inside = ps.nest(ps.clip(spans, (20 * MS, 120 * MS)))
    got = ps.totals(inside, line=0)
    assert got["engine/prefill"] == {"count": 2, "ns": 60 * MS,
                                     "self_ns": 20 * MS}
    assert got["engine/forward"]["ns"] == 40 * MS
    assert got["engine/idle"] == {"count": 1, "ns": 40 * MS,
                                  "self_ns": 40 * MS}
    assert "server/wait" not in got and "server/wait" in ps.totals(inside)
    assert not any(sp.name == "Executor::Run" for sp in inside)  # outside
    # an engine's loop wins over a dispatching thread; without an engine
    # the dispatching thread; without either, nothing
    assert ps.feeding_line(spans) == (0, ps.ENGINE_LOOP)
    assert ps.feeding_line([sp for sp in spans if sp.line]) == \
        (2, ps.DISPATCH)
    assert ps.feeding_line([sp for sp in spans if sp.line == 1]) == \
        (None, ())


def test_idle_gaps_are_attributed_on_the_feeding_thread_only():
    """`server/wait` on a handler thread covers every instant and explains
    none: only the engine thread's spans take the device's idle time."""
    spans = ps.nest([S("engine/prefill", 0, 90), S("engine/forward", 10, 70),
                     S("engine/fetch", 70, 85), S("engine/idle", 90, 100),
                     S("server/wait", 0, 100, line=1)])
    jax_own = [("PjitFunction(add)", 20 * MS, 30 * MS, 0),
               ("PjitFunction(add)", 40 * MS, 45 * MS, 1)]  # another thread
    parsed = ps.assemble(spans, (0, 100 * MS), jax_own,
                         [(72 * MS, 80 * MS)])
    mine, both = ps.reattribute(parsed)
    assert mine == {"engine/prefill": 15 * MS, "engine/forward": 60 * MS,
                    "engine/fetch": 7 * MS, "engine/idle": 10 * MS}
    assert both["PjitFunction(add)"] == 10 * MS
    assert both["engine/forward"] == 50 * MS
    assert "server/wait" not in both and "unattributed" not in both
    assert ps.reattribute(ps.assemble([], (0, 1))) == ({}, {})


def test_spans_open_at_the_slices_edges_are_accounted_for_by_name():
    """A TraceMe is recorded only if it begins and ends inside the
    session: of the prefill under way when the slice began only the
    children that began later are there, orphaned, and of the one under
    way when it ended only those that had finished.  Shares are taken
    between the first and the last whole loop span, and the two edges are
    named in the attribution instead of reading `unattributed`."""
    spans = [S("engine/fetch", 30, 38), S("engine/finish", 38, 40),
             S("engine/admit", 40, 41)] + _prefill(41, 7, 5.0, 64) + \
        [S("engine/idle", 241, 300), S("engine/admit", 300, 301),
         S("engine/build", 301, 311), S("engine/upload", 311, 313)]
    parsed = ps.assemble(spans, (0, 400 * MS))
    assert parsed["extent"] == (40 * MS, 301 * MS)
    assert {sp.name for sp in parsed["loop"]} >= {"engine/prefill",
                                                  "engine/idle"}
    assert not any(sp.start < 40 * MS or sp.end > 301 * MS
                   for sp in parsed["loop"])
    run = FakeRun()
    run.program_spans = parsed
    shares = {n: _reader(n).reduce(run) for n in (
        "engine_forward_share", "engine_fetch_share", "engine_host_share",
        "engine_idle_share")}
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares["engine_forward_share"] == pytest.approx(
        100.0 * 150 / 261)
    mine, _ = ps.reattribute(parsed)        # no device: all of it idle
    assert mine[ps.OPEN_AT_START] == 30 * MS      # 0-30; then the orphans
    assert mine[ps.OPEN_AT_END] == (400 - 313) * MS
    assert mine["engine/fetch"] == (8 + 20) * MS
    assert "unattributed" not in mine


# ---------------------------------------------------------------------------
# the twelve readers on hand-built runs
# ---------------------------------------------------------------------------
class FakeRun:
    def __init__(self, spans=(), scopes=None, units=None, window_ms=1000):
        self.program_spans = ps.assemble(spans, (0, window_ms * MS))
        self.device_scopes = scopes
        self.slice_units = units
        self.lines = []

    def log(self, line):
        self.lines.append(line)


def _prefill(t0, req, waited, bucket):
    """One 200 ms prefill from `t0` ms: 10 build, 2 upload, 150 forward,
    20 fetch, 3 sample, 5 finish, 10 of its own."""
    logits = bucket * 50257 * 4
    return [
        S("engine/prefill", t0, t0 + 200, req=req, prompt=bucket - 3,
          bucket=bucket, waited_ms=waited, radix_hit=0),
        S("engine/build", t0 + 2, t0 + 12),
        S("engine/upload", t0 + 12, t0 + 14, bytes=8 * bucket),
        S("engine/forward", t0 + 15, t0 + 165),
        S("engine/fetch", t0 + 165, t0 + 185, bytes=logits),
        S("engine/sample", t0 + 186, t0 + 189),
        S("engine/finish", t0 + 190, t0 + 195)]


SERVING = (_prefill(0, 1, 600.0, 64) + _prefill(200, 2, 610.0, 128)
           + [S("engine/admit", 400, 410, admitted=2, queued=0),
              S("engine/idle", 410, 450)]
           + _prefill(450, 3, 590.0, 128) + _prefill(650, 4, 800.0, 256)
           + [S("engine/idle", 850, 1000),
              S("server/generate", 0, 1000, line=5, req=1, n=1),
              S("server/wait", 1, 999, line=5)])
TRAINING = (
    [S("Executor::RunSteps", 100 * i, 100 * i + 12) for i in range(10)]
    + [S("executor/launch", 100 * i + 3, 100 * i + 11) for i in range(10)]
    + [S("executor/prepare", 100 * i, 100 * i + 2) for i in range(10)]
    + [S("prefetcher/build", 100 * i, 100 * i + 30, line=1)
       for i in range(10)]
    + [S("prefetcher/place", 100 * i + 30, 100 * i + 40, line=1)
       for i in range(10)]
    + [S("mesh/place_feed", 100 * i + 31, 100 * i + 39, line=1)
       for i in range(10)]
    + [S("prefetcher/wait", 100 * i + 12, 100 * i + 13) for i in range(10)])
SCOPES = {"busy_ns": 1000, "roles": {"forward": 400, "backward": 520,
                                     "optimize": 30},
          "ops": {}, "unscoped": {"copy-done": 50}}
READERS = {
    "scope_forward_share": ("train", 40.0),
    "scope_backward_share": ("train", 52.0),
    "scope_optimize_share": ("train", 3.0),
    # 10 dispatches of 12 ms less 8 ms of launch, over 80 steps
    "exec_overhead_ms_per_step": ("train", 0.5),
    # 10 x (30 build + 10 place); the place_feed inside place is not added
    "input_busy_ms_per_step": ("train", 5.0),
    "queue_wait_ms_p50": ("serve", 605.0),
    "prefill_ms_p50": ("serve", 200.0),
    "engine_forward_share": ("serve", 60.0),
    "engine_fetch_share": ("serve", 8.0),
    # per prefill 10 + 2 + 3 + 5 and 10 of its own; admission 10
    "engine_host_share": ("serve", 13.0),
    "engine_idle_share": ("serve", 19.0),
    "host_link_mb_per_forward": (
        "serve", (64 + 128 + 128 + 256) * (50257 * 4 + 8) / 4e6),
}


def _reader(name):
    path = os.path.join(tiny.REPO, "benchmark", "layer_metrics",
                        name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_hand_built_run(name):
    kind, want = READERS[name]
    run = FakeRun(TRAINING, SCOPES, units=80) if kind == "train" \
        else FakeRun(SERVING)
    assert _reader(name).reduce(run) == pytest.approx(want)
    # a program from before the spans and scopes (this PR's parent, which
    # the driver runs these same files against): nothing, and no raise
    assert _reader(name).reduce(FakeRun(units=80)) is None


def test_the_four_engine_shares_account_for_the_slice():
    run = FakeRun(SERVING)
    shares = [_reader(n).reduce(run) for n in (
        "engine_forward_share", "engine_fetch_share", "engine_host_share",
        "engine_idle_share")]
    assert sum(shares) == pytest.approx(100.0)


def test_split_of_a_parent_among_its_children():
    """The table S2 waits for: what a prefill or a decode step is made
    of, with the bytes each part moved."""
    parsed = FakeRun(SERVING).program_spans
    n, ms, parts = ps.split(parsed, "engine/prefill")
    assert (n, ms) == (4, pytest.approx(200.0))
    assert parts["engine/forward"] == (pytest.approx(150.0), 0)
    assert parts["engine/fetch"] == (
        pytest.approx(20.0), (64 + 128 + 128 + 256) * 50257 * 4 / 4)
    assert parts["(self)"] == (pytest.approx(10.0), 0)
    assert sum(t for t, _ in parts.values()) == pytest.approx(ms)
    assert ps.split(parsed, "engine/step") is None


def test_readers_are_the_manifests_twelve_new_entries():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    new = {m["name"].split(".")[-1]: m for m in per_layer
           if m["source"] == "program_span"
           or m["name"].startswith("train.scope_")}
    assert set(new) == set(READERS)
    for name, m in new.items():
        group = "train" if READERS[name][0] == "train" else "score"
        assert m["name"] == f"{group}.{name}"
        assert m["workloads"] == (
            ["gpt2-medium.score_short"] if group == "score" else
            ["bert-base.pretrain_s512", "bert-base.pretrain_s512_dp4"])
    assert per_layer[-12:] == [new[m["name"].split(".")[-1]]
                               for m in per_layer[-12:]]   # appended last


# ---------------------------------------------------------------------------
# real runs on the CPU under a profiler session the program did not start
# ---------------------------------------------------------------------------
def _trace(tmp_path, body):
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.SLICE_SPAN):
            body()
    finally:
        jax.profiler.stop_trace()
    path = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
            for f in fs if f.endswith(".xplane.pb")][0]
    return ps.load(path)


def _by_name(parsed):
    out = {}
    for sp in parsed["whole"]:
        out.setdefault(sp.name, []).append(sp)
    return out


def test_engine_and_server_spans_reach_a_foreign_trace(tmp_path):
    import paddle_tpu
    import paddle_tpu.dygraph as dg
    from paddle_tpu.inference.server import InferenceServer
    from paddle_tpu.models import GPTConfig, GPTForGeneration, GPTModel
    from benchmark import serving
    model_dir = tmp_path / "model"
    model_dir.mkdir()
    serving._save_stub_predictor(str(model_dir))
    replies = {}

    def post(base, key, ids, n):
        req = urllib.request.Request(
            base + "/generate", data=json.dumps(
                {"input_ids": ids, "max_length": n}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            replies[key] = json.loads(r.read())["output_ids"]

    with dg.guard():
        paddle_tpu.seed(5)
        gen = GPTForGeneration(GPTModel(GPTConfig(
            vocab_size=40, hidden_size=16, num_layers=2, num_heads=2,
            max_position=64, dropout=0.0)))
        gen.eval()
        srv = InferenceServer(str(model_dir), generator=gen,
                              gen_kv_pool="auto")
        srv.start()
        base = f"http://{srv.host}:{srv.port}"
        try:
            post(base, "warm", [3, 4, 5], 3)

            def body():
                threads = [threading.Thread(target=post, args=(
                    base, i, [7 + i, 9, 11, 13 + i], 1 + 3 * (i % 2)))
                    for i in range(3)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=120)
                assert not any(t.is_alive() for t in threads)
                time.sleep(0.3)     # the engine's loop goes idle
            parsed = _trace(tmp_path / "trace", body)
        finally:
            srv.stop()
    assert set(replies) == {"warm", 0, 1, 2}
    by = _by_name(parsed)
    engine = {"engine/idle", "engine/admit", "engine/prefill",
              "engine/build", "engine/upload", "engine/forward",
              "engine/fetch", "engine/sample", "engine/kv_install",
              "engine/finish", "engine/step", "engine/gather",
              "engine/kv_append"}
    assert engine | {"server/generate", "server/wait"} <= set(by), set(by)
    # a request's handler span and its prefill share a `req`
    posts = {sp.fields["req"]: sp for sp in by["server/generate"]}
    # every engine span on the engine's thread (this server's: the one
    # whose prefills carry these requests), every handler off it
    line, = {sp.line for sp in by["engine/prefill"]
             if sp.fields["req"] in posts}
    assert line == parsed["line"]
    by = {name: [sp for sp in spans if sp.line == line
                 or not name.startswith("engine/")]
          for name, spans in by.items()}
    assert all(by[name] for name in engine)
    assert line not in {sp.line for sp in by["server/generate"]}
    assert len(posts) == 3 and all(sp.fields["n"] == 1
                                   for sp in posts.values())
    prefills = {sp.fields["req"]: sp for sp in by["engine/prefill"]}
    assert set(prefills) == set(posts)
    for req, sp in prefills.items():
        assert posts[req].start <= sp.start and sp.end <= posts[req].end
        assert sp.fields["prompt"] == 4 and sp.fields["bucket"] == 16
        assert sp.fields["waited_ms"] >= 0 and sp.fields["radix_hit"] == 0
        assert sp.parent is None
    for sp in by["server/wait"]:
        assert sp.parent.name == "server/generate"
    # children, and the bytes each crossing moved
    for name in ("engine/build", "engine/kv_install"):
        assert {sp.parent.name for sp in by[name]} == {"engine/prefill"}
    for name in ("engine/gather", "engine/kv_append"):
        assert {sp.parent.name for sp in by[name]} == {"engine/step"}
    assert {sp.parent.name for sp in by["engine/forward"]} == \
        {"engine/prefill", "engine/step"}
    for name in ("engine/upload", "engine/fetch", "engine/gather",
                 "engine/kv_install", "engine/kv_append"):
        assert all(sp.fields["bytes"] > 0 for sp in by[name]), name
    logits = 16 * 40 * 4           # [1, bucket, vocab] fp32, whole
    assert {sp.fields["bytes"] for sp in by["engine/fetch"]
            if sp.parent.name == "engine/prefill"} == {logits}
    assert all(sp.fields["active"] >= 1 and sp.fields["lpad"] == 16
               for sp in by["engine/step"])
    assert all({"admitted", "queued"} <= set(sp.fields)
               for sp in by["engine/admit"])
    assert sum(sp.fields["admitted"] for sp in by["engine/admit"]) == 3
    # JAX's own TraceMes nest inside engine/forward: single ops are not
    # spanned by the program
    forward = by["engine/forward"][0]
    assert any(n.startswith("PjitFunction(") and h_line == line
               and forward.start <= s and e <= forward.end
               for n, s, e, h_line in parsed["host"])


def test_executor_mesh_and_prefetcher_spans_reach_a_foreign_trace(tmp_path):
    import jax
    import paddle_tpu.static as static
    from paddle_tpu.distributed.compiled_program import CompiledProgram
    from paddle_tpu.reader.prefetcher import Prefetcher
    from paddle_tpu.static import layers
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 8])
        y = layers.data("y", [-1, 1])
        loss = layers.mean(layers.square(layers.fc(x, 1) - y))
        static.Adam(1e-3).minimize(loss)
    main.random_seed = startup.random_seed = 11
    rng = np.random.default_rng(0)

    def feed(*lead):
        return {"x": rng.random(lead + (8, 8), np.float32),
                "y": rng.random(lead + (8, 1), np.float32)}

    exe, scope = static.Executor(), static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed(), fetch_list=[loss])

        def body():
            with Prefetcher(iter([feed(), feed()])) as feeder:
                for f in feeder:
                    exe.run(main, feed=f, fetch_list=[loss])
            exe.run_steps(main, feed=feed(3), fetch_list=[loss])  # a miss
            target = CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, places=jax.devices()[:4])
            with Prefetcher(iter([feed()]),
                            place_fn=target.place_feed) as feeder:
                for f in feeder:
                    exe.run(target, feed=f, fetch_list=[loss])
        parsed = _trace(tmp_path, body)
    by = _by_name(parsed)
    assert {"Executor::Run", "Executor::RunSteps", "executor/prepare",
            "executor/trace_compile", "executor/launch", "executor/fetch",
            "executor/observe", "executor/hooks", "mesh/place_feed",
            "prefetcher/build", "prefetcher/place",
            "prefetcher/wait"} <= set(by)
    line, = {sp.line for sp in by["Executor::Run"]}
    assert len(by["Executor::Run"]) == 3
    assert len(by["Executor::RunSteps"]) == 1
    for name in ("executor/prepare", "executor/launch", "executor/fetch",
                 "executor/observe", "executor/hooks",
                 "executor/trace_compile"):
        assert {sp.line for sp in by[name]} == {line}
        assert {sp.parent.name for sp in by[name]} <= \
            {"Executor::Run", "Executor::RunSteps"}
        assert len(by[name]) >= 2
    # the miss path names what compiled: the scan, then the mesh's step
    assert [(sp.fields["mode"], len(sp.fields["fingerprint"]))
            for sp in by["executor/trace_compile"]] == \
        [("run_steps", 16), ("compiled", 16)]
    # the producer builds and places on its own thread; the consumer
    # waits on the dispatching one
    producer = {sp.line for sp in by["prefetcher/build"]}
    assert producer == {sp.line for sp in by["prefetcher/place"]}
    assert line not in producer
    assert {sp.line for sp in by["prefetcher/wait"]} == {line}
    assert len(by["prefetcher/place"]) == 3
    # place_feed: inside the prefetcher's place for the mesh, and again
    # (a pass-through of placed arrays) inside the mesh's Executor::Run
    assert sorted(sp.parent.name for sp in by["mesh/place_feed"]) == \
        ["Executor::Run", "prefetcher/place"]
    # what the readers take from it
    run = FakeRun(units=6)
    run.program_spans = parsed
    assert 0 < _reader("exec_overhead_ms_per_step").reduce(run) < 200
    assert 0 < _reader("input_busy_ms_per_step").reduce(run) < 200
    mine, _ = ps.reattribute(parsed)
    window = parsed["window"][1] - parsed["window"][0]
    assert sum(mine.values()) == pytest.approx(window)   # no device: idle
    ps.report(run, parsed)
    assert any("span Executor::Run:" in ln for ln in run.lines)


# ---------------------------------------------------------------------------
# a trace recorded on the chip after the spans existed
# ---------------------------------------------------------------------------
def test_recorded_scoped_trace_holds_the_programs_spans():
    parsed = ps.load(SCOPED)
    by = _by_name(parsed)
    assert len(by["Executor::Run"]) == 2
    assert len(by["Executor::RunSteps"]) == 1
    assert len(by["executor/launch"]) == 3
    assert len(by["prefetcher/place"]) == 3
    line = parsed["line"]
    assert {sp.line for sp in by["prefetcher/place"]} != {line}
    assert parsed["busy"] and trace_reduce.total(parsed["busy"]) > 0
    # the device idles in a tiny run; the program's spans on the
    # dispatching thread and the benchmark's filter explain the gaps
    mine, both = ps.reattribute(parsed)
    idle = sum(both.values())
    assert idle > 0 and both.get("unattributed", 0) < 0.5 * idle
    assert any(k.startswith("executor/") for k in mine)
    assert any(k.startswith("PjitFunction(") for k in both)


# ---------------------------------------------------------------------------
# the whole path: a traced rehearsal with a span reader dropped in
# ---------------------------------------------------------------------------
@pytest.fixture
def cpu_stands_in_for_a_v5e(monkeypatch):
    real = work.peaks
    monkeypatch.setattr(
        work, "peaks",
        lambda kind: real("TPU v5 lite" if kind == "cpu" else kind))
    summarize = trace_reduce.summarize
    monkeypatch.setattr(
        trace_reduce, "summarize", lambda path, n_devices=1: summarize(
            os.path.join(DATA, "tiny_train_v5e.xplane.pb"), 1))


def test_traced_score_rehearsal_reports_the_span_metrics(
        tmp_path, cpu_stands_in_for_a_v5e):
    root = tiny.make(tmp_path, extra_per_layer=[SPAN_METRIC])
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "program_span_count.py"), "w") as f:
        f.write(SPAN_READER)
    lines = []
    result = harness.run_cell("gpt-tiny.tiny_score", 3, 1.5, 1, root=root,
                              require_tpu=False, log=lines.append)
    got = result["metrics"]
    names = ["score." + n for n, (kind, _) in READERS.items()
             if kind == "serve"]
    assert set(names) <= set(got), (sorted(got), lines)
    shares = [got[n]["value"] for n in names if n.endswith("_share")]
    assert 90.0 < sum(shares) <= 100.0 + 1e-6
    assert got["score.prefill_ms_p50"]["value"] > 0
    assert got["score.host_link_mb_per_forward"]["value"] > 0
    # no device plane on the CPU: the scope readers say nothing
    assert not any(n.startswith("train.") for n in got)
    # the reader that exists only in the throw-away root
    assert got[SPAN_METRIC["name"]]["value"] >= 1
    assert any(ln.startswith("program_spans: idle gaps of chip 0 by "
                             "program spans") for ln in lines)
    assert device_scopes.read_device(
        harness.TraceSlice(True, os.path.join(
            root, ".bench_trace", "gpt-tiny.tiny_score"),
            1.5).xplane_path()) is None
