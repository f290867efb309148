"""`profiler.Phase`: the kept span, JAX's stages booked beside it, the
`phase.*` counters, the sites that book one — and the rule that the steady
state executes no phase code (docs/observability.md §6)."""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.dygraph as dg
import paddle_tpu.static as static
from paddle_tpu import models, profiler
from paddle_tpu.core import compile_cache
from paddle_tpu.core.monitor import monitor_snapshot, prometheus_text

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def empty_store():
    """The store is bounded and a worker's earlier tests fill it: every
    test here starts from an empty one."""
    profiler.reset_profiler()
    yield


def _named(name, records=None):
    return [p for p in (records or profiler.phases()) if p.name == name]


def _one(name):
    found = _named(name)
    assert len(found) == 1, (name, [p.name for p in profiler.phases()])
    return found[0]


# ---------------------------------------------------------------------------
# the mechanism
# ---------------------------------------------------------------------------
def test_phase_is_kept_with_no_session(tmp_path):
    t0 = time.perf_counter()
    with profiler.Phase("test/outer", req=7) as outer:
        with profiler.Phase("test/inner") as inner:
            inner.set(bytes=128)
        elsewhere = threading.Thread(
            target=lambda: profiler.Phase("test/elsewhere").__enter__()
            .__exit__(None, None, None))
        elsewhere.start()
        elsewhere.join(timeout=10)
        assert not elsewhere.is_alive()
        booked = profiler.record_phase("test/booked", t0, t0 + 0.25, k=1)
        outer.set(n=2)
    t1 = time.perf_counter()
    assert [p.name for p in profiler.phases()] == [
        "test/inner", "test/elsewhere", "test/booked", "test/outer"]
    outer, inner = _one("test/outer"), _one("test/inner")
    assert outer.fields == {"req": 7, "n": 2} and outer.parent is None
    assert inner.fields == {"bytes": 128} and inner.parent is outer
    # the absolute clock of `time.perf_counter()`: the benchmark's
    assert t0 <= outer.start <= inner.start <= inner.end <= outer.end <= t1
    assert outer.thread == threading.get_ident()
    # a phase open on another thread is no parent
    other = _one("test/elsewhere")
    assert other.parent is None and other.thread != outer.thread
    assert booked is _one("test/booked")
    assert (booked.start, booked.end, booked.fields, booked.parent) == \
        (t0, t0 + 0.25, {"k": 1}, outer)
    with profiler.Phase("test/after"):
        pass
    assert _one("test/after").parent is None        # the stack unwound


def test_phase_is_a_record_event_under_a_session(tmp_path, capsys):
    """Under `start_profiler()` a phase is in the summary's spans like any
    span (its parent there a NAME: the innermost span, kept or not), and it
    is kept; a plain span is not kept.  One clock for both."""
    path = str(tmp_path / "profile")
    with profiler.profiler(state="CPU", profile_path=path):
        with profiler.RecordEvent("test/plain"):
            with profiler.Phase("test/kept", k=1):
                with profiler.RecordEvent("test/leaf"):
                    pass
                profiler.record_phase("test/booked", time.perf_counter(),
                                      time.perf_counter())
    capsys.readouterr()
    events = {e.name: e for e in profiler._state.events}
    assert events["test/kept"].parent == "test/plain"
    assert events["test/leaf"].parent == "test/kept"
    assert events["test/booked"].parent == "test/kept"
    assert [p.name for p in profiler.phases()] == ["test/booked",
                                                   "test/kept"]
    kept = _one("test/kept")
    assert kept.parent is None and kept.fields == {"k": 1}
    assert abs(kept.start - events["test/kept"].start) < 0.05
    with open(path + ".json") as f:
        trace = json.load(f)["traceEvents"]
    assert min(e["ts"] for e in trace) == 0         # from the first start
    assert {e["name"]: e["args"]["parent"] for e in trace}["test/leaf"] \
        == "test/kept"


def test_phase_annotates_a_trace_it_did_not_start(tmp_path):
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.Phase("test/traced", req=3) as phase:
            phase.set(bytes=4096)
    finally:
        jax.profiler.stop_trace()
    path = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
            for f in fs if f.endswith(".xplane.pb")][0]
    found = {e.name: dict(e.stats)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("test/")}
    assert found == {"test/traced": {"req": 3, "bytes": 4096}}
    assert _one("test/traced").fields == {"req": 3, "bytes": 4096}


def test_the_store_is_bounded_and_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(profiler, "PHASES_MAX", 3)
    before = monitor_snapshot("phase.").get("phase.test/full.calls", 0)
    for i in range(5):
        with profiler.Phase("test/full", i=i):
            pass
    assert [p.fields["i"] for p in profiler.phases()] == [0, 1, 2]
    assert profiler.phases_dropped() == 2
    # what the store dropped the counters still have
    assert monitor_snapshot("phase.")["phase.test/full.calls"] == before + 5
    profiler.reset_profiler()
    assert profiler.phases() == [] and profiler.phases_dropped() == 0


def test_phase_counters_reach_stats_and_metrics():
    calls = monitor_snapshot("phase.").get("phase.test/counted.calls", 0)
    us = monitor_snapshot("phase.").get("phase.test/counted.us", 0)
    with profiler.Phase("test/counted"):
        time.sleep(0.01)
    snap = monitor_snapshot("phase.")
    assert snap["phase.test/counted.calls"] == calls + 1
    assert snap["phase.test/counted.us"] >= us + 10_000
    text = prometheus_text()
    assert f"phase_test_counted_calls_total {calls + 1}" in text
    assert "phase_test_counted_us_total " in text


def test_the_import_books_itself_first():
    """In a process of its own: this one's store and counters have been
    reset by tests before."""
    code = ("import time; t0 = time.perf_counter(); import paddle_tpu; "
            "from paddle_tpu import profiler; "
            "from paddle_tpu.core.monitor import monitor_snapshot; "
            "first = profiler.phases()[0]; "
            "assert first.name == 'import/paddle_tpu' and "
            "first.parent is None, first.name; "
            "assert t0 <= first.start < first.end <= time.perf_counter(); "
            "assert monitor_snapshot('phase.')"
            "['phase.import/paddle_tpu.calls'] == 1")
    done = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]


# ---------------------------------------------------------------------------
# JAX's stages
# ---------------------------------------------------------------------------
def test_jax_stages_are_children_of_the_phase_on_the_compiling_thread(
        monkeypatch):
    import jax
    import jax.numpy as jnp
    # a program's trace takes tenths of a second; this test's is short
    monkeypatch.setattr(profiler, "_TRACE_MIN_S", 0.0)

    def fresh(x):               # a function JAX has not seen
        return jnp.tanh(x) * 3.0 + jnp.sum(x)

    def obtain(rows):
        with profiler.Phase("test/program", bucket=rows):
            jax.jit(fresh)(jnp.ones((rows, 7), jnp.float32)) \
                .block_until_ready()

    obtain(5)
    elsewhere = threading.Thread(target=obtain, args=(9,))
    elsewhere.start()
    elsewhere.join(timeout=120)
    assert not elsewhere.is_alive()
    first, other = _named("test/program")
    for stage in ("jax/trace", "jax/lower", "jax/compile"):
        mine = [p for p in _named(stage) if p.fields["fun"] in (
            "fresh", "jit(fresh)")]
        assert [p.parent.fields.get("bucket") for p in mine] == [5, 9], stage
        for p in mine:
            assert p.parent.name == "test/program"
            assert p.thread == p.parent.thread
            assert p.parent.start <= p.start <= p.end <= p.parent.end
    assert first.thread != other.thread
    # `jnp`'s own helpers traced inside `fresh`'s trace are that trace's
    # work: no `jax/trace` record has a `jax/trace` parent
    assert not [p for p in _named("jax/trace")
                if p.parent is not None and p.parent.name == "jax/trace"]


CACHE_LOAD = """
import sys, jax, jax.numpy as jnp
from paddle_tpu import profiler
from paddle_tpu.core import compile_cache
compile_cache.initialize(sys.argv[1], min_compile_time_s=0.0, force=True)
def obtain(**fields):       # ONE call site: source lines are in the key
    with profiler.Phase("test/program", **fields) as phase:
        jax.jit(lambda x: jnp.tanh(x) * 3.0 + jnp.sum(x))(
            jnp.ones((5, 7), jnp.float32)).block_until_ready()
    return phase
obtain()
jax.clear_caches()          # the executable comes back from the disk
again = obtain(again=1)
loads = [p for p in profiler.phases() if p.name == "jax/cache_load"]
assert loads, [p.name for p in profiler.phases()]
for load in loads:
    assert load.parent.name == "jax/compile", load.parent.name
    assert load.parent.start <= load.start <= load.end
    assert "saved_s" in load.fields and load.thread == load.parent.thread
assert [p for p in loads if p.parent.parent.fields.get("again")]
assert again.fields["executables"] >= 1
"""


def test_a_cache_load_is_under_the_compile_it_belongs_to(tmp_path):
    """In a process of its own: whether an executable comes back from the
    persistent cache depends on what a worker's earlier tests left set."""
    done = subprocess.run(
        [sys.executable, "-c", CACHE_LOAD, str(tmp_path / "xla_cache")],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]


def test_a_short_trace_stays_in_its_parents_self_time():
    import jax
    import jax.numpy as jnp
    with profiler.Phase("test/builder") as builder:
        for n in (2, 3, 4):
            jax.eval_shape(jax.jit(lambda x: jnp.exp(x) + 1), jnp.ones(n))
    assert not [p for p in _named("jax/trace")
                if p.parent is _one("test/builder")]
    assert builder.fields["short_traces"] >= 3
    assert 0 < builder.fields["short_trace_s"] < 3 * profiler._TRACE_MIN_S \
        * builder.fields["short_traces"]


# ---------------------------------------------------------------------------
# the sites, each once; and nothing in the steady state
# ---------------------------------------------------------------------------
def _tiny_bert():
    return models.build_bert_base(vocab=64, seq=8, hidden=16, layers_n=1,
                                  heads=2, batch=4, use_amp=True)


def _feed(k=None):
    ids = np.arange(32, dtype=np.int64).reshape(4, 8) % 64
    feed = {"ids": ids, "pos": np.tile(np.arange(8, dtype=np.int64), (4, 1)),
            "labels": ids[..., None]}
    return feed if k is None else {n: np.stack([v] * k)
                                   for n, v in feed.items()}


def test_trainer_sites_book_once_and_dispatches_book_nothing():
    main, startup, loss = _tiny_bert()
    build = _one("program/build")
    block = main.global_block()
    assert (build.fields["ops"], build.fields["vars"]) == (
        len(block.ops), len(block.vars))
    # shape inference traces an op at a time: named, not a record each
    assert build.fields["short_traces"] > 10
    for child in ("amp/rewrite", "static/head_loss_rewrite",
                  "static/backward"):
        assert _one(child).parent is build

    exe, scope = static.Executor(), static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        first = _one("executor/first_launch")
        assert first.fields["startup"] == 1 and first.fields["mode"] == \
            "startup" and len(first.fields["fingerprint"]) == 16
        exe.run(main, feed=_feed(), fetch_list=[loss])
        exe.run_steps(main, feed=_feed(3), fetch_list=[loss])
        launches = _named("executor/first_launch")[1:]
        assert [p.fields["mode"] for p in launches] == ["run", "run_steps"]
        checks = _named("executor/trace_compile")
        assert [p.fields["mode"] for p in checks] == ["run", "run_steps"]
        for launch, check in zip(launches, checks):
            assert launch.fields["startup"] == 0
            assert launch.fields["fingerprint"] == \
                check.fields["fingerprint"] != first.fields["fingerprint"]
            # the executable was obtained under the phase that names it
            step = [p for p in _named("jax/compile")
                    if p.parent is launch]
            assert len(step) == launch.fields["executables"] > 0
            assert all(p.fields["fun"].startswith("jit(") for p in step)
        # an entry's launches are phases until one obtains no executable
        exe.run(main, feed=_feed(), fetch_list=[loss])
        exe.run_steps(main, feed=_feed(3), fetch_list=[loss])
        settled = _named("executor/first_launch")[3:]
        assert [p.fields["mode"] for p in settled] == ["run", "run_steps"]
        assert not any("executables" in p.fields for p in settled)
        # everything warm: N more dispatches execute no phase code
        kept = len(profiler.phases())
        calls = monitor_snapshot("phase.")
        for _ in range(5):
            exe.run(main, feed=_feed(), fetch_list=[loss])
            exe.run_steps(main, feed=_feed(3), fetch_list=[loss])
        assert len(profiler.phases()) == kept
        assert monitor_snapshot("phase.") == calls


def test_mesh_dispatch_books_its_first_launch_and_then_nothing():
    import jax
    from paddle_tpu.distributed.compiled_program import CompiledProgram
    main, startup, loss = _tiny_bert()
    exe, scope = static.Executor(), static.Scope()
    target = CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=jax.devices()[:2])
    with static.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):      # until every entry's launches settle
            exe.run(target, feed=_feed(), fetch_list=[loss])
            exe.run_steps(target, feed=_feed(2), fetch_list=[loss])
        assert not exe._unsettled
        launches = _named("executor/first_launch")[1:]
        assert {p.fields["mode"] for p in launches} == {
            "compiled", "compiled_steps"}
        assert [p.fields["mode"] for p in _named(
            "executor/trace_compile")] == ["compiled", "compiled_steps"]
        # the second launch of the per-step entry obtains its program
        # again (its state came back from the scanned one); it too is
        # under the phase that names it, as every executable here is
        assert sum("executables" in p.fields for p in launches) >= 2
        mine = [p for p in _named("jax/compile")
                if p.fields["fun"] in ("jit(step)", "jit(multi)")]
        assert mine and all(p.parent in launches for p in mine)
        kept = len(profiler.phases())
        for _ in range(3):
            exe.run(target, feed=_feed(), fetch_list=[loss])
            exe.run_steps(target, feed=_feed(2), fetch_list=[loss])
        assert len(profiler.phases()) == kept


def _post(server, ids, n):
    req = urllib.request.Request(
        f"http://{server.host}:{server.port}/generate",
        data=json.dumps({"input_ids": ids, "max_length": n}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as r:
        return json.loads(r.read())["output_ids"]


def test_serving_sites_book_once_and_decode_steps_book_nothing(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serve_smoke
    from paddle_tpu.inference.server import InferenceServer
    serve_smoke.save_tiny_model(str(tmp_path))
    with dg.guard():
        paddle_tpu.seed(11)
        model = models.granite_hybrid_tiny()
        built = _one("model/build")
        n = sum(int(np.prod(p.shape)) for p in model.parameters())
        assert (built.fields["params"], built.fields["bytes"]) == (n, 4 * n)
        plan = static.page_budget(model, page_tokens=4, max_context=64,
                                  hbm_bytes=8 << 20, max_slots_cap=2)
        t0 = time.perf_counter()
        server = InferenceServer(str(tmp_path), generator=model,
                                 gen_kv_pool=plan)
        server.start()
        try:
            pool = _one("kv_pool/allocate")
            state = server.engine.kv_pool.state
            assert (pool.fields["slots"], pool.fields["pages"]) == (2, 0)
            assert pool.fields["bytes"] == sum(
                a.nbytes for a in state.arrays.values())
            up = _one("server/start")
            assert t0 <= up.start <= pool.start and pool.end <= up.end
            prompt = list(range(3, 10))
            first = _post(server, prompt, 6)
            programs = {(p.fields["kind"], p.fields.get("bucket"),
                         p.fields.get("columns")): p
                        for p in _named("jit/program")}
            assert set(programs) == {("prefill", 16, None),
                                     ("install", 16, None),
                                     ("decode", None, 16)}
            engine_thread = programs["decode", None, 16].thread
            assert engine_thread != up.thread
            for (kind, _, _), program in programs.items():
                assert program.thread == engine_thread
                assert program.fields["rows"] == (2 if kind == "decode"
                                                  else 1)
                # every executable of the route under the program's name
                assert [p for p in _named("jax/compile")
                        if p.parent is program], kind
                if kind != "install":
                    record, = [p for p in _named("jit/record")
                               if p.parent is program]
                    assert record.fields["ops"] == program.fields["ops"] > 0
            assert not [p for p in _named("jax/compile")
                        if p.thread == engine_thread and p.parent is None]
            # everything warm: more requests, more decode steps, no record
            kept = len(profiler.phases())
            steps = monitor_snapshot("serving.")["serving.gen.steps"]
            for _ in range(3):
                assert _post(server, prompt, 6) == first
            assert monitor_snapshot("serving.")["serving.gen.steps"] \
                >= steps + 10
            assert len(profiler.phases()) == kept
            with urllib.request.urlopen(
                    f"http://{server.host}:{server.port}/stats",
                    timeout=10) as r:
                stats = json.loads(r.read())
            assert stats["phases"]["phase.server/start.calls"] >= 1
            assert stats["phases"]["phase.jit/program.us"] > 0
        finally:
            server.stop()


def test_gpt_model_and_the_compile_cache_book_their_phases(tmp_path):
    with dg.guard():
        model = models.GPTModel(models.GPTConfig(
            vocab_size=32, hidden_size=16, num_layers=1, num_heads=2,
            max_position=16))
    built = _one("model/build")
    n = sum(int(np.prod(p.shape)) for p in model.parameters())
    assert (built.fields["params"], built.fields["bytes"]) == (n, 4 * n)
    d = str(tmp_path / "xla_cache")
    compile_cache.initialize(d, force=True)
    try:
        assert _one("compile_cache/initialize").fields == {"entries": 0}
        compile_cache.initialize()              # set up already: no phase
        _one("compile_cache/initialize")
    finally:
        compile_cache.initialize(force=True)
