"""Telemetry tier (docs/observability.md): run journal, rank heartbeats,
executor step telemetry, metrics sidecar, and the supervisor's
heartbeat stall deadline.

Tier-1 keeps the cheap units and the in-process integration (one train
step -> monitor gauges + journal events + heartbeat file).  The two
acceptance scenarios are ``slow``: a chaos-wedged rank (permanent
collective_fail) detected by the stall deadline and torn down by the
real launcher with elastic re-form, and a kill/resume 8->4->8 run whose
restart timeline reconstructs from the journals alone.
"""
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu import observability as obs
from paddle_tpu.core import monitor
from paddle_tpu.core.program import _reset_unique_names

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "elastic_worker.py")


def _build_train():
    from paddle_tpu.static import layers
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 8])
        y = layers.data("y", [-1, 1])
        h = layers.fc(x, 16, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        static.Adam(learning_rate=1e-2).minimize(loss)
    return main, startup, loss


# ---------------------------------------------------------------------------
# journal units
# ---------------------------------------------------------------------------
def test_journal_schema_and_seq_chain(tmp_path):
    j = obs.RunJournal(str(tmp_path), run_id="r1", rank=3)
    j.event("step", step=1, wall_ms=2.5)
    j.event("checkpoint_commit", step=1, path="/x")
    j.close()
    events = obs.read_journal(str(tmp_path / "journal.rank3.jsonl"))
    assert [e["kind"] for e in events] == ["step", "checkpoint_commit"]
    for e in events:
        assert e["v"] == 1 and e["run_id"] == "r1" and e["rank"] == 3
        assert "t" in e
    assert [e["seq"] for e in events] == [0, 1]


def test_journal_appends_across_incarnations(tmp_path):
    a = obs.RunJournal(str(tmp_path), run_id="runA", rank=0)
    a.event("step", step=1)
    a.close()
    b = obs.RunJournal(str(tmp_path), run_id="runB", rank=0)
    b.event("restore", step=1, global_step=1)
    b.event("step", step=2)
    b.close()
    events = obs.read_journal(str(tmp_path / "journal.rank0.jsonl"))
    assert len(events) == 3  # append-only: both incarnations, one file
    tl = obs.reconstruct_timeline(events)
    assert tl["n_incarnations"] == 2
    assert tl["incarnations"][0]["run_id"] == "runA"
    assert tl["incarnations"][1]["restored_step"] == 1
    assert tl["incarnations"][1]["steps"] == [2]


def test_journal_skips_torn_lines_strict_raises(tmp_path):
    path = str(tmp_path / "journal.rank0.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "step", "seq": 0}) + "\n")
        f.write('{"kind": "step", "se')  # SIGKILL mid-write
    events = obs.read_journal(path)
    assert len(events) == 1
    with pytest.raises(ValueError):
        obs.read_journal(path, strict=True)


def test_journal_append_after_sigkill_tear_seals_the_fragment(tmp_path):
    """A new incarnation appending onto a torn tail must not weld its
    run_start onto the fragment: the writer seals the tear with a
    newline, the reader skips the fragment, every later event parses."""
    path = str(tmp_path / "journal.rank0.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "step", "seq": 0,
                            "run_id": "a"}) + "\n")
        f.write('{"kind": "chaos", "direc')  # died mid-write
    j = obs.RunJournal(str(tmp_path), run_id="b", rank=0)
    j.event("restore", step=1)
    j.event("step", step=2)
    j.close()
    events = obs.read_journal(path)
    assert [e["kind"] for e in events] == ["step", "restore", "step"]
    tl = obs.reconstruct_timeline(events)
    assert tl["n_incarnations"] == 2


def test_journal_emit_disabled_is_noop(tmp_path, monkeypatch):
    monkeypatch.delenv(obs.JOURNAL_ENV, raising=False)
    obs.set_journal_dir(None)
    obs.emit("step", step=1)  # must not throw, must not create files
    assert obs.get_journal() is None


# ---------------------------------------------------------------------------
# heartbeat units
# ---------------------------------------------------------------------------
def test_heartbeat_write_read_and_stall(tmp_path):
    d = str(tmp_path)
    w0 = obs.HeartbeatWriter(d, rank=0)
    w1 = obs.HeartbeatWriter(d, rank=1)
    w0.beat(5)
    w1.beat(7)
    beats = obs.read_heartbeats(d)
    assert beats[0]["step"] == 5 and beats[1]["step"] == 7
    now = time.time()
    assert obs.stalled_ranks(d, 10.0, now=now) == []
    # age rank 1's beat past the deadline
    rec = json.load(open(obs.heartbeat.heartbeat_path(d, 1)))
    rec["t"] = now - 60
    json.dump(rec, open(obs.heartbeat.heartbeat_path(d, 1), "w"))
    assert obs.stalled_ranks(d, 10.0, now=now) == [1]
    # the live-ranks filter drops ranks the supervisor no longer owns
    assert obs.stalled_ranks(d, 10.0, ranks=[0], now=now) == []
    # a rank with no file yet (still compiling) is never stalled
    assert obs.stalled_ranks(d, 10.0, ranks=[0, 1, 2], now=now) == [1]


def test_watchdog_tears_down_stalled_rank(tmp_path):
    """watch_local_trainers with a heartbeat dir treats a stale-beat
    LIVE rank like a dead one: pod killed, RuntimeError raised."""
    from paddle_tpu.distributed.launch_utils import (TrainerProc,
                                                     watch_local_trainers)
    d = str(tmp_path)
    tp = TrainerProc()
    tp.proc = subprocess.Popen([sys.executable, "-c",
                                "import time; time.sleep(60)"])
    tp.rank = 0
    w = obs.HeartbeatWriter(d, rank=0)
    w.beat(1)
    try:
        # fresh beat: healthy
        alive = watch_local_trainers([tp], 1, heartbeat_dir=d,
                                     stall_timeout_s=30.0)
        assert [t.rank for t in alive] == [0]
        rec = json.load(open(obs.heartbeat.heartbeat_path(d, 0)))
        rec["t"] -= 3600
        json.dump(rec, open(obs.heartbeat.heartbeat_path(d, 0), "w"))
        with pytest.raises(RuntimeError, match="stalled"):
            watch_local_trainers([tp], 1, heartbeat_dir=d,
                                 stall_timeout_s=30.0)
        assert tp.proc.poll() is not None  # wedged rank was torn down
    finally:
        if tp.proc.poll() is None:
            tp.proc.kill()
            tp.proc.wait()


# ---------------------------------------------------------------------------
# monitor: collision guard + /stats compatibility
# ---------------------------------------------------------------------------
def test_monitor_refuses_cross_kind_name_collision():
    monitor.stat_add("obs.collide.counter")
    with pytest.raises(ValueError, match="already registered"):
        monitor.gauge_set("obs.collide.counter", 1.0)
    with pytest.raises(ValueError, match="already registered"):
        monitor.hist_observe("obs.collide.counter", 1.0)
    monitor.gauge_set("obs.collide.gauge", 2.0)
    with pytest.raises(ValueError, match="already registered"):
        monitor.stat_add("obs.collide.gauge")
    # same-kind re-registration stays legal, snapshot stays merged
    monitor.stat_add("obs.collide.counter", 2)
    snap = monitor.monitor_snapshot("obs.collide.")
    assert snap["obs.collide.counter"] == 3
    assert snap["obs.collide.gauge"] == 2.0
    monitor.stat_reset("obs.collide.counter")
    monitor.stat_reset("obs.collide.gauge")


# ---------------------------------------------------------------------------
# executor step telemetry (integration)
# ---------------------------------------------------------------------------
def test_train_step_telemetry_gauges_journal_heartbeat(tmp_path,
                                                       monkeypatch):
    jdir = str(tmp_path / "journal")
    hdir = str(tmp_path / "hb")
    monkeypatch.setenv(obs.HEARTBEAT_ENV, hdir)
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e9")
    obs.heartbeat._reset_for_tests()
    obs.set_journal_dir(jdir)
    try:
        main, startup, loss = _build_train()
        exe, scope = static.Executor(), static.Scope()
        rng = np.random.RandomState(0)
        feed = {"x": rng.rand(4, 8).astype(np.float32),
                "y": rng.rand(4, 1).astype(np.float32)}
        steps_before = monitor.stat_get("train.steps")
        ms_before = monitor.hist_snapshot("train.step_ms")["count"]
        with static.scope_guard(scope):
            exe.run(startup)  # startup is NOT a train step: no telemetry
            assert monitor.stat_get("train.steps") == steps_before
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss])
        assert monitor.stat_get("train.steps") == steps_before + 3
        assert monitor.hist_snapshot("train.step_ms")["count"] >= \
            ms_before + 2
        assert monitor.gauge_get("train.tokens_per_sec") > 0
        assert monitor.gauge_get("train.mfu") > 0  # peak armed via env
        assert monitor.gauge_get("executor.retraces") >= 1
        assert monitor.gauge_get("hbm.predicted_peak_bytes") > 0
    finally:
        obs.set_journal_dir(None)
        obs.heartbeat._reset_for_tests()
    events = obs.read_rank_journals(jdir)[0]
    kinds = [e["kind"] for e in events]
    assert kinds.count("step") == 3
    assert "compile" in kinds
    # a step's time is the interval since the previous observation of the
    # program: the first step (it holds the compile) has none, the other
    # two do — in the journal, the histogram and the heartbeat alike
    steps = [e for e in events if e["kind"] == "step"]
    assert "wall_ms" not in steps[0] and "tokens_per_sec" not in steps[0]
    for step_ev in steps[1:]:
        assert step_ev["wall_ms"] > 0 and step_ev["tokens_per_sec"] > 0
    beats = obs.read_heartbeats(hdir)
    assert beats[0]["beats"] == 3 and beats[0]["wall_ms"] > 0


def test_run_steps_telemetry_counts_micro_steps(tmp_path):
    obs.set_journal_dir(str(tmp_path))
    try:
        main, startup, loss = _build_train()
        exe, scope = static.Executor(), static.Scope()
        rng = np.random.RandomState(0)
        k = 4
        feed = {"x": rng.rand(k, 2, 8).astype(np.float32),
                "y": rng.rand(k, 2, 1).astype(np.float32)}
        before = monitor.stat_get("train.steps")
        with static.scope_guard(scope):
            exe.run(startup)
            for _ in range(2):
                exe.run_steps(main, feed=feed, fetch_list=[loss])
        assert monitor.stat_get("train.steps") == before + 2 * k
    finally:
        obs.set_journal_dir(None)
    events = obs.read_rank_journals(str(tmp_path))[0]
    first, second = [e for e in events if e["kind"] == "step"]
    assert first["micro_steps"] == second["micro_steps"] == k
    # the window that compiled has no interval; the next one's is divided
    # over its micro-steps
    assert "wall_ms" not in first and second["wall_ms"] > 0
    compile_ev = next(e for e in events if e["kind"] == "compile")
    assert compile_ev["mode"] == "run_steps"


def test_compiled_program_mfu_scales_by_mesh_chips(monkeypatch):
    """The MFU denominator must be chips * peak on a multi-device
    dispatch — a global-batch step priced against ONE chip's peak would
    read 8x the true MFU on the 8-device mesh."""
    import jax
    from paddle_tpu.distributed.compiled_program import CompiledProgram
    from paddle_tpu.static.executor import _wrapper_chips
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e9")
    main, startup, loss = _build_train()
    cp = CompiledProgram(main).with_data_parallel(loss_name=loss.name)
    exe, scope = static.Executor(), static.Scope()
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 8).astype(np.float32),
            "y": rng.rand(8, 1).astype(np.float32)}
    with static.scope_guard(scope):
        exe.run(startup)
        monitor.gauge_set("train.mfu", 0)
        for _ in range(3):      # the mesh compiles twice; then an interval
            exe.run(cp, feed=feed, fetch_list=[loss])
    n_dev = len(jax.devices())
    assert _wrapper_chips(cp) == n_dev
    # an unbuilt wrapper (no mesh yet) falls back to 1
    assert _wrapper_chips(object()) == 1
    assert monitor.gauge_get("train.mfu") > 0


def test_chaos_injection_is_journaled(tmp_path, monkeypatch):
    from paddle_tpu.testing import chaos
    obs.set_journal_dir(str(tmp_path))
    try:
        monkeypatch.setenv(chaos.CHAOS_ENV, "collective_fail@7:times=1")
        chaos.reload()
        with pytest.raises(chaos.ChaosCollectiveError):
            chaos.collective_hook(7)
    finally:
        monkeypatch.setenv(chaos.CHAOS_ENV, "")
        chaos.reload()
        obs.set_journal_dir(None)
    events = obs.read_rank_journals(str(tmp_path))[0]
    fired = [e for e in events if e["kind"] == "chaos"]
    assert fired and fired[0]["directive"] == "collective_fail"
    assert fired[0]["step"] == 7


def test_chaos_collective_fail_rank_filter(monkeypatch):
    from paddle_tpu.testing import chaos
    monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
    monkeypatch.setenv(chaos.CHAOS_ENV, "collective_fail@1:rank=1")
    chaos.reload()
    chaos.collective_hook(1)  # rank mismatch: no injection
    monkeypatch.setenv(chaos.CHAOS_ENV, "collective_fail@1:rank=0")
    chaos.reload()
    with pytest.raises(chaos.ChaosCollectiveError):
        chaos.collective_hook(1)
    monkeypatch.setenv(chaos.CHAOS_ENV, "")
    chaos.reload()


# ---------------------------------------------------------------------------
# metrics sidecar
# ---------------------------------------------------------------------------
def test_metrics_sidecar_scrape():
    monitor.stat_add("obs.sidecar.pings", 3)
    srv = obs.start_metrics_server(port=0)
    try:
        url = f"http://{srv.host}:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert "obs_sidecar_pings_total" in body
        with urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/healthz", timeout=10) as r:
            assert json.loads(r.read())["status"] == "ok"
    finally:
        srv.stop()
        monitor.stat_reset("obs.sidecar.pings")


# ---------------------------------------------------------------------------
# acceptance e2e (slow)
# ---------------------------------------------------------------------------
def _worker_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in ("PADDLE_TPU_CHAOS", obs.JOURNAL_ENV, obs.HEARTBEAT_ENV):
        env.pop(k, None)
    env.update(extra)
    return env


def _run_worker(root, out, world, steps, env=None, timeout=300):
    return subprocess.run(
        [sys.executable, WORKER, root, out, str(world), str(steps)],
        env=env or _worker_env(), capture_output=True, text=True,
        timeout=timeout)


@pytest.mark.slow
def test_wedged_rank_stall_detected_and_reformed(tmp_path, monkeypatch,
                                                 capfd):
    """THE wedge scenario: a permanent collective_fail leaves rank 1
    alive but wedged mid-step (retrying forever, heartbeat frozen).
    Process liveness says healthy; the heartbeat stall deadline says
    lost — the launcher tears the pod down and elastically re-forms
    from the survivor, which finishes the schedule."""
    from paddle_tpu.distributed import launch
    base = str(tmp_path)
    hb = os.path.join(base, "hb")
    steps = 4
    monkeypatch.setenv("PADDLE_TPU_ELASTIC_TEST_DIR", base)
    monkeypatch.setenv("ELASTIC_TOTAL_STEPS", str(steps))
    # rank 1 wedges at its 2nd train step and never recovers
    monkeypatch.setenv("PADDLE_TPU_CHAOS",
                       "collective_fail@2:times=1000000000:rank=1")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv(obs.JOURNAL_ENV, os.path.join(base, "journal"))
    rc = launch.main(["--elastic", "--max_restarts", "2",
                      "--nproc_per_node", "2", "--term_grace", "30",
                      "--heartbeat_dir", hb, "--stall_timeout", "6",
                      "--log_dir", os.path.join(base, "logs"), WORKER])
    assert rc == 0
    err = capfd.readouterr().err
    assert "stalled: no heartbeat" in err, err[-2000:]
    # the re-formed (restart 1) pod ran one "host" = world 4 and finished
    out = os.path.join(base, "out_rank0_r1.json")
    assert os.path.exists(out), os.listdir(base)
    rep = json.load(open(out))
    assert rep["restart"] == 1 and rep["world"] == 4
    assert sorted(map(int, rep["losses"])) or rep["resumed_global"] >= 1
    # the wedged rank's journal recorded the injections and its retries
    journals = obs.read_rank_journals(os.path.join(base, "journal"))
    r1 = journals.get(1, [])
    assert any(e["kind"] == "chaos" and
               e["directive"] == "collective_fail" for e in r1)
    assert any(e["kind"] == "collective_retry" for e in r1)


@pytest.mark.slow
def test_kill_resume_timeline_reconstructs_from_journals(tmp_path):
    """Acceptance: a chaos kill/resume 8->4->8 elastic run is
    reconstructable post-hoc from the run journals ALONE — three
    incarnations, each resume's restore step, the topology reanchors,
    checkpoint commits and the injected kills, in order."""
    steps = 5
    root = str(tmp_path / "ckpts")
    jdir = str(tmp_path / "journal")
    env = lambda **kw: _worker_env(**{obs.JOURNAL_ENV: jdir, **kw})  # noqa: E731

    outA = str(tmp_path / "a.json")
    p = _run_worker(root, outA, 8, steps,
                    env=env(PADDLE_TPU_CHAOS="kill@2"))
    assert p.returncode == -signal.SIGKILL, (p.returncode, p.stderr[-2000:])
    outB = str(tmp_path / "b.json")
    p = _run_worker(root, outB, 4, steps,
                    env=env(PADDLE_TPU_CHAOS="kill@3:signal=term"))
    assert p.returncode == 143, (p.returncode, p.stderr[-2000:])
    outC = str(tmp_path / "c.json")
    p = _run_worker(root, outC, 8, steps, env=env())
    assert p.returncode == 0, p.stderr[-3000:]
    final = json.load(open(outC))

    events = obs.read_rank_journals(jdir)[0]
    tl = obs.reconstruct_timeline(events)
    assert tl["n_incarnations"] == 3, tl
    first, second, third = tl["incarnations"]
    # incarnation 1: fresh start (no restore), died to an injected kill
    assert first["restored_step"] is None
    assert any(c["directive"] == "kill" for c in first["chaos"])
    assert first["steps"], "no steps journaled before the kill"
    assert first["commits"], "no checkpoint commit before the kill"
    # incarnation 2: restored, re-anchored onto the 4-device world
    assert second["restored_step"] is not None
    assert any(r["world"] == 4 for r in second["reanchors"])
    # incarnation 3: restored again, re-anchored back to 8, ran to done
    assert third["restored_step"] is not None
    assert any(r["world"] == 8 for r in third["reanchors"])
    assert third["restored_global"] == final["resumed_global"]
    # the journal's step record is gap-free within each incarnation
    for inc in (first, second, third):
        seqs = [e["seq"] for e in events
                if e["run_id"] == inc["run_id"]]
        assert seqs == list(range(len(seqs))), inc["run_id"]


# ---------------------------------------------------------------------------
# step telemetry times the step, not the enqueue
# ---------------------------------------------------------------------------
def test_step_telemetry_is_the_interval_between_observations(tmp_path):
    """With `return_numpy=False` (every real training loop) `run` returns
    once the step is enqueued, so timing the call describes the host.  The
    interval between consecutive observations of the program is the step
    in steady state either way: a loop held to 40 ms a step reads >= 40 ms
    however fast its dispatches return."""
    obs.set_journal_dir(str(tmp_path))
    try:
        main, startup, loss = _build_train()
        other, other_startup, other_loss = _build_train()
        exe, scope = static.Executor(), static.Scope()
        rng = np.random.RandomState(0)
        feed = {"x": rng.rand(4, 8).astype(np.float32),
                "y": rng.rand(4, 1).astype(np.float32)}
        with static.scope_guard(scope):
            exe.run(startup)
            for _ in range(4):
                exe.run(main, feed=feed, fetch_list=[loss],
                        return_numpy=False)
                time.sleep(0.04)
            # another program in between: no interval across the switch
            exe.run(other_startup)
            exe.run(other, feed=feed, fetch_list=[other_loss])
            exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        obs.set_journal_dir(None)
    steps = [e for e in obs.read_rank_journals(str(tmp_path))[0]
             if e["kind"] == "step"]
    assert len(steps) == 6
    assert "wall_ms" not in steps[0]            # it holds the compile
    assert all(e["wall_ms"] >= 40.0 for e in steps[1:4])
    assert "wall_ms" not in steps[4] and "wall_ms" not in steps[5]
