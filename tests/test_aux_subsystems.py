"""Auxiliary subsystems (SURVEY.md §5): profiler, flags, monitor,
auto-checkpoint, debugger, NaN check."""
import json
import os

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.static as static
from paddle_tpu.static import layers


def test_flags_get_set_roundtrip():
    v = paddle_tpu.get_flags("FLAGS_check_nan_inf")["FLAGS_check_nan_inf"]
    assert v is False
    paddle_tpu.set_flags({"FLAGS_check_nan_inf": True})
    assert paddle_tpu.get_flags(["check_nan_inf"])["check_nan_inf"] is True
    paddle_tpu.set_flags({"FLAGS_check_nan_inf": False})
    with pytest.raises(ValueError):
        paddle_tpu.get_flags("FLAGS_not_a_flag")
    # parity flags registered
    assert paddle_tpu.get_flags("FLAGS_fraction_of_gpu_memory_to_use")


def test_monitor_counters():
    from paddle_tpu.core.monitor import stat_add, stat_get, stat_reset
    stat_reset()
    stat_add("my_counter", 3)
    stat_add("my_counter")
    assert stat_get("my_counter") == 4
    stat_reset("my_counter")
    assert stat_get("my_counter") == 0


def test_profiler_records_and_exports(tmp_path, capsys):
    from paddle_tpu import profiler as prof
    path = str(tmp_path / "profile")
    with prof.profiler(state="CPU", profile_path=path):
        with prof.RecordEvent("my_block"):
            _ = sum(range(1000))
    out = capsys.readouterr().out
    assert "my_block" in out
    with open(path + ".json") as f:
        trace = json.load(f)
    assert any(e["name"] == "my_block" for e in trace["traceEvents"])


def test_executor_records_events_and_stats():
    from paddle_tpu.core.monitor import stat_get, stat_reset
    stat_reset()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 4])
        y = layers.fc(x, 2)
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[y])
    assert stat_get("executor_run_times") >= 1


def test_nan_inf_check_raises():
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 2])
        y = layers.log(x)  # log(-1) = nan
    exe = static.Executor()
    scope = static.Scope()
    paddle_tpu.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with static.scope_guard(scope):
            exe.run(startup)
            with pytest.raises(RuntimeError, match="non-finite"):
                exe.run(main, feed={"x": -np.ones((2, 2), np.float32)},
                        fetch_list=[y])
    finally:
        paddle_tpu.set_flags({"FLAGS_check_nan_inf": False})


def test_debugger_dot_dump(tmp_path):
    from paddle_tpu.utils import draw_block_graphviz, print_program
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 4])
        y = layers.fc(x, 2)
        loss = layers.mean(y)
        static.SGD(learning_rate=0.1).minimize(loss)
    p = str(tmp_path / "g.dot")
    draw_block_graphviz(main.global_block(), path=p)
    dot = open(p).read()
    assert "digraph G" in dot and "mul" in dot
    text = print_program(main, skip_vars=True)
    assert "sgd" in text


def test_checkpoint_saver_roundtrip(tmp_path):
    from paddle_tpu.incubate.checkpoint import (CheckpointSaver,
                                                SerializableBase)

    class Obj(SerializableBase):
        def __init__(self, v):
            self.v = v

        def serialize(self, path):
            os.makedirs(path, exist_ok=True)
            with open(os.path.join(path, "v.json"), "w") as f:
                json.dump(self.v, f)

        def deserialize(self, path):
            with open(os.path.join(path, "v.json")) as f:
                self.v = json.load(f)

    root = str(tmp_path / "ckpt")
    saver = CheckpointSaver()
    for i in range(5):
        saver.save_checkpoint(root, [Obj(i)], max_keep=3)
    assert saver.get_last_checkpoint_no(root) == 4
    o = Obj(None)
    saver.load_checkpoint(root, [o])
    assert o.v == 4
    # pruned to max_keep
    import glob
    assert len(glob.glob(os.path.join(root, "__paddle_checkpoint__.*"))) == 3


def test_auto_checkpoint_resume(tmp_path, monkeypatch):
    """Kill-and-restart epoch resume (reference test_auto_checkpoint.py)."""
    monkeypatch.setenv("PADDLE_RUNNING_ENV", "PADDLE_EDL_AUTO_CHECKPOINT")
    monkeypatch.setenv("PADDLE_JOB_ID", "job_test_1")
    monkeypatch.setenv("PADDLE_EDL_HDFS_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("PADDLE_EDL_SAVE_CHECKPOINT_INTER", "0")
    import paddle_tpu.incubate.checkpoint.auto_checkpoint as acp
    acp.g_checker = None  # re-read env

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 4])
        pred = layers.fc(x, 1)
        loss = layers.mean(layers.square(pred))
        static.SGD(learning_rate=0.1).minimize(loss)
    exe = static.Executor()
    scope = static.Scope()
    xb = np.ones((4, 4), np.float32)
    seen = []
    with static.scope_guard(scope):
        exe.run(startup)
        for epoch in acp.train_epoch_range(3):
            seen.append(epoch)
            exe.run(main, feed={"x": xb}, fetch_list=[loss])
            if epoch == 1:
                break  # simulated failure DURING epoch 1 (before its
                # end-of-epoch checkpoint commits)
    assert seen == [0, 1]

    w_name = main.all_parameters()[0].name
    with static.scope_guard(scope):
        w_trained = np.asarray(scope.get(w_name)).copy()

    # restart: epoch 0 committed, the interrupted epoch 1 re-runs — and
    # the checkpointed WEIGHTS are restored, not reinitialized
    acp.g_checker = None
    seen2 = []
    scope2 = static.Scope()
    with static.scope_guard(scope2):
        exe2 = static.Executor()
        exe2.run(startup)
        w_fresh = np.asarray(scope2.get(w_name)).copy()
        for epoch in acp.train_epoch_range(3):
            if not seen2:
                # first executor.run of the resumed job attaches + restores
                exe2.run(main, feed={"x": xb}, fetch_list=[loss])
                w_resumed = np.asarray(scope2.get(w_name))
            else:
                exe2.run(main, feed={"x": xb}, fetch_list=[loss])
            seen2.append(epoch)
    assert seen2 == [1, 2], seen2
    # resumed weights came from the checkpoint (epoch-0 trained state),
    # not the fresh same-seed init the startup program produced
    assert not np.allclose(w_resumed, w_fresh)


def test_per_op_nan_scan_names_offending_op():
    """Eager mode + FLAGS_check_nan_inf: the error must name the op that
    produced the NaN (reference nan_inf_utils_detail per-op scan)."""
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 2])
        y = layers.log(x)          # log(-1) = nan  <- offending op
        z = layers.scale(y, 2.0)   # downstream op must not be blamed
    exe = static.Executor()
    scope = static.Scope()
    paddle_tpu.set_flags({"FLAGS_check_nan_inf": True,
                          "FLAGS_eager_run": True})
    try:
        with static.scope_guard(scope):
            exe.run(startup)
            with pytest.raises(RuntimeError, match="op 'log'"):
                exe.run(main, feed={"x": -np.ones((2, 2), np.float32)},
                        fetch_list=[z])
    finally:
        paddle_tpu.set_flags({"FLAGS_check_nan_inf": False,
                              "FLAGS_eager_run": False})


def test_explicit_program_roles():
    """program_guard stamps the two-program contract: a startup program
    containing non-init ops still runs eagerly; a main program containing
    only init ops still takes the jit path."""
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 2])
        layers.scale(x, 2.0)
    assert main._role == "main" and startup._role == "startup"
    exe = static.Executor()
    # a startup program with a non-init op (scale after init) is still
    # treated as startup
    with static.program_guard(static.Program(), static.Program()):
        pass
    sp = static.Program()
    sp._role = "startup"
    assert exe._program_is_startup(sp)
    mp = static.Program()
    mp._role = "main"
    assert not exe._program_is_startup(mp)


def test_install_check():
    from paddle_tpu.install_check import run_check
    run_check()  # raises on failure


def test_data_feeder():
    import numpy as np
    import paddle_tpu.static as static
    from paddle_tpu.static import layers
    from paddle_tpu.io import DataFeeder
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        img = layers.data("img", [-1, 4])
        lbl = layers.data("lbl", [-1, 1], dtype="int64")
        pred = layers.fc(img, 3)
    feeder = DataFeeder(feed_list=[img, lbl])
    batch = [(np.ones(4) * i, [i % 3]) for i in range(5)]
    feed = feeder.feed(batch)
    assert feed["img"].shape == (5, 4) and feed["img"].dtype == np.float32
    assert feed["lbl"].shape == (5, 1) and feed["lbl"].dtype == np.int64
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        (out,) = exe.run(main, feed=feed, fetch_list=[pred])
    assert np.asarray(out).shape == (5, 3)


def test_weighted_average():
    import pytest
    from paddle_tpu.utils import WeightedAverage
    wa = WeightedAverage()
    with pytest.raises(ValueError):
        wa.eval()
    wa.add(1.0, weight=1)
    wa.add(3.0, weight=3)
    assert abs(wa.eval() - 2.5) < 1e-9
    wa.reset()
    wa.add([2.0, 4.0])  # arrays reduce to their mean
    assert abs(wa.eval() - 3.0) < 1e-9


# ---------------------------------------------------------------------------
# RecordEvent: fields, parent, and any profiler session as the switch
# ---------------------------------------------------------------------------
def test_record_event_keeps_fields_and_parent_per_thread(tmp_path, capsys):
    import threading
    from paddle_tpu import profiler as prof
    path = str(tmp_path / "profile")
    with prof.profiler(state="CPU", profile_path=path):
        with prof.RecordEvent("outer", req=7):
            with prof.RecordEvent("inner") as inner:
                inner.set(bytes=128)
            t = threading.Thread(
                target=lambda: prof.RecordEvent("elsewhere").__enter__()
                .__exit__(None, None, None))
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
        with prof.RecordEvent("after"):
            pass
    capsys.readouterr()
    events = {e.name: e for e in prof._state.events}
    assert events["outer"].fields == {"req": 7}
    assert events["outer"].parent is None
    assert events["inner"].fields == {"bytes": 128}
    assert events["inner"].parent == "outer"
    # a span open on another thread is no parent: cause across threads
    # travels in a field
    assert events["elsewhere"].parent is None
    assert events["elsewhere"].thread != events["outer"].thread
    assert events["after"].parent is None       # the stack unwound
    with open(path + ".json") as f:
        trace = json.load(f)
    args = {e["name"]: e["args"] for e in trace["traceEvents"]}
    assert args["inner"] == {"bytes": 128, "parent": "outer"}
    assert args["outer"] == {"req": 7, "parent": None}


def _host_events(trace_dir):
    import os
    from jax.profiler import ProfileData
    paths = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    data = ProfileData.from_file(paths[0])
    return [(i, e.name, dict(e.stats))
            for plane in data.planes if plane.name == "/host:CPU"
            for i, line in enumerate(plane.lines) for e in line.events]


def test_record_event_annotates_a_trace_it_did_not_start(tmp_path):
    """Whoever starts the profiler session — a benchmark, TensorBoard —
    gets the program's spans with their fields; there is no switch to
    throw first and no sentinel to set."""
    import jax
    from paddle_tpu import profiler as prof
    assert not hasattr(prof, "set_device_trace_active")
    assert not hasattr(prof, "_EXTERNAL_TRACE")
    # no session: nothing is built
    with prof.RecordEvent("aux/quiet", k=1) as quiet:
        assert quiet._jax_ctx is None
    before = len(prof._state.events)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with prof.RecordEvent("aux/foreign", req=3) as span:
            with prof.RecordEvent("aux/child"):
                pass
            span.set(bytes=4096)
    finally:
        jax.profiler.stop_trace()
    assert len(prof._state.events) == before    # in-memory list: its own
    found = {name: (line, stats)
             for line, name, stats in _host_events(str(tmp_path))
             if name.startswith("aux/")}
    assert set(found) == {"aux/foreign", "aux/child"}
    assert found["aux/foreign"][1] == {"req": 3, "bytes": 4096}
    assert found["aux/foreign"][0] == found["aux/child"][0]   # one thread


def test_executor_spans_nest_under_the_parity_names(capsys, tmp_path):
    from paddle_tpu import profiler as prof
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 4])
        y = layers.fc(x, 2)
    exe, scope = static.Executor(), static.Scope()
    feed = {"x": np.ones((2, 4), np.float32)}
    with static.scope_guard(scope):
        exe.run(startup)
        with prof.profiler(state="CPU",
                           profile_path=str(tmp_path / "p")):
            exe.run(main, feed=feed, fetch_list=[y])
            exe.run_steps(main, feed={"x": np.ones((3, 2, 4), np.float32)},
                          fetch_list=[y])
    capsys.readouterr()
    events = list(prof._state.events)
    by_parent = {}
    for e in events:
        by_parent.setdefault(e.parent, set()).add(e.name)
    assert by_parent[None] == {"Executor::Run", "Executor::RunSteps"}
    children = {"executor/prepare", "executor/trace_compile",
                "executor/launch", "executor/fetch", "executor/observe",
                "executor/hooks"}
    assert by_parent["Executor::Run"] == children
    assert by_parent["Executor::RunSteps"] == children
    # both calls are misses: each launch holds the kept phase
    # `executor/first_launch`, with JAX's stages of obtaining the program
    assert by_parent["executor/launch"] == {"executor/first_launch"}
    assert {"jax/lower", "jax/compile"} <= \
        by_parent["executor/first_launch"]
    compiled = [e.fields for e in events
                if e.name == "executor/trace_compile"]
    assert [f["mode"] for f in compiled] == ["run", "run_steps"]
    assert all(len(f["fingerprint"]) == 16 for f in compiled)
