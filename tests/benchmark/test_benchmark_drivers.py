"""Every driver rehearsed tiny on the CPU through the same function
`benchmark/run.py` calls, from a throw-away root that adds its
configurations, mixes, cells and a per-layer metric as new files only."""
import json
import os

import pytest

import benchmark_tiny_root as tiny
from benchmark import harness, trace_reduce, work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}

EXTRA_READER = '''"""A per-layer metric dropped in as a new file."""
LAYER, SOURCE, UNIT, BETTER = "device", "device_trace", "count", "lower"


def reduce(run):
    return float(run.trace["launches"])
'''


@pytest.fixture(autouse=True)
def cpu_stands_in_for_a_v5e(monkeypatch):
    """An unknown device kind is an error in the benchmark, never a
    default; the rehearsal lends the CPU the v5e's row here, in the test."""
    real = work.peaks
    monkeypatch.setattr(
        work, "peaks",
        lambda kind: real("TPU v5 lite" if kind == "cpu" else kind))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    extra = [{"name": "extra.launch_count", "unit": "count",
              "better": "lower", "source": "device_trace",
              "layer": "device", "moves": "setup_s"}]
    path = tiny.make(tmp_path_factory.mktemp("bench"), extra_per_layer=extra)
    with open(os.path.join(path, "benchmark", "layer_metrics",
                           "launch_count.py"), "w") as f:
        f.write(EXTRA_READER)
    return path


def _run(root, cell, trace=0, seconds=1.0, seed=3):
    lines = []
    result = harness.run_cell(cell, seed, seconds, trace, root=root,
                              require_tpu=False, log=lines.append)
    # the last line is JSON and survives a round trip
    return json.loads(harness.result_line(result)), lines


@pytest.mark.parametrize("cell,metric", [
    ("bert-tiny.tiny_scan", "train_tok_per_s_chip"),
    ("bert-tiny.tiny_dp", "train_tok_per_s_chip"),
    ("gpt-tiny.tiny_chat", "serve_s_per_answer_token"),
    ("gpt-tiny.tiny_score", "serve_closed_latency_p50_s"),
])
def test_driver_end_to_end_line(root, cell, metric):
    result, lines = _run(root, cell)
    assert set(result) == LINE_KEYS, lines
    assert set(result["device"]) == DEVICE_KEYS
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {metric, "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert result["device"]["platform"] == "cpu"      # named, never hidden
    assert any("compilations inside 0, retraces 0" in ln for ln in lines)


def test_traced_run_reports_per_layer_metrics_and_breakdown(root,
                                                            monkeypatch):
    """The CPU has no device plane, so the reduction is pointed at the
    trace recorded on the v5e; everything around it is the real path: the
    profiler runs, a slice is cut, the readers are found by name."""
    real = trace_reduce.summarize
    seen = []

    def recorded(path, n_devices=1):
        seen.append(path)
        with pytest.raises(ValueError, match="no /device:TPU"):
            real(path, n_devices)
        return real(os.path.join(DATA, "tiny_train_v5e.xplane.pb"), 1)

    monkeypatch.setattr(trace_reduce, "summarize", recorded)
    result, lines = _run(root, "bert-tiny.tiny_scan", trace=1, seconds=1.5)
    assert seen and seen[0].endswith(".xplane.pb")
    assert seen[0].startswith(os.path.join(root, ".bench_trace"))
    assert set(result) == LINE_KEYS | {"breakdown"}
    assert set(result["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < result["device"]["busy_s"] < result["device"]["window_s"]
    got = set(result["metrics"])
    assert {"train.device_idle_share", "train.launches_per_step",
            "train.exec_enqueue_ms_per_step", "train.feed_wait_ms_per_step",
            "compile_s", "cache_hits"} <= got, got
    assert 0 < result["metrics"]["train.work_roofline"]["value"]
    # a reader that finds nothing to read returns nothing and the harness
    # leaves the metric out: no collectives' cell is named for this one
    assert "train.collective_share" in got     # no `workloads` in this root
    assert not any(name.startswith(("chat.", "score.")) for name in got)
    # the metric that exists only as a new file and a manifest entry
    assert result["metrics"]["extra.launch_count"]["value"] > 0
    b = result["breakdown"]
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"])


def test_serving_traced_run_counts_forwards_in_the_slice(root, monkeypatch):
    real = trace_reduce.summarize
    monkeypatch.setattr(
        trace_reduce, "summarize", lambda path, n_devices=1: real(
            os.path.join(DATA, "tiny_train_v5e.xplane.pb"), 1))
    result, lines = _run(root, "gpt-tiny.tiny_chat", trace=1, seconds=2.0)
    got = set(result["metrics"])
    assert {"chat.device_idle_share", "chat.launches_per_step",
            "chat.engine_step_occupancy", "chat.engine_steps_per_s",
            "chat.kv_pages_peak_share", "chat.latency_p50_s",
            "chat.latency_p90_s", "chat.gen_late_ms_p90",
            "compile_s"} <= got, (got, lines)
    # the cell is held back: it is not in the manifest and brought the
    # entries for these metrics itself
    assert any("held back, not in BENCHMARK.json" in ln for ln in lines)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        assert "gpt-tiny.tiny_chat" not in [
            w["name"] for w in json.load(f)["workloads"]]
    assert 0 < result["metrics"]["chat.engine_step_occupancy"]["value"] <= 100
    assert any(ln.startswith("slice: ") for ln in lines)


def test_closed_loop_traced_run_reports_tokens_per_second(root, monkeypatch):
    real = trace_reduce.summarize
    monkeypatch.setattr(
        trace_reduce, "summarize", lambda path, n_devices=1: real(
            os.path.join(DATA, "tiny_train_v5e.xplane.pb"), 1))
    result, lines = _run(root, "gpt-tiny.tiny_score", trace=1, seconds=1.5)
    got = set(result["metrics"])
    assert {"score.device_idle_share", "score.launches_per_step",
            "score.tok_per_s", "compile_s"} <= got, (got, lines)
    assert result["metrics"]["score.tok_per_s"]["value"] > 0
    assert not any(name.startswith(("chat.", "train.")) for name in got)


def test_reachable_buckets_are_what_gets_warmed():
    from benchmark import serving
    with open(os.path.join(tiny.REPO, "benchmark", "traffic",
                           "chat_open.json")) as f:
        chat = json.load(f)
    with open(os.path.join(tiny.REPO, "benchmark", "traffic",
                           "score_short.json")) as f:
        score = json.load(f)
    assert serving.reachable_buckets(chat, 1024) == \
        ([16, 32, 64, 128, 256], [16, 32, 64, 128, 256])
    assert serving.reachable_buckets(score, 1024) == ([32, 64, 128, 256], [])
    with open(os.path.join(tiny.REPO, "benchmark", "traffic",
                           "score_long.json")) as f:
        assert serving.reachable_buckets(json.load(f), 1024) == \
            ([128, 256, 512, 1024], [])
    assert [serving.next_pow2(n) for n in (1, 16, 17, 129, 1000)] == \
        [16, 16, 32, 256, 1024]


def test_unknown_cell_and_too_few_chips_are_refused(root):
    with pytest.raises(KeyError, match="no workload"):
        harness.run_cell("nope.nope", 1, 1.0, 0, root=root,
                         require_tpu=False)
    with pytest.raises(SystemExit, match="not 'tpu'"):
        harness.run_cell("bert-tiny.tiny_scan", 1, 1.0, 0, root=root,
                         log=lambda s: None)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"][0]["chips"] = 64
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    with pytest.raises(SystemExit, match="needs 64 chips"):
        harness.run_cell("bert-tiny.tiny_scan", 1, 1.0, 0, root=root,
                         require_tpu=False, log=lambda s: None)
