"""The hybrid (state-space + attention) cell's benchmark files: required
work from the published sizes, the per-launch pairing the roofline shares
rest on, the manifest entries, and the driver rehearsed tiny on the CPU
through the function `benchmark/run.py` calls."""
import json
import os

import numpy as np
import pytest

import benchmark_tiny_root as tiny
from benchmark import harness, launch_events, work, work_hybrid
from benchmark.program_spans import Span, nest

REPO = tiny.REPO
CELL = "granite-4.0-h-micro.chat_closed"
TINY_HYBRID = {
    "name": "hybrid-tiny", "vocab_size": 128, "hidden_size": 64,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 96, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_chunk_size": 8, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.25,
    "logits_scaling": 8, "rms_norm_eps": 1e-5, "num_local_experts": 0,
    "position_embedding_type": "nope", "eos_token_id": 127,
    "engine": {"dtype": "float32", "page_tokens": 4, "max_context": 64,
               "max_slots_cap": 3, "hbm_bytes": 8 << 20}}
TINY_MIX = {
    "driver": "serve_closed_cached", "callers": 3,
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 5, "max": 30, "stratify": 4},
    "new_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.4,
                   "min": 2, "max": 6}}


def _published():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


# -- required work ----------------------------------------------------------
def test_parameter_count_is_the_issues_sum():
    cfg = _published()
    m = work_hybrid.matmul_params(cfg)
    assert m["mamba"] == 76_152_832 and m["attention"] == 60_817_408
    assert m["head"] == 100_352 * 2048
    assert work_hybrid.all_params(cfg) == 3_191_396_096     # 3,191 M
    assert round(2 * work_hybrid.all_params(cfg) / 1e9, 2) == 6.38


def test_state_is_nine_tenths_of_a_chat_sequences_cache():
    cfg = _published()
    ssm, conv = work_hybrid.state_bytes_per_row(cfg)
    assert ssm == 36 * 64 * 64 * 128 * 4 and conv == 36 * 3 * 4352 * 2
    kv_token = 2 * 4 * 8 * 64 * 4          # float32 pages
    assert (ssm + conv) / (ssm + conv + 192 * kv_token) > 0.9


def test_decode_step_is_memory_bound_and_prefill_compute_bound():
    cfg, peak = _published(), work.peaks("TPU v5 lite")
    least, bound = work.roofline_seconds(
        *work_hybrid.decode_step_work(cfg, 16, 16 * 200), peak)
    assert bound == "memory" and 0.009 < least < 0.013
    # the state is ~a quarter of the step's bytes at 16 rows
    _, with_state = work_hybrid.decode_step_work(cfg, 16, 0)
    _, weights = work_hybrid.decode_step_work(cfg, 0, 0)
    assert 0.2 < (with_state - weights) / with_state < 0.35
    assert work.roofline_seconds(
        *work_hybrid.prefill_work(cfg, 512), peak)[1] == "compute"
    assert 2.5e6 < work_hybrid.scan_flops_per_token(cfg) < 2.8e6
    assert work.roofline_seconds(
        *work_hybrid.ssm_update_work(cfg, 16), peak)[1] == "memory"


# -- launches ---------------------------------------------------------------
def test_pair_takes_each_forwards_own_longest_module_and_its_scoped_ops():
    spans = nest([
        Span("engine/step", 0, 100, 0, {"active": 2, "context": 9}),
        Span("engine/forward", 10, 20, 0, {"bucket": 16, "rows": 2}),
        Span("engine/prefill", 100, 300, 0, {"prompt": 7}),
        Span("engine/forward", 110, 130, 0, {"bucket": 16, "rows": 1})])
    forwards = [sp for sp in spans if sp.name == "engine/forward"]
    modules = [("jit_upload", 11, 12), ("jit_fn", 15, 60),
               ("jit_write", 70, 71), ("jit_fn", 120, 200),
               ("jit_stray", 5, 9)]
    ops = [("forward/mamba2_state_update", 16, 20),
           ("forward/mamba2_state_update", 30, 35),
           (None, 40, 50), ("forward/mamba2_chunk_scan", 130, 150),
           ("forward/mamba2_state_update", 61, 62)]
    got = launch_events.pair(forwards, modules, ops)
    assert [g["module"] for g in got] == [(15, 60), (120, 200)]
    assert got[0]["scoped"] == {"forward/mamba2_state_update": 9}
    assert got[1]["scoped"] == {"forward/mamba2_chunk_scan": 20}
    assert got[0]["span"].parent.name == "engine/step"


def test_a_forward_without_a_device_event_is_left_out():
    spans = nest([Span("engine/step", 0, 100, 0, {"active": 1}),
                  Span("engine/forward", 10, 20, 0, {"bucket": 16})])
    assert launch_events.pair([spans[1]], [("jit_fn", 1, 5)], []) == []


# -- the manifest -----------------------------------------------------------
def test_manifest_gains_the_configuration_the_cell_and_the_hyb_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    cfg = [c for c in m["configs"] if c["name"] == "granite-4.0-h-micro"]
    assert cfg and cfg[0]["reduced"] == ["kv_pool_device_bytes"]
    cells = [w for w in m["workloads"] if w["name"] == CELL]
    assert cells and cells[0]["chips"] == 1
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    latency = [e for e in m["end_to_end"]
               if e["name"] == "serve_closed_latency_p50_s"][0]
    assert latency["workloads"][-1] == CELL and latency["bound"] == 0.09
    hyb = [p for p in m["per_layer"] if p["name"].startswith("hyb.")]
    assert len(hyb) == 16 and "hyb.work_roofline" not in {
        p["name"] for p in hyb}
    for p in hyb:
        assert p["workloads"] == [CELL]
        assert p["moves"] == "serve_closed_latency_p50_s"
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics",
            p["name"].split(".")[-1] + ".py"))
    cell = harness.Cell(REPO, CELL)
    assert cell.driver == "serve_closed_cached"
    assert cell.traffic["callers"] == 16 == cell.config["engine"][
        "max_slots_cap"]
    assert {p["name"] for p in cell.per_layer} == {
        p["name"] for p in hyb} | {"compile_s", "cache_hits"}


def test_configuration_file_holds_every_key_of_the_catalog_row():
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(rows):
        pytest.skip("no catalog beside the guides here")
    with open(rows) as f:
        row = [json.loads(ln) for ln in f
               if '"granite-4.0-h-micro"' in ln][0]
    cfg = _published()
    for key, value in row["config"].items():
        assert cfg[key] == value, key


# -- the driver, tiny, on the CPU -------------------------------------------
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tiny.make(tmp_path_factory.mktemp("bench_hybrid"))
    bdir = os.path.join(path, "benchmark")
    for kind, name, obj in (
            ("configs", "hybrid-tiny", TINY_HYBRID),
            ("traffic", "tiny_chat_closed", TINY_MIX),
            ("cells", "hybrid-tiny.tiny_chat_closed",
             {"reports": ["serve_closed_latency_p50_s"]})):
        with open(os.path.join(bdir, kind, name + ".json"), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(path, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "hybrid-tiny",
        "file": "benchmark/configs/hybrid-tiny.json"})
    manifest["workloads"].append({
        "name": "hybrid-tiny.tiny_chat_closed", "config": "hybrid-tiny",
        "traffic": "tiny_chat_closed", "chips": 1})
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return path


def test_closed_loop_driver_serves_the_hybrid_model_correctly(root):
    lines = []
    result = harness.run_cell("hybrid-tiny.tiny_chat_closed", 2**31 + 5,
                              2.0, 0, root=root, require_tpu=False,
                              log=lines.append)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] >= 3, lines
    assert set(result["metrics"]) == {"serve_closed_latency_p50_s",
                                      "setup_s"}
    assert any("compilations inside 0, retraces 0" in ln for ln in lines)
    plan = [ln for ln in lines if ln.startswith("plan:")][0]
    assert "state_slot_bytes=30336" in plan, plan
    margin = [ln for ln in lines if ln.startswith("reference:")][0]
    # float32 served against float32 reference: ties only
    assert float(margin.split("worst ")[1].split(" sigma")[0]) < 1e-3
    assert float(margin.split("mean ")[1].split(" sigma")[0]) < 1e-4


def test_int8_reading_rounds_the_matrices_and_moves_the_logits():
    """The reading that has to come out as not correct on the chip: the
    reference with its matrices rounded through int8 moves the logits by
    hundredths of a row sigma — three orders over float32 rounding, which
    is what lets MEAN_SIGMA sit between it and the served path's mean
    margin (serving_cached has the chip's two readings)."""
    import paddle_tpu
    import paddle_tpu.dygraph as dg
    from paddle_tpu.models import GraniteHybridConfig, GraniteHybridModel
    from benchmark import loadgen, serving_cached
    from benchmark.reference import granite_hybrid as reference
    with dg.guard():
        paddle_tpu.seed(5)
        model = GraniteHybridModel(GraniteHybridConfig.from_published(
            TINY_HYBRID, eos_id=127, bos_id=127, dtype="float32",
            embed_init_rms=0.01))
        params = reference.params_of(model)
        ids = np.random.default_rng(0).integers(0, 126, 24).astype(np.int32)
        full = np.asarray(reference.logits(params, ids, TINY_HYBRID))
        rounded = np.asarray(reference.logits(params, ids, TINY_HYBRID,
                                              weights_as="int8"))
        moved = float(np.abs(rounded - full).max() / full.std())
        assert 1e-3 < moved < 1.0, moved
        with pytest.raises(ValueError):
            reference.logits(params, ids, TINY_HYBRID, weights_as="fp8")
        # the rule itself: a sequence that follows the reference's argmax
        # has margin 0; one that takes the runner-up has its gap in sigmas
        served = type("S", (), {
            "cfg": TINY_HYBRID, "reference_params": lambda self: params})()
        best = int(full[7].argmax())
        second = int(np.argsort(full[7])[-2])
        req = loadgen.Request(0, None, ids[:8], 1)
        assert serving_cached.check_against_reference(
            served, [(req, list(ids[:8]) + [best])], 1) == (0.0, 0.0)
        gap, mean = serving_cached.check_against_reference(
            served, [(req, list(ids[:8]) + [second])], 1)
        assert mean == gap          # one served token: its own margin
        want = float((full[7].max() - full[7][second]) / full[7].std())
        # the comparison pads the sequence to 16: float32 rounding only
        assert gap > 0 and abs(gap - want) < 1e-3 * max(want, 1e-3) + 1e-5
