"""The cell with routed experts (`nemotron-3-super-120b-a12b.
chat_closed_ep4`): its configuration file against the catalog row and the
manifest, required work against hand counts, each new reader on a
hand-built run, the reference's int8 reading, and the driver rehearsed
tiny on the CPU through the function `benchmark/run.py` calls."""
import json
import os

import numpy as np
import pytest

import benchmark_tiny_root as tiny
from benchmark import harness, work, work_nemotron_h
from benchmark.program_spans import Span, nest

REPO = tiny.REPO
CONFIG = "nemotron-3-super-120b-a12b"
CELL = CONFIG + ".chat_closed_ep4"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size", "num_nextn_predict_layers",
           "kv_pool_device_bytes"]
TINY_MOE = {
    "name": "moe-hybrid-tiny", "vocab_size": 96, "hidden_size": 64,
    "hybrid_override_pattern": "MEM*EME", "num_hidden_layers": 7,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "expand": 2, "chunk_size": 8,
    "n_routed_experts": 4, "num_experts_per_tok": 4,
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "norm_topk_prob": True,
    "routed_scaling_factor": 5, "layer_norm_epsilon": 1e-5,
    "max_position_embeddings": 256, "num_nextn_predict_layers": 0,
    "published": {"n_routed_experts": 16, "vocab_size": 128},
    "first_held_expert": 4, "eos_token_id": 95,
    "layer_types": ["mamba", "moe", "mamba", "attention", "moe", "mamba",
                    "moe"],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_expand": 2,
    "engine": {"dtype": "float32", "page_tokens": 4, "max_context": 64,
               "max_slots_cap": 4, "hbm_bytes": 8 << 20}}
TINY_MIX = {
    "driver": "serve_closed_moe_hybrid", "callers": 4,
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 5, "max": 30, "stratify": 4},
    "new_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.4,
                   "min": 2, "max": 6}}


def _published():
    with open(os.path.join(REPO, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def _manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the configuration file --------------------------------------------------
def test_configuration_file_holds_the_catalog_row_but_what_it_reduces():
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(rows):
        pytest.skip("no catalog beside the guides here")
    with open(rows) as f:
        row = [json.loads(ln) for ln in f
               if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in ln][0]
    cfg = _published()
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key


def test_every_published_width_is_unchanged_and_the_cut_is_stated():
    cfg = _published()
    widths = dict(hidden_size=4096, mamba_num_heads=128, mamba_head_dim=64,
                  ssm_state_size=128, n_groups=8, conv_kernel=4,
                  chunk_size=128, num_attention_heads=32,
                  num_key_value_heads=2, head_dim=128,
                  num_experts_per_tok=22, routed_scaling_factor=5,
                  moe_latent_size=1024, moe_intermediate_size=2688,
                  moe_shared_expert_intermediate_size=5376)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["published"]["n_routed_experts"] == 512   # the router's width
    assert cfg["reduced"] == REDUCED
    assert set(cfg["reduced_why"]) == set(REDUCED)
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"]) \
        == (11, "MEMEMEM*EME")
    assert cfg["published"]["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert (cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"], cfg["kv_pool_device_bytes"],
            cfg["first_held_expert"]) == (128, 32768, 0, 0, 0)
    assert "4 v5e chips" in cfg["deployment"]
    assert {"no_rotary", "latent_projections", "router_bias",
            "initialization", "ssm_state_dtype", "eos_token_id"} \
        <= set(cfg["assumed"])
    assert 0 <= cfg["eos_token_id"] < cfg["vocab_size"]
    assert cfg["engine"]["max_slots_cap"] == 64
    # the names the shared readers read repeat the published keys
    assert (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"], cfg["mamba_d_conv"], cfg["mamba_expand"]) \
        == (128, 64, 128, 8, 4, 2)
    assert cfg["layer_types"].count("mamba") == 5 \
        and cfg["layer_types"].count("attention") == 1 \
        and cfg["layer_types"].count("moe") == 5


def test_manifest_gains_the_configuration_the_cell_and_the_nem_metrics():
    m = _manifest()
    cfg = [c for c in m["configs"] if c["name"] == CONFIG]
    assert cfg and cfg[0]["reduced"] == _published()["reduced"] == REDUCED
    assert cfg[0]["source"].endswith(
        "NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json")
    cells = [w for w in m["workloads"] if w["name"] == CELL]
    assert cells and cells[0]["chips"] == 1 and len(cells[0]["why"]) <= 200
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    latency = [e for e in m["end_to_end"]
               if e["name"] == "serve_closed_latency_p50_s"][0]
    assert CELL in latency["workloads"] and latency["bound"] == 0.09
    nem = [p for p in m["per_layer"] if p["name"].startswith("nem.")]
    assert len(nem) == 20 and m["per_layer"][-20:] == nem     # appended
    hyb = {p["name"][4:]: p for p in m["per_layer"]
           if p["name"].startswith("hyb.")}
    for p in nem:
        assert p["workloads"] == [CELL]
        assert p["moves"] == "serve_closed_latency_p50_s"
        reader = p["name"].split(".")[-1]
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", reader + ".py"))
        if reader in hyb:       # the same reader again: the same entry
            assert {k: p[k] for k in ("unit", "better", "source", "layer")} \
                == {k: hyb[reader][k]
                    for k in ("unit", "better", "source", "layer")}
    assert {p["name"][4:] for p in nem} - set(hyb) == {
        "moe_share", "moe_experts_roofline", "moe_decode_step_roofline",
        "moe_touched_share", "moe_load_max_over_mean"}
    cell = harness.Cell(REPO, CELL)
    assert cell.driver == "serve_closed_moe_hybrid"
    assert cell.traffic["callers"] == 64 == cell.config["engine"][
        "max_slots_cap"]
    assert {p["name"] for p in cell.per_layer} == {
        p["name"] for p in nem} | {"compile_s", "cache_hits"}
    # no other cell reports them
    other = harness.Cell(REPO, "granite-4.0-h-micro.chat_closed")
    assert not [p for p in other.per_layer if p["name"].startswith("nem.")]


# -- required work -----------------------------------------------------------
def test_parameter_count_and_bytes_are_the_issues_sums():
    cfg = _published()
    m = work_nemotron_h.matmul_params(cfg)
    assert m["mamba"] == 4096 * 18560 + 8192 * 4096        # 109.6 M
    assert m["attention"] == 2 * 4096 * 4096 + 2 * 4096 * 256
    assert m["expert"] == 2 * 1024 * 2688                   # 5.505 M
    assert m["experts_dense"] == 4096 * 512 + 2 * 4096 * 1024 \
        + 2 * 4096 * 5376
    assert m["head"] == 4096 * 32768
    assert work_nemotron_h.all_params(cfg) == 4_648_163_712     # 4,648.2 M
    assert round(2 * work_nemotron_h.all_params(cfg) / 1e9, 2) == 9.30
    from paddle_tpu.models import NemotronHConfig
    from benchmark import serving_moe_hybrid
    built = serving_moe_hybrid.model_config(cfg, cfg["engine"])
    assert isinstance(built, NemotronHConfig)
    assert built.param_count() == work_nemotron_h.all_params(cfg)
    assert (built.n_routed_experts, built.held_experts, built.first_held,
            built.vocab_size, built.eos_id) == (512, 128, 0, 32768, 32767)


def test_a_64_row_step_is_memory_bound_and_mostly_experts():
    cfg, peak = _published(), work.peaks("TPU v5 lite")
    # 64 rows x 22 picks over 512 experts, a quarter held: 352 pairs a
    # layer on ~120 of 128 experts (1 - (1 - 22/512)^64 = 94%)
    pairs, touched = 5 * 352, 5 * 120
    flops, moved = work_nemotron_h.experts_work(cfg, pairs, touched)
    assert flops == 2 * 2 * 1024 * 2688 * pairs
    assert moved == 2 * 2 * 1024 * 2688 * touched + pairs * 1024 * 6
    least, bound = work.roofline_seconds(
        *work_nemotron_h.decode_step_work(cfg, 64, 64 * 200, pairs, touched),
        peak)
    assert bound == "memory" and 0.012 < least < 0.015      # ~13.8 ms
    _, step = work_nemotron_h.decode_step_work(cfg, 64, 64 * 200, pairs,
                                               touched)
    assert 0.55 < moved / step < 0.65           # the issue's ~63%
    _, none = work_nemotron_h.decode_step_work(cfg, 64, 64 * 200, 0, 0)
    assert step - none == moved                 # untouched experts: unread
    # even the longest prompt streams every held expert for ~22 tokens
    # each: a prefill is memory-bound too, and costs about a step
    least, bound = work.roofline_seconds(*work_nemotron_h.prefill_work(
        cfg, 512, 5 * 2816, 5 * 128), peak)
    assert bound == "memory" and 0.010 < least < 0.013


# -- the new readers on a hand-built run --------------------------------------
class _Run:
    """What a reader takes of a traced run, built by hand."""

    def __init__(self, config, spans=(), launches=(), scopes=None):
        self.config = config
        self.devices = [type("D", (), {"device_kind": "TPU v5 lite"})()]
        self.launch_events = list(launches)
        self.program_spans = {"whole": list(spans)}
        self.device_scopes = scopes
        self.log = lambda *_: None


def _reader(name):
    return harness.load_module(REPO, "layer_metrics", name)


def _launch(parent, module_ns, scoped):
    spans = nest([parent, Span("engine/forward", parent.start + 1,
                               parent.start + 2, 0, {"bucket": 64})])
    return {"span": spans[1], "module": (0, module_ns), "scoped": scoped,
            "n_ops": 1}


def test_roofline_readers_take_each_launchs_own_counts_and_event(monkeypatch):
    cfg, peak = _published(), work.peaks("TPU v5 lite")
    step = Span("engine/step", 0, 100, 0, {
        "active": 64, "context": 12800, "moe_pairs": 1760,
        "moe_touched": 600, "moe_max_load": 40, "moe_routed": 7040})
    prefill = Span("engine/prefill", 200, 300, 0, {
        "prompt": 100, "moe_pairs": 2750, "moe_touched": 640})
    need_step = work.roofline_seconds(
        *work_nemotron_h.experts_work(cfg, 1760, 600), peak)[0]
    need_prefill = work.roofline_seconds(
        *work_nemotron_h.experts_work(cfg, 2750, 640), peak)[0]
    scope = "forward/moe_grouped_experts"
    launches = [
        _launch(step, 20_000_000, {scope: int(need_step * 1e9 / 0.5)}),
        _launch(prefill, 30_000_000, {scope: int(need_prefill * 1e9 / 0.25)})]
    run = _Run(cfg, launches=launches)
    got = _reader("moe_experts_roofline").reduce(run)
    assert abs(got - 37.5) < 0.01               # median of 50% and 25%
    whole = work.roofline_seconds(*work_nemotron_h.decode_step_work(
        cfg, 64, 12800, 1760, 600), peak)[0]
    got = _reader("moe_decode_step_roofline").reduce(run)
    assert abs(got - 100 * whole / 0.02) < 1e-6 and 60 < got < 75
    # spans without the fields (the parent's program): nothing to read
    bare = Span("engine/step", 0, 100, 0, {"active": 64, "context": 1})
    run = _Run(cfg, launches=[_launch(bare, 10, {scope: 5})])
    assert _reader("moe_experts_roofline").reduce(run) is None
    assert _reader("moe_decode_step_roofline").reduce(run) is None
    # another configuration's cell
    assert _reader("moe_experts_roofline").reduce(
        _Run({"layer_types": []}, launches=launches)) is None


def test_span_readers_take_the_medians_over_the_steps():
    cfg = _published()
    spans = [Span("engine/step", i, i + 1, 0, {
        "moe_pairs": p, "moe_touched": t, "moe_max_load": mx})
        for i, (p, t, mx) in enumerate(
            [(1760, 600, 40), (1600, 580, 50), (1700, 610, 35)])]
    spans.append(Span("engine/prefill", 9, 10, 0,
                      {"moe_pairs": 9, "moe_touched": 9}))
    run = _Run(cfg, spans=spans)
    assert _reader("moe_touched_share").reduce(run) == 100 * 600 / 640
    assert _reader("moe_load_max_over_mean").reduce(run) \
        == 40 * 128 / 1760
    empty = _Run(cfg, spans=[Span("engine/step", 0, 1, 0, {"active": 3})])
    assert _reader("moe_touched_share").reduce(empty) is None
    assert _reader("moe_load_max_over_mean").reduce(empty) is None


def test_moe_share_sums_the_experts_scopes_over_busy_time():
    scopes = {"busy_ns": 1000, "roles": {"forward": 900}, "unscoped": {},
              "ops": {"forward/moe_grouped_experts": 400,
                      "forward/moe_router_topk": 50,
                      "forward/matmul_v2": 300}}
    assert _reader("moe_share").reduce(_Run({}, scopes=scopes)) == 45.0
    scopes["ops"] = {"forward/matmul_v2": 300}
    assert _reader("moe_share").reduce(_Run({}, scopes=scopes)) is None


# -- the driver, tiny, on the CPU ---------------------------------------------
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tiny.make(tmp_path_factory.mktemp("bench_nemotron"))
    bdir = os.path.join(path, "benchmark")
    for kind, name, obj in (
            ("configs", "moe-hybrid-tiny", TINY_MOE),
            ("traffic", "tiny_chat_closed_ep4", TINY_MIX),
            ("cells", "moe-hybrid-tiny.tiny_chat_closed_ep4",
             {"reports": ["serve_closed_latency_p50_s"]})):
        with open(os.path.join(bdir, kind, name + ".json"), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(path, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "moe-hybrid-tiny",
        "file": "benchmark/configs/moe-hybrid-tiny.json"})
    manifest["workloads"].append({
        "name": "moe-hybrid-tiny.tiny_chat_closed_ep4",
        "config": "moe-hybrid-tiny", "traffic": "tiny_chat_closed_ep4",
        "chips": 1})
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return path


def test_closed_loop_driver_serves_the_share_correctly(root):
    lines = []
    result = harness.run_cell("moe-hybrid-tiny.tiny_chat_closed_ep4",
                              2**31 + 7, 2.0, 0, root=root,
                              require_tpu=False, log=lines.append)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] >= 4, lines
    assert set(result["metrics"]) == {"serve_closed_latency_p50_s",
                                      "setup_s"}
    assert any("compilations inside 0, retraces 0" in ln for ln in lines)
    counted = [ln for ln in lines if ln.startswith("experts over the")][0]
    # a quarter of the 16 experts is held: about a quarter of the pairs
    share = float(counted.split("(")[1].split("%")[0])
    assert 10 < share < 45, counted
    margin = [ln for ln in lines if ln.startswith("reference:")][0]
    # float32 served against float32 reference: ties only
    assert float(margin.split("worst ")[1].split(" sigma")[0]) < 1e-3
    assert float(margin.split("mean ")[1].split(" sigma")[0]) < 1e-4
    router = [ln for ln in lines if ln.startswith("router:")][0]
    # the float32 served gate picks the reference's own sets
    assert float(router.split("shortfall ")[1].split(" (")[0]) < 1e-6, router
    assert float(router.split("), ")[1].split(" of the rows")[0]) == 0.0


def test_int8_reading_rounds_the_matrices_and_moves_the_logits():
    """The reading that has to come out as not correct on the chip: the
    reference with its matrices (the experts' each on its own) rounded
    through int8 moves the logits by hundredths of a row sigma — orders
    over float32 rounding, which is what lets MEAN_SIGMA sit between it
    and the served path's mean margin."""
    import paddle_tpu
    import paddle_tpu.dygraph as dg
    from paddle_tpu.models import NemotronHModel
    from benchmark import loadgen, serving_moe_hybrid
    from benchmark.reference import nemotron_h as reference
    with dg.guard():
        paddle_tpu.seed(5)
        model = NemotronHModel(serving_moe_hybrid.model_config(
            TINY_MOE, TINY_MOE["engine"]))
        params = reference.params_of(model)
        ids = np.random.default_rng(0).integers(0, 94, 24).astype(np.int32)
        picks = []
        full = np.asarray(reference.logits(params, ids, TINY_MOE,
                                           picks=picks))
        assert len(picks) == 3 and picks[0].shape == (24, 4)
        rounded = np.asarray(reference.logits(params, ids, TINY_MOE,
                                              weights_as="int8"))
        moved = float(np.abs(rounded - full).max() / full.std())
        assert 1e-3 < moved < 1.0, moved
        with pytest.raises(ValueError):
            reference.logits(params, ids, TINY_MOE, weights_as="fp8")
        w = np.asarray(params["layers"][1]["w1"], np.float32)
        back = np.asarray(reference._through_int8(w))
        scale = np.abs(w).max(axis=-2, keepdims=True) / 127
        assert np.abs(back - w).max() <= scale.max() / 2 + 1e-7
        assert len(np.unique(np.round(back / scale)[0, :, 0])) <= 255
        # the rule itself: a sequence that follows the reference's argmax
        # has margin 0; one that takes the runner-up has its gap in sigmas
        served = type("S", (), {
            "cfg": dict(TINY_MOE,
                        n_positions=TINY_MOE["engine"]["max_context"]),
            "model": model, "reference_params": lambda self: params})()
        best = int(full[7].argmax())
        second = int(np.argsort(full[7])[-2])
        req = loadgen.Request(0, None, ids[:8], 1)
        got = serving_moe_hybrid.check_against_reference(
            served, [(req, list(ids[:8]) + [best])], 1)
        assert (got["worst"], got["mean"]) == (0.0, 0.0)
        # float32 served gate, float32 reference: the sets agree
        assert got["rows"] == 3 * 9 and got["shortfall"] < 1e-6
        assert got["apart"] == 0.0
        assert serving_moe_hybrid.within_limits(got)
        got = serving_moe_hybrid.check_against_reference(
            served, [(req, list(ids[:8]) + [second])], 1)
        gap = got["worst"]
        assert got["mean"] == gap   # one served token: its own margin
        want = float((full[7].max() - full[7][second]) / full[7].std())
        assert gap > 0 and abs(gap - want) < 1e-3 * max(want, 1e-3) + 1e-5
        # GIVEN picks: the reference weights the experts it is given with
        # its own scores and says how far under its own k-th best they lie
        own = np.stack([np.asarray(p) for p in picks])         # [3, 24, 4]
        short = []
        same = np.asarray(reference.logits(
            params, ids, TINY_MOE, forced=own, shortfall=short))
        np.testing.assert_allclose(same, full, rtol=0, atol=1e-5)
        assert len(short) == 3 and float(np.max(short)) == 0.0
        other = own.copy()              # token 5, first expert layer: its
        absent = [e for e in range(16) if e not in own[0, 5]]   # best pick
        other[0, 5, 0] = absent[0]      # swapped for one it did not pick
        short = []
        moved = np.asarray(reference.logits(
            params, ids, TINY_MOE, forced=other, shortfall=short))
        assert float(short[0][5]) > 0 and float(np.delete(
            np.asarray(short[0]), 5).max()) == 0.0
        assert np.abs(moved[:5] - full[:5]).max() < 1e-5    # causal
        assert np.abs(moved[5] - full[5]).max() > 1e-4


def test_each_of_the_four_limits_refuses_and_summary_reads_them():
    from benchmark import serving_moe_hybrid
    for limit, reading in (("TIE_SIGMA", "worst"), ("MEAN_SIGMA", "mean"),
                           ("PICK_EPSILON", "shortfall"),
                           ("PICKS_APART", "apart")):
        got = {"worst": 0.0, "mean": 0.0, "shortfall": 0.0, "apart": 0.0}
        assert serving_moe_hybrid.within_limits(got)
        got[reading] = 1.01 * getattr(serving_moe_hybrid, limit)
        assert not serving_moe_hybrid.within_limits(got), limit
    per = [{"margins": np.asarray([0.0, 0.5]),
            "shortfall": np.asarray([[0.0, 0.25]]),
            "apart": np.asarray([[0, 2]])},
           {"margins": np.asarray([0.25]), "shortfall": np.asarray([[0.125]]),
            "apart": np.asarray([[1]])}]
    assert serving_moe_hybrid.summary(per) == {
        "worst": 0.5, "mean": 0.25, "rows": 3, "shortfall": 0.25,
        "differ": 2 / 3, "apart": 1 / 3, "by_layer": [(0.6667, 0.33333, 2)]}
