"""The cell with gated short convolutions and every expert of a layer held
(`lfm2-8b-a1b.agent_closed_pp2`): its configuration file against the
catalog row and the manifest, required work against the configuration
file's sums, each new reader on a hand-built run, and the driver rehearsed
tiny on the CPU through the function `benchmark/run.py` calls, traced."""
import json
import os

import numpy as np
import pytest

import benchmark_tiny_root as tiny
from benchmark import harness, work, work_lfm2_moe as wl
from benchmark.program_spans import Span, nest

REPO = tiny.REPO
CONFIG = "lfm2-8b-a1b"
CELL = CONFIG + ".agent_closed_pp2"
REDUCED = ["num_hidden_layers", "layer_types", "max_position_embeddings"]
FIRST_12 = ["conv", "conv", "full_attention", "conv", "conv", "conv",
            "full_attention", "conv", "conv", "conv", "full_attention",
            "conv"]
TINY_LFM = {
    "name": "lfm-tiny", "vocab_size": 96, "hidden_size": 64,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv"],
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_dense_layers": 2, "num_experts": 8, "n_routed_experts": 8,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True, "norm_eps": 1e-5,
    "rope_theta": 1000000, "max_position_embeddings": 64,
    "first_held_expert": 0, "eos_token_id": 95,
    "engine": {"dtype": "float32", "page_tokens": 4, "max_context": 64,
               "max_slots_cap": 4, "hbm_bytes": 8 << 20}}
TINY_MIX = {
    "driver": "serve_closed_lfm2_moe", "callers": 4,
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 5, "max": 30, "stratify": 4},
    "new_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.4,
                   "min": 3, "max": 10},
    "max_total_tokens": 64}
LFM = ["device_idle_share", "launches_per_step", "tok_per_s",
       "prefill_ms_p50", "step_ms_p50", "engine_forward_share",
       "state_slots_peak_share", "moe_share", "moe_load_max_over_mean",
       "prefill_pad_share", "experts_roofline", "prompt_attention_roofline",
       "conv_prefill_mfu", "conv_decode_step_roofline"]


def _published():
    with open(os.path.join(REPO, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def _manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the configuration file --------------------------------------------------
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
# the published config.json's numbers and kinds, as the source states them
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": FIRST_12 + ["conv", "conv", "full_attention", "conv",
                               "conv", "conv", "full_attention", "conv",
                               "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def test_configuration_file_holds_the_published_keys_but_what_it_reduces():
    cfg = _published()
    assert SOURCE in cfg["source"]
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key


def test_every_published_width_is_unchanged_and_the_cut_is_stated():
    cfg = _published()
    widths = dict(hidden_size=2048, num_attention_heads=32,
                  num_key_value_heads=8, intermediate_size=7168,
                  moe_intermediate_size=1792, num_experts=32,
                  num_experts_per_tok=4, conv_L_cache=3, vocab_size=65536,
                  num_dense_layers=2, rope_theta=1000000,
                  routed_scaling_factor=1)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["published"]["num_hidden_layers"] == 24
    assert cfg["published"]["max_position_embeddings"] == 128000
    assert cfg["published"]["layer_types"][:12] == FIRST_12 \
        == cfg["layer_types"]
    assert cfg["reduced"] == REDUCED and set(cfg["reduced_why"]) == set(
        REDUCED)
    assert (cfg["num_hidden_layers"], cfg["max_position_embeddings"],
            cfg["n_routed_experts"], cfg["first_held_expert"]) \
        == (12, 8192, 32, 0)
    assert "two pipeline stages" in cfg["deployment"]
    assert {"router", "dense_width", "tie_word_embeddings", "positions",
            "short_conv", "eos_token_id", "initialization"} \
        <= set(cfg["assumed"])
    assert "1e-6" in cfg["assumed"]["router"]
    assert len(cfg["departures"]) >= 4 and cfg["reference"] == \
        "benchmark/reference/lfm2_moe.py"
    assert cfg["eos_token_id"] == cfg["vocab_size"] - 1
    eng = cfg["engine"]
    assert (eng["dtype"], eng["page_tokens"], eng["max_context"],
            eng["max_slots_cap"]) == ("bfloat16", 16, 8192, 64)
    assert "3,928.7 M parameters = 7.86 GB" in cfg["bytes"] \
        and "3.22 GB" in cfg["bytes"]


def test_manifest_gains_the_configuration_the_cell_and_the_lfm_metrics():
    m = _manifest()
    cfg = [c for c in m["configs"] if c["name"] == CONFIG]
    assert cfg and cfg[0]["reduced"] == REDUCED
    assert cfg[0]["source"] == SOURCE
    assert len(cfg[0]["why"]) <= 200
    cells = [w for w in m["workloads"] if w["name"] == CELL]
    assert cells and cells[0]["chips"] == 1 and len(cells[0]["why"]) <= 200
    latency = [e for e in m["end_to_end"]
               if e["name"] == "serve_closed_latency_p50_s"][0]
    assert CELL in latency["workloads"] and latency["bound"] == 0.09
    # order and contiguity of the lfm.* entries, not the manifest's tail
    names = [p["name"] for p in m["per_layer"]]
    lfm = [p for p in m["per_layer"] if p["name"].startswith("lfm.")]
    first = names.index(lfm[0]["name"])
    assert [p["name"] for p in lfm] == ["lfm." + n for n in LFM]
    assert m["per_layer"][first:first + len(LFM)] == lfm
    assert first > names.index("pgu.decode_read_pad_share")     # appended
    assert len(names) <= 128            # the contract's most per-layer
    pgu = {p["name"][4:]: p for p in m["per_layer"]
           if p["name"].startswith("pgu.")}
    for p in lfm:
        assert p["workloads"] == [CELL]
        assert p["moves"] == "serve_closed_latency_p50_s"
        reader = p["name"].split(".")[-1]
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", reader + ".py"))
        if reader in pgu:       # the same reader again: the same entry
            assert {k: p[k] for k in ("unit", "better", "source", "layer")} \
                == {k: pgu[reader][k]
                    for k in ("unit", "better", "source", "layer")}
        if reader.endswith("_roofline") or "mfu" in reader:
            assert p["unit"] == "%" and p["better"] == "higher"
    cell = harness.Cell(REPO, CELL)
    assert cell.driver == "serve_closed_lfm2_moe"
    assert cell.traffic["callers"] == 64 == cell.config["engine"][
        "max_slots_cap"]
    mix = cell.traffic
    assert (mix["prompt_tokens"]["median"], mix["prompt_tokens"]["sigma"],
            mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"],
            mix["new_tokens"]["median"], mix["new_tokens"]["sigma"],
            mix["new_tokens"]["min"], mix["new_tokens"]["max"]) \
        == (2048, 0.7, 256, 7936, 128, 0.6, 16, 512)
    assert mix["prompt_tokens"]["stratify"] == mix["new_tokens"][
        "stratify"] == 8
    assert mix["max_total_tokens"] == cell.config["engine"]["max_context"]
    # the cell reports them all; later metrics may join it
    assert {p["name"] for p in lfm} <= {p["name"] for p in cell.per_layer}


# -- required work -----------------------------------------------------------
def test_parameter_count_and_bytes_are_the_configuration_files_sums():
    cfg = _published()
    m = wl.matmul_params(cfg)
    assert m["conv"] == 2048 * 6144 + 2048 * 2048                # 16.78 M
    assert m["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512    # 10.49 M
    assert m["mlp"] == 3 * 2048 * 7168 and m["expert"] == 3 * 2048 * 1792
    assert round(wl.all_params(cfg) / 1e6, 1) == 3928.7
    assert round(2 * wl.all_params(cfg) / 1e9, 2) == 7.86
    from paddle_tpu.models import Lfm2MoeConfig
    from benchmark import serving_lfm2_moe
    built = serving_lfm2_moe.model_config(cfg, cfg["engine"])
    assert isinstance(built, Lfm2MoeConfig)
    assert built.param_count() == wl.all_params(cfg)
    assert (built.n_routed_experts, built.held_experts, built.vocab_size,
            built.eos_id, built.max_position, built.num_layers,
            built.expert_layers) == (32, 32, 65536, 65535, 8192, 12, 10)
    from paddle_tpu.serving.kv_pool import state_slot_bytes, token_bytes
    spec = built.cache_spec()
    assert token_bytes(spec) == 2 * 3 * 8 * 64 * 2
    assert round(64 * 8192 * token_bytes(spec) / 1e9, 2) == 3.22
    # the conv tails: 9 layers x 2 rows x 2,048, bfloat16, a slot
    assert state_slot_bytes(spec, 8192) == 8192 * token_bytes(spec) \
        + 9 * 2 * 2048 * 2


def test_the_work_counts_a_pair_a_column_and_a_row():
    cfg = _published()
    flops, moved = wl.prompt_attention_work(cfg, 4)
    assert flops == 3 * 8192 * 10                  # 10 causal pairs
    assert moved == 3 * 4 * 2 * 64 * 2 * (32 + 8)
    flops, moved = wl.decode_attention_work(cfg, 1000, 2)
    assert flops == 8192 * 1000
    assert moved == 2048 * 1000 + 2 * 2 * 3 * 2 * 32 * 64
    # a step of 10 rows: the conv tails' two rows a layer read and written
    flops, moved = wl.decode_step_work(cfg, 10, 0, 0, 0)
    m = wl.matmul_params(cfg)
    assert flops == 2 * m["dense"] * 10
    assert moved == 2 * m["dense"] + 10 * 9 * 2 * 2 * 2 * 2048 \
        + 2 * 10 * 3 * 2 * 32 * 64


def test_a_64_row_step_is_memory_bound_and_a_long_prompt_compute_bound():
    cfg, peak = _published(), work.peaks("TPU v5 lite")
    # 64 rows x 4 picks over 32 experts a layer: 8 pairs an expert, all
    # 32 touched in each of 10 layers
    pairs, touched = 10 * 64 * 4, 10 * 32
    columns = 3 * 64 * 2700
    least, bound = work.roofline_seconds(*wl.decode_step_work(
        cfg, 64, columns, pairs, touched), peak)
    assert bound == "memory" and 0.009 < least < 0.012
    _, step = wl.decode_step_work(cfg, 64, columns, pairs, touched)
    # the weights a step streams: the experts are 7.05 GB of it
    assert 7.0e9 < wl.experts_work(cfg, pairs, touched)[1] < 7.1e9
    assert 7.85e9 < step - wl.decode_attention_work(cfg, columns, 64)[1] \
        < 7.95e9
    # with ~2.7k columns a row of KV read: ~8.9 GB a step
    assert 8.9e9 < step < 9.0e9
    flops, moved = wl.prefill_work(cfg, 2048, 10 * 2048 * 4, 320)
    least, bound = work.roofline_seconds(flops, moved, peak)
    assert bound == "compute"
    # the cell's reckoning: ~1.45 GFLOP a token, the experts ~61% of it
    # and the conv mixers ~21%
    assert 1.3e9 < flops / 2048 < 1.6e9
    assert 0.55 < wl.experts_work(cfg, 10 * 2048 * 4, 320)[0] / flops < 0.66
    conv = 9 * 2 * wl.matmul_params(cfg)["conv"] * 2048
    assert 0.18 < conv / flops < 0.24


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


def test_roofline_readers_take_each_launchs_own_counts_and_event():
    cfg, peak = _published(), work.peaks("TPU v5 lite")
    step = Span("engine/step", 0, 100, 0, {
        "active": 64, "context": 170000, "kv_columns": 520000,
        "moe_pairs": 2560, "moe_touched": 320, "moe_max_load": 130,
        "moe_routed": 2560})
    prefill = Span("engine/prefill", 200, 300, 0, {
        "prompt": 3000, "bucket": 4096, "moe_pairs": 120000,
        "moe_touched": 320})

    def ns(share, work_of):
        return int(work.roofline_seconds(*work_of, peak)[0] * 1e9 / share)

    launches = [
        _launch(step, 16_000_000, {
            "forward/moe_grouped_experts": ns(0.8, wl.experts_work(
                cfg, 2560, 320))}),
        _launch(prefill, 90_000_000, {
            "forward/moe_grouped_experts": ns(0.6, wl.experts_work(
                cfg, 120000, 320)),
            "forward/windowed_prefill_attention": ns(
                0.45, wl.prompt_attention_work(cfg, 3000))})]
    run = _Run(cfg, launches=launches)
    assert abs(_reader("experts_roofline").reduce(run) - 70) < 0.01
    assert abs(_reader("prompt_attention_roofline").reduce(run) - 45) < 0.01
    whole = work.roofline_seconds(*wl.decode_step_work(
        cfg, 64, 520000, 2560, 320), peak)[0]
    got = _reader("conv_decode_step_roofline").reduce(run)
    assert abs(got - 100 * whole / 0.016) < 1e-6
    flops = wl.prefill_work(cfg, 3000, 120000, 320)[0]
    got = _reader("conv_prefill_mfu").reduce(run)
    assert abs(got - 100 * flops / 197e12 / 0.09) < 1e-6
    # spans without the fields, another configuration: nothing to read
    bare = Span("engine/step", 0, 100, 0, {"context": 1})
    names = ("experts_roofline", "prompt_attention_roofline",
             "conv_prefill_mfu", "conv_decode_step_roofline")
    for name in names:
        assert _reader(name).reduce(_Run(cfg, launches=[_launch(
            bare, 10, {"forward/moe_grouped_experts": 5})])) is None, name
        assert _reader(name).reduce(_Run({"kv_lora_rank": 8},
                                         launches=launches)) is None, name


def test_the_reused_readers_read_this_configuration():
    scopes = {"busy_ns": 1000, "roles": {"forward": 900}, "unscoped": {},
              "ops": {"forward/short_conv": 250,
                      "forward/moe_grouped_experts": 400,
                      "forward/moe_router_topk": 20,
                      "forward/matmul_v2": 200}}
    assert _reader("moe_share").reduce(_Run({}, scopes=scopes)) == 42.0
    # the load reader takes the experts held from `n_routed_experts`,
    # which the configuration file repeats: 32
    spans = [Span("engine/step", 0, 1, 0, {"moe_max_load": 16,
                                           "moe_pairs": 256})]
    got = _reader("moe_load_max_over_mean").reduce(_Run(_published(),
                                                        spans=spans))
    assert got == 16 * 32 / 256


# -- the driver, tiny, on the CPU, traced -------------------------------------
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tiny.make(tmp_path_factory.mktemp("bench_lfm2"))
    bdir = os.path.join(path, "benchmark")
    for kind, name, obj in (
            ("configs", "lfm-tiny", TINY_LFM),
            ("traffic", "tiny_agent_closed", TINY_MIX),
            ("cells", "lfm-tiny.tiny_agent_closed",
             {"reports": ["serve_closed_latency_p50_s"]})):
        with open(os.path.join(bdir, kind, name + ".json"), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(path, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "lfm-tiny", "file": "benchmark/configs/lfm-tiny.json"})
    manifest["workloads"].append({
        "name": "lfm-tiny.tiny_agent_closed", "config": "lfm-tiny",
        "traffic": "tiny_agent_closed", "chips": 1})
    # the tiny root reports every per-layer metric in every cell: keep the
    # lfm.* ones
    manifest["per_layer"] = [p for p in manifest["per_layer"]
                             if p["name"].startswith("lfm.")]
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return path


@pytest.fixture
def cpu_stands_in_for_a_v5e(monkeypatch):
    """The CPU's trace has no device plane: the recorded tiny v5e trace
    stands in for it (`test_benchmark_program_spans.py`'s fixture)."""
    from benchmark import trace_reduce
    real = work.peaks
    monkeypatch.setattr(
        work, "peaks",
        lambda kind: real("TPU v5 lite" if kind == "cpu" else kind))
    summarize = trace_reduce.summarize
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tiny_train_v5e.xplane.pb")
    monkeypatch.setattr(trace_reduce, "summarize",
                        lambda path, n_devices=1: summarize(data, 1))


def test_closed_loop_driver_serves_the_stage_correctly_traced(
        root, cpu_stands_in_for_a_v5e):
    lines = []
    result = harness.run_cell("lfm-tiny.tiny_agent_closed", 2**31 + 13,
                              2.0, 1, root=root, require_tpu=False,
                              log=lines.append)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] >= 4, lines
    # prompts of 5-30 pad to 16 and 32; decode bounds 16, 32 and 64
    warm = [ln for ln in lines if ln.startswith("warm-up:")][0]
    assert "prefill buckets [16, 32]" in warm \
        and "decode buckets [16, 32, 64]" in warm \
        and "engine reports 5 buckets" in warm, warm
    ref = [ln for ln in lines if ln.startswith("reference:")][0]
    # float32 served against float32 reference, through the KV columns and
    # the conv tails
    prefill = json.loads(ref.split("last row ")[1].split(" of the")[0])
    decode = json.loads(ref.split("decoded row ")[1].split(" (limit")[0])
    assert prefill and max(prefill) < 1e-4 and max(decode) < 1e-4, ref
    assert float(ref.split("shortfall ")[1].split(" (")[0]) < 1e-6, ref
    # every served token the replay's own pick and within a float32 tie of
    # the reference's; the twins with the picks the engine programs' to
    # the bit
    import re
    worst = float(re.search(r"worst ([-0-9.e]+) sigma", ref).group(1))
    off = int(re.search(r"; (\d+) of \d+ served tokens", ref).group(1))
    twin = float(re.search(r"twins' logits ([-0-9.e]+) from", ref).group(1))
    assert worst < 1e-3 and off == 0 and twin == 0.0, ref
    counted = [ln for ln in lines if ln.startswith("counters over")][0]
    # the first 4 of the 5 layer types run: 3 conv layers x 4 slots x 2
    # rows x 64, float32
    assert "'state.conv_bytes': 6144" in counted, counted
    # what the CPU's trace can read: the program's spans and counters
    got = {k: v["value"] for k, v in result["metrics"].items()}
    for name in ("tok_per_s", "prefill_ms_p50", "step_ms_p50",
                 "engine_forward_share", "state_slots_peak_share",
                 "moe_load_max_over_mean", "prefill_pad_share"):
        assert got.get("lfm." + name) is not None, (name, sorted(got))
    assert 0 < got["lfm.state_slots_peak_share"] <= 100
    assert 0 < got["lfm.prefill_pad_share"] < 100
    assert got["lfm.moe_load_max_over_mean"] >= 1


def test_the_limit_readings_tool_passes_the_model_and_fails_float8(root):
    """`tools/lfm2_limit_readings.py` on the tiny float32 cell: the cell's
    own check passes on the model's weights and fails with the reference's
    matrices through float8 e4m3."""
    tool = harness.load_module(REPO, "tools", "lfm2_limit_readings")
    got = tool.main(["--seed", "5", "--requests", "6", "--allow-cpu",
                     "--root", root, "--cell", "lfm-tiny.tiny_agent_closed"])
    assert got["float32"] is True and got["float8_e4m3fn"] is False
    assert set(got) == {"float32", "float8_e4m3fn", "int8"}


def test_each_limit_refuses_a_reading_past_it():
    from benchmark import serving_lfm2_moe as slm
    good = {"prefill": 0.001, "decode": 0.001, "shortfall": 0.0,
            "apart": 0.0, "worst": 0.0, "twin_apart": 0.0}
    assert slm.within_limits(good)
    for key, limit in (("prefill", slm.PREFILL_APART),
                       ("decode", slm.DECODE_APART),
                       ("worst", slm.TIE_SIGMA),
                       ("twin_apart", slm.TWIN_APART),
                       ("shortfall", slm.PICK_EPSILON),
                       ("apart", slm.PICKS_APART)):
        assert not slm.within_limits(dict(good, **{key: limit * 1.01})), key
    assert slm.within_limits(dict(good, decode=None))
    rows = [{"rows": np.asarray([0.2, 0.01, 0.03, 0.02]),
             "margins": np.asarray([0.0, 0.1, 0.0, 0.3]),
             "off": np.asarray([False, True, False, False]), "twin": 0.0,
             "shortfall": np.zeros((2, 5)), "apart": np.zeros((2, 5)),
             "length": 5000},
            {"rows": np.asarray([0.1]), "margins": np.asarray([0.2]),
             "off": np.asarray([False]), "twin": 1e-3,
             "shortfall": np.zeros((2, 3)),
             "apart": np.asarray([[0, 2, 0], [1, 0, 0]]), "length": 100}]
    got = slm.summary(rows)
    assert got["prefill"] == 0.2 and got["decode"] == 0.02
    assert got["long"] == 1 and got["apart"] == 1 / 16
    assert got["worst"] == 0.3 and abs(got["mean"] - 0.12) < 1e-12
    assert (got["tokens_off"], got["tokens"], got["twin_apart"]) \
        == (1, 5, 1e-3)


def test_the_check_compares_the_prompts_row_and_the_first_decoded_rows():
    """The replay and the reference stop at `DECODED` decode steps a
    sequence: the compared positions end there, or before the last token
    (whose row picked nothing)."""
    from benchmark import serving_lfm2_moe as slm

    class Req:
        prompt = list(range(300))

    assert slm._compared(Req, list(range(301))) == 300        # no step
    assert slm._compared(Req, list(range(340))) == 339
    assert slm._compared(Req, list(range(300 + 5 * slm.DECODED))) \
        == 300 + slm.DECODED
    # the limits against the v5e readings they lie between (module
    # comment): over the largest sound one, under the smallest of int8
    assert 0.0488 < slm.PREFILL_APART == slm.DECODE_APART < 0.1255
    assert 0.179 < slm.TIE_SIGMA < 0.632
    assert 0.0625 < slm.PICK_EPSILON < 0.1686
    assert 3.95e-4 < slm.PICKS_APART < 9.8e-3
