"""The cell with window and full attention layers over a ring cache
(`command-a-plus-05-2026.rag_closed_ep8`): its configuration file against
the catalog row and the manifest, required work against hand counts, each
new reader on a hand-built run, the controls' readings tiny, and the
driver rehearsed tiny on the CPU through the function `benchmark/run.py`
calls."""
import json
import os

import numpy as np
import pytest

import benchmark_tiny_root as tiny
from benchmark import harness, work, work_cohere2_moe
from benchmark.program_spans import Span, nest

REPO = tiny.REPO
CONFIG = "command-a-plus-05-2026"
CELL = CONFIG + ".rag_closed_ep8"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "max_position_embeddings"]
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
TINY_CMD = {
    "name": "cmd-tiny", "vocab_size": 96, "hidden_size": 64,
    "layer_types": PERIOD * 2, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 8, "rope_theta": 50000, "intermediate_size": 48,
    "num_experts": 4, "num_experts_per_tok": 4, "num_shared_experts": 2,
    "norm_topk_prob": True, "layer_norm_eps": 1e-5, "logit_scale": 1,
    "max_position_embeddings": 64, "use_parallel_block": True,
    "published": {"num_experts": 16, "vocab_size": 128,
                  "num_hidden_layers": 8, "max_position_embeddings": 4096},
    "first_held_expert": 4, "eos_token_id": 95, "n_routed_experts": 4,
    "engine": {"dtype": "float32", "page_tokens": 4, "max_context": 64,
               "max_slots_cap": 4, "hbm_bytes": 8 << 20}}
TINY_MIX = {
    "driver": "serve_closed_cohere2_moe", "callers": 4,
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 5, "max": 30, "stratify": 4},
    "new_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.4,
                   "min": 3, "max": 10}}


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
               if '"command-a-plus-05-2026"' in ln][0]
    cfg = _published()
    assert cfg["source"].endswith(row["source_url"].split("//")[1][-40:]) \
        or row["source_url"] in cfg["source"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key       # layer_types whole, too


def test_every_published_width_is_unchanged_and_the_cut_is_stated():
    cfg = _published()
    widths = dict(hidden_size=4096, num_attention_heads=128,
                  num_key_value_heads=8, head_dim=128, intermediate_size=4096,
                  num_experts_per_tok=8, num_shared_experts=4,
                  sliding_window=4096, rope_theta=50000, rotary_pct=1)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["published"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144,
        "max_position_embeddings": 200000}
    assert cfg["reduced"] == REDUCED
    assert set(cfg["reduced_why"]) == set(REDUCED)
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"], cfg["first_held_expert"]) \
        == (4, 16, 32768, 8192, 0)
    # the layers run are the first period of the pattern as published
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == PERIOD
    assert len(cfg["layer_types"]) == 32
    assert "8 v5e chips" in cfg["deployment"] \
        and "4 long-context sessions a chip" in cfg["deployment"]
    assert {"shared_expert_average", "positions", "expert_width",
            "softmax_scale", "initialization", "eos_token_id"} \
        <= set(cfg["assumed"])
    assert any("vision tower" in d for d in cfg["departures"])
    assert 0 <= cfg["eos_token_id"] < cfg["vocab_size"]
    eng = cfg["engine"]
    assert (eng["dtype"], eng["page_tokens"], eng["max_context"],
            eng["max_slots_cap"]) == ("bfloat16", 16, 8192, 32)
    # the name a shared reader reads repeats the published key
    assert cfg["n_routed_experts"] == cfg["num_experts"]
    assert "4,733.3 M parameters = 9.47 GB" in cfg["bytes"] \
        and "2.68 GB" in cfg["bytes"]


def test_manifest_gains_the_configuration_the_cell_and_the_cmd_metrics():
    m = _manifest()
    cfg = [c for c in m["configs"] if c["name"] == CONFIG]
    assert cfg and cfg[0]["reduced"] == _published()["reduced"] == REDUCED
    assert cfg[0]["source"] == "https://huggingface.co/CohereLabs/" \
        "command-a-plus-05-2026/blob/main/config.json"
    assert len(cfg[0]["why"]) <= 200
    cells = [w for w in m["workloads"] if w["name"] == CELL]
    assert cells and cells[0]["chips"] == 1 and len(cells[0]["why"]) <= 200
    latency = [e for e in m["end_to_end"]
               if e["name"] == "serve_closed_latency_p50_s"][0]
    assert CELL in latency["workloads"] and latency["bound"] == 0.09
    names = [p["name"] for p in m["per_layer"]]
    cmd = [p for p in m["per_layer"] if p["name"].startswith("cmd.")]
    first = names.index(cmd[0]["name"])
    assert len(cmd) == 23 and m["per_layer"][first:first + 23] == cmd
    assert first > names.index("nem.moe_load_max_over_mean")  # appended
    nem = {p["name"][4:]: p for p in m["per_layer"]
           if p["name"].startswith("nem.")}
    for p in cmd:
        assert p["workloads"] == [CELL]
        assert p["moves"] == "serve_closed_latency_p50_s"
        reader = p["name"].split(".")[-1]
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", reader + ".py"))
        if reader in nem:       # the same reader again: the same entry
            assert {k: p[k] for k in ("unit", "better", "source", "layer")} \
                == {k: nem[reader][k]
                    for k in ("unit", "better", "source", "layer")}
        if reader.endswith("_roofline") or "mfu" in reader:
            assert p["unit"] == "%" and p["better"] == "higher"
    assert {p["name"][4:] for p in cmd} - set(nem) == {
        "attention_share", "prefill_attention_roofline",
        "decode_attention_roofline", "gated_experts_roofline",
        "prefill_mfu", "window_decode_step_roofline",
        "kv_ring_wrapped_share", "prefill_pad_share",
        "experts_touched_share"}
    cell = harness.Cell(REPO, CELL)
    assert cell.driver == "serve_closed_cohere2_moe"
    assert cell.traffic["callers"] == 32 == cell.config["engine"][
        "max_slots_cap"]
    mix = cell.traffic
    assert (mix["prompt_tokens"]["median"], mix["prompt_tokens"]["min"],
            mix["prompt_tokens"]["max"], mix["new_tokens"]["median"],
            mix["new_tokens"]["min"], mix["new_tokens"]["max"]) \
        == (4096, 1024, 7936, 128, 32, 256)
    assert mix["prompt_tokens"]["max"] + mix["new_tokens"]["max"] \
        == cell.config["engine"]["max_context"]
    assert {p["name"] for p in cell.per_layer} == {
        p["name"] for p in cmd} | {"compile_s", "cache_hits"}
    # no other cell reports them
    other = harness.Cell(REPO, "nemotron-3-super-120b-a12b.chat_closed_ep4")
    assert not [p for p in other.per_layer if p["name"].startswith("cmd.")]


# -- required work -----------------------------------------------------------
def test_parameter_count_and_bytes_are_the_issues_sums():
    cfg = _published()
    m = work_cohere2_moe.matmul_params(cfg)
    assert m["attention"] == 4096 * (128 + 8 + 8) * 128 + 128 * 128 * 4096
    assert m["expert"] == 3 * 4096 * 4096                       # 50.33 M
    assert m["layer_dense"] == m["attention"] + 4 * m["expert"] + 4096 * 128
    assert m["head"] == 4096 * 32768
    assert work_cohere2_moe.all_params(cfg) == 4_733_292_544    # 4,733.3 M
    assert round(2 * work_cohere2_moe.all_params(cfg) / 1e9, 2) == 9.47
    # the whole model by the same sums: the name's 218B, 25B active
    whole = 32 * (m["layer_dense"] + 4096 + 128 * m["expert"]) \
        + 262144 * 4096
    active = 32 * (m["layer_dense"] + 4096 + 8 * m["expert"]) \
        + 262144 * 4096
    assert round(whole / 1e9, 1) == 218.3 and round(active / 1e9, 1) == 25.0
    from paddle_tpu.models import Cohere2MoeConfig
    from benchmark import serving_cohere2_moe
    built = serving_cohere2_moe.model_config(cfg, cfg["engine"])
    assert isinstance(built, Cohere2MoeConfig)
    assert built.param_count() == work_cohere2_moe.all_params(cfg)
    assert (built.n_routed_experts, built.held_experts, built.first_held,
            built.vocab_size, built.eos_id, built.max_position) \
        == (128, 16, 0, 32768, 32767, 8192)
    assert built.layer_types == PERIOD
    # the cache the planner prices: 83.9 MB a slot, 2.68 GB at 32 slots
    from paddle_tpu.serving.kv_pool import state_slot_bytes
    slot = state_slot_bytes(built.cache_spec(), 8192)
    assert slot == 2 * 2 * 8 * 128 * (3 * 4096 + 8192) == 83_886_080
    assert round(32 * slot / 1e9, 2) == 2.68


def test_attention_work_counts_the_pairs_inside_the_window():
    cfg = _published()
    assert work_cohere2_moe.visible_pairs(5) == 15
    assert work_cohere2_moe.visible_pairs(5, 8) == 15       # inside it
    assert work_cohere2_moe.visible_pairs(5, 2) == 1 + 2 + 2 + 2 + 2
    # a prompt of 8,192: a window layer meets 3/4 of a full layer's pairs
    full = work_cohere2_moe.visible_pairs(8192)
    window = work_cohere2_moe.visible_pairs(8192, 4096)
    assert full == 8192 * 8193 // 2
    assert window == 4096 * 4097 // 2 + 4096 * 4096
    flops, moved = work_cohere2_moe.prefill_attention_work(cfg, 8192)
    assert flops == 4 * 16384 * (3 * window + full)
    # 7.1 TFLOP (the issue reckoned 1.2-5: it counted half of these pairs)
    assert 7.0e12 < flops < 7.3e12
    assert moved == 4 * 8192 * 2 * 2 * (16384 + 1024)
    # a decode step: each column's K and V read once, 8 heads x 128
    flops, moved = work_cohere2_moe.decode_attention_work(cfg, 1000, 2)
    assert flops == 4 * 16384 * 1000
    assert moved == 2 * (2 * 1024 * 1000 + 2 * 4 * 2 * (1024 + 16384))


def test_a_32_row_step_is_memory_bound_and_a_long_prompt_compute_bound():
    cfg, peak = _published(), work.peaks("TPU v5 lite")
    # 32 rows x 8 picks over 128 experts, an eighth held: 32 pairs a layer
    # on ~14 of 16 experts (1 - (1 - 8/128)^32 = 87%)
    pairs, touched = 4 * 32, 4 * 14
    flops, moved = work_cohere2_moe.experts_work(cfg, pairs, touched)
    assert flops == 2 * 3 * 4096 * 4096 * pairs
    assert moved == 2 * 3 * 4096 * 4096 * touched + pairs * 4096 * 6
    columns = 32 * (3 * 4096 + 6000)        # rows 6,000 tokens long
    least, bound = work.roofline_seconds(*work_cohere2_moe.decode_step_work(
        cfg, 32, columns, pairs, touched), peak)
    assert bound == "memory" and 0.0125 < least < 0.0145     # ~13.4 ms
    _, step = work_cohere2_moe.decode_step_work(cfg, 32, columns, pairs,
                                                touched)
    _, none = work_cohere2_moe.decode_step_work(cfg, 32, columns, 0, 0)
    assert step - none == moved             # untouched experts: unread
    # the weights a step streams: the issue's 8.65 GB
    assert 8.6e9 < step - work_cohere2_moe.decode_attention_work(
        cfg, columns, 32)[1] < 8.7e9
    flops, _ = work_cohere2_moe.prefill_work(cfg, 8192, 4 * 8192, 4 * 16)
    least, bound = work.roofline_seconds(flops, _, peak)
    assert bound == "compute" and 0.13 < least < 0.17
    # 3.16 GFLOP a token in matmuls (the issue's reckoning)
    m = work_cohere2_moe.matmul_params(cfg)
    assert round(2 * (m["dense"] - m["head"] + 4 * m["expert"]) / 1e9, 2) \
        == 3.16


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
        "active": 32, "context": 160000, "kv_columns": 500000,
        "ring_rows": 20, "moe_pairs": 128, "moe_touched": 56,
        "moe_max_load": 9, "moe_routed": 1024})
    prefill = Span("engine/prefill", 200, 300, 0, {
        "prompt": 5000, "bucket": 8192, "moe_pairs": 20000,
        "moe_touched": 64})
    need = {
        "experts_step": work_cohere2_moe.experts_work(cfg, 128, 56),
        "experts_prefill": work_cohere2_moe.experts_work(cfg, 20000, 64),
        "prefill_attention": work_cohere2_moe.prefill_attention_work(
            cfg, 5000),
        "decode_attention": work_cohere2_moe.decode_attention_work(
            cfg, 500000, 32)}
    need = {k: work.roofline_seconds(*v, peak)[0] for k, v in need.items()}
    launches = [
        _launch(step, 20_000_000, {
            "forward/moe_grouped_experts":
                int(need["experts_step"] * 1e9 / 0.5),
            "forward/cached_decode_attention":
                int(need["decode_attention"] * 1e9 / 0.4)}),
        _launch(prefill, 200_000_000, {
            "forward/moe_grouped_experts":
                int(need["experts_prefill"] * 1e9 / 0.25),
            "forward/windowed_prefill_attention":
                int(need["prefill_attention"] * 1e9 / 0.3)})]
    run = _Run(cfg, launches=launches)
    assert abs(_reader("gated_experts_roofline").reduce(run) - 37.5) < 0.01
    assert abs(_reader("prefill_attention_roofline").reduce(run) - 30) < 0.01
    assert abs(_reader("decode_attention_roofline").reduce(run) - 40) < 0.01
    whole = work.roofline_seconds(*work_cohere2_moe.decode_step_work(
        cfg, 32, 500000, 128, 56), peak)[0]
    got = _reader("window_decode_step_roofline").reduce(run)
    assert abs(got - 100 * whole / 0.02) < 1e-6 and 60 < got < 70
    flops = work_cohere2_moe.prefill_work(cfg, 5000, 20000, 64)[0]
    got = _reader("prefill_mfu").reduce(run)
    assert abs(got - 100 * flops / 197e12 / 0.2) < 1e-6 and 40 < got < 55
    # spans without the fields (the parent's program): nothing to read
    bare = Span("engine/step", 0, 100, 0, {"active": 32, "context": 1})
    run = _Run(cfg, launches=[_launch(bare, 10, {
        "forward/moe_grouped_experts": 5})])
    for name in ("gated_experts_roofline", "decode_attention_roofline",
                 "prefill_attention_roofline", "prefill_mfu",
                 "window_decode_step_roofline"):
        assert _reader(name).reduce(run) is None, name
        # another configuration's cell
        assert _reader(name).reduce(_Run({"layer_types": []},
                                         launches=launches)) is None, name


def test_span_readers_take_the_medians_over_the_steps():
    cfg = _published()
    spans = [Span("engine/step", i, i + 1, 0, {
        "active": a, "ring_rows": r, "moe_pairs": p, "moe_touched": t,
        "moe_max_load": mx})
        for i, (a, r, p, t, mx) in enumerate(
            [(32, 16, 128, 56, 9), (30, 18, 120, 52, 8), (32, 8, 130, 58, 7)])]
    spans += [Span("engine/prefill", 9, 10, 0,
                   {"prompt": 3000, "bucket": 4096, "moe_pairs": 9,
                    "moe_touched": 9}),
              Span("engine/prefill", 11, 12, 0,
                   {"prompt": 4100, "bucket": 8192})]
    run = _Run(cfg, spans=spans)
    assert _reader("kv_ring_wrapped_share").reduce(run) == 50.0
    assert _reader("experts_touched_share").reduce(run) == 100 * 56 / 64
    assert _reader("moe_load_max_over_mean").reduce(run) == 8 * 16 / 120
    want = 100 * ((1 - 3000 / 4096) + (1 - 4100 / 8192)) / 2
    assert abs(_reader("prefill_pad_share").reduce(run) - want) < 1e-9
    empty = _Run(cfg, spans=[Span("engine/step", 0, 1, 0, {"active": 3})])
    for name in ("kv_ring_wrapped_share", "experts_touched_share",
                 "prefill_pad_share"):
        assert _reader(name).reduce(empty) is None, name
    assert _reader("experts_touched_share").reduce(
        _Run({"hybrid_override_pattern": "ME"}, spans=spans)) is None


def test_attention_share_sums_the_attention_scopes_over_busy_time():
    scopes = {"busy_ns": 1000, "roles": {"forward": 900}, "unscoped": {},
              "ops": {"forward/windowed_prefill_attention": 200,
                      "forward/cached_decode_attention": 100,
                      "forward/rotary_embedding": 40,
                      "forward/kv_ring_pack": 10,
                      "forward/matmul_v2": 300}}
    assert _reader("attention_share").reduce(_Run({}, scopes=scopes)) == 35.0
    scopes["ops"] = {"forward/matmul_v2": 300}
    assert _reader("attention_share").reduce(_Run({}, scopes=scopes)) is None


# -- the driver, tiny, on the CPU ---------------------------------------------
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tiny.make(tmp_path_factory.mktemp("bench_command_a_plus"))
    bdir = os.path.join(path, "benchmark")
    for kind, name, obj in (
            ("configs", "cmd-tiny", TINY_CMD),
            ("traffic", "tiny_rag_closed_ep8", TINY_MIX),
            ("cells", "cmd-tiny.tiny_rag_closed_ep8",
             {"reports": ["serve_closed_latency_p50_s"]})):
        with open(os.path.join(bdir, kind, name + ".json"), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(path, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "cmd-tiny", "file": "benchmark/configs/cmd-tiny.json"})
    manifest["workloads"].append({
        "name": "cmd-tiny.tiny_rag_closed_ep8", "config": "cmd-tiny",
        "traffic": "tiny_rag_closed_ep8", "chips": 1})
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return path


def test_closed_loop_driver_serves_the_share_correctly(root):
    lines = []
    result = harness.run_cell("cmd-tiny.tiny_rag_closed_ep8", 2**31 + 7,
                              2.0, 0, root=root, require_tpu=False,
                              log=lines.append)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] >= 4, lines
    assert set(result["metrics"]) == {"serve_closed_latency_p50_s",
                                      "setup_s"}
    assert any("compilations inside 0, retraces 0" in ln for ln in lines)
    # prompts of 5-30 pad to 16 and 32: two prefill programs, ONE decode
    warm = [ln for ln in lines if ln.startswith("warm-up:")][0]
    assert "[16, 32]" in warm and "engine reports 3 buckets" in warm
    assert any(ln.startswith("memory peak before the reference check")
               for ln in lines)
    counted = [ln for ln in lines if ln.startswith("experts over the")][0]
    # a quarter of the 16 experts is held: about a quarter of the pairs
    share = float(counted.split("(")[1].split("%")[0])
    assert 10 < share < 45, counted
    margin = [ln for ln in lines if ln.startswith("reference:")][0]
    # float32 served against float32 reference: ties only — through ring
    # wraps: every sampled sequence is longer than the window of 8
    assert float(margin.split("worst ")[1].split(" sigma")[0]) < 1e-3
    assert float(margin.split("mean ")[1].split(" sigma")[0]) < 1e-4
    assert "(12 past the window)" in margin or " past the window)" in margin
    hidden = json.loads(margin.split("window: ")[1].split(" of what")[0])
    assert len(hidden) >= 3 and max(hidden) < 1e-4, margin
    router = [ln for ln in lines if ln.startswith("router:")][0]
    assert float(router.split("shortfall ")[1].split(" (")[0]) < 1e-6, router
    assert float(router.split("), ")[1].split(" of the rows")[0]) == 0.0


def test_the_three_controls_read_not_correct_and_the_sound_path_correct():
    """The readings that have to come out as not correct on the chip, tiny:
    the reference with its matrices through int8, with the window mask
    off, and without rotary each move what the six limits read — the
    window control the hidden state after the first sliding layer, which
    the served tokens' margins alone would not show; and a ring kept
    wrongly from the prompt's end on (`reference.RING_FAULTS`) moves what
    the ENGINE'S OWN programs give when the sequences are replayed through
    them, and nothing a prompt's rows read."""
    import types
    import paddle_tpu
    import paddle_tpu.dygraph as dg
    import paddle_tpu.static as static
    from paddle_tpu.models import Cohere2MoeModel
    from paddle_tpu.serving import ContinuousBatchingEngine
    from benchmark import loadgen, serving_cohere2_moe as cmd
    from benchmark.reference import cohere2_moe as reference
    eng_cfg = TINY_CMD["engine"]
    with dg.guard():
        paddle_tpu.seed(5)
        model = Cohere2MoeModel(cmd.model_config(TINY_CMD, eng_cfg))
        params = reference.params_of(model)
        plan = static.page_budget(
            model, page_tokens=eng_cfg["page_tokens"],
            max_context=eng_cfg["max_context"],
            hbm_bytes=eng_cfg["hbm_bytes"],
            max_slots_cap=eng_cfg["max_slots_cap"])
        engine = ContinuousBatchingEngine(model, kv_pool=plan).start()
        served = types.SimpleNamespace(
            cfg=dict(TINY_CMD, n_positions=eng_cfg["max_context"]),
            model=model, reference_params=lambda: params,
            server=types.SimpleNamespace(engine=engine))
        rng = np.random.default_rng(0)
        # three pass the window of 8; their answers decode across wraps
        prompts = [rng.integers(0, 94, n).astype(np.int32)
                   for n in (24, 30, 5, 6)]
        news = (9, 12, 14, 1)
        futs = [engine.submit(p, max_length=n)
                for p, n in zip(prompts, news)]
        done = [(loadgen.Request(0, None, p, n),
                 [int(t) for t in f.result(timeout=900)])
                for p, n, f in zip(prompts, news, futs)]
        try:
            programs = engine.step_programs.programs
            sound = cmd.check_against_reference(served, done, 1, sample=4)
            # the replay compiled nothing: the engine's own executables
            assert engine.step_programs.programs == programs
            assert sound["worst"] < 1e-3 and sound["mean"] < 1e-4
            assert sound["sequences"] == 4 and sound["long"] == 3
            assert sound["shortfall"] < 1e-6 and sound["apart"] == 0.0
            assert sound["hidden"] < 1e-5 and len(sound["decode_each"]) == 3
            assert sound["decode"] < 1e-4 and sound["prefill_row"] < 1e-4
            assert cmd.within_limits(sound)
            window = cmd.check_against_reference(served, done, 1, sample=4,
                                                 window=False)
            assert window["hidden"] > 10 * cmd.HIDDEN_APART
            assert window["decode"] > cmd.DECODE_APART
            assert not cmd.within_limits(window)
            rotary = cmd.check_against_reference(served, done, 1, sample=4,
                                                 rotary=False)
            assert rotary["hidden"] > cmd.HIDDEN_APART
            assert not cmd.within_limits(rotary)
            int8 = cmd.check_against_reference(served, done, 1, sample=4,
                                               weights_as="int8")
            assert int8["hidden"] > 100 * sound["hidden"]
            for fault in reference.RING_FAULTS:
                ring = cmd.check_against_reference(served, done, 1,
                                                   sample=4, ring=fault)
                # the prompt's rows are the sound ones, the decoded not
                assert ring["prefill_row"] == sound["prefill_row"], fault
                assert ring["decode"] > 10 * cmd.DECODE_APART, fault
                assert not cmd.within_limits(ring), fault
            with pytest.raises(ValueError):
                reference.logits(params, done[0][1], TINY_CMD,
                                 weights_as="fp8")
            with pytest.raises(ValueError, match="ring"):
                reference.logits(params, done[0][1], TINY_CMD, ring="late")
            # a sample with too few sequences past the window is not correct
            short = cmd.check_against_reference(served, done[2:], 1,
                                                sample=4)
            assert short["long"] == 1 and not cmd.within_limits(
                dict(short, hidden=0.0, decode=0.0))
        finally:
            engine.stop()


def test_each_limit_refuses_and_the_sample_holds_long_sequences():
    from benchmark import serving_cohere2_moe as cmd
    base = {"worst": 0.0, "mean": 0.0, "shortfall": 0.0, "apart": 0.0,
            "hidden": 0.0, "decode": 0.0, "long": cmd.LONG}
    assert cmd.within_limits(base)
    for limit, reading in (("TIE_SIGMA", "worst"), ("MEAN_SIGMA", "mean"),
                           ("PICK_EPSILON", "shortfall"),
                           ("PICKS_APART", "apart"),
                           ("HIDDEN_APART", "hidden"),
                           ("DECODE_APART", "decode")):
        got = dict(base)
        got[reading] = 1.01 * getattr(cmd, limit)
        assert not cmd.within_limits(got), limit
    assert not cmd.within_limits(dict(base, long=cmd.LONG - 1))
    # 40 sequences of which only the last four pass the window: whatever
    # the seed, the sample holds three of them
    done = [(None, [0] * (5000 if i >= 36 else 3000)) for i in range(40)]
    for seed in range(20):
        chosen = cmd.sample_of(done, seed, 12, 4096)
        assert len(chosen) == len(set(chosen)) == 12
        assert sum(i >= 36 for i in chosen) >= 3
    assert len(cmd.sample_of(done[:5], 0, 12, 4096)) == 5
