"""A throw-away benchmark root for the CPU rehearsals: the real drivers and
per-layer readers, copied, beside tiny configurations, mixes and cells that
exist only here.  Nothing under the real `benchmark/` is edited — which is
the point: a configuration, a mix, a cell or a per-layer metric is added by
adding files and manifest entries."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_BERT = {
    "name": "bert-tiny", "hidden_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 2, "intermediate_size": 128, "vocab_size": 128,
    "max_position_embeddings": 16,
    "trainer": {"compute_dtype": "bfloat16"}}
TINY_GPT = {
    "name": "gpt-tiny", "n_embd": 32, "n_layer": 2, "n_head": 2,
    "n_inner": None, "n_positions": 64, "vocab_size": 96,
    "bos_token_id": 95, "eos_token_id": 95,
    "engine": {"page_tokens": 4, "max_context": 64, "max_slots_cap": 4,
               "hbm_bytes": 4 << 20}}
TRAIN_CHECKS = {"step0_loss_rtol": 0.02, "reference_chunk": 4,
                "loss_step": 12, "loss_margin": 0.02}
MIXES = {
    "tiny_scan": {"driver": "train_job", "seq_len": 16, "batch_per_chip": 4,
                  "steps_per_dispatch": 4, "data_parallel": False,
                  "zipf_exponent": 1.0},
    "tiny_dp": {"driver": "train_job", "seq_len": 16, "batch_per_chip": 4,
                "steps_per_dispatch": 1, "data_parallel": True,
                "zipf_exponent": 1.0},
    "tiny_chat": {
        "driver": "serve_open",
        "prompt_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                          "min": 3, "max": 12, "stratify": 4},
        "new_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                       "min": 2, "max": 6},
        "max_total_tokens": 16, "drain_s": 60, "client_threads": 8,
        "late_limit_s": 1.0},
    "tiny_score": {
        "driver": "serve_closed", "callers": 2,
        "prompt_tokens": {"dist": "lognormal", "median": 20, "sigma": 0.4,
                          "min": 9, "max": 40},
        "new_tokens": {"dist": "fixed", "value": 1}},
}
CELLS = {
    "bert-tiny.tiny_scan": {"config": "bert-tiny", "traffic": "tiny_scan",
                            "chips": 1, "checks": TRAIN_CHECKS,
                            "reports": ["train_tok_per_s_chip"]},
    "bert-tiny.tiny_dp": {"config": "bert-tiny", "traffic": "tiny_dp",
                          "chips": 4, "checks": TRAIN_CHECKS,
                          "reports": ["train_tok_per_s_chip"]},
    # held back, like the cell it rehearses: `make` gives it the real
    # cell's `held_back` entries and leaves it out of the manifest
    "gpt-tiny.tiny_chat": {"config": "gpt-tiny", "traffic": "tiny_chat",
                           "chips": 1, "rate_per_s": 4.0,
                           "reports": ["serve_s_per_answer_token"]},
    "gpt-tiny.tiny_score": {"config": "gpt-tiny", "traffic": "tiny_score",
                            "chips": 1, "reports": ["serve_closed_latency_p50_s"]},
}


def make(tmp, extra_per_layer=()):
    """Write the throw-away root under `tmp` and return its path."""
    root = os.path.join(str(tmp), "root")
    bdir = os.path.join(root, "benchmark")
    for kind in ("drivers", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", kind),
                        os.path.join(bdir, kind))
    for kind in ("configs", "cells", "traffic"):
        os.makedirs(os.path.join(bdir, kind))

    def dump(kind, name, obj):
        with open(os.path.join(bdir, kind, name + ".json"), "w") as f:
            json.dump(obj, f)

    for cfg in (TINY_BERT, TINY_GPT):
        dump("configs", cfg["name"], cfg)
    for name, mix in MIXES.items():
        dump("traffic", name, mix)
    with open(os.path.join(REPO, "benchmark", "cells",
                           "gpt2-medium.chat_open.json")) as f:
        held = json.load(f)["held_back"]
    cells = dict(CELLS)
    cells["gpt-tiny.tiny_chat"] = dict(
        CELLS["gpt-tiny.tiny_chat"], held_back=dict(
            held, workload={"name": "gpt-tiny.tiny_chat",
                            "config": "gpt-tiny", "traffic": "tiny_chat",
                            "chips": 1},
            end_to_end=[{k: v for k, v in m.items() if k != "workloads"}
                        for m in held["end_to_end"]]))
    for name, cell in cells.items():
        dump("cells", name, cell)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [
        {"name": c["name"], "file": f"benchmark/configs/{c['name']}.json"}
        for c in (TINY_BERT, TINY_GPT)]
    manifest["workloads"] = [
        {"name": n, "config": c["config"], "traffic": c["traffic"],
         "chips": c["chips"]} for n, c in cells.items()
        if "held_back" not in c]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        m.pop("workloads", None)
    manifest["per_layer"] += list(extra_per_layer)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
