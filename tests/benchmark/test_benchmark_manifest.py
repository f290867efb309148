"""BENCHMARK.json and the files it names, held to the contract's limits
that can be checked without a chip."""
import importlib.util
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return _json("BENCHMARK.json")


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["configs"]) <= 24
    assert 2 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128


def test_names_are_plain_and_used_once(manifest):
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in manifest[key]]
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    for e in manifest["configs"] + manifest["workloads"]:
        assert len(e["why"]) <= 200, e["name"]
    for dirpath, _, files in os.walk(os.path.join(REPO, "benchmark")):
        if "__pycache__" in dirpath:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), REPO)
            assert PATH.match(rel), rel


def test_every_configuration_states_its_source_and_cuts(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["name"] in used
        assert c["source"].startswith("https://")
        assert c["file"].startswith("benchmark/")
        cfg = _json(c["file"])
        for key in ("source", "reduced", "assumed", "departures",
                    "deployment", "reference"):
            assert key in cfg, (c["name"], key)
        assert cfg["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(REPO, cfg["reference"]))
    bert = _json("benchmark/configs/bert-base.json")
    assert (bert["hidden_size"], bert["num_hidden_layers"],
            bert["num_attention_heads"], bert["intermediate_size"],
            bert["vocab_size"], bert["max_position_embeddings"]) == \
        (768, 12, 12, 3072, 30522, 512)
    gpt = _json("benchmark/configs/gpt2-medium.json")
    assert (gpt["n_embd"], gpt["n_layer"], gpt["n_head"], gpt["vocab_size"],
            gpt["n_positions"]) == (1024, 24, 16, 50257, 1024)


def test_every_cell_has_its_files_and_metrics(manifest):
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in manifest["workloads"])
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for w in manifest["workloads"]:
        cell = _json("benchmark", "cells", w["name"] + ".json")
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        mix = _json("benchmark", "traffic", w["traffic"] + ".json")
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "drivers", mix["driver"] + ".py"))
        assert cell["reports"] and set(cell["reports"]) <= set(e2e)
        for name in cell["reports"]:
            assert w["name"] in e2e[name].get("workloads", [w["name"]])
        layer = [m for m in manifest["per_layer"]
                 if m["moves"] in cell["reports"]
                 and w["name"] in m.get("workloads", [w["name"]])]
        assert layer, f"{w['name']} reports no per-layer metric"
    for m in manifest["end_to_end"]:
        for name in m.get("workloads", []):
            cell = _json("benchmark", "cells", name + ".json")
            assert m["name"] in cell["reports"] or m["name"] == "setup_s"


def _reader(name):
    reader = name.split(".")[-1]
    path = os.path.join(REPO, "benchmark", "layer_metrics", reader + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + reader, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_every_per_layer_metric_has_its_reader(manifest):
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and m["source"] in SOURCES
        mod = _reader(m["name"])
        assert (mod.LAYER, mod.SOURCE, mod.UNIT, mod.BETTER) == \
            (m["layer"], m["source"], m["unit"], m["better"]), m["name"]
        assert callable(mod.reduce)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_a_cell_outside_the_manifest_is_held_back_and_says_why(manifest):
    """Every file under cells/ is a manifest cell or a held-back one that
    carries its reason, what lifts it, and the manifest entries it will
    need — so that admitting it is adding data."""
    listed = {w["name"] for w in manifest["workloads"]}
    pairs = {(w["config"], w["traffic"]) for w in manifest["workloads"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    cells = sorted(f[:-5] for f in os.listdir(
        os.path.join(REPO, "benchmark", "cells")))
    assert listed <= set(cells)
    held = [c for c in cells if c not in listed]
    used_mixes = set()
    for name in cells:
        cell = _json("benchmark", "cells", name + ".json")
        used_mixes.add(cell["traffic"])
        if name not in held:
            continue        # admitted since: its block is history
        assert "held_back" in cell, name
        block = cell["held_back"]
        assert len(block["why"]) > 100 and len(block["admit_when"]) > 50
        w = block["workload"]
        assert NAME.match(w["name"]) and w["name"] == name
        assert len(w["why"]) <= 200 and w["chips"] == 1
        assert (w["config"], w["traffic"], w["chips"]) == \
            (cell["config"], cell["traffic"], cell["chips"])
        assert (w["config"], w["traffic"]) not in pairs
        assert w["config"] in {c["name"] for c in manifest["configs"]}
        mix = _json("benchmark", "traffic", w["traffic"] + ".json")
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "drivers", mix["driver"] + ".py"))
        new = {m["name"] for m in block["end_to_end"]}
        assert set(cell["reports"]) <= e2e | new
        for m in block["end_to_end"]:
            assert NAME.match(m["name"]) and "bound" not in m  # measured
            assert m["source"] in ("host_clock", "device_trace")
        assert block["per_layer"]
        for m in block["per_layer"]:
            assert NAME.match(m["name"]) and m["moves"] in cell["reports"]
            mod = _reader(m["name"])
            assert (mod.LAYER, mod.SOURCE, mod.UNIT, mod.BETTER) == \
                (m["layer"], m["source"], m["unit"], m["better"]), m["name"]
    mixes = {f[:-5] for f in os.listdir(
        os.path.join(REPO, "benchmark", "traffic"))}
    assert mixes == used_mixes
    readers = {f[:-3] for f in os.listdir(os.path.join(
        REPO, "benchmark", "layer_metrics")) if f.endswith(".py")}
    named = {m["name"].split(".")[-1] for m in manifest["per_layer"]}
    for name in held:
        named |= {m["name"].split(".")[-1] for m in _json(
            "benchmark", "cells", name + ".json")["held_back"]["per_layer"]}
    assert readers == named       # no reader that nothing reads
