"""Tier-1 memory-accounting gate (NOT marked slow — a regression in the
HBM estimator or a remat-induced retrace must fail the suite, not wait
for a perf round).

Drives tools/mem_smoke.py in-process: bert-tiny estimated with and
without the FLAGS_recompute=always rewrite, the expected
activation-peak reduction, and zero post-warmup retraces on the
rewritten program.  Mirrors the perf_smoke/ckpt_smoke gate pattern;
the CLI round-trip is `slow` (a fresh interpreter + jit warmup buys no
extra coverage over the in-process gate — run it in perf rounds).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_mem_smoke_gate():
    import mem_smoke
    result = mem_smoke.run_smoke(steps=2)
    assert result["value"] > 0, result            # peak actually shrank
    assert result["traces_after_warmup"] == 0, result
    assert result["barriers"] >= 1, result
    assert result["remat_peak_bytes"] < result["plain_peak_bytes"], result


@pytest.mark.slow
def test_mem_smoke_cli_prints_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mem_smoke.py"),
         "--steps", "2"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["traces_after_warmup"] == 0
    assert result["value"] > 0
