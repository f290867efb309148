"""Tier-1 observability gate (NOT marked slow — a regression in the
FLOPs walker or the journal schema must fail the suite, not wait for a
perf round to notice the MFU denominator went wrong).

Drives tools/obs_smoke.py in-process: the 2-layer-toy matmul FLOPs match
the hand count, one journaled train step yields parseable JSONL with the
step-event schema, and prometheus_text() renders the minted metrics.
Mirrors the verify_smoke/mem_smoke gate pattern; the
CLI round-trip is `slow`.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_obs_smoke_gate():
    import obs_smoke
    result = obs_smoke.run_smoke()
    assert result["matmul_flops"] == result["hand_counted_flops"], result
    assert result["journal_events"] >= 3, result
    assert "step" in result["journal_kinds"], result
    assert result["prometheus_bytes"] > 0, result


@pytest.mark.slow
def test_obs_smoke_cli_prints_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_smoke.py")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["matmul_flops"] == result["hand_counted_flops"]
