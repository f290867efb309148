"""Tier-1 static-analysis gate (NOT marked slow — a regression in the IR
verifier must fail the suite, not wait for an 8-device deadlock to
rediscover it).

Drives tools/verify_smoke.py in-process: a clean ZeRO-1-sharded training
program verifies with ZERO diagnostics, a seeded rank-conditional
collective (guaranteed mesh deadlock) is caught as V205, a seeded
read-after-donate ordering is caught as V302.
Mirrors the mem_smoke/shard_smoke gate pattern; the CLI round-trip is
`slow` (a fresh interpreter buys no extra coverage over the in-process
gate).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_verify_smoke_gate():
    import verify_smoke
    result = verify_smoke.run_smoke()
    assert result["clean_diagnostics"] == 0, result
    assert "V205" in result["deadlock_codes"], result
    assert "V302" in result["read_after_donate_codes"], result
    assert result["collectives_extracted"] >= 2, result


@pytest.mark.slow
def test_verify_smoke_cli_prints_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "verify_smoke.py")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["clean_diagnostics"] == 0
    assert "V205" in result["deadlock_codes"]
