"""Tier-1 ZeRO-1 sharding gate (NOT marked slow — a regression in the
bucket rewrite, the shard shapes, the estimator's world-size slot
accounting, or a sharding-induced retrace must fail the suite, not wait
for a perf round).

Drives tools/shard_smoke.py in-process: small Adam model sharded for the
8-device CPU mesh — rewrite applied, slot shapes correct
and genuinely rank-sharded, slot bytes ≈ 1/8, zero post-warmup
recompiles.  Mirrors the mem_smoke/ckpt_smoke gate pattern; the CLI
round-trip is `slow` (a fresh interpreter + jit warmup buys no extra
coverage over the in-process gate — run it in perf rounds).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_shard_smoke_gate():
    import shard_smoke
    result = shard_smoke.run_smoke(steps=2)
    # the whole point: ~8x smaller optimizer slots per chip
    assert result["value"] >= 4, result
    assert result["compiles_after_warmup"] == 0, result
    assert result["buckets"] >= 1, result
    assert result["sharded_slot_bytes"] < result["plain_slot_bytes"], result


@pytest.mark.slow
def test_shard_smoke_cli_prints_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "shard_smoke.py"),
         "--steps", "2"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["compiles_after_warmup"] == 0
    assert result["value"] >= 4
