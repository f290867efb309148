"""Tier-1 layout-analysis gate (NOT marked slow — a regression in the
sharding-propagation analyzer must fail the suite, not wait for a 4×2
mesh run to compute garbage).

Drives tools/layout_smoke.py in-process: a clean Megatron col→row
tensor-parallel program infers its full SPMD layout with ZERO
diagnostics and an exactly-ring-priced mp reshard table; a seeded
dropped row-parallel allreduce (partial sums read as complete) is
caught as V602 with op provenance.  Mirrors the
verify_smoke gate pattern; the CLI round-trip is `slow`.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_layout_smoke_gate():
    import layout_smoke
    result = layout_smoke.run_smoke()
    assert result["clean_diagnostics"] == 0, result
    assert "V602" in result["seeded_codes"], result
    assert result["mp_reshard_bytes"] > 0, result


@pytest.mark.slow
def test_layout_smoke_cli_prints_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "layout_smoke.py")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["clean_diagnostics"] == 0
    assert "V602" in result["seeded_codes"]
