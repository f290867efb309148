"""Tier-1 auto-parallel-planner gate (NOT marked slow — a regression in
the planner's argmax or its strict-clean contract must fail the suite,
not wait for a perf round).

Drives tools/plan_smoke.py in-process: `static.plan_program` on a toy
transformer returns a verified plan that ties or beats the knob-free
baseline on predicted step time, the applied plan is
`check_program(level="collective")`-clean with the plan on record
(V504 drift surface).  Mirrors the mem_smoke/verify_smoke gate pattern.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_plan_smoke_gate():
    import plan_smoke
    result = plan_smoke.run_smoke()
    assert result["n_candidates"] >= 4, result    # the lattice was real
    assert result["predicted_step_ms"] <= result["baseline_step_ms"], result


@pytest.mark.slow
def test_plan_smoke_cli():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "plan_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"metric": "plan_smoke_wall_s"' in out.stdout
