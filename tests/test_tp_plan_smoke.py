"""Tier-1 2-D-planner gate (NOT marked slow — a regression in the tp
lattice axis, the per-axis wire pricing, the tp HBM division, or the
layout-level candidate gating must fail the suite, not wait for a perf
round).

Drives tools/tp_plan_smoke.py in-process: the planner must pick a 4×2
dp×tp plan UNPROMPTED (tp variants auto-generated from a model config,
never hand-fed) for a shape where every pure-dp candidate is
walker-infeasible, the applied plan must be
`check_program(level="all")`-clean, and the winning build must train on
the real 8-device 4×2 CPU mesh with zero post-warmup retraces.
Mirrors the plan_smoke/mem_smoke gate pattern.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_tp_plan_smoke_gate():
    import tp_plan_smoke
    result = tp_plan_smoke.run_smoke()
    assert result["chosen_knobs"]["tp_degree"] == 2, result
    # the per-axis wire split priced BOTH rings (mp at its own degree)
    assert result["wire_bytes_per_axis"].get("mp", 0) > 0, result
    assert result["wire_bytes_per_axis"].get("dp", 0) > 0, result
    # the premise held: the tp walk is strictly below the pure-dp floor
    assert result["best_tp_peak_bytes"] < result["best_dp_peak_bytes"]
    assert result["losses"][-1] < result["losses"][0], result


@pytest.mark.slow
def test_tp_plan_smoke_cli():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tp_plan_smoke.py")],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    assert '"metric": "tp_plan_smoke_wall_s"' in out.stdout
