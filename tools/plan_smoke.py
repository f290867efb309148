"""Fast CPU auto-parallel-planner gate: plan a toy transformer and prove
the plan is strict-clean and ties-or-beats the no-knob baseline.

The cheap canary for the planner tier (tests/test_plan_smoke.py runs it
as a tier-1 test, mirroring mem_smoke/verify_smoke):

  * `static.plan_program` on a bert-tiny training program returns a
    plan whose knob point exists in the trace, was VERIFIED
    (`check_program(level="collective")` clean), and whose predicted
    step time ties or beats the knob-free baseline candidate — the
    argmax property the whole tier rests on;
  * applying the plan (`static.apply_plan`) leaves a program that
    passes `check_program(level="collective")` under strict mode with
    ZERO diagnostics, including the V504 plan-drift check against the
    recorded registry entry.

Prints one JSON line; `value` is the wall time of the walk, reported
and never asserted.

Usage: python tools/plan_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def run_smoke():
    """Run the gate; returns the result dict (AssertionError on any
    planner regression)."""
    # every tier-1 smoke doubles as a verifier sweep (ISSUE 10): armed
    # here, the executor/rewrite first-compile hooks verify for free
    os.environ.setdefault("PADDLE_TPU_VERIFY", "warn")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static
    from paddle_tpu.core.program import _reset_unique_names
    import perf_smoke

    t0 = time.time()

    # -- plan a toy transformer --------------------------------------------
    _reset_unique_names()
    main, startup, loss, _ = perf_smoke.build_bert_tiny()
    plan = static.plan_program(main, startup, world=8, batch=8,
                               knobs={"grad_merge": (1,)})
    assert plan.trace, "plan smoke FAILED: empty candidate trace"
    chosen_in_trace = [c for c in plan.trace if "chosen" in c["verdict"]]
    assert chosen_in_trace, \
        "plan smoke FAILED: chosen knobs not marked in the trace"
    assert plan.predicted_fits, (
        f"plan smoke FAILED: bert-tiny plan predicted over budget "
        f"({plan.predicted_peak_bytes} bytes)")

    # argmax property: the chosen plan ties or beats the knob-free
    # baseline candidate on predicted step time
    baseline = [c for c in plan.trace
                if not c["remat"] and c["dp_shard"] == 0
                and c["grad_merge"] == 1 and not c["ring"]]
    assert baseline, "plan smoke FAILED: no knob-free baseline in trace"
    assert plan.predicted_step_ms <= baseline[0]["step_ms"] + 1e-9, (
        f"plan smoke FAILED: chosen plan ({plan.predicted_step_ms:.4f} ms) "
        f"is WORSE than the no-knob baseline "
        f"({baseline[0]['step_ms']:.4f} ms)")

    # -- applied plan is strict-clean (incl. V504 drift check) -------------
    static.apply_plan(main, startup, plan)
    report = static.check_program(main, level="collective",
                                  startup=startup, fetch_list=[loss])
    assert not report.diagnostics, (
        f"plan smoke FAILED: applied plan not strict-clean:\n"
        f"{report.render()}")
    from paddle_tpu.core.pass_framework import has_applied
    assert has_applied(main, "auto_parallel_plan"), \
        "plan smoke FAILED: plan not recorded in the applied-passes registry"

    wall = time.time() - t0
    return {
        "metric": "plan_smoke_wall_s",
        "value": round(wall, 2),
        "unit": "s",
        "n_candidates": len(plan.trace),
        "chosen_knobs": dict(plan.knobs),
        "predicted_step_ms": round(plan.predicted_step_ms, 4),
        "baseline_step_ms": round(baseline[0]["step_ms"], 4),
    }


if __name__ == "__main__":
    print(json.dumps(run_smoke()))
