"""Fast CPU 2-D-planner gate: the planner must pick a 4×2 dp×tp plan
UNPROMPTED — no ``variants=`` hand-feed of the winner — for a shape
where pure dp is walker-infeasible, and the applied plan must train on
the 8-device CPU mesh with zero post-warmup retraces.

The cheap canary for the 2-D planner tier (tests/test_tp_plan_smoke.py
runs it as a tier-1 test, mirroring plan_smoke/mem_smoke):

  1. build a toy transformer LM (plain, tp=1) and plan it once with the
     tp axis DISABLED to learn the best pure-dp walked peak under the
     same knob set;
  2. set the HBM budget strictly BETWEEN the best tp candidate's peak
     and the best pure-dp peak (derived at runtime from the trace, so
     the gate tracks the walker instead of baking in byte counts);
  3. re-plan with ``model_config=`` only — the tp variants are
     auto-generated through the tensor_parallel builders, never
     hand-fed — and require the chosen plan to be dp×tp = 4×2 with
     every pure-dp candidate walker-infeasible;
  4. apply the plan to the winning build variant, require
     ``check_program(level="all")`` strict-clean (the V6xx layout level
     included), and train it on the real 4×2 CPU mesh: finite
     decreasing loss, ZERO post-warmup retraces.

Prints one JSON line; `value` is the wall time of the walk, reported and
never asserted.

Usage: python tools/tp_plan_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# the toy shape: activations dominate (batch×seq×4h intermediates), so
# tensor parallelism cuts what remat+ZeRO alone cannot
GEOM = dict(vocab_size=128, hidden=64, num_layers=2, num_heads=4,
            seq_len=32, learning_rate=1e-2)
WORLD, BATCH = 8, 16
# axes held fixed for determinism and speed: the gate is about the tp
# axis, and the budget below is derived under this same knob set
KNOBS = {"batch": (BATCH,), "grad_merge": (1,), "zero_stage": (1,)}


def _build_base():
    import paddle_tpu.static as static
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.models import build_transformer_lm
    _reset_unique_names()
    main, startup, loss, _ = build_transformer_lm(
        vocab_size=GEOM["vocab_size"], hidden=GEOM["hidden"],
        num_layers=GEOM["num_layers"], num_heads=GEOM["num_heads"],
        seq_len=GEOM["seq_len"])
    with static.program_guard(main, startup):
        static.Adam(learning_rate=GEOM["learning_rate"]).minimize(loss)
    return main, startup, loss


def run_smoke():
    """Run the gate; returns the result dict (AssertionError on any
    2-D-planner regression)."""
    os.environ.setdefault("PADDLE_TPU_VERIFY", "warn")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu.static as static

    t0 = time.time()

    # -- 1. learn the pure-dp frontier under a loose budget ----------------
    main, startup, _ = _build_base()
    probe = static.plan_program(
        main, startup, world=WORLD, hbm_budget=1 << 50,
        knobs=dict(KNOBS, tp_degree=(0, 2)), model_config=GEOM,
        verify=False)
    dp_peaks = [c["peak_bytes"] for c in probe.trace
                if not c["tp_degree"] and c["peak_bytes"] > 0]
    tp_peaks = [c["peak_bytes"] for c in probe.trace
                if c["tp_degree"] == 2 and c["peak_bytes"] > 0]
    assert dp_peaks and tp_peaks, "probe trace missing candidates"
    best_dp, best_tp = min(dp_peaks), min(tp_peaks)
    assert best_tp < best_dp, (
        f"tp plan smoke FAILED: the tp=2 build no longer walks below "
        f"the best pure-dp candidate ({best_tp} >= {best_dp}) — the "
        f"tp HBM division regressed")
    # the fits verdict grants the calibrated XLA-remat slack, so the
    # budget sits just under best_dp/slack: every pure-dp candidate
    # misses even WITH the slack, while the tp walk (strictly below
    # best_dp) still clears it
    from paddle_tpu.static.memory_analysis import XLA_REMAT_SLACK
    budget = int(best_dp / XLA_REMAT_SLACK) - 1

    # -- 2/3. the real search: tp variants auto-generated, tight budget ----
    main, startup, _ = _build_base()
    plan = static.plan_program(
        main, startup, world=WORLD, hbm_budget=budget,
        knobs=dict(KNOBS), model_config=GEOM)
    assert plan.predicted_fits, (
        f"tp plan smoke FAILED: nothing fits at the derived budget "
        f"({budget} B)\n{plan.render_table()}")
    assert plan.knobs["tp_degree"] == 2, (
        f"tp plan smoke FAILED: planner chose "
        f"{plan.knobs} instead of the 4×2 dp×tp plan\n"
        f"{plan.render_table()}")
    for c in plan.trace:
        if not c["tp_degree"]:
            assert not c["fits"], (
                f"tp plan smoke FAILED: pure-dp candidate fits at the "
                f"tight budget — the gate lost its premise: {c}")
    chosen = [c for c in plan.trace if "chosen" in c["verdict"]]
    assert chosen and chosen[0]["verdict"].startswith("verified"), chosen
    # the per-axis wire split must price the mp ring at its OWN degree
    per_axis = plan.predicted_wire_bytes_per_axis
    assert per_axis.get("mp", 0) > 0, per_axis

    # -- 4. apply + train the winner on the real 4×2 mesh ------------------
    from paddle_tpu.distributed.compiled_program import (CompiledProgram,
                                                         BuildStrategy)
    win_main, win_startup, loss_name = plan.build_variants[2]
    static.apply_plan(win_main, win_startup, plan)
    report = static.check_program(win_main, level="all",
                                  startup=win_startup)
    assert report.ok, (
        "tp plan smoke FAILED: applied 2-D plan not strict-clean:\n"
        + report.render())
    assert "V504" not in report.codes()

    bs = BuildStrategy()
    bs.tensor_parallel_degree = 2
    compiled = CompiledProgram(win_main).with_data_parallel(
        loss_name=loss_name, build_strategy=bs)
    assert dict(compiled._get_mesh().shape) == {"dp": 4, "tp": 2}

    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(0)
    seq = GEOM["seq_len"]
    feed = {
        "ids": rng.randint(0, GEOM["vocab_size"],
                           (BATCH, seq)).astype(np.int64),
        "pos": np.tile(np.arange(seq), (BATCH, 1)).astype(np.int64),
        "labels": rng.randint(0, GEOM["vocab_size"],
                              (BATCH, seq, 1)).astype(np.int64),
    }
    losses = []
    with static.scope_guard(scope):
        exe.run(win_startup)
        for i in range(6):
            out = exe.run(compiled, feed=feed, fetch_list=[loss_name])
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
            if i == 0:
                warm = len(compiled._cache)
        assert len(compiled._cache) == warm, (
            "tp plan smoke FAILED: recompile after warmup "
            f"({len(compiled._cache)} != {warm})")
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses

    wall = time.time() - t0
    return {
        "metric": "tp_plan_smoke_wall_s",
        "value": round(wall, 2),
        "unit": "s",
        "chosen_knobs": dict(plan.knobs),
        "budget_bytes": int(budget),
        "best_dp_peak_bytes": int(best_dp),
        "best_tp_peak_bytes": int(best_tp),
        "wire_bytes_per_axis": dict(per_axis),
        "losses": [round(v, 4) for v in losses],
        "n_candidates": len(plan.trace),
    }


if __name__ == "__main__":
    print(json.dumps(run_smoke()))
