"""Fast CPU memory-accounting gate: bert-tiny, estimator + remat, hard
assertions.

The cheap canary for the memory-for-throughput tier
(tests/test_mem_smoke.py runs it as a tier-1 test, mirroring
perf_smoke/ckpt_smoke): builds bert-tiny twice — plain and with
FLAGS_recompute=always auto-selected layer checkpoints — and asserts
the contract the HBM accounting rests on:

  * the estimator walks BOTH programs (the estimate phase's wall time
    is reported as `estimate_wall_s`, never asserted);
  * remat's walked activation peak shows the expected reduction vs the
    plain program (the rewrite actually cuts live ranges, not just adds
    barrier ops);
  * the rewritten program still honors the compile-once contract: a
    short training run traces at most the two steady signatures and
    NEVER re-traces after warmup (remat must not poison the step cache).

Prints one JSON line; correctness never depends on throughput.

Usage: python tools/mem_smoke.py [--steps 4]
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_smoke(steps: int = 4, batch: int = 8):
    """Run the gate; returns the result dict (AssertionError on an
    estimator or retrace regression)."""
    # every tier-1 smoke doubles as a verifier sweep (ISSUE 10):
    # armed here, the first-compile hook and the rewrite-pass
    # self-checks verify every program this gate builds, for free
    os.environ.setdefault("PADDLE_TPU_VERIFY", "warn")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.core.program import _reset_unique_names
    import perf_smoke

    # -- estimate phase: must stay compile-time cheap -----------------------
    t_est = time.time()
    _reset_unique_names()
    main_plain, _, _, _ = perf_smoke.build_bert_tiny()
    _reset_unique_names()
    set_flags({"recompute": "always"})
    try:
        main_remat, startup_remat, loss_remat, _ = \
            perf_smoke.build_bert_tiny()
    finally:
        set_flags({"recompute": ""})
    plain = static.analyze_program(main_plain, batch=batch)
    remat = static.analyze_program(main_remat, batch=batch)
    est_wall = time.time() - t_est

    n_barriers = sum(1 for op in main_remat.global_block().ops
                     if op.type == "optimization_barrier")
    assert n_barriers >= 1, \
        "mem smoke FAILED: FLAGS_recompute=always inserted no barriers"
    assert remat["activation_peak_bytes"] < plain["activation_peak_bytes"], (
        f"mem smoke FAILED: remat activation peak "
        f"{remat['activation_peak_bytes']} not below plain "
        f"{plain['activation_peak_bytes']}")
    assert remat["persistable_bytes"] == plain["persistable_bytes"], \
        "mem smoke FAILED: remat changed the persistable footprint"

    # -- retrace gate: the rewritten program keeps compile-once -------------
    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(0)
    idt = np.int64 if jax.config.jax_enable_x64 else np.int32
    vocab = 512

    def make_batch(b):
        return {"ids": rng.randint(0, vocab, (b, 32)).astype(idt),
                "labels": rng.randint(0, vocab, (b, 32, 1)).astype(idt)}

    with static.scope_guard(scope):
        exe.run(startup_remat)
        warm = make_batch(batch)
        exe.run(main_remat, feed=warm, fetch_list=[loss_remat])
        exe.run(main_remat, feed=warm, fetch_list=[])
        warm_traces = exe.cache_stats()["traces"]
        for _ in range(steps):
            exe.run(main_remat, feed=warm, fetch_list=[])
        # ragged tail must bucket into the compiled executable
        exe.run(main_remat, feed=make_batch(max(1, batch - 1)),
                fetch_list=[])
        out = exe.run(main_remat, feed=warm, fetch_list=[loss_remat])
        assert np.isfinite(np.asarray(out[0])).all()
    stats = exe.cache_stats()
    new_traces = stats["traces"] - warm_traces
    assert new_traces == 0, (
        f"mem smoke FAILED: {new_traces} recompile(s) after warmup on the "
        f"remat program (stats {stats})")

    return {
        "metric": "mem_smoke_remat_peak_reduction_pct",
        "value": round((1.0 - remat["activation_peak_bytes"]
                        / plain["activation_peak_bytes"]) * 100, 1),
        "estimate_wall_s": round(est_wall, 2),
        "plain_peak_bytes": plain["peak_bytes"],
        "remat_peak_bytes": remat["peak_bytes"],
        "barriers": n_barriers,
        "traces": stats["traces"],
        "traces_after_warmup": new_traces,
    }


def main():
    steps = 4
    if "--steps" in sys.argv:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])
    print(json.dumps(run_smoke(steps=steps)))


if __name__ == "__main__":
    main()
