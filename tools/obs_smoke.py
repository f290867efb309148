"""Fast CPU observability gate: exact FLOPs on a hand-countable toy,
one journaled train step, non-empty Prometheus exposition — in seconds.

The cheap canary for the telemetry tier (tests/test_obs_smoke.py runs it
as a tier-1 test, mirroring verify_smoke/mem_smoke):

  * `static.analyze_flops` on a 2-layer toy MLP matches the matmul
    FLOPs counted by hand from the layer shapes (fwd 2·M·K·N, bwd 2×) —
    the walker's arithmetic, not just its plumbing;
  * one training step with the run journal armed produces parseable
    JSONL whose `step` event carries the step/wall-time schema, and a
    heartbeat file with the same step;
  * `monitor.prometheus_text()` renders the train.* metrics that step
    minted (TYPE lines present, non-empty).

Prints one JSON line; `value` is the gate's wall time, reported and
never asserted.

Usage: python tools/obs_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

IN, H1, H2 = 16, 32, 8
BATCH = 4


def build_toy():
    """2-layer MLP whose matmul FLOPs are countable on one hand."""
    import paddle_tpu.static as static
    from paddle_tpu.static import layers
    from paddle_tpu.core.program import _reset_unique_names
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, IN])
        y = layers.data("y", [-1, 1])
        h = layers.fc(x, H1, act="relu")
        h = layers.fc(h, H2, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        static.SGD(learning_rate=1e-2).minimize(loss)
    return main, startup, loss


def hand_counted_matmul_flops(batch: int) -> int:
    """fwd: 2·B·K·N per fc; bwd (dX + dW): 2× fwd."""
    fwd = 2 * batch * (IN * H1 + H1 * H2 + H2 * 1)
    return fwd * 3


def run_smoke():
    # every tier-1 smoke doubles as a verifier sweep (ISSUE 10):
    # armed here, the first-compile hook and the rewrite-pass
    # self-checks verify every program this gate builds, for free
    os.environ.setdefault("PADDLE_TPU_VERIFY", "warn")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu.static as static
    from paddle_tpu.core import monitor
    from paddle_tpu import observability as obs

    t0 = time.time()

    # -- FLOPs walker vs hand count -----------------------------------------
    main, startup, loss = build_toy()
    rep = static.analyze_flops(main, batch=BATCH)
    want = hand_counted_matmul_flops(BATCH)
    got = rep["by_class"].get("matmul", 0)
    assert got == want, (
        f"obs smoke FAILED: walker matmul FLOPs {got} != hand-counted "
        f"{want} on the 2-layer toy")
    assert rep["phase_flops"]["forward"] > 0
    assert rep["phase_flops"]["backward"] > rep["phase_flops"]["forward"]

    # -- one journaled train step -------------------------------------------
    jdir = tempfile.mkdtemp(prefix="obs_smoke_journal_")
    obs.set_journal_dir(jdir)
    try:
        exe, scope = static.Executor(), static.Scope()
        rng = np.random.RandomState(0)
        feed = {"x": rng.rand(BATCH, IN).astype(np.float32),
                "y": rng.rand(BATCH, 1).astype(np.float32)}
        with static.scope_guard(scope):
            exe.run(startup)
            # the first step compiles; a step's wall time is the interval
            # to the next observation, so it takes two
            for _ in range(2):
                exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        obs.set_journal_dir(None)
    journals = obs.read_rank_journals(jdir)
    assert 0 in journals and journals[0], (
        f"obs smoke FAILED: no parseable journal under {jdir}")
    kinds = [e["kind"] for e in journals[0]]
    assert "run_start" in kinds and "step" in kinds, kinds
    step_ev = [e for e in journals[0] if e["kind"] == "step"][-1]
    for key in ("run_id", "rank", "seq", "t", "step", "wall_ms"):
        assert key in step_ev, (key, step_ev)
    seqs = [e["seq"] for e in journals[0]]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs), seqs

    # -- Prometheus exposition ----------------------------------------------
    text = monitor.prometheus_text()
    assert text.strip(), "obs smoke FAILED: empty prometheus_text()"
    assert "# TYPE train_steps_total counter" in text, text[:400]
    assert "train_step_ms" in text, text[:400]

    wall = time.time() - t0
    return {
        "metric": "obs_smoke_wall_s",
        "value": round(wall, 2),
        "matmul_flops": got,
        "hand_counted_flops": want,
        "total_flops": rep["total_flops"],
        "journal_events": len(journals[0]),
        "journal_kinds": sorted(set(kinds)),
        "prometheus_bytes": len(text),
    }


def main():
    print(json.dumps(run_smoke()))


if __name__ == "__main__":
    main()
