"""Fast CPU scanned-window gate: K->1 dispatches, ONE publish per
window, parity with the looped path (losses and integer state bit for
bit, float32 state to one ulp), zero post-warmup retraces.

The cheap canary for the scanned micro-step hot path
(tests/test_scan_smoke.py runs it as a tier-1 test, mirroring
shard_smoke/mem_smoke): builds a small Adam model under ZeRO-2 x
gradient merge K on the 8-device CPU mesh and asserts the contracts the
tier rests on:

  * the window SPLITS — `split_commit_tail` finds a hoistable commit
    tail; the tail holds exactly one publish allgather per ZeRO bucket
    and the scan body holds none (the wire the hoist deletes);
  * dispatch collapse — K looped `Executor.run` calls become ONE
    `Executor.run_steps` device dispatch per window, and the compiled
    cache entry is the HOISTED variant (cache key carries the flag);
  * numerics — per-micro-step losses and integer state (the gm
    counter) match the looped path bit for bit after the same feeds;
    float32 persistables (params, bucketed master state) to one ulp.
    The two programs are the same arithmetic in HLO
    (`0.9 * m + 0.025 * acc` for Adam's first moment), but XLA:CPU's
    LLVM back end contracts a different one of the two multiplies into
    the add's FMA when the commit sits outside the scan body, so one
    rounding moves: the first moment differs by 1 ulp after the second
    window, and a parameter after the third.  With
    `--xla_backend_optimization_level=0` (no contraction) every
    persistable is bit-equal through three windows;
  * the host-side step counter and RNG phase stay aligned — a scanned
    window advances `_dispatches` by 1 but the training-step counter by
    K, so a following looped step lands on the same seed either way;
  * compile-once — after the first window, further windows never
    re-trace.

Prints one JSON line; correctness never depends on throughput.

Usage: python tools/scan_smoke.py [--windows 2]
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WORLD = 8
GM_K = 4


def _build(static, layers, k):
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.distributed.sharding import shard_optimizer_states
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 16])
        y = layers.data("y", [-1, 1])
        h = layers.fc(x, 32, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        static.Adam(learning_rate=1e-2).minimize(loss)
    plan = shard_optimizer_states(main, startup, dp_degree=WORLD, stage=2)
    static.gradient_merge(main, k, startup_program=startup)
    return main, startup, loss, plan


def run_smoke(windows: int = 2, batch: int = 8):
    """Run the gate; returns the result dict (AssertionError on a
    hoist, parity, or retrace regression)."""
    os.environ.setdefault("PADDLE_TPU_VERIFY", "warn")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={WORLD}"
        ).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static
    from paddle_tpu.static import layers, collective_sequence
    from paddle_tpu.distributed.compiled_program import CompiledProgram
    from paddle_tpu.distributed.scan_window import split_commit_tail

    t0 = time.time()
    k = GM_K
    main_l, startup_l, loss_l, _ = _build(static, layers, k)
    main_s, startup_s, loss_s, zplan = _build(static, layers, k)

    # -- the window splits, and the publish wire lives ONLY in the tail --
    split = split_commit_tail(main_s)
    assert split is not None and split.k == k, split
    tail_pub = [e for e in collective_sequence(split.tail)
                if e.get("zero_role") == "publish"]
    body_pub = [e for e in collective_sequence(split.body)
                if e.get("zero_role") == "publish"]
    assert len(tail_pub) == zplan.n_buckets and not body_pub, (
        f"scan smoke FAILED: publish allgathers tail={len(tail_pub)} "
        f"body={len(body_pub)}, want {zplan.n_buckets}/0 — the hoist "
        f"would not delete the masked re-publishes")
    rewrite_wall = time.time() - t0

    # identical per-micro-step feeds for both paths
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.rand(batch, 16).astype(np.float32),
              "y": rng.rand(batch, 1).astype(np.float32)}
             for _ in range(windows * k)]

    # -- looped path: K dispatches per window -------------------------------
    cp_l = CompiledProgram(main_l).with_data_parallel(loss_name=loss_l.name)
    exe_l = static.Executor()
    scope_l = static.Scope()
    losses_l = []
    with static.scope_guard(scope_l):
        exe_l.run(startup_l)
        d0 = cp_l._dispatches
        for f in feeds:
            out = exe_l.run(cp_l, feed=f, fetch_list=[loss_l])
            losses_l.append(np.asarray(out[0]))
        looped_disp = cp_l._dispatches - d0
    assert looped_disp == windows * k, (looped_disp, windows * k)

    # -- scanned path: ONE hoisted dispatch per window ----------------------
    cp_s = CompiledProgram(main_s).with_data_parallel(loss_name=loss_s.name)
    exe_s = static.Executor()
    scope_s = static.Scope()
    losses_s = []
    with static.scope_guard(scope_s):
        exe_s.run(startup_s)
        d0 = cp_s._dispatches
        warm = None
        for w in range(windows):
            sfeed = {n: np.stack([feeds[w * k + i][n] for i in range(k)])
                     for n in ("x", "y")}
            outs = exe_s.run_steps(cp_s, feed=sfeed, fetch_list=[loss_s])
            losses_s.extend(np.asarray(outs[0]))
            if warm is None:
                warm = len(cp_s._cache)
        scanned_disp = cp_s._dispatches - d0
        retraces = len(cp_s._cache) - warm
    assert scanned_disp == windows, (scanned_disp, windows)
    assert retraces == 0, (
        f"scan smoke FAILED: {retraces} recompile(s) after the first "
        f"window on the scanned program")
    hoisted_keys = [key for key in cp_s._cache
                    if key[0] == "steps" and key[1]]
    assert hoisted_keys, (
        "scan smoke FAILED: no HOISTED cache entry — run_steps fell "
        "back to the unhoisted scan (gate: splittable window, K %% "
        "gm_k == 0, PADDLE_TPU_SCAN_HOIST unset)")

    # -- parity: losses and integer state bit for bit, float32 to 1 ulp -----
    assert len(losses_l) == len(losses_s) == windows * k
    for i, (a, b) in enumerate(zip(losses_l, losses_s)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), (
            f"scan smoke FAILED: micro-step {i} loss differs "
            f"(looped {np.asarray(a)!r} vs scanned {np.asarray(b)!r})")
    blk = main_l.global_block()
    n_state = max_ulp = 0
    for name, v in blk.vars.items():
        if not v.persistable:
            continue
        a, b = scope_l.get(name), scope_s.get(name)
        if a is None or b is None:
            continue
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.float32:
            max_ulp = max(max_ulp, int(np.testing.assert_array_max_ulp(
                a, b, maxulp=1).max()))
        else:
            assert a.tobytes() == b.tobytes(), (
                f"scan smoke FAILED: persistable {name!r} ({a.dtype}) "
                f"differs after {windows * k} steps: {a!r} vs {b!r}")
        n_state += 1
    assert n_state >= 4, f"only {n_state} persistables compared"

    # -- host counter / RNG phase stay window-aligned -----------------------
    seed_l = exe_l._seed_for_step(main_l)
    seed_s = exe_s._seed_for_step(main_s)
    assert seed_l == seed_s, (
        f"scan smoke FAILED: RNG phase diverged — a looped step after "
        f"{windows * k} steps would seed {seed_l}, a post-window step "
        f"{seed_s}")

    return {
        "metric": "scan_smoke_dispatch_reduction_x",
        "value": round(looped_disp / max(1, scanned_disp), 2),
        "k": k,
        "windows": windows,
        "looped_dispatches": int(looped_disp),
        "scanned_dispatches": int(scanned_disp),
        "publish_allgathers_per_window": len(tail_pub),
        "persistables_compared": n_state,
        "persistables_max_ulp": max_ulp,
        "compiles_after_warmup": int(retraces),
        "rewrite_wall_s": round(rewrite_wall, 2),
        "wall_s": round(time.time() - t0, 2),
    }


def main():
    windows = 2
    if "--windows" in sys.argv:
        windows = int(sys.argv[sys.argv.index("--windows") + 1])
    print(json.dumps(run_smoke(windows=windows)))


if __name__ == "__main__":
    main()
