"""Tier-1 scanned-window gate (NOT marked slow — a regression in the
commit-tail hoist, the window's dispatch accounting, the seed/counter
phase, or scanned-vs-looped numerics must fail the suite, not wait for
a perf round).

Drives tools/scan_smoke.py in-process: small Adam model under ZeRO-2 x
gradient merge K=4 on the 8-device CPU mesh — the window
splits with exactly one publish allgather per ZeRO bucket in the tail,
K looped dispatches collapse to ONE hoisted `run_steps` dispatch per
window, losses and integer state land bitwise-equal to the looped path
and float32 state within one ulp (XLA:CPU contracts the commit's
multiply-adds differently outside the scan body: tools/scan_smoke.py),
and nothing re-traces after the first window.  The RNG-phase test seals the
ISSUE 16 seed audit with a model whose numerics DEPEND on the per-step
seed (dropout): the scanned window derives micro-step i's seed as
`seed_for_step + i`, so any drift from K looped `run` calls flips the
dropout masks and the bitwise check.  Mirrors the shard_smoke gate
pattern; the CLI round-trip is `slow`.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_scan_smoke_gate():
    import scan_smoke
    result = scan_smoke.run_smoke(windows=2)
    # the whole point: K dispatches -> 1 per window, publish once
    assert result["value"] == result["k"] == 4, result
    assert result["scanned_dispatches"] == result["windows"], result
    assert result["publish_allgathers_per_window"] >= 1, result
    assert result["compiles_after_warmup"] == 0, result
    assert result["persistables_compared"] >= 4, result
    assert result["persistables_max_ulp"] <= 1, result


def _dropout_model(static, layers, k, world):
    """fc tower with DROPOUT — numerics depend on the per-step seed."""
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.distributed.sharding import shard_optimizer_states
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 16])
        y = layers.data("y", [-1, 1])
        h = layers.fc(x, 32, act="relu")
        h = layers.dropout(h, 0.5,
                           dropout_implementation="upscale_in_train")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        static.Adam(learning_rate=1e-2).minimize(loss)
    shard_optimizer_states(main, startup, dp_degree=world, stage=2)
    static.gradient_merge(main, k, startup_program=startup)
    return main, startup, loss


def test_scan_window_rng_counter_and_dispatch_parity():
    """ISSUE 16 satellite: the hoisted window's host accounting — the
    training-step counter advances K per window (so the NEXT step's
    seed matches K looped calls), `_dispatches` advances 1, and a
    seed-sensitive model (dropout) stays bitwise-equal to looped."""
    import jax
    import paddle_tpu.static as static
    from paddle_tpu.static import layers
    from paddle_tpu.distributed.compiled_program import CompiledProgram

    world = len(jax.devices())
    k, windows, batch = 2, 2, 8
    rng = np.random.RandomState(7)
    feeds = [{"x": rng.rand(batch, 16).astype(np.float32),
              "y": rng.rand(batch, 1).astype(np.float32)}
             for _ in range(windows * k)]

    main_l, startup_l, loss_l = _dropout_model(static, layers, k, world)
    cp_l = CompiledProgram(main_l).with_data_parallel(loss_name=loss_l.name)
    exe_l = static.Executor()
    scope_l = static.Scope()
    losses_l = []
    with static.scope_guard(scope_l):
        exe_l.run(startup_l)
        step0 = exe_l._step
        for f in feeds:
            out = exe_l.run(cp_l, feed=f, fetch_list=[loss_l])
            losses_l.append(np.asarray(out[0]))
        assert exe_l._step - step0 == windows * k

    main_s, startup_s, loss_s = _dropout_model(static, layers, k, world)
    cp_s = CompiledProgram(main_s).with_data_parallel(loss_name=loss_s.name)
    exe_s = static.Executor()
    scope_s = static.Scope()
    losses_s = []
    with static.scope_guard(scope_s):
        exe_s.run(startup_s)
        for w in range(windows):
            sfeed = {n: np.stack([feeds[w * k + i][n] for i in range(k)])
                     for n in ("x", "y")}
            step0, d0 = exe_s._step, cp_s._dispatches
            outs = exe_s.run_steps(cp_s, feed=sfeed, fetch_list=[loss_s])
            losses_s.extend(np.asarray(outs[0]))
            # ONE device dispatch, K training steps of counter/RNG phase
            assert cp_s._dispatches - d0 == 1
            assert exe_s._step - step0 == k

    # dropout masks are a function of the micro-step seed: bitwise
    # equality here proves the scanned seed schedule IS the looped one
    for i, (a, b) in enumerate(zip(losses_l, losses_s)):
        assert a.tobytes() == b.tobytes(), (i, a, b)
    assert exe_l._seed_for_step(main_l) == exe_s._seed_for_step(main_s)


@pytest.mark.slow
def test_scan_smoke_cli_prints_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scan_smoke.py"),
         "--windows", "2"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["value"] == 4.0
    assert result["compiles_after_warmup"] == 0
