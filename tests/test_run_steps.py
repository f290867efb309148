"""Executor.run_steps: K training steps scanned inside one jitted
dispatch (device-resident training loop).

TPU-first redesign of the reference's in-runtime trainer loop
(paddle/fluid/framework/trainer.h:1 MultiTrainer::Run — the C++ side
loops batches without returning to Python); here the loop is compiled
onto the device with lax.scan so one dispatch covers K optimizer steps.
"""
import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu.static import layers


def _build(lr=0.1, seed=0):
    main, startup = static.Program(), static.Program()
    main.random_seed = startup.random_seed = seed
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 8])
        y = layers.data("y", [-1, 1])
        h = layers.fc(x, 16, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        static.SGD(learning_rate=lr).minimize(loss)
    return main, startup, loss


def _data(k, batch=4):
    rng = np.random.RandomState(7)
    xs = rng.rand(k, batch, 8).astype(np.float32)
    ys = xs.sum(2, keepdims=True).astype(np.float32)
    return xs, ys


def test_run_steps_matches_sequential():
    K = 6
    xs, ys = _data(K)

    main, startup, loss = _build()
    exe, sc = static.Executor(), static.Scope()
    seq_losses = []
    with static.scope_guard(sc):
        exe.run(startup)
        for i in range(K):
            (lv,) = exe.run(main, feed={"x": xs[i], "y": ys[i]},
                            fetch_list=[loss])
            seq_losses.append(float(lv))

    main2, startup2, loss2 = _build()
    exe2, sc2 = static.Executor(), static.Scope()
    with static.scope_guard(sc2):
        exe2.run(startup2)
        (stacked,) = exe2.run_steps(main2, feed={"x": xs, "y": ys},
                                    fetch_list=[loss2])
    assert stacked.shape == (K,)
    np.testing.assert_allclose(stacked, seq_losses, rtol=1e-4, atol=1e-5)


def test_run_steps_state_carries_between_calls():
    """Two successive run_steps calls continue training (scope state
    advances on device), and the loss keeps falling."""
    K = 8
    xs, ys = _data(2 * K)
    main, startup, loss = _build()
    exe, sc = static.Executor(), static.Scope()
    with static.scope_guard(sc):
        exe.run(startup)
        (l1,) = exe.run_steps(main, feed={"x": xs[:K], "y": ys[:K]},
                              fetch_list=[loss])
        (l2,) = exe.run_steps(main, feed={"x": xs[K:], "y": ys[K:]},
                              fetch_list=[loss])
    assert float(l2[-1]) < float(l1[0])


def test_run_steps_validates_feed():
    main, startup, loss = _build()
    exe, sc = static.Executor(), static.Scope()
    xs, ys = _data(3)
    with static.scope_guard(sc):
        exe.run(startup)
        with pytest.raises(ValueError):
            exe.run_steps(main, feed={}, fetch_list=[loss])
        with pytest.raises(ValueError):
            exe.run_steps(main, feed={"x": xs, "y": ys[:2]},
                          fetch_list=[loss])
        with pytest.raises(ValueError, match="scalar"):
            exe.run_steps(main, feed={"x": xs, "y": np.float32(0.5)},
                          fetch_list=[loss])


def test_run_steps_honors_check_nan_inf():
    """FLAGS_check_nan_inf raises on the scanned path like run() does."""
    from paddle_tpu.core.flags import set_flags
    main, startup, loss = _build(lr=1e6)  # divergent lr -> inf/nan fast
    exe, sc = static.Executor(), static.Scope()
    xs, ys = _data(6)
    set_flags({"FLAGS_check_nan_inf": True})
    try:
        with static.scope_guard(sc):
            exe.run(startup)
            with pytest.raises(RuntimeError, match="check_nan_inf"):
                exe.run_steps(main, feed={"x": xs, "y": ys},
                              fetch_list=[loss])
    finally:
        set_flags({"FLAGS_check_nan_inf": False})


def test_run_steps_short_final_chunk_no_scan_retrace():
    """A K' < K final chunk is served step-by-step through run()'s cache
    (at most ONE single-step trace, reused forever) instead of retracing
    the whole scan — and numerics match the all-sequential walk."""
    K = 6
    xs, ys = _data(K + 2)
    main, startup, loss = _build()
    exe, sc = static.Executor(), static.Scope()
    with static.scope_guard(sc):
        exe.run(startup)
        (l1,) = exe.run_steps(main, feed={"x": xs[:K], "y": ys[:K]},
                              fetch_list=[loss])
        t0 = exe.cache_stats()["traces"]
        (l2,) = exe.run_steps(main, feed={"x": xs[K:], "y": ys[K:]},
                              fetch_list=[loss])
        assert l2.shape == (2,)
        assert exe.cache_stats()["traces"] - t0 <= 1  # single-step sig
        t1 = exe.cache_stats()["traces"]
        exe.run_steps(main, feed={"x": xs[K:], "y": ys[K:]},
                      fetch_list=[loss])
        assert exe.cache_stats()["traces"] == t1  # steady thereafter

    main2, startup2, loss2 = _build()
    exe2, sc2 = static.Executor(), static.Scope()
    seq = []
    with static.scope_guard(sc2):
        exe2.run(startup2)
        for i in range(K + 2):
            (lv,) = exe2.run(main2, feed={"x": xs[i], "y": ys[i]},
                             fetch_list=[loss2])
            seq.append(float(lv))
    np.testing.assert_allclose(np.concatenate([l1, l2]), seq,
                               rtol=1e-4, atol=1e-5)


def test_run_steps_ragged_batch_buckets_into_compiled_scan():
    """Same K but a smaller PER-STEP batch pads up into the compiled
    stacked bucket (zero new traces) and the stacked fetches un-pad."""
    K = 4
    xs, ys = _data(K)
    main, startup, loss = _build()
    exe, sc = static.Executor(), static.Scope()
    per_row = next(v for v in main.global_block().vars.values()
                   if v.shape == (-1, 1) and not v.is_data
                   and not v.persistable)
    with static.scope_guard(sc):
        exe.run(startup)
        exe.run_steps(main, feed={"x": xs, "y": ys},
                      fetch_list=[loss, per_row])
        t0 = exe.cache_stats()["traces"]
        b0 = exe.cache_stats()["bucket_hits"]
        lv, pred_rows = exe.run_steps(
            main, feed={"x": xs[:, :3], "y": ys[:, :3]},
            fetch_list=[loss, per_row])
        assert exe.cache_stats()["traces"] == t0, "scan retraced"
        assert exe.cache_stats()["bucket_hits"] == b0 + 1
        assert lv.shape == (K,)  # scalar loss: nothing to un-pad
        # per-row fetch un-padded from the bucket batch 4 back to 3
        assert pred_rows.shape[:2] == (K, 3), pred_rows.shape
