"""Tentpole coverage for the compile-once hot path (ISSUE 1):

(a) a second run with a ragged final batch causes ZERO new traces
    (shape bucketing serves it from the compiled larger bucket);
(b) bucketed-padded execution is numerically identical to unpadded on
    per-row fetches;
(c) Prefetcher preserves batch order and re-raises worker exceptions at
    the call site;
(d) the persistent cache dir is created and populated.
"""
import os

import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu.static import layers
from paddle_tpu.core import compile_cache


def _simple_program(width=4):
    # width makes the traced HLO distinct per test — JAX's compilation
    # cache has an in-memory layer keyed on the HLO alone, so tests that
    # assert on-disk population need a computation not seen earlier in
    # the process
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 6], dtype="float32")
        h = layers.fc(x, width, act="relu")
        y = layers.fc(h, 3)
        row = layers.reduce_sum(y, dim=1)  # per-row fetch [B]
    return main, startup, y, row


# -- (a) ragged final batch: zero new traces --------------------------------
def test_ragged_final_batch_zero_new_traces():
    main, startup, y, row = _simple_program()
    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(0)
    full = rng.randn(8, 6).astype(np.float32)
    with static.scope_guard(scope):
        exe.run(startup)
        # "epoch 1": steady batches of 8, ragged tail of 5
        exe.run(main, feed={"x": full}, fetch_list=[row])
        warm = exe.cache_stats()
        assert warm["traces"] == 1
        exe.run(main, feed={"x": full[:5]}, fetch_list=[row])
        # "epoch 2": same shapes again
        exe.run(main, feed={"x": full}, fetch_list=[row])
        exe.run(main, feed={"x": full[:5]}, fetch_list=[row])
    stats = exe.cache_stats()
    assert stats["traces"] == warm["traces"], stats
    assert stats["bucket_hits"] >= 2, stats
    assert stats["hits"] == 3, stats


def test_bucket_requires_matching_trailing_dims():
    # a feed with a DIFFERENT trailing dim must not be padded into the
    # wrong executable — it traces fresh.  The [-1, -1] feed here is the
    # exact shape static.check_program lints as V401 (non-leading dynamic
    # dim escapes the bucketing policy): under PADDLE_TPU_VERIFY that
    # warning is EXPECTED — this test exists to pin the retrace the lint
    # predicts.
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, -1], dtype="float32")
        s = layers.reduce_sum(x, dim=1)
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((8, 6), np.float32)},
                fetch_list=[s])
        exe.run(main, feed={"x": np.ones((5, 7), np.float32)},
                fetch_list=[s])
    stats = exe.cache_stats()
    assert stats["traces"] == 2
    assert stats["bucket_hits"] == 0


# -- (b) numerically identical fetches --------------------------------------
def test_bucketed_fetches_match_unpadded():
    main, startup, y, row = _simple_program()
    rng = np.random.RandomState(7)
    full = rng.randn(8, 6).astype(np.float32)
    ragged = full[:3]

    def run_with(policy):
        exe = static.Executor()
        exe.bucket_policy = policy
        scope = static.Scope()
        with static.scope_guard(scope):
            exe.run(startup)
            if policy != "off":
                exe.run(main, feed={"x": full}, fetch_list=[y, row])
            outs = exe.run(main, feed={"x": ragged}, fetch_list=[y, row])
        return exe, outs

    exe_b, bucketed = run_with("existing")
    exe_o, unpadded = run_with("off")
    assert exe_b.cache_stats()["bucket_hits"] == 1
    assert exe_o.cache_stats()["bucket_hits"] == 0
    for got, want in zip(bucketed, unpadded):
        assert got.shape == want.shape  # un-padding restored real batch
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_pow2_policy_cold_compiles_at_bucket():
    # inference-style policy: batch 5 cold-compiles the 8-bucket; batch 3
    # compiles its own cheaper 4-bucket (smallest sufficient pow2 wins);
    # batch 7 reuses the 8-bucket without tracing
    main, startup, y, row = _simple_program()
    exe = static.Executor()
    exe.bucket_policy = "pow2"
    scope = static.Scope()
    rng = np.random.RandomState(1)
    with static.scope_guard(scope):
        exe.run(startup)
        r5 = exe.run(main, feed={"x": rng.randn(5, 6).astype(np.float32)},
                     fetch_list=[row])
        r3 = exe.run(main, feed={"x": rng.randn(3, 6).astype(np.float32)},
                     fetch_list=[row])
        r7 = exe.run(main, feed={"x": rng.randn(7, 6).astype(np.float32)},
                     fetch_list=[row])
    assert r5[0].shape == (5,) and r3[0].shape == (3,) and \
        r7[0].shape == (7,)
    stats = exe.cache_stats()
    assert stats["traces"] == 2, stats
    assert stats["bucket_hits"] == 1, stats


def test_pow2_small_requests_do_not_ride_huge_bucket():
    # batch-16 compiled first must NOT capture a batch-3 stream (5.3x the
    # compute per request) — pow2 compiles the cheap 4-bucket instead
    main, startup, y, row = _simple_program()
    exe = static.Executor()
    exe.bucket_policy = "pow2"
    scope = static.Scope()
    rng = np.random.RandomState(2)
    with static.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": rng.randn(16, 6).astype(np.float32)},
                fetch_list=[row])
        r3 = exe.run(main, feed={"x": rng.randn(3, 6).astype(np.float32)},
                     fetch_list=[row])
    assert r3[0].shape == (3,)
    # second trace = the 4-bucket, memoized for the rest of the stream
    assert exe.cache_stats()["traces"] == 2
    _, (b, target_b) = next(iter(exe._bucket_map.values()))
    assert (b, target_b) == (3, 4)


# -- (c) Prefetcher order + exception propagation ---------------------------
def test_prefetcher_preserves_order():
    from paddle_tpu.reader import Prefetcher
    src = [{"i": np.full((2, 2), k, np.float32)} for k in range(50)]
    out = list(Prefetcher(iter(src), depth=2))
    assert len(out) == 50
    for k, feed in enumerate(out):
        assert float(np.asarray(feed["i"])[0, 0]) == k


def test_prefetcher_reraises_worker_exception_in_order():
    from paddle_tpu.reader import Prefetcher

    def source():
        yield np.zeros(2)
        yield np.ones(2)
        raise ValueError("exploded in worker")

    pf = Prefetcher(source(), depth=2)
    got = []
    with pytest.raises(ValueError, match="exploded in worker"):
        for item in pf:
            got.append(item)
    # both good batches were delivered BEFORE the error surfaced
    assert len(got) == 2


def test_prefetcher_close_unblocks_worker():
    from paddle_tpu.reader import Prefetcher

    def endless():
        k = 0
        while True:
            yield np.full(4, k)
            k += 1

    pf = Prefetcher(endless(), depth=1)
    next(pf)
    pf.close()  # must not deadlock on the full queue
    pf.close()  # idempotent


def test_prefetcher_casts_int64_when_x64_off():
    import jax
    from paddle_tpu.reader import place_feed
    placed = place_feed({"ids": np.arange(4, dtype=np.int64)})
    want = np.int64 if jax.config.jax_enable_x64 else np.int32
    assert np.asarray(placed["ids"]).dtype == want


# -- (d) persistent cache dir created and populated -------------------------
def test_persistent_cache_dir_populated(tmp_path):
    d = str(tmp_path / "xla_cache")
    assert compile_cache.initialize(d, min_compile_time_s=0.0,
                                   force=True) == d
    assert os.path.isdir(d)
    before = compile_cache.persistent_entries()
    main, startup, y, row = _simple_program(width=11)
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((4, 6), np.float32)},
                fetch_list=[row])
    assert compile_cache.persistent_entries() > before
    stats = exe.cache_stats()
    assert stats["persistent_dir"] == d
    # restore the default so later tests don't write into tmp_path
    compile_cache.initialize(force=True)


def test_default_dir_is_repo_jax_cache(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR unset: one fixed path inside the
    checkout, derived from the package location."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.REPO_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert compile_cache.initialize(force=True) == \
        compile_cache.REPO_CACHE_DIR
    assert compile_cache.cache_stats()["persistent_dir"] == \
        compile_cache.REPO_CACHE_DIR


def test_unwritable_dir_raises(tmp_path):
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("x")
    with pytest.raises(OSError):
        compile_cache.initialize(str(blocker / "cache"), force=True)
    compile_cache.initialize(force=True)


_ENV_DIR_SCRIPT = """
import os, sys
import numpy as np
import jax
want = os.environ["JAX_COMPILATION_CACHE_DIR"]
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
import paddle_tpu.static as static
from paddle_tpu.static import layers
from paddle_tpu.core import compile_cache
updates = []
real = jax.config.update
def spy(name, val):
    updates.append(name)
    return real(name, val)
jax.config.update = spy
assert compile_cache.initialize() == want
assert "jax_compilation_cache_dir" not in updates, updates
assert jax.config.jax_compilation_cache_dir == want
main, startup = static.Program(), static.Program()
with static.program_guard(main, startup):
    x = layers.data("x", [-1, 6], dtype="float32")
    y = layers.reduce_sum(layers.fc(x, 17, act="relu"), dim=1)
exe = static.Executor()
exe.run(startup)
exe.run(main, feed={"x": np.ones((4, 6), np.float32)}, fetch_list=[y])
assert jax.config.jax_compilation_cache_dir == want
stats = exe.cache_stats()
assert stats["persistent_dir"] == want, stats
assert stats["persistent_entries"] > 0, stats
print("ENV_DIR_OK")
"""


def test_env_placed_dir_is_left_to_jax(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set (fresh interpreter): initialize()
    sets no directory in code, reads JAX's own back, entries land there."""
    import subprocess
    import sys
    d = str(tmp_path / "placed")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=d, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo)
    out = subprocess.run([sys.executable, "-c", _ENV_DIR_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ENV_DIR_OK" in out.stdout
    assert any(f.endswith("-cache") for f in os.listdir(d))


# -- executor close / cache_stats contracts ---------------------------------
def test_close_idempotent_keeps_disk_cache(tmp_path):
    d = str(tmp_path / "xla_cache2")
    compile_cache.initialize(d, min_compile_time_s=0.0, force=True)
    main, startup, y, row = _simple_program(width=13)
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((4, 6), np.float32)},
                fetch_list=[row])
    entries = compile_cache.persistent_entries()
    assert entries > 0
    exe.close()
    exe.close()  # idempotent
    assert exe._cache == {}
    # on-disk cache untouched by close()
    assert compile_cache.persistent_entries() == entries
    # counters survive close
    assert exe.cache_stats()["traces"] == 1
    compile_cache.initialize(force=True)
