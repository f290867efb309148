"""Fast CPU ZeRO sharding gate: rewrite applies, shard shapes correct,
zero post-warmup retraces, estimator shows the slot/param reduction.

The cheap canary for the sharded data-parallel tier
(tests/test_shard_smoke.py runs it as a tier-1 test, mirroring
mem_smoke/ckpt_smoke): builds a small Adam model, applies
`shard_optimizer_states` for the 8-device CPU mesh, and asserts the
contracts the tier rests on:

  * the rewrite actually applied — per-param optimizer ops collapsed
    into bucketed c_reducescatter → sharded update → c_allgather chains;
  * shard shapes are correct — bucket slots declared at the padded
    global length, divisible by the dp world, marked ``dp_shard``, and
    on-mesh each rank materializes exactly 1/world of the slot;
  * the HBM estimator's world-size accounting reports the slot
    reduction (≤ plain/world + one bucket of padding);
  * the compile-once contract holds — a short mesh training run compiles
    ONE executable and never re-traces after warmup;
  * the ZeRO-3 leg: full parameter sharding packs the params into
    dp_shard buckets at ~1/world per chip, just-in-time allgathers are
    present in forward (and the stage-1 publish is gone), a short mesh
    run trains finite with zero post-warmup retraces.

Prints one JSON line; correctness never depends on throughput.

Usage: python tools/shard_smoke.py [--steps 4]
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WORLD = 8


def run_smoke(steps: int = 4, batch: int = 16):
    """Run the gate; returns the result dict (AssertionError on a
    sharding or retrace regression)."""
    # every tier-1 smoke doubles as a verifier sweep (ISSUE 10):
    # armed here, the first-compile hook and the rewrite-pass
    # self-checks verify every program this gate builds, for free
    os.environ.setdefault("PADDLE_TPU_VERIFY", "warn")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={WORLD}"
        ).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static
    from paddle_tpu.static import layers
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.distributed.compiled_program import CompiledProgram
    from paddle_tpu.distributed.sharding import shard_optimizer_states

    t0 = time.time()
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 16])
        y = layers.data("y", [-1, 1])
        h = layers.fc(x, 32, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        static.Adam(learning_rate=1e-2).minimize(loss)

    plain = static.analyze_program(main, batch=batch)
    n_adam_before = sum(1 for op in main.global_block().ops
                        if op.type == "adam")
    plan = shard_optimizer_states(main, startup, dp_degree=WORLD)
    sharded = static.analyze_program(main, batch=batch)

    # -- rewrite applied ----------------------------------------------------
    types = [op.type for op in main.global_block().ops]
    n_rs = types.count("c_reducescatter")
    n_ag = types.count("c_allgather")
    assert plan.buckets and n_rs == n_ag == plan.n_buckets, (
        f"shard smoke FAILED: expected {plan.n_buckets} "
        f"reduce-scatter/allgather pairs, got {n_rs}/{n_ag}")
    n_adam_after = types.count("adam")
    assert n_adam_after == plan.n_buckets < n_adam_before, (
        f"shard smoke FAILED: per-param adam ops not coalesced "
        f"({n_adam_before} -> {n_adam_after}, {plan.n_buckets} buckets)")

    # -- shard shapes -------------------------------------------------------
    block = main.global_block()
    for b in plan.buckets:
        assert b["padded_len"] % WORLD == 0 and \
            b["shard_len"] * WORLD == b["padded_len"], b
        for name in b["slots"].values():
            v = block.var(name)
            assert v.persistable and v.attrs.get("dp_shard") == WORLD \
                and tuple(v.shape) == (b["padded_len"],), (name, v.shape)
            sv = startup.global_block().var(name)
            assert tuple(sv.shape) == (b["padded_len"],), name

    # -- estimator slot reduction ------------------------------------------
    one_bucket = max(b["padded_len"] for b in plan.buckets) * 4
    assert sharded["optimizer_slot_bytes"] <= \
        plain["optimizer_slot_bytes"] // WORLD + one_bucket, (
        f"shard smoke FAILED: sharded slot bytes "
        f"{sharded['optimizer_slot_bytes']} not <= plain/{WORLD} "
        f"({plain['optimizer_slot_bytes'] // WORLD}) + bucket")

    # wall times are reported, never asserted (shared CPU cores)
    rewrite_wall = time.time() - t0

    # -- compile-once on the mesh ------------------------------------------
    compiled = CompiledProgram(main).with_data_parallel(loss_name=loss.name)
    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(0)

    def feed():
        return {"x": rng.rand(batch, 16).astype(np.float32),
                "y": rng.rand(batch, 1).astype(np.float32)}

    with static.scope_guard(scope):
        exe.run(startup)
        exe.run(compiled, feed=feed(), fetch_list=[loss])
        warm_compiles = len(compiled._cache)
        for _ in range(steps):
            out = exe.run(compiled, feed=feed(), fetch_list=[loss])
        assert np.isfinite(np.asarray(out[0])).all()
        # rank-sharded slot: the global array is [padded], each device
        # holds padded/WORLD elements
        sname = next(iter(plan.buckets[0]["slots"].values()))
        slot = scope.get(sname)
        shards = getattr(slot, "addressable_shards", None)
        if shards:
            per_rank = {tuple(s.data.shape) for s in shards}
            assert per_rank == {(plan.buckets[0]["shard_len"],)}, per_rank
    new_compiles = len(compiled._cache) - warm_compiles
    assert new_compiles == 0, (
        f"shard smoke FAILED: {new_compiles} recompile(s) after warmup "
        f"on the sharded program")

    # -- ZeRO-3 leg: full parameter sharding --------------------------------
    t3 = time.time()
    _reset_unique_names()
    main3, startup3 = static.Program(), static.Program()
    with static.program_guard(main3, startup3):
        x = layers.data("x", [-1, 16])
        y = layers.data("y", [-1, 1])
        h = layers.fc(x, 32, act="relu")
        pred = layers.fc(h, 1)
        loss3 = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        static.Adam(learning_rate=1e-2).minimize(loss3)
    plain3 = static.analyze_program(main3, batch=batch)
    plan3 = shard_optimizer_states(main3, startup3, dp_degree=WORLD,
                                   stage=3)
    sharded3 = static.analyze_program(main3, batch=batch)
    assert plan3.stage == 3 and plan3.param_bucket_names(), plan3
    blk3 = main3.global_block()
    # per-chip param bytes ≈ total/8: every param is packed into a
    # dp_shard bucket the walker charges 1/world (+ pow2 padding)
    pbytes = sum(blk3.var(n).shape[0] * 4
                 for n in plan3.param_bucket_names())
    raw_pbytes = sum(b["raw_len"] * 4 for b in plan3.buckets
                     if b.get("param_bucket"))
    assert sharded3["parameter_bytes"] <= \
        raw_pbytes // WORLD + len(plan3.buckets) * WORLD * 4, (
        f"shard smoke FAILED: zero3 per-chip param bytes "
        f"{sharded3['parameter_bytes']} not ~1/{WORLD} of {raw_pbytes}")
    assert sharded3["persistable_bytes"] < plain3["persistable_bytes"] // 4
    # JIT allgather present in FORWARD, no stage-1 publish
    from paddle_tpu.core.program import OpRole as _OpRole
    roles = [op.attrs.get("zero_role") for op in blk3.ops
             if op.type == "c_allgather"]
    assert roles.count("gather_fwd") == len(plan3.buckets) and \
        "publish" not in roles, roles
    first_mul = next(i for i, op in enumerate(blk3.ops)
                     if op.type == "mul")
    first_gather = next(i for i, op in enumerate(blk3.ops)
                        if op.attrs.get("zero_role") == "gather_fwd")
    assert first_gather < first_mul
    rewrite3_wall = time.time() - t3

    compiled3 = CompiledProgram(main3).with_data_parallel(
        loss_name=loss3.name)
    exe3 = static.Executor()
    scope3 = static.Scope()
    with static.scope_guard(scope3):
        exe3.run(startup3)
        exe3.run(compiled3, feed=feed(), fetch_list=[loss3])
        warm3 = len(compiled3._cache)
        for _ in range(steps):
            out3 = exe3.run(compiled3, feed=feed(), fetch_list=[loss3])
        assert np.isfinite(np.asarray(out3[0])).all()
        pb = scope3.get(plan3.param_bucket_names()[0])
        shards3 = getattr(pb, "addressable_shards", None)
        if shards3:
            b0 = next(b for b in plan3.buckets if b.get("param_bucket"))
            per_rank = {tuple(s.data.shape) for s in shards3}
            assert per_rank == {(b0["shard_len"],)}, per_rank
    new3 = len(compiled3._cache) - warm3
    assert new3 == 0, (
        f"shard smoke FAILED: {new3} recompile(s) after warmup on the "
        f"zero3 program")

    return {
        "metric": "shard_smoke_slot_reduction_x",
        "value": round(plain["optimizer_slot_bytes"]
                       / max(1, sharded["optimizer_slot_bytes"]), 2),
        "rewrite_wall_s": round(rewrite_wall, 2),
        "wall_s": round(time.time() - t0, 2),
        "buckets": plan.n_buckets,
        "plain_slot_bytes": plain["optimizer_slot_bytes"],
        "sharded_slot_bytes": sharded["optimizer_slot_bytes"],
        "compiles_after_warmup": new_compiles,
        "zero3_param_reduction_x": round(
            plain3["parameter_bytes"]
            / max(1, sharded3["parameter_bytes"]), 2),
        "zero3_buckets": plan3.n_buckets,
        "zero3_compiles_after_warmup": new3,
        "zero3_rewrite_wall_s": round(rewrite3_wall, 2),
    }


def main():
    steps = 4
    if "--steps" in sys.argv:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])
    print(json.dumps(run_smoke(steps=steps)))


if __name__ == "__main__":
    main()
