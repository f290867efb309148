"""ZeRO sharded data parallelism, stages 1-3 (distributed/sharding.py).

The contracts this tier rests on, all on the virtual 8-device CPU mesh
(conftest.py):
  * numerical equivalence — plain-DP and ZeRO-1/2/3 training produce
    the same loss trajectory and parameters (allclose atol=1e-6 fp32)
    for Adam/AdamW with and without AMP, gradient_merge and remat;
  * the bucketed c_reducescatter / c_allgather round-trip with pow2
    padding un-pads correctly at the kernel level;
  * optimizer slots (stage 1), gradient accumulators (stage 2 under
    gradient_merge) and parameters (stage 3) are genuinely sharded:
    per-chip bytes ≈ 1/8 of the replicated footprint (memory_analysis
    world-size accounting), and stage 3 emits just-in-time per-bucket
    forward/backward allgathers with NO publish allgather;
  * insert_grad_allreduce is idempotent and ZeRO-aware (no double
    reduction, regression for the fleet double-apply bug — including
    the stage-2 shard-accumulator producer chain);
  * the degenerate single-chip path (collectives → identity) matches
    plain training bit-for-bit, including run_steps donated-state
    threading;
  * checkpoint layout converters round-trip across STAGE changes
    (zero3 → zero1 → plain) via unshard_state/reshard_state.

Tier-1 keeps the acceptance bar (Adam 20 steps at stages 1 and 3,
zero2+gm) and the fullest composition (AdamW+AMP+gradient_merge); the
rest of the equivalence matrix is marked `slow` — each is two more
whole-mesh compiles and the tier-1 suite runs against a hard 870 s
timeout (ROADMAP).  Perf rounds run the full matrix.
"""
import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu.static import layers
from paddle_tpu import amp
from paddle_tpu.core.program import _reset_unique_names
from paddle_tpu.distributed.compiled_program import (
    CompiledProgram, insert_grad_allreduce)
from paddle_tpu.distributed.sharding import (
    shard_optimizer_states, ShardingPlan, unshard_state, reshard_state)

WORLD = 8


def _build(opt_fn=None, use_amp=False):
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 8])
        y = layers.data("y", [-1, 1])
        h = layers.fc(x, 16, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        opt = (opt_fn or (lambda: static.Adam(learning_rate=1e-2)))()
        if use_amp:
            opt = amp.decorate(opt, init_loss_scaling=1.0,
                               use_dynamic_loss_scaling=False,
                               dest_dtype="bfloat16")
        opt.minimize(loss)
    return main, startup, loss


def _feeds(n, batch=16, seed=0):
    rng = np.random.RandomState(seed)
    return [{"x": rng.rand(batch, 8).astype(np.float32),
             "y": rng.rand(batch, 1).astype(np.float32)}
            for _ in range(n)]


def _params_of(main, scope, plan=None):
    """Trainable params as host arrays — through the stage-3 layout
    converter when the params live packed in dp_shard buckets."""
    if plan is not None and getattr(plan, "stage", 1) >= 3 and \
            plan.param_bucket_names():
        from paddle_tpu.static.executor import _persistable_names
        state = {n: np.asarray(scope.get(n))
                 for n in _persistable_names(main)
                 if scope.get(n) is not None}
        unpacked = unshard_state(state, plan)
        return {p.name: unpacked[p.name] for p in main.all_parameters()
                if p.name in unpacked}
    return {p.name: np.asarray(scope.get(p.name))
            for p in main.all_parameters() if scope.get(p.name) is not None}


def _train_mesh(main, startup, loss, steps, plan=None):
    compiled = CompiledProgram(main).with_data_parallel(loss_name=loss.name)
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        losses = [float(exe.run(compiled, feed=f, fetch_list=[loss])[0])
                  for f in _feeds(steps)]
        params = _params_of(main, scope, plan)
    return losses, params, scope


def _assert_equiv(opt_fn=None, use_amp=False, gm=0, steps=8, atol=1e-6,
                  stage=1):
    runs = []
    for shard in (False, True):
        main, startup, loss = _build(opt_fn, use_amp)
        plan = None
        if shard:
            plan = shard_optimizer_states(main, startup, dp_degree=WORLD,
                                          stage=stage)
            assert plan.buckets and plan.stage == stage
        if gm:
            static.gradient_merge(main, gm, startup)
        runs.append(_train_mesh(main, startup, loss, steps, plan)[:2])
    (l0, p0), (l1, p1) = runs
    np.testing.assert_allclose(l0, l1, atol=atol, rtol=atol)
    for k in p0:
        np.testing.assert_allclose(p0[k], p1[k], atol=atol, rtol=atol,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# numerical equivalence, 8-device mesh
# ---------------------------------------------------------------------------
def test_adam_equivalence_20_steps():
    # the acceptance bar: ≥20 steps, fp32, allclose atol=1e-6
    _assert_equiv(lambda: static.Adam(learning_rate=1e-2), steps=20)


def test_zero3_adam_equivalence_20_steps():
    # the stage-3 acceptance bar: params sharded + JIT gathers, ≥20
    # steps, allclose atol=1e-6 to the fully replicated run
    _assert_equiv(lambda: static.Adam(learning_rate=1e-2), steps=20,
                  stage=3)


def test_zero2_gm_equivalence_20_steps():
    # stage 2 is only distinct under gradient_merge: the accumulator is
    # the 1/N reduce-scattered shard, numerics must still match plain+gm
    _assert_equiv(lambda: static.Adam(learning_rate=1e-2), steps=20,
                  gm=2, stage=2)


@pytest.mark.slow
def test_zero3_adamw_equivalence_20_steps():
    _assert_equiv(lambda: static.AdamW(learning_rate=1e-2,
                                       weight_decay=0.01), steps=20,
                  stage=3)


@pytest.mark.slow
def test_zero2_adamw_gm_equivalence_20_steps():
    _assert_equiv(lambda: static.AdamW(learning_rate=1e-2,
                                       weight_decay=0.01), steps=20, gm=2,
                  stage=2)


@pytest.mark.slow
def test_zero3_gm_equivalence():
    _assert_equiv(lambda: static.Adam(learning_rate=1e-2), gm=2, stage=3)


@pytest.mark.slow
def test_zero3_amp_equivalence():
    _assert_equiv(lambda: static.Adam(learning_rate=1e-2), use_amp=True,
                  stage=3)


@pytest.mark.slow
def test_zero2_amp_gm_falls_back_and_matches():
    # AMP interposes unscale between backward and the buckets, so the
    # sharded accumulator is unsound — gradient_merge must fall back to
    # full-size accumulators (with a warning) and numerics must hold
    import warnings as _w
    runs = []
    for shard in (False, True):
        main, startup, loss = _build(use_amp=True)
        if shard:
            shard_optimizer_states(main, startup, dp_degree=WORLD, stage=2)
            with _w.catch_warnings(record=True) as rec:
                _w.simplefilter("always")
                static.gradient_merge(main, 2, startup)
            assert any("falling back" in str(x.message) for x in rec)
        else:
            static.gradient_merge(main, 2, startup)
        runs.append(_train_mesh(main, startup, loss, 8)[:2])
    (l0, p0), (l1, p1) = runs
    np.testing.assert_allclose(l0, l1, atol=1e-6, rtol=1e-6)
    for k in p0:
        np.testing.assert_allclose(p0[k], p1[k], atol=1e-6, rtol=1e-6,
                                   err_msg=k)


@pytest.mark.slow
def test_adam_amp_equivalence():
    _assert_equiv(lambda: static.Adam(learning_rate=1e-2), use_amp=True)


@pytest.mark.slow
def test_adamw_equivalence():
    _assert_equiv(lambda: static.AdamW(learning_rate=1e-2,
                                       weight_decay=0.01))


def test_adamw_amp_gradient_merge_equivalence():
    _assert_equiv(lambda: static.AdamW(learning_rate=1e-2,
                                       weight_decay=0.01),
                  use_amp=True, gm=2)


@pytest.mark.slow
def test_adam_gradient_merge_equivalence():
    _assert_equiv(lambda: static.Adam(learning_rate=1e-2), gm=2)


@pytest.mark.slow
def test_momentum_and_sgd_equivalence():
    _assert_equiv(lambda: static.Momentum(learning_rate=1e-2,
                                          momentum=0.9), steps=6)
    _assert_equiv(lambda: static.SGD(learning_rate=1e-2), steps=6)


@pytest.mark.slow
def test_recompute_composes_with_sharding():
    """FLAGS_recompute-style activation checkpointing rewrites
    forward/backward; sharding rewrites the optimize tail — composed,
    training still matches plain DP."""
    def build_remat():
        _reset_unique_names()
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = layers.data("x", [-1, 8])
            y = layers.data("y", [-1, 1])
            h1 = layers.fc(x, 16, act="relu")
            h2 = layers.fc(h1, 16, act="relu")
            pred = layers.fc(h2, 1)
            loss = layers.mean(
                layers.square(layers.elementwise_sub(pred, y)))
            opt = static.RecomputeOptimizer(
                static.Adam(learning_rate=1e-2))
            opt._set_checkpoints([h1])
            opt.minimize(loss)
        return main, startup, loss

    runs = []
    for shard in (False, True):
        main, startup, loss = build_remat()
        # the rewrite replays the h1->h2 segment inside backward: the
        # relu forward runs once more than the plain program's two
        assert sum(1 for op in main.global_block().ops
                   if op.type == "relu") == 3
        if shard:
            shard_optimizer_states(main, startup, dp_degree=WORLD)
        runs.append(_train_mesh(main, startup, loss, 6)[:2])
    (l0, p0), (l1, p1) = runs
    np.testing.assert_allclose(l0, l1, atol=1e-6)
    for k in p0:
        np.testing.assert_allclose(p0[k], p1[k], atol=1e-6, err_msg=k)


@pytest.mark.slow
def test_lamb_equivalence_global_norms():
    # LAMB's trust ratio needs GLOBAL ‖p‖/‖r‖ — the sharded kernel psums
    # the squared norms, so per-param numbers match the unsharded update
    # (reduction-order wiggle only)
    _assert_equiv(lambda: static.Lamb(learning_rate=1e-2), steps=6,
                  atol=1e-5)


# ---------------------------------------------------------------------------
# degenerate single-chip + run_steps threading
# ---------------------------------------------------------------------------
def test_single_device_degenerate_matches_plain():
    runs = []
    for shard in (False, True):
        main, startup, loss = _build()
        if shard:
            shard_optimizer_states(main, startup, dp_degree=WORLD)
        exe = static.Executor()
        scope = static.Scope()
        with static.scope_guard(scope):
            exe.run(startup)
            losses = [float(exe.run(main, feed=f, fetch_list=[loss])[0])
                      for f in _feeds(6)]
            params = {p.name: np.asarray(scope.get(p.name))
                      for p in main.all_parameters()}
        runs.append((losses, params))
    (l0, p0), (l1, p1) = runs
    np.testing.assert_allclose(l0, l1, atol=1e-6)
    for k in p0:
        np.testing.assert_allclose(p0[k], p1[k], atol=1e-6, err_msg=k)


def test_run_steps_threads_sharded_slots():
    runs = []
    for shard in (False, True):
        main, startup, loss = _build()
        if shard:
            shard_optimizer_states(main, startup, dp_degree=WORLD)
        exe = static.Executor()
        scope = static.Scope()
        fs = _feeds(5)
        sfeed = {k: np.stack([f[k] for f in fs]) for k in fs[0]}
        with static.scope_guard(scope):
            exe.run(startup)
            out = exe.run_steps(main, feed=sfeed, fetch_list=[loss])
        runs.append(np.asarray(out[0]))
    np.testing.assert_allclose(runs[0], runs[1], atol=1e-6)


# ---------------------------------------------------------------------------
# kernel-level reduce-scatter / allgather round trip with pow2 padding
# ---------------------------------------------------------------------------
def test_reducescatter_allgather_roundtrip_pow2_pad():
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.ops.registry import get_op_info, OpContext

    rs = get_op_info("c_reducescatter").kernel
    ag = get_op_info("c_allgather").kernel
    devs = np.array(jax.devices()[:WORLD])
    mesh = Mesh(devs, ("dp",))
    raw = np.arange(10, dtype=np.float32)  # 10 does not divide 8
    padded_len = -(-raw.size // WORLD) * WORLD  # 16 (pow2 world → pow2 pad)
    padded = np.pad(raw, (0, padded_len - raw.size))

    def step(x):
        ctx = OpContext(mesh_axes=("dp",), dist_info={0: "dp"})
        shard = rs({"X": x}, {"ring_id": 0}, ctx)["Out"]
        full = ag({"X": shard}, {"ring_id": 0}, ctx)["Out"]
        return shard, full

    fn = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(),), out_specs=(P("dp"), P()),
        check_vma=False))
    shard, full = fn(padded)
    # reduce-scatter sums the replicated input over 8 ranks, each rank
    # keeping its slice; the gathered result reassembles rank-order
    assert shard.shape == (padded_len,)  # global view of [2]-per-rank
    np.testing.assert_allclose(np.asarray(full), padded * WORLD)
    # un-pad recovers the raw segment exactly
    np.testing.assert_allclose(np.asarray(full)[:raw.size], raw * WORLD)


# ---------------------------------------------------------------------------
# insert_grad_allreduce idempotency (regression: fleet double-apply)
# ---------------------------------------------------------------------------
def test_insert_grad_allreduce_idempotent():
    main, startup, loss = _build()
    once = insert_grad_allreduce(main)
    n1 = sum(1 for op in once.global_block().ops
             if op.type == "c_allreduce_sum")
    assert n1 == len(main.all_parameters())
    twice = insert_grad_allreduce(once)
    n2 = sum(1 for op in twice.global_block().ops
             if op.type == "c_allreduce_sum")
    assert n2 == n1, "double apply double-reduced"


def test_insert_grad_allreduce_skips_sharded_grads():
    main, startup, loss = _build()
    shard_optimizer_states(main, startup, dp_degree=WORLD)
    rewritten = insert_grad_allreduce(main)
    assert not any(op.type == "c_allreduce_sum"
                   for op in rewritten.global_block().ops)


# ---------------------------------------------------------------------------
# memory accounting + plan + wire-byte accounting
# ---------------------------------------------------------------------------
def test_sharded_slot_bytes_one_eighth():
    main, startup, loss = _build()
    plain = static.analyze_program(main, batch=16)
    predicted = static.analyze_program(main, batch=16, dp_shard=WORLD)
    shard_optimizer_states(main, startup, dp_degree=WORLD)
    sharded = static.analyze_program(main, batch=16)
    one_bucket = max(b.shape[0] for b in
                     main.global_block().vars.values()
                     if b.attrs.get("dp_shard")) * 4
    # acceptance: slot bytes ≤ plain/8 + one bucket (padding overhead)
    assert sharded["optimizer_slot_bytes"] <= \
        plain["optimizer_slot_bytes"] // WORLD + one_bucket
    assert predicted["optimizer_slot_bytes"] <= \
        plain["optimizer_slot_bytes"] // WORLD + one_bucket
    assert sharded["persistable_bytes"] < plain["persistable_bytes"]


def test_prediction_skips_unshardable_optimizer_slots():
    """analyze_program(dp_shard=N) must divide ONLY slots the rewrite
    would actually shard — an Adamax moment stays replicated, so the
    predicted verdict never claims memory the pass cannot deliver."""
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 8])
        y = layers.data("y", [-1, 1])
        pred = layers.fc(x, 1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        static.Adamax(learning_rate=1e-2).minimize(loss)
    plain = static.analyze_program(main, batch=16)
    predicted = static.analyze_program(main, batch=16, dp_shard=WORLD)
    assert predicted["optimizer_slot_bytes"] == \
        plain["optimizer_slot_bytes"]
    # and the pass itself refuses the op: no buckets
    assert shard_optimizer_states(main, startup,
                                  dp_degree=WORLD).buckets == []


def test_collective_bytes_zero1_matches_allreduce_volume():
    # ZeRO-1's whole point: SAME wire volume (rs + ag == allreduce),
    # 1/N the optimizer memory.  Priced by the verifier's ring-accounted
    # extractor (static.collective_wire_bytes — the planner's wire
    # substrate, which superseded sharding.collective_bytes_per_step).
    main, startup, loss = _build()
    plain = static.collective_wire_bytes(insert_grad_allreduce(main), WORLD)
    shard_optimizer_states(main, startup, dp_degree=WORLD)
    zero = static.collective_wire_bytes(insert_grad_allreduce(main), WORLD)
    assert plain > 0
    # padding can only add a sliver
    assert plain <= zero <= int(plain * 1.25)


def test_collective_bytes_per_step_shim_retired():
    """The PR-5 helper `sharding.collective_bytes_per_step` was a
    warn-once shim since PR 9 and is now RETIRED: the accounting lives
    only in static.collective_wire_bytes (ring-accounted, all
    collective types/rings)."""
    from paddle_tpu.distributed import sharding as sharding_mod
    assert not hasattr(sharding_mod, "collective_bytes_per_step")
    import paddle_tpu.distributed as dist
    assert not hasattr(dist, "collective_bytes_per_step")


def test_zero3_structure_and_per_rank_param_shards():
    """Stage 3 op-chain contracts: params packed into a dp_shard
    persistable bucket at 1/8 per rank, JIT forward AND backward
    gathers present, the stage-1 publish allgather GONE, original
    params no longer persistable, and a short mesh run compiles once."""
    main, startup, loss = _build()
    n_params = len(main.all_parameters())
    plan = shard_optimizer_states(main, startup, dp_degree=WORLD, stage=3)
    assert plan.stage == 3 and plan.buckets
    block = main.global_block()
    # params de-persisted, bucket persistable + marked
    for p in main.all_parameters():
        assert not block.var(p.name).persistable, p.name
    pbuckets = plan.param_bucket_names()
    assert pbuckets
    for name in pbuckets:
        v = block.var(name)
        assert v.persistable and v.attrs.get("dp_shard") == WORLD
        assert v.attrs.get("zero_param_bucket")
    # JIT gathers: one fwd + one bwd per bucket, no publish allgather
    ags = [op for op in block.ops if op.type == "c_allgather"]
    roles = [op.attrs.get("zero_role") for op in ags]
    assert roles.count("gather_fwd") == len(plan.buckets)
    assert roles.count("gather_bwd") == len(plan.buckets)
    assert "publish" not in roles
    # backward readers were renamed onto the re-gathered aliases
    from paddle_tpu.core.program import OpRole
    pnames = {p["param"] for b in plan.buckets for p in b["params"]}
    for op in block.ops:
        role = int(op.attrs.get(OpRole.KEY, 0))
        if role & OpRole.Backward and op.attrs.get("zero_role") is None:
            assert not (pnames & set(op.input_names())), op
    # mesh run: loss finite, param bucket sharded 1/8 per rank
    compiled = CompiledProgram(main).with_data_parallel(loss_name=loss.name)
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        for f in _feeds(3):
            out = exe.run(compiled, feed=f, fetch_list=[loss])
        assert np.isfinite(np.asarray(out[0])).all()
        v = scope.get(pbuckets[0])
        shards = getattr(v, "addressable_shards", None)
        if shards:
            b0 = plan.buckets[0]
            assert {tuple(s.data.shape) for s in shards} == \
                {(b0["shard_len"],)}
    assert n_params == len(main.all_parameters())  # still introspectable


def test_zero2_interleaves_reduce_scatter_into_backward():
    """Stage>=2 places each bucket's reduce-scatter right after the
    bucket's last gradient producer (Backward role), so full-size grads
    die bucket-by-bucket instead of pooling in the optimizer tail — the
    walker must see the grad-HBM cut."""
    from paddle_tpu.core.program import OpRole
    main, startup, loss = _build()
    plain = static.analyze_program(main, batch=16)
    shard_optimizer_states(main, startup, dp_degree=WORLD, stage=2)
    block = main.global_block()
    rs_idx = [i for i, op in enumerate(block.ops)
              if op.type == "c_reducescatter"]
    first_opt = next(i for i, op in enumerate(block.ops)
                     if int(op.attrs.get(OpRole.KEY, 0)) == OpRole.Optimize)
    assert rs_idx and all(i < first_opt for i in rs_idx), \
        (rs_idx, first_opt)
    for i in rs_idx:
        assert int(block.ops[i].attrs.get(OpRole.KEY)) == OpRole.Backward
    sharded = static.analyze_program(main, batch=16)
    assert sharded["phase_peaks"]["backward"] <= \
        plain["phase_peaks"]["backward"] + 4 * max(
            b["padded_len"] for b in main._zero_shard_plan.buckets) * 2


def test_zero2_gm_shard_accumulator_is_dp_shard():
    """Under stage 2 + gradient_merge the accumulation buffer is the
    reduce-scattered bucket shard: a dp_shard persistable at the global
    padded length (1/N per chip), and NO full-size per-param
    @GradientMerge accumulators exist for bucketed grads."""
    main, startup, loss = _build()
    plan = shard_optimizer_states(main, startup, dp_degree=WORLD, stage=2)
    static.gradient_merge(main, 2, startup)
    block = main.global_block()
    saccs = [v for v in block.vars.values()
             if "@GSHARD_ACC" in v.name and v.persistable]
    assert len(saccs) == plan.n_buckets
    for v in saccs:
        assert v.attrs.get("dp_shard") == WORLD
        assert v.shape[0] % WORLD == 0
    full_accs = [v for v in block.vars.values()
                 if "@GradientMerge" in v.name and v.persistable]
    assert not full_accs
    # resume contract: the shard accumulators ride _gm_meta like any
    # accumulator (topology-shifted restore zeroes partial windows)
    assert set(v.name for v in saccs) <= set(main._gm_meta["accs"])


def test_checkpoint_roundtrip_across_stage_changes():
    """zero3 → zero1 → plain via the extended converters: a stage-3
    checkpoint restores into a stage-1 program (params unpacked,
    slots re-bucketed), then into a plain program, with the parameter
    payload bitwise intact at every hop."""
    from paddle_tpu.static.executor import _persistable_names
    main, startup, loss = _build()
    plan3 = shard_optimizer_states(main, startup, dp_degree=WORLD, stage=3)
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        for f in _feeds(3):
            exe.run(main, feed=f, fetch_list=[loss])
        state3 = {n: np.asarray(scope.get(n))
                  for n in _persistable_names(main)
                  if scope.get(n) is not None}
    # hop 1: zero3 -> plain layout (params unpacked to full shapes)
    plain_state = unshard_state(state3, plan3)
    for b in plan3.buckets:
        assert b["param_bucket"] not in plain_state
        for p in b["params"]:
            assert list(plain_state[p["param"]].shape) == p["shape"]
    # hop 2: plain -> zero1 layout of a FRESH program build
    m1, s1, _ = _build()
    plan1 = shard_optimizer_states(m1, s1, dp_degree=WORLD, stage=1)
    z1_state = reshard_state(plain_state, plan1)
    for b in plan1.buckets:
        for name in b["slots"].values():
            assert name in z1_state
    # params in the zero1 layout stay replicated full-shape
    for b in plan3.buckets:
        for p in b["params"]:
            np.testing.assert_array_equal(z1_state[p["param"]],
                                          plain_state[p["param"]])
    # hop 3: zero1 -> plain -> back to zero3: the bucket payload
    # round-trips bitwise
    back3 = reshard_state(unshard_state(state3, plan3), plan3)
    for b in plan3.buckets:
        np.testing.assert_array_equal(back3[b["param_bucket"]],
                                      state3[b["param_bucket"]])
        for name in b["slots"].values():
            np.testing.assert_array_equal(
                np.asarray(back3[name]).reshape(-1)[:b["raw_len"]],
                np.asarray(state3[name]).reshape(-1)[:b["raw_len"]])


def test_reshard_state_refuses_missing_params():
    main, startup, loss = _build()
    plan3 = shard_optimizer_states(main, startup, dp_degree=WORLD, stage=3)
    with pytest.raises(KeyError):
        reshard_state({}, plan3)


def test_partition_rule_keeps_param_replicated_under_stage3():
    """The declarative layer in action: a user rule pinning one param
    to REPLICATED makes its bucket take the stage-1 chain (flatten /
    c_split / publish) while other buckets pack — no new pass code."""
    main, startup, loss = _build()
    first = main.all_parameters()[0].name
    import re
    plan = shard_optimizer_states(
        main, startup, dp_degree=WORLD, stage=3,
        rules=[(r"^param:" + re.escape(first) + r"$", (), False)])
    packed = [b for b in plan.buckets if b.get("param_bucket")]
    unpacked = [b for b in plan.buckets if not b.get("param_bucket")]
    assert packed and unpacked
    assert any(p["param"] == first for b in unpacked for p in b["params"])
    block = main.global_block()
    assert block.var(first).persistable  # stayed replicated state
    # and the mixed program still verifies clean
    rep = static.check_program(main, level="collective", startup=startup)
    assert rep.ok, rep.render()


def test_plan_and_state_conversion_roundtrip():
    main, startup, loss = _build()
    plan = shard_optimizer_states(main, startup, dp_degree=WORLD)
    assert main._zero_shard_plan is plan
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        for f in _feeds(3):
            exe.run(main, feed=f, fetch_list=[loss])
        from paddle_tpu.static.executor import _persistable_names
        state = {n: np.asarray(scope.get(n))
                 for n in _persistable_names(main)
                 if scope.get(n) is not None}
    # ZeRO-1 -> plain layout: bucket slots sliced to per-param names
    plain_state = unshard_state(state, plan)
    for b in plan.buckets:
        for name in b["slots"].values():
            assert name not in plain_state
        for p in b["params"]:
            m1 = plain_state[b["orig_slots"][p["param"]]["moment1"]]
            assert list(m1.shape) == p["shape"]
    # ... and back: bitwise round trip of the moment payload
    back = reshard_state(plain_state, plan.to_dict())
    for b in plan.buckets:
        for name in b["slots"].values():
            got = np.asarray(back[name]).reshape(-1)
            want = np.asarray(state[name]).reshape(-1)
            np.testing.assert_array_equal(got[:b["raw_len"]],
                                          want[:b["raw_len"]])


def test_dp_shard_attr_survives_serialization():
    main, startup, loss = _build()
    shard_optimizer_states(main, startup, dp_degree=WORLD)
    blob = main.serialize_to_string()
    back = static.Program.parse_from_string(blob)
    marked = [v for v in back.global_block().vars.values()
              if v.attrs.get("dp_shard")]
    assert marked and all(v.attrs["dp_shard"] == WORLD for v in marked)
    # programs sharded for different worlds must fingerprint apart
    # (checkpoint mismatch warnings key off this)
    main4, startup4, _ = _build()
    shard_optimizer_states(main4, startup4, dp_degree=4)
    assert main4.fingerprint() != main.fingerprint()


def test_shard_optimizer_states_idempotent():
    """Double application (fleet strategy.sharding + a script calling the
    pass directly) must be a no-op the second time — re-sharding the
    bucket op would reduce-scatter the already-scattered shard across
    ranks and 1/N-scale twice, invisibly on one device."""
    main, startup, loss = _build()
    plan1 = shard_optimizer_states(main, startup, dp_degree=WORLD)
    ops_before = len(main.global_block().ops)
    plan2 = shard_optimizer_states(main, startup, dp_degree=WORLD)
    assert plan2.buckets == []
    assert len(main.global_block().ops) == ops_before
    # the original plan (checkpoint-conversion layout) survives
    assert main._zero_shard_plan is plan1
    types = [op.type for op in main.global_block().ops]
    assert types.count("c_reducescatter") == plan1.n_buckets
    # sgd buckets carry no slot vars — the op-level marker must guard too
    main2, startup2 = _build(lambda: static.SGD(learning_rate=1e-2))[:2]
    p1 = shard_optimizer_states(main2, startup2, dp_degree=WORLD)
    assert p1.buckets
    p2 = shard_optimizer_states(main2, startup2, dp_degree=WORLD)
    assert p2.buckets == []


def test_fp16_allreduce_wraps_bucket_reduce_scatter():
    """strategy.fp16_allreduce keeps its meaning under sharding: the
    bucket reduce-scatter's wire leg is bf16 (half the ICI bytes) and
    the accounting sees it."""
    main, startup, loss = _build()
    full = static.collective_wire_bytes(insert_grad_allreduce(main), WORLD)
    main._fp16_allreduce = True
    shard_optimizer_states(main, startup, dp_degree=WORLD)
    block = main.global_block()
    rs = next(op for op in block.ops if op.type == "c_reducescatter")
    assert block.var(rs.inputs["X"][0]).dtype == "bfloat16"
    # wire accounting: bf16 reduce-scatter + fp32 allgather < fp32 both
    zero = static.collective_wire_bytes(main, WORLD)
    assert zero < full


def test_world1_is_noop():
    main, startup, loss = _build()
    n_ops = len(main.global_block().ops)
    plan = shard_optimizer_states(main, startup, dp_degree=1)
    assert plan.buckets == [] and len(main.global_block().ops) == n_ops


def test_bucket_bytes_splits_groups():
    main, startup, loss = _build()
    # tiny bucket budget: every param lands in its own bucket
    plan = shard_optimizer_states(main, startup, dp_degree=WORLD,
                                  bucket_bytes=8)
    assert plan.n_buckets == len(main.all_parameters())


# ---------------------------------------------------------------------------
# fleet meta-optimizer wiring
# ---------------------------------------------------------------------------
def test_fleet_sharding_meta_optimizer_applies_and_trains():
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.fleet.base.fleet_base import Fleet
    f = Fleet()
    f.init(is_collective=True)
    main, startup, loss = _build(lambda: static.Adam(learning_rate=5e-2))
    # _build already minimized; fleet needs to drive minimize itself
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 8])
        y = layers.data("y", [-1, 1])
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        strategy = dist.fleet.DistributedStrategy()
        strategy.sharding = True
        strategy.sharding_configs = {"dp_degree": WORLD, "bucket_mb": 32}
        f.distributed_optimizer(static.Adam(learning_rate=5e-2), strategy)
        f.minimize(loss)
    assert "ShardingOptimizer" in f.applied_meta_list()
    types = [op.type for op in main.global_block().ops]
    assert "c_reducescatter" in types and "c_allgather" in types
    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(0)
    w = rng.rand(8, 1).astype(np.float32)
    with static.scope_guard(scope):
        exe.run(startup)
        losses = []
        for _ in range(15):
            xb = rng.rand(16, 8).astype(np.float32)
            (lv,) = exe.run(f.main_program, feed={"x": xb, "y": xb @ w},
                            fetch_list=[loss])
            losses.append(float(lv))
    assert losses[-1] < losses[0] * 0.5, losses
