"""Auto-parallel planner (static/planner.py): argmax correctness,
candidate-verification property, cost-model monotonicity, the post-hoc
remat rewrite's numerical equivalence, and the V504 plan-drift code.

The planner's contract (ISSUE 10): every candidate is a REAL rewrite on
a clone, priced by the three substrates (HBM walker / FLOPs walker /
ring-accounted wire bytes), gated through
`check_program(level="collective")` — so the search space never
contains a deadlocking plan — and the chosen plan is recorded in the
applied-passes registry so later hand-edits are flagged as drift.
"""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import paddle_tpu.static as static
from paddle_tpu.core.pass_framework import applied_passes, has_applied
from paddle_tpu.core.program import _reset_unique_names

WORLD = 8


def _tiny(layers_n=2, seq=32, hidden=64, vocab=256):
    import perf_smoke
    _reset_unique_names()
    return perf_smoke.build_bert_tiny(vocab=vocab, seq=seq, hidden=hidden,
                                      layers_n=layers_n)


# ---------------------------------------------------------------------------
# property: every emitted plan is collective-clean under strict mode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("knobs", [
    None,
    {"grad_merge": (1,)},
    {"dp_shard": (WORLD,), "bucket_mb": (1,)},
    {"remat": (True,), "grad_merge": (2,)},
])
def test_every_emitted_plan_is_strict_clean(knobs):
    main, startup, loss, _ = _tiny()
    plan = static.plan_program(main, startup, world=WORLD, batch=8,
                               knobs=knobs)
    # every candidate the search kept feasible was verified clean
    for cand in plan.trace:
        if cand["fits"]:
            assert cand["verdict"].startswith("verified"), cand
    # the chosen plan, applied for real, is strict-clean with ZERO
    # diagnostics — including the V504 drift check against the record
    static.apply_plan(main, startup, plan)
    report = static.check_program(main, level="collective",
                                  startup=startup, fetch_list=[loss],
                                  raise_on_error=True)
    assert not report.diagnostics, report.render()
    assert has_applied(main, "auto_parallel_plan")


def test_chosen_plan_ties_or_beats_every_feasible_candidate():
    main, startup, loss, _ = _tiny()
    plan = static.plan_program(main, startup, world=WORLD, batch=8)
    feas = [c for c in plan.trace if c["fits"]]
    assert feas
    best = max(c["samples_per_sec"] for c in feas)
    assert plan.predicted_samples_per_sec >= best - 1e-9


# ---------------------------------------------------------------------------
# monotonicity
# ---------------------------------------------------------------------------
def test_budget_monotonicity_looser_budget_never_slower():
    """The planner is a proper argmin over a feasibility set: shrinking
    the HBM budget can only shrink the feasible set, so the chosen
    plan's predicted step time is non-decreasing as the budget tightens
    (equivalently: a looser budget never yields a slower plan)."""
    main, startup, loss, _ = _tiny()
    plain = static.analyze_program(main, batch=8)
    # budgets: loose (everything fits) .. tight (plain no longer fits,
    # remat should) — derived from the walked peaks so the test does
    # not bake in absolute byte counts
    loose = plain["peak_bytes"] * 2
    tight = int(plain["peak_bytes"] / 1.10) - 1  # plain misses the slack
    prev_ms = None
    for budget in (loose, tight):
        m, s, loss_i, _ = _tiny()
        plan = static.plan_program(m, s, world=1, batch=8,
                                   hbm_budget=budget)
        if not plan.predicted_fits:
            break  # nothing fits at all: no feasible step time to rank
        if prev_ms is not None:
            assert plan.predicted_step_ms >= prev_ms - 1e-9, (
                f"tighter budget produced a FASTER plan "
                f"({plan.predicted_step_ms} < {prev_ms})")
        prev_ms = plan.predicted_step_ms
    # and the tight budget actually flipped the knob: remat chosen
    m, s, loss_i, _ = _tiny()
    plan_tight = static.plan_program(m, s, world=1, batch=8,
                                     hbm_budget=tight)
    assert plan_tight.predicted_fits
    assert plan_tight.knobs["remat"] is True


def test_world_monotonicity_wire_time_per_sample_never_worsens():
    """Growing the data-parallel world never worsens predicted wire
    time per GLOBAL sample: per-rank ring bytes grow like 2(N-1)/N
    (bounded) while samples per step grow like N."""
    per_sample = []
    for world in (2, 4, 8):
        main, startup, loss, _ = _tiny()
        plan = static.plan_program(
            main, startup, world=world, batch=8,
            knobs={"remat": (False,), "dp_shard": (0,),
                   "grad_merge": (1,)})
        per_sample.append(plan.predicted_wire_ms / (plan.batch * world))
    assert per_sample[0] >= per_sample[1] >= per_sample[2], per_sample


# ---------------------------------------------------------------------------
# post-hoc remat rewrite (the planner's remat knob)
# ---------------------------------------------------------------------------
def test_apply_recompute_posthoc_numerics_and_peak():
    """`apply_recompute` on a finished program must (a) cut the walked
    activation peak like the build-time rewrite and (b) leave training
    numerics unchanged — the replay computes the same values the
    backward read before."""
    main, startup, loss, _ = _tiny()
    clone = main.clone()
    static.apply_recompute(clone)
    assert has_applied(clone, "recompute")
    n_barriers = sum(1 for op in clone.global_block().ops
                     if op.type == "optimization_barrier")
    assert n_barriers >= 1
    plain_mem = static.analyze_program(main, batch=8)
    remat_mem = static.analyze_program(clone, batch=8)
    assert remat_mem["activation_peak_bytes"] < \
        plain_mem["activation_peak_bytes"]

    rng = np.random.RandomState(0)
    feed = {"ids": rng.randint(0, 256, (4, 32)).astype(np.int64),
            "labels": rng.randint(0, 256, (4, 32, 1)).astype(np.int64)}

    def run(prog):
        exe, scope = static.Executor(), static.Scope()
        with static.scope_guard(scope):
            exe.run(startup)
            return [np.asarray(
                exe.run(prog, feed=feed, fetch_list=[loss.name])[0])
                for _ in range(3)]

    for a, b in zip(run(main), run(clone)):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_apply_recompute_idempotent():
    main, startup, loss, _ = _tiny()
    static.apply_recompute(main)
    n_ops = len(main.global_block().ops)
    static.apply_recompute(main)  # registry-guarded no-op
    assert len(main.global_block().ops) == n_ops


# ---------------------------------------------------------------------------
# V504 plan drift
# ---------------------------------------------------------------------------
def test_plan_drift_v504_fires_on_hand_edit_after_planning():
    """Mutation test (ISSUE 10 acceptance): apply a plan, then hand-
    apply a knob the plan did not choose — the verifier must flag V504
    with the planned-vs-applied values."""
    main, startup, loss, _ = _tiny()
    plan = static.plan_program(main, startup, world=1, batch=8,
                               knobs={"remat": (False,),
                                      "grad_merge": (1,)})
    static.apply_plan(main, startup, plan)
    clean = static.check_program(main, level="collective", startup=startup)
    assert "V504" not in clean.codes()
    # the hand-edit: gradient_merge k=4 was never planned
    static.gradient_merge(main, 4, startup)
    drifted = static.check_program(main, level="collective",
                                   startup=startup)
    assert any(d.code == "V504" for d in drifted.errors), drifted.render()
    msg = next(d.message for d in drifted.errors if d.code == "V504")
    assert "grad_merge" in msg


def test_plan_drift_v504_fires_on_missing_pass():
    """The reverse mutation: the plan chose remat but the rewrite was
    stripped (or never applied) — same drift code."""
    main, startup, loss, _ = _tiny()
    from paddle_tpu.core.pass_framework import record_applied
    record_applied(main, "auto_parallel_plan", batch=8, remat=True,
                   dp_shard=0, grad_merge=1, bucket_mb=0, ring=False)
    report = static.check_program(main, level="collective")
    assert any(d.code == "V504" and "remat" in d.message
               for d in report.errors), report.render()


def test_plan_drift_v504_scan_hoist_missing_pass():
    """Mutation (ISSUE 16): the plan chose the scanned commit-tail
    hoist but `mark_scan_hoist` never recorded — the runtime would
    silently run the looped K-publish window the plan priced away."""
    main, startup, loss, _ = _tiny()
    from paddle_tpu.core.pass_framework import record_applied
    static.gradient_merge(main, 4, startup)
    record_applied(main, "auto_parallel_plan", batch=8, remat=False,
                   dp_shard=0, zero_stage=0, grad_merge=4, bucket_mb=0,
                   ring=False, tp_degree=0, scan_hoist=True)
    report = static.check_program(main, level="collective",
                                  startup=startup)
    assert any(d.code == "V504" and "scan_hoist" in d.message
               for d in report.errors), report.render()


def test_plan_drift_v504_scan_hoist_hand_marked():
    """The reverse mutation: the plan said LOOPED (scan_hoist False)
    but someone hand-marked the hoist after planning."""
    from paddle_tpu.distributed.scan_window import mark_scan_hoist
    main, startup, loss, _ = _tiny()
    plan = static.plan_program(main, startup, world=1, batch=8,
                               knobs={"remat": (False,),
                                      "grad_merge": (4,),
                                      "scan_hoist": (False,)})
    static.apply_plan(main, startup, plan)
    clean = static.check_program(main, level="collective", startup=startup)
    assert "V504" not in clean.codes(), clean.render()
    mark_scan_hoist(main)
    drifted = static.check_program(main, level="collective",
                                   startup=startup)
    assert any(d.code == "V504" and "scan_hoist" in d.message
               for d in drifted.errors), drifted.render()


def test_plan_prefers_fitting_knobs_over_infeasible_plain():
    """The planner's whole point: when plain doesn't fit, the chosen
    plan carries the knob that makes it fit (remat here), with a FITS
    verdict."""
    main, startup, loss, _ = _tiny(layers_n=3)
    plain = static.analyze_program(main, batch=8)
    tight = int(plain["peak_bytes"] / 1.10) - 1
    plan = static.plan_program(main, startup, world=1, batch=8,
                               hbm_budget=tight)
    assert plan.predicted_fits
    assert plan.knobs["remat"] is True
    plain_cand = [c for c in plan.trace
                  if not c["remat"] and c["grad_merge"] == 1][0]
    assert not plain_cand["fits"]


# ---------------------------------------------------------------------------
# BASELINE decision-table acceptance (ISSUE 10)
# ---------------------------------------------------------------------------
def test_planner_rediscovers_bert96_remat_verdict():
    """Tier-1 slice of the decision-table acceptance: on the real
    bert-base b96 shape the planner must rediscover the hand-tuned
    verdict (remat flips predicted OOM to FITS) with the documented
    walked peak, unprompted."""
    from paddle_tpu.models import build_bert_base
    _reset_unique_names()
    main, startup, _ = build_bert_base(30522, 512, 768, 12, 12, 96,
                                       use_amp=True)
    plan = static.plan_program(main, startup, world=1, batch=96,
                               knobs={"grad_merge": (1,)})
    assert plan.predicted_fits
    assert plan.knobs["remat"] is True
    # the docs/perf.md hand row: b96+remat walks 7.8 GiB.  (Was 14.0
    # before the ISSUE-11 liveness fix: buffers read only through
    # alias/fusable views — remat's replay aliases among them — were
    # never freed by the sweep; un-rematerialized peaks are unchanged,
    # see the "Full parameter sharding" docs section.)  Since PR 26 the
    # head and its loss run over blocks of tokens
    # (static/head_loss_rewrite.py): the 3.0 GB logits gradient that
    # was the remat build's peak no longer exists, and it walks 5.8.
    assert abs(plan.predicted_peak_bytes / 2 ** 30 - 5.8) < 0.5
    plain = [c for c in plan.trace if not c["remat"]][0]
    assert not plain["fits"]          # b96 plain walks 24.9 GiB: OOM


def _fc_tower(width=512, depth=6):
    from paddle_tpu.static import layers
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, width])
        y = layers.data("y", [-1, 1])
        h = x
        for _ in range(depth):
            h = layers.fc(h, width, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        static.Adam(learning_rate=1e-2).minimize(loss)
    return main, startup, loss


def test_planner_searches_zero_stages_and_picks_zero3_unprompted():
    """ISSUE 11 acceptance: for a shape whose PARAM bytes exceed the
    chip budget — so replicated-param plans (plain AND ZeRO-1) are
    infeasible — the planner searches the zero2/zero3 axes and picks a
    stage unprompted, with a walker-verified predicted_fits flip."""
    import numpy as np
    main, startup, loss = _fc_tower()
    param_bytes = sum(int(np.prod(p.shape)) * 4
                      for p in main.all_parameters())
    # params alone exceed the chip; the +2 MiB headroom covers the
    # stage-3 backward-gather PREFETCH double buffer (two gathered
    # 1-MiB buckets live at once — the walker charges the overlap the
    # prefetch really costs), still far under any replicated-param peak
    budget = int(param_bytes * 0.9) + 2 * 2 ** 20
    plan = static.plan_program(main, startup, world=8, batch=4,
                               hbm_budget=budget,
                               knobs={"batch": (4,), "grad_merge": (1,),
                                      "bucket_mb": (1,)})
    stages = {c["zero_stage"] for c in plan.trace}
    assert {0, 1, 3} <= stages        # the axes were actually searched
    assert plan.predicted_fits
    assert plan.knobs["zero_stage"] == 3
    assert plan.predicted_peak_bytes < param_bytes
    for c in plan.trace:              # every replicated-param plan OOMs
        if c["zero_stage"] < 3:
            assert not c["fits"]


def test_zero3_plan_trains_on_the_mesh():
    """The chosen zero3 plan is not just priced — applied for real it
    trains on the 8-device mesh with finite loss and zero post-warmup
    retraces."""
    import numpy as np
    from paddle_tpu.distributed.compiled_program import CompiledProgram
    main, startup, loss = _fc_tower(width=8, depth=2)
    plan = static.plan_program(main, startup, world=8, batch=8,
                               knobs={"batch": (8,), "grad_merge": (1,),
                                      "dp_shard": (8,),
                                      "zero_stage": (3,)})
    assert plan.knobs["zero_stage"] == 3
    static.apply_plan(main, startup, plan)
    rep = static.check_program(main, level="collective", startup=startup)
    assert rep.ok, rep.render()
    compiled = CompiledProgram(main).with_data_parallel(loss_name=loss.name)
    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(0)
    with static.scope_guard(scope):
        exe.run(startup)
        for i in range(4):
            out = exe.run(compiled,
                          feed={"x": rng.rand(8, 8).astype("float32"),
                                "y": rng.rand(8, 1).astype("float32")},
                          fetch_list=[loss])
            if i == 0:
                warm = len(compiled._cache)
        assert np.isfinite(np.asarray(out[0])).all()
        assert len(compiled._cache) == warm


@pytest.mark.slow
def test_decision_table_planner_matches_or_beats_hand_verdicts():
    """Full ISSUE 10 acceptance: the planner ties or beats the
    hand-tuned docs/perf.md decision table (predicted step time, FITS)
    on every BASELINE shape — tools/plan_decision_table.py exits 0."""
    import subprocess
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "plan_decision_table.py")],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]


def test_planner_pins_preapplied_knobs():
    """A program already rewritten (build-time remat, pre-sharded)
    cannot un-apply those knobs — the lattice must pin them instead of
    emitting candidates the clone cannot realize."""
    from paddle_tpu.distributed.sharding import shard_optimizer_states
    main, startup, loss, _ = _tiny()
    shard_optimizer_states(main, startup, dp_degree=WORLD)
    plan = static.plan_program(main, startup, world=WORLD, batch=8)
    assert all(c["dp_shard"] == WORLD for c in plan.trace)
    assert plan.knobs["dp_shard"] == WORLD
    # ... and plan+apply on the pinned program must not V504
    static.apply_plan(main, startup, plan)
    report = static.check_program(main, level="collective",
                                  startup=startup)
    assert "V504" not in report.codes(), report.render()
    # a pre-sharded degree OUTSIDE the default (0, world) axis pins
    # through the axis — the batch search must survive, not collapse
    # to the batch=1 fallback
    main4, startup4, loss4, _ = _tiny()
    shard_optimizer_states(main4, startup4, dp_degree=4)
    plan4 = static.plan_program(main4, startup4, world=WORLD)
    assert plan4.knobs["dp_shard"] == 4
    assert len({c["batch"] for c in plan4.trace}) > 1
    assert plan4.batch > 1


def test_planner_pins_preapplied_gradient_merge():
    """A pre-merged program pins grad_merge=k: the plan records the
    truth, apply_plan is a no-op for that knob, and no spurious V504
    fires (the plan/apply round-trip on an already-rewritten program is
    a legitimate, drift-free flow)."""
    main, startup, loss, _ = _tiny()
    static.gradient_merge(main, 2, startup)
    plan = static.plan_program(main, startup, world=1, batch=8)
    assert plan.knobs["grad_merge"] == 2
    assert all(c["grad_merge"] == 2 for c in plan.trace)
    static.apply_plan(main, startup, plan)
    report = static.check_program(main, level="collective",
                                  startup=startup)
    assert "V504" not in report.codes(), report.render()


def test_planner_pins_ring_built_program():
    """A program built with ring attention can't drop the op — the ring
    knob pins True even without a variants= pair, the trace is labeled
    truthfully, and apply_plan accepts the plan on the same program."""
    from paddle_tpu.static import layers, nets
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        ids = layers.data("ids", [-1, 16], dtype="int64")
        labels = layers.data("labels", [-1, 16, 1], dtype="int64")
        h = layers.embedding(ids, size=[64, 32])
        q = layers.fc(h, 32, num_flatten_dims=2)
        k = layers.fc(h, 32, num_flatten_dims=2)
        v = layers.fc(h, 32, num_flatten_dims=2)
        ctx = nets.scaled_dot_product_attention(q, k, v, num_heads=2,
                                                sequence_parallel=True)
        logits = layers.fc(ctx, 64, num_flatten_dims=2)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits,
                                                             labels))
        static.Adam(learning_rate=1e-3).minimize(loss)
    plan = static.plan_program(main, startup, world=1, batch=4)
    assert plan.knobs["ring"] is True
    assert all(c["ring"] for c in plan.trace)
    static.apply_plan(main, startup, plan)   # must not raise
    report = static.check_program(main, level="collective",
                                  startup=startup)
    assert "V504" not in report.codes(), report.render()


# ---------------------------------------------------------------------------
# tp_degree lattice axis (ISSUE 15)
# ---------------------------------------------------------------------------
_TP_GEOM = dict(vocab_size=128, hidden=64, num_layers=2, num_heads=4,
                seq_len=32, learning_rate=1e-2)


def _build_lm(tp=1):
    from paddle_tpu.models import build_transformer_lm
    _reset_unique_names()
    main, startup, loss, _ = build_transformer_lm(
        vocab_size=_TP_GEOM["vocab_size"], hidden=_TP_GEOM["hidden"],
        num_layers=_TP_GEOM["num_layers"], num_heads=_TP_GEOM["num_heads"],
        seq_len=_TP_GEOM["seq_len"], tensor_parallel_degree=tp)
    import paddle_tpu.static as _s
    with _s.program_guard(main, startup):
        _s.Adam(learning_rate=_TP_GEOM["learning_rate"]).minimize(loss)
    return main, startup, loss


def test_tp_lattice_from_hand_variants_prices_both_axes():
    """A hand-fed {"tp": {2: pair}} variant puts tp on the lattice:
    2-D candidates carry per-axis wire with the mp ring priced at its
    OWN degree (batch-proportional activations included), and dp_shard
    candidates under tp shrink to the dp sub-world."""
    base = _build_lm(tp=1)
    tp2 = _build_lm(tp=2)
    plan = static.plan_program(base[0], base[1], world=WORLD, batch=8,
                               knobs={"grad_merge": (1,)},
                               variants={"tp": {2: (tp2[0], tp2[1])}})
    tp_cands = [c for c in plan.trace if c["tp_degree"] == 2]
    assert tp_cands, plan.render_table()
    for c in tp_cands:
        if c["fits"]:
            assert c["wire_bytes_per_axis"].get("mp", 0) > 0, c
            assert c["verdict"].startswith("verified"), c
        assert c["dp_shard"] in (0, WORLD // 2), c
    # the mp wire is batch-proportional: replanning at twice the batch
    # must grow it
    base2 = _build_lm(tp=1)
    tp2b = _build_lm(tp=2)
    plan2 = static.plan_program(base2[0], base2[1], world=WORLD, batch=16,
                                knobs={"grad_merge": (1,)},
                                variants={"tp": {2: (tp2b[0], tp2b[1])}})
    mp8 = next(c["wire_bytes_per_axis"]["mp"] for c in plan.trace
               if c["tp_degree"] == 2 and not c["remat"]
               and not c["dp_shard"])
    mp16 = next(c["wire_bytes_per_axis"]["mp"] for c in plan2.trace
                if c["tp_degree"] == 2 and not c["remat"]
                and not c["dp_shard"])
    assert mp16 > mp8, (mp8, mp16)


def test_tp_lattice_charges_compute_and_hbm_at_one_over_tp():
    """The 2-D pricing contract: a tp=2 candidate's walked HBM peak and
    compute leg both drop below the same-batch pure-dp candidate's
    (sharded weights/activations at 1/tp, mp-stamped matmul FLOPs at
    1/tp)."""
    base = _build_lm(tp=1)
    tp2 = _build_lm(tp=2)
    plan = static.plan_program(base[0], base[1], world=WORLD, batch=8,
                               knobs={"grad_merge": (1,), "remat": (False,),
                                      "dp_shard": (0,)},
                               variants={"tp": {2: (tp2[0], tp2[1])}})
    dp_c = next(c for c in plan.trace if not c["tp_degree"])
    tp_c = next(c for c in plan.trace if c["tp_degree"] == 2)
    assert tp_c["peak_bytes"] < dp_c["peak_bytes"], (dp_c, tp_c)
    assert tp_c["compute_ms"] < dp_c["compute_ms"], (dp_c, tp_c)


def test_planner_picks_4x2_unprompted_when_pure_dp_infeasible():
    """The ISSUE 15 acceptance core (also gated by tools/
    tp_plan_smoke.py): with tp variants auto-generated from a model
    config — never hand-fed — and a budget below the best pure-dp walk,
    the planner chooses the 4×2 dp×tp plan."""
    from paddle_tpu.static.memory_analysis import XLA_REMAT_SLACK
    base = _build_lm(tp=1)
    knobs = {"batch": (8,), "grad_merge": (1,), "zero_stage": (1,)}
    probe = static.plan_program(base[0], base[1], world=WORLD,
                                hbm_budget=1 << 50,
                                knobs=dict(knobs, tp_degree=(0, 2)),
                                model_config=_TP_GEOM, verify=False)
    best_dp = min(c["peak_bytes"] for c in probe.trace
                  if not c["tp_degree"] and c["peak_bytes"] > 0)
    base2 = _build_lm(tp=1)
    plan = static.plan_program(base2[0], base2[1], world=WORLD,
                               hbm_budget=int(best_dp / XLA_REMAT_SLACK) - 1,
                               knobs=dict(knobs), model_config=_TP_GEOM)
    assert plan.predicted_fits, plan.render_table()
    assert plan.knobs["tp_degree"] == 2, plan.render_table()
    assert all(not c["fits"] for c in plan.trace if not c["tp_degree"])
    assert 2 in plan.build_variants


def test_global_batch_constraint_gm_tp_candidate_wins():
    """ISSUE 15 acceptance: when the user demands a global batch no
    single-chip plan can hold, the effective-global-batch constraint
    turns gm×tp candidates into feasible winners instead of the search
    returning predicted_fits=False."""
    from paddle_tpu.static.memory_analysis import XLA_REMAT_SLACK
    base = _build_lm(tp=1)
    # dp_shard pinned off: ZeRO slot sharding would undercut the
    # pure-dp floor below the gm accumulators' cost and close the
    # budget window this scenario needs (demanded batch + tight HBM)
    knobs = {"batch": (4, 8), "zero_stage": (1,), "remat": (False,),
             "dp_shard": (0,), "tp_degree": (0, 2)}
    probe = static.plan_program(base[0], base[1], world=WORLD,
                                hbm_budget=1 << 50, knobs=dict(knobs),
                                model_config=_TP_GEOM, verify=False)
    # premise: every batch-8 plan (any axis) and every pure-dp plan is
    # walker-infeasible, while the gm×tp winner (batch 4, tp 2, gm 2 —
    # the only lattice point reaching the demanded global batch) fits
    floor = min(c["peak_bytes"] for c in probe.trace
                if c["peak_bytes"] > 0 and
                (not c["tp_degree"] or c["batch"] > 4))
    win_peak = min(c["peak_bytes"] for c in probe.trace
                   if c["tp_degree"] == 2 and c["batch"] == 4
                   and c["grad_merge"] == 2 and c["peak_bytes"] > 0)
    assert win_peak < floor, probe.render_table()
    budget = int(floor / XLA_REMAT_SLACK) - 1
    # demand a global batch only a gm window can reach at batch 4 on
    # the dp=4 sub-axis: 4 × 4 × 2 = 32
    base2 = _build_lm(tp=1)
    plan = static.plan_program(base2[0], base2[1], world=WORLD,
                               hbm_budget=budget, knobs=dict(knobs),
                               model_config=_TP_GEOM, global_batch=32)
    assert plan.predicted_fits, plan.render_table()
    assert plan.knobs["tp_degree"] == 2, plan.render_table()
    assert plan.knobs["grad_merge"] == 2, plan.render_table()
    assert plan.predicted_effective_global_batch >= 32
    # and WITHOUT the constraint the same search picks gm=1 (gm is a
    # priced no-win that only the batch demand justifies)
    base3 = _build_lm(tp=1)
    plan_free = static.plan_program(base3[0], base3[1], world=WORLD,
                                    hbm_budget=budget, knobs=dict(knobs),
                                    model_config=_TP_GEOM)
    assert plan_free.knobs["grad_merge"] == 1, plan_free.render_table()
