"""Pipeline-parallelism tests (SectionWorker/PipelineTrainer analog,
reference section_worker.cc:82 GPipe schedule).  Run on the virtual
8-device CPU mesh; stages are pinned to distinct cpu devices."""
import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu.static import layers
import paddle_tpu.distributed as dist


def _pipeline_model():
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        with static.device_guard("xla:0"):
            x = layers.data("x", [-1, 8])
            y = layers.data("y", [-1, 1])
            h = layers.fc(x, size=16, act="relu")
        with static.device_guard("xla:1"):
            pred = layers.fc(h, size=1)
            loss = layers.mean(
                layers.square(layers.elementwise_sub(pred, y)))
    return main, startup, loss


def test_stage_assignment():
    from paddle_tpu.pipeline import assign_stages
    main, startup, loss = _pipeline_model()
    with static.program_guard(main, startup):
        static.SGD(learning_rate=0.05).minimize(loss)
    stages = assign_stages(main.global_block())
    assert max(stages) == 1
    # backward ops inherit their forward op's stage via the copied attrs
    from paddle_tpu.core.program import OpRole
    bwd_stages = [s for op, s in zip(main.global_block().ops, stages)
                  if op.op_role & OpRole.Backward]
    assert 0 in bwd_stages and 1 in bwd_stages


def test_pipeline_trains_and_matches_plain():
    """Pipelined run must match the plain executor numerically: same
    program, same fixed batch, M=4 micro-batches of identical rows →
    identical gradients."""
    xb = np.tile(np.random.RandomState(0).rand(4, 8).astype(np.float32),
                 (4, 1))
    yb = xb.sum(1, keepdims=True).astype(np.float32)

    # plain run
    main, startup, loss = _pipeline_model()
    with static.program_guard(main, startup):
        static.SGD(learning_rate=0.05).minimize(loss)
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            (plain_loss,) = exe.run(main, feed={"x": xb, "y": yb},
                                    fetch_list=[loss])
        plain_w = {p.name: np.asarray(scope.get(p.name))
                   for p in main.all_parameters()}

    # pipelined run (fresh, same seed/initialization via same program clone)
    main2, startup2, loss2 = _pipeline_model()
    with static.program_guard(main2, startup2):
        opt = static.SGD(learning_rate=0.05)
        from paddle_tpu.pipeline import PipelineOptimizer
        popt = PipelineOptimizer(opt, num_microbatches=4)
        popt.minimize(loss2)
    pp = main2._pipeline_compiled
    counts = pp.stage_op_counts()
    assert len(counts["fwd"]) == 2, counts
    assert all(c > 0 for c in counts["fwd"]), counts
    exe2 = static.Executor()
    scope2 = static.Scope()
    with static.scope_guard(scope2):
        exe2.run(startup2)
        for _ in range(3):
            (pp_loss,) = exe2.run(pp, feed={"x": xb, "y": yb},
                                  fetch_list=[loss2])
        pp_w = {p.name: np.asarray(scope2.get(p.name))
                for p in main2.all_parameters()}

    assert np.isfinite(pp_loss).all()
    np.testing.assert_allclose(float(pp_loss), float(plain_loss),
                               rtol=1e-4, atol=1e-5)
    # in creation order: the two builds' names differ by the process's
    # name counter, and sorted they pair up wrong across a digit boundary
    # (`fc_9` / `fc_10`)
    for (n1, w1), (n2, w2) in zip(plain_w.items(), pp_w.items()):
        np.testing.assert_allclose(w1, w2, rtol=1e-4, atol=1e-5)


def test_pipeline_converges():
    main, startup, loss = _pipeline_model()
    with static.program_guard(main, startup):
        from paddle_tpu.pipeline import PipelineOptimizer
        PipelineOptimizer(static.Adam(learning_rate=0.01),
                          num_microbatches=2).minimize(loss)
    pp = main._pipeline_compiled
    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(1)
    xb = rng.rand(16, 8).astype(np.float32)
    yb = xb.sum(1, keepdims=True).astype(np.float32)
    with static.scope_guard(scope):
        exe.run(startup)
        losses = []
        for _ in range(40):
            (lv,) = exe.run(pp, feed={"x": xb, "y": yb}, fetch_list=[loss])
            losses.append(float(lv))
    assert losses[-1] < losses[0] * 0.3, (losses[0], losses[-1])


def test_fleet_pipeline_strategy():
    from paddle_tpu.distributed.fleet.base.fleet_base import Fleet
    f = Fleet()
    f.init(is_collective=True)
    main, startup, loss = _pipeline_model()
    strategy = dist.fleet.DistributedStrategy()
    strategy.pipeline = True
    strategy.pipeline_configs = {"micro_batch": 2, "accumulate_steps": 2}
    with static.program_guard(main, startup):
        f.distributed_optimizer(static.SGD(learning_rate=0.05), strategy)
        f.minimize(loss)
    assert "FleetPipelineOptimizer" in f.applied_meta_list()
    from paddle_tpu.pipeline import PipelineCompiledProgram
    assert isinstance(f.main_program, PipelineCompiledProgram)
    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(2)
    with static.scope_guard(scope):
        exe.run(startup)
        xb = rng.rand(8, 8).astype(np.float32)
        yb = xb.sum(1, keepdims=True).astype(np.float32)
        l0 = None
        for _ in range(20):
            (lv,) = exe.run(f.main_program, feed={"x": xb, "y": yb},
                            fetch_list=[loss])
            l0 = l0 if l0 is not None else float(lv)
        assert float(lv) < l0


def test_pipeline_per_example_fetch_concatenates():
    """Per-example fetches (leading dim == micro-batch size) come back
    concatenated to the full mini-batch, not averaged (section_worker
    fetch semantics)."""
    xb = np.random.RandomState(1).rand(8, 8).astype(np.float32)
    yb = xb.sum(1, keepdims=True).astype(np.float32)
    main, startup, loss = _pipeline_model()
    with static.program_guard(main, startup):
        from paddle_tpu.pipeline import PipelineOptimizer
        PipelineOptimizer(static.SGD(learning_rate=0.01),
                          num_microbatches=4).minimize(loss)
    pp = main._pipeline_compiled
    # locate the prediction var (elementwise_sub X input, per-example [B, 1])
    pred_var = None
    for op in main.global_block().ops:
        if op.type == "elementwise_sub":
            pred_var = op.inputs["X"][0]
            break
    assert pred_var is not None
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        pred_out, loss_out = exe.run(pp, feed={"x": xb, "y": yb},
                                     fetch_list=[pred_var, loss])
    assert pred_out.shape == (8, 1), pred_out.shape
    assert np.asarray(loss_out).ndim == 0 or np.asarray(loss_out).size == 1
