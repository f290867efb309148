"""Driver `train_job`: BERT pretraining through the program's Executor.

Builds through `bench.build_bert_base`, feeds through
`reader.Prefetcher` (a fresh seeded batch per step, built on the host and
placed while the previous dispatch runs), and dispatches through
`Executor.run_steps` (K > 1 steps a dispatch) or `Executor.run`, on one
chip or data-parallel under `CompiledProgram.with_data_parallel`.  Every
dispatch ends in a fetched loss on the host.
"""
import itertools
import math
import time

import numpy as np

from benchmark import loadgen, work
from benchmark.reference import bert_mlm


def run(run):
    import bench
    import paddle_tpu.static as static
    from paddle_tpu.distributed.compiled_program import CompiledProgram
    from paddle_tpu.reader.prefetcher import Prefetcher

    cfg, mix, checks = run.config, run.traffic, run.cell.cell["checks"]
    seq, k = int(mix["seq_len"]), int(mix["steps_per_dispatch"])
    global_batch = int(mix["batch_per_chip"]) * run.chips
    step_tokens = global_batch * seq
    if seq > cfg["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq} exceeds the configuration's "
                         f"{cfg['max_position_embeddings']} positions")

    # -- set-up: program, weights from the seed, feeder, warm-up ------------
    main, startup, loss = bench.build_bert_base(
        cfg["vocab_size"], seq, cfg["hidden_size"],
        cfg["num_hidden_layers"], cfg["num_attention_heads"], global_batch,
        use_amp=cfg["trainer"]["compute_dtype"] == "bfloat16")
    main.random_seed = startup.random_seed = run.seed
    exe, scope = static.Executor(), static.Scope()
    if mix.get("data_parallel"):
        target = CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=run.devices)
        place_fn = target.place_feed
    else:
        target, place_fn = main, None
    dispatch = exe.run_steps if k > 1 else exe.run
    batches = loadgen.training_batches(mix, cfg["vocab_size"], run.seed,
                                       global_batch)
    losses, windows = [], []

    def one_dispatch(feeder):
        with run.span("feed_wait"):
            feed = next(feeder)
        with run.span("dispatch"):
            out = dispatch(target, feed=feed, fetch_list=[loss],
                           return_numpy=False)
        with run.span("fetch"):
            got = np.asarray(out[0], np.float64).reshape(-1)
        losses.extend(got.tolist())

    with static.scope_guard(scope):
        exe.run(startup)
        # the initial parameters, on the host, for the reference check
        # after the window (HBM is full while the program steps)
        params0 = [np.asarray(scope.get(p.name))
                   for p in main.all_parameters()]
        first = next(batches)
        ids0 = first["ids"][0] if k > 1 else first["ids"]
        feeder = Prefetcher(itertools.chain([first], batches), depth=2,
                            place_fn=place_fn)
        try:
            # warm-up: dispatch until one obtains no new executable (the
            # first compiles the step; under a mesh the second compiles
            # again, for state that now lives sharded on the mesh)
            for _ in range(4):
                before = run.clock.compiles
                one_dispatch(feeder)
                if run.clock.compiles == before:
                    break
            warm_steps = len(losses)
            run.spans.clear()

            # -- the measured window ----------------------------------------
            t0 = run.begin_window()
            while True:
                elapsed = time.perf_counter() - t0
                if elapsed >= run.seconds:
                    break
                # the slice starts and stops here, between dispatches, so
                # it holds whole dispatches only
                run.slice.poll(elapsed)
                tracing = run.slice.state == "tracing"
                w0 = time.perf_counter()
                one_dispatch(feeder)
                windows.append((w0, time.perf_counter(), tracing))
            measured_s = windows[-1][1] - t0
            run.end_window(measured_s)
        finally:
            feeder.close()

    # -- what the window did --------------------------------------------------
    steps = len(windows) * k
    run.attempted = steps
    window_losses = losses[warm_steps:]
    run.failed = sum(not math.isfinite(v) for v in window_losses)
    run.counters.update(window_steps=steps)
    run.end_to_end["train_tok_per_s_chip"] = \
        steps * step_tokens / measured_s / run.chips
    flops_tok = work.bert_train_flops_per_token(cfg, seq)
    peak = work.peaks(run.devices[0].device_kind)
    tok_s = run.end_to_end["train_tok_per_s_chip"]
    run.log(f"train: {steps} steps of {step_tokens} tokens in "
            f"{measured_s:.3f} s on {run.chips} chip(s) = {tok_s:.1f} "
            f"tok/s/chip; required {flops_tok / 1e6:.1f} MFLOP/token, MFU "
            f"{tok_s * flops_tok / peak['bf16_flops_per_s']:.4f}")
    from paddle_tpu.static import analyze_flops
    walked = analyze_flops(main, batch=global_batch)["total_flops"]
    run.log(f"cross-check: static.analyze_flops walks "
            f"{walked / step_tokens / 1e6:.1f} MFLOP/token (counts what the "
            f"program executes; never used for a metric)")
    traced = sum(1 for w in windows if w[2])
    if traced:
        run.slice_units = traced * k
        per_chip_tokens = run.slice_units * step_tokens / run.chips
        run.work = (per_chip_tokens * flops_tok,
                    run.slice_units * work.bert_train_bytes_per_step(cfg))

    # -- correctness ----------------------------------------------------------
    ref0 = bert_mlm.mlm_loss_chunked(
        params0, ids0, ids0, cfg["num_hidden_layers"],
        cfg["num_attention_heads"], chunk=checks["reference_chunk"])
    rel = abs(losses[0] - ref0) / abs(ref0)
    idx = int(checks["loss_step"])
    fell = len(losses) > idx and \
        losses[idx] < losses[0] - float(checks["loss_margin"])
    run.log(f"losses: step 0 {losses[0]:.4f} (reference {ref0:.4f}, rel "
            f"{rel:.2e}, tolerance {checks['step0_loss_rtol']}), step {idx} "
            f"{losses[idx] if len(losses) > idx else 'not reached'}, last "
            f"{losses[-1]:.4f} of {len(losses)}")
    run.checks.update(
        losses_finite=all(math.isfinite(v) for v in losses),
        step0_matches_reference=rel <= float(checks["step0_loss_rtol"]),
        loss_fell=bool(fell))
    run.correct = True
