"""Benchmark entry — prints ONE JSON line the driver records.

Runs a BERT/ERNIE-base-style pretraining step (the north-star workload,
BASELINE.md: ERNIE-base pretrain tokens/sec/chip) built with the paddle_tpu
static-graph API and executed as one jitted XLA computation on the TPU.
A run that finds no TPU fails; BENCH_FORCE_CPU=1 asks for the CPU rehearsal
explicitly (tiny model, result names `platform: cpu`, never a device metric).

MFU accounting: 6 * params * tokens/sec vs chip peak (v5e bf16 ~197 TFLOPs;
measured-only on CPU).
"""
import json
import os
import sys
import time

import numpy as np


def build_bert_base(vocab=30522, seq=512, hidden=768, layers_n=12, heads=12,
                    batch=8, use_amp=True, use_ring=False):
    import paddle_tpu.static as static
    from paddle_tpu.static import layers, nets
    from paddle_tpu import amp

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        ids = layers.data("ids", [-1, seq], dtype="int64")
        pos = layers.data("pos", [-1, seq], dtype="int64")
        labels = layers.data("labels", [-1, seq, 1], dtype="int64")
        emb = layers.embedding(ids, size=[vocab, hidden])
        pemb = layers.embedding(pos, size=[seq, hidden])
        h = layers.elementwise_add(emb, pemb)
        h = layers.layer_norm(h, begin_norm_axis=2)
        for _ in range(layers_n):
            # self-attention (use_ring: the ring_attention op — sequence
            # shards over an "sp" mesh axis under CompiledProgram, plain
            # attention on one device; the long-seq path's kernel)
            q = layers.fc(h, hidden, num_flatten_dims=2)
            k = layers.fc(h, hidden, num_flatten_dims=2)
            v = layers.fc(h, hidden, num_flatten_dims=2)
            ctx = nets.scaled_dot_product_attention(
                q, k, v, num_heads=heads, sequence_parallel=use_ring)
            attn_out = layers.fc(ctx, hidden, num_flatten_dims=2)
            h = layers.layer_norm(layers.elementwise_add(h, attn_out),
                                  begin_norm_axis=2)
            # ffn
            ffn = layers.fc(h, hidden * 4, num_flatten_dims=2, act="gelu")
            ffn = layers.fc(ffn, hidden, num_flatten_dims=2)
            h = layers.layer_norm(layers.elementwise_add(h, ffn),
                                  begin_norm_axis=2)
        logits = layers.fc(h, vocab, num_flatten_dims=2)
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits, labels))
        opt = static.Adam(learning_rate=1e-4)
        if use_amp:
            # bf16 compute on the MXU, fp32 master weights; bf16 shares
            # fp32's exponent range so no dynamic loss scaling is needed
            opt = amp.decorate(opt, init_loss_scaling=1.0,
                               use_dynamic_loss_scaling=False,
                               dest_dtype="bfloat16")
        opt.minimize(loss)
    return main, startup, loss


def _require_tpu_or_forced_cpu():
    """The device rule of every measuring mode: BENCH_FORCE_CPU=1 is the
    explicit CPU rehearsal; otherwise the default backend must be a TPU
    and anything else ends the run non-zero — a measurement path that
    finds no chip fails, it does not fall back."""
    import jax
    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
        return
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench: no TPU (jax.devices()[0].platform == {platform!r}); "
            "set BENCH_FORCE_CPU=1 for the CPU rehearsal")


def checkpoint_main():
    """Checkpoint-overhead A/B (`python bench.py --checkpoint` or
    BENCH_MODE=checkpoint): steady-state bert-tiny training throughput
    with (a) no checkpointing, (b) async CheckpointManager saves every
    step, (c) synchronous saves every step.  The async number must sit
    within a few percent of baseline — that's the whole point of
    decoupling snapshot from persistence — while sync pays the full
    serialize+fsync cost on the train path.  Prints ONE JSON line;
    numbers quoted in docs/checkpoint.md."""
    import tempfile
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import perf_smoke
    import paddle_tpu.static as static
    from paddle_tpu.checkpoint import CheckpointManager

    steps = int(os.environ.get("BENCH_CKPT_STEPS", 60))
    every = int(os.environ.get("BENCH_CKPT_EVERY", 10))
    reps = int(os.environ.get("BENCH_CKPT_REPS", 2))
    batch, seq, vocab = 8, 64, 2048
    rng = np.random.RandomState(0)
    idt = np.int64 if jax.config.jax_enable_x64 else np.int32

    def measure(mode):
        from paddle_tpu.core.program import _reset_unique_names
        _reset_unique_names()
        main_p, startup_p, loss, _ = perf_smoke.build_bert_tiny(
            vocab=vocab, seq=seq, hidden=128, layers_n=2, heads=4)
        exe = static.Executor()
        scope = static.Scope()
        feed = {"ids": rng.randint(0, vocab, (batch, seq)).astype(idt),
                "labels": rng.randint(0, vocab,
                                      (batch, seq, 1)).astype(idt)}
        mgr = None
        root = None
        try:
            with static.scope_guard(scope):
                exe.run(startup_p)
                exe.run(main_p, feed=feed, fetch_list=[loss])  # warm/compile
                if mode == "async":
                    root = tempfile.mkdtemp(prefix=f"bench_ckpt_{mode}_")
                    mgr = CheckpointManager(root, keep_last_n=3,
                                            max_in_flight=1)
                    exe.enable_checkpointing(mgr, program=main_p,
                                             every_n_steps=every,
                                             scope=scope)
                if mode == "sync":
                    root = tempfile.mkdtemp(prefix=f"bench_ckpt_{mode}_")
                    mgr = CheckpointManager(root, keep_last_n=3)
                t0 = time.time()
                for i in range(steps):
                    out = exe.run(main_p, feed=feed, fetch_list=[loss])
                    if mode == "sync" and (i + 1) % every == 0:
                        s, state, extra = exe.checkpoint_snapshot(
                            main_p, scope)
                        mgr.save(s, state, extra=extra, sync=True)
                np.asarray(out[0])
                dt = time.time() - t0
                if mgr is not None:
                    mgr.wait()
                    mgr.close()
        finally:
            if root is not None:
                import shutil
                shutil.rmtree(root, ignore_errors=True)
        return steps * batch * seq / dt

    # best-of-N per mode: CPU CI boxes swing 20%+ run-to-run, and the A/B
    # claim is about the checkpoint path, not scheduler noise
    base = max(measure("off") for _ in range(reps))
    async_tps = max(measure("async") for _ in range(reps))
    sync_tps = max(measure("sync") for _ in range(reps))
    result = {
        "metric": "ckpt_async_overhead_pct",
        "value": round((base / async_tps - 1.0) * 100, 2),
        "unit": "%",
        "steps": steps,
        "save_every_n_steps": every,
        "tokens_per_sec": {"off": round(base, 1),
                           "async": round(async_tps, 1),
                           "sync": round(sync_tps, 1)},
        "sync_overhead_pct": round((base / sync_tps - 1.0) * 100, 2),
    }
    print(json.dumps(result))


def elastic_main():
    """Elastic-schedule A/B (`python bench.py --elastic` or
    BENCH_MODE=elastic): steady-state training throughput of the plain
    data-parallel step vs the elasticized one (distributed/elastic.py) on
    the full local mesh.  The elastic path swaps psum gradient reduction
    for the world-size-invariant ordered fold (all_gather + explicit
    left-fold continuation) plus the masked commit — topology-invariant
    bitwise resume is bought with extra gradient wire volume and the fold
    chain, and this mode prices it.  Also re-runs two global steps on a
    half-size mesh and reports whether the committed loss matched the
    full-mesh value bitwise (the elastic contract, continuously
    verified).  Prints ONE JSON line."""
    import tempfile
    import jax
    if os.environ.get("BENCH_FORCE_CPU") or not os.environ.get(
            "BENCH_ELASTIC_TPU"):
        jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.distributed.compiled_program import CompiledProgram
    from paddle_tpu.distributed.elastic import elasticize, rebucket_feeds
    from paddle_tpu.static import layers

    steps = int(os.environ.get("BENCH_ELASTIC_STEPS", 40))
    world = len(jax.devices())
    logical = 1 << (world.bit_length() - 1)  # pow2 floor
    batch_per_rank = int(os.environ.get("BENCH_ELASTIC_BATCH", 4))
    hidden = int(os.environ.get("BENCH_ELASTIC_HIDDEN", 256))
    rng = np.random.RandomState(0)
    gb = logical * batch_per_rank
    feeds = [{"x": rng.rand(gb, hidden).astype(np.float32),
              "y": rng.rand(gb, 1).astype(np.float32)}
             for _ in range(steps)]

    def build(elastic):
        _reset_unique_names()
        main_p, startup_p = static.Program(), static.Program()
        with static.program_guard(main_p, startup_p):
            x = layers.data("x", [-1, hidden])
            y = layers.data("y", [-1, 1])
            h = layers.fc(x, hidden, act="relu")
            h = layers.fc(h, hidden, act="relu")
            pred = layers.fc(h, 1)
            loss = layers.mean(
                layers.square(layers.elementwise_sub(pred, y)))
            static.Adam(learning_rate=1e-3).minimize(loss)
        meta = None
        if elastic:
            meta = elasticize(main_p, startup_p, logical_dp=logical,
                              loss_name=loss)
        return main_p, startup_p, loss, meta

    def measure(elastic, run_world, n_steps, warm=2):
        warm = min(warm, max(0, n_steps - 1))
        main_p, startup_p, loss, meta = build(elastic)
        cp = CompiledProgram(main_p).with_data_parallel(
            loss_name=loss.name,
            places=list(jax.devices())[:run_world])
        fetch = meta["loss_avg"] if elastic else loss
        exe = static.Executor()
        scope = static.Scope()
        losses = []
        t0 = time.time()
        with static.scope_guard(scope):
            exe.run(startup_p)
            for i, f in enumerate(feeds[:n_steps]):
                if i == warm:
                    t0 = time.time()
                for mf in rebucket_feeds(f, logical, run_world):
                    out = exe.run(cp, feed=mf, fetch_list=[fetch])
                losses.append(np.asarray(out[0]))
        dt = max(1e-9, time.time() - t0)
        return (n_steps - warm) * gb / dt, losses

    # A/B on `logical` devices, not `world`: a non-power-of-two device
    # count would not divide the schedule (the pow2 floor is the mesh)
    plain_tps, _ = measure(False, logical, steps)
    elastic_tps, ref_losses = measure(True, logical, steps)
    # contract check: two global steps on a half-size mesh, same math
    _, half_losses = measure(True, max(1, logical // 2), 4)
    bitwise = all(np.array_equal(a, b)
                  for a, b in zip(ref_losses[:4], half_losses))
    result = {
        "metric": "elastic_overhead_pct",
        "value": round((plain_tps / elastic_tps - 1.0) * 100, 2),
        "unit": "%",
        "steps": steps,
        "logical_dp": logical,
        "rows_per_sec": {"plain_dp": round(plain_tps, 1),
                         "elastic": round(elastic_tps, 1)},
        "half_mesh_loss_bitwise": bool(bitwise),
    }
    print(json.dumps(result))


def serving_main():
    """Serving benchmark mode (`python bench.py --serving` or
    BENCH_MODE=serving): N concurrent clients hammer the HTTP server's
    /predict on a tiny saved model and the steady-state QPS + p99 is
    measured twice — dynamic batching ON vs the serial-lock baseline —
    so the coalescing win is a number, not a claim.  Prints ONE JSON
    line like the training mode."""
    import tempfile
    if os.environ.get("BENCH_FORCE_CPU"):
        import jax
        jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import serve_smoke
    from paddle_tpu.inference.server import InferenceServer
    from paddle_tpu.serving.metrics import reset_serving_stats

    clients = int(os.environ.get("BENCH_SERVING_CLIENTS", 8))
    requests = int(os.environ.get("BENCH_SERVING_REQUESTS", 25))
    max_batch = int(os.environ.get("BENCH_SERVING_MAX_BATCH", 8))
    # ~1ms fill window measured best on CPU: requests pile up naturally
    # while the device runs, so a long stall only adds latency
    wait_ms = float(os.environ.get("BENCH_SERVING_WAIT_MS", 1.0))
    model_dir = tempfile.mkdtemp(prefix="bench_serving_")
    # weights-streaming-bound mlp (2048 hidden x 8 layers): a batch-8 run
    # streams the same 128MB of weights as batch-1, so coalescing is
    # near-free — the serving regime batching exists for (on the TPU the
    # same holds for MXU occupancy at small batch)
    xb, ref, out_name = serve_smoke.save_tiny_model(
        model_dir, in_dim=256, classes=8, hidden=2048, depth=8)
    payloads = [{"inputs": {"x": xb[j:j + 1].tolist()}}
                for j in range(xb.shape[0])]

    def measure(batching):
        reset_serving_stats()
        srv = InferenceServer(model_dir, batching=batching,
                              max_batch=max_batch, max_wait_ms=wait_ms,
                              max_queue=max(64, clients * 4))
        srv.start()
        try:
            base = f"http://{srv.host}:{srv.port}"
            b = 1
            while b <= max_batch:  # warm every pow2 bucket
                serve_smoke.http_json(
                    base + "/predict",
                    {"inputs": {"x": np.repeat(xb[:1], b, 0).tolist()}})
                b <<= 1
            # untimed pre-load: absorbs process-global first-dispatch
            # costs so neither phase's number depends on phase ORDER
            serve_smoke.run_load(base, payloads, clients,
                                 max(3, requests // 5))
            warm_traces = serve_smoke.http_json(base + "/stats")[
                "predictor_cache"]["traces"]
            reset_serving_stats()  # latency percentiles: steady only
            dt = serve_smoke.run_load(base, payloads, clients, requests)
            stats = serve_smoke.http_json(base + "/stats")
        finally:
            srv.stop()
        s = stats["serving"]
        lat = s.get("serving.latency_ms", {})
        return {
            "qps": round(clients * requests / dt, 2),
            "p50_ms": round(lat.get("p50", 0.0), 3),
            "p99_ms": round(lat.get("p99", 0.0), 3),
            "coalesced": s.get("serving.batch.coalesced", 0),
            "batch_runs": s.get("serving.batch.runs", 0),
            "traces_after_warmup":
                stats["predictor_cache"]["traces"] - warm_traces,
        }

    batched = measure(batching=True)
    serial = measure(batching=False)
    result = {
        "metric": "serving_steady_qps",
        "value": batched["qps"],
        "unit": "req/s",
        "clients": clients,
        "requests_per_client": requests,
        "p50_ms": batched["p50_ms"],
        "p99_ms": batched["p99_ms"],
        "coalesced_batches": batched["coalesced"],
        "batch_runs": batched["batch_runs"],
        "traces_after_warmup": batched["traces_after_warmup"],
        "serial_baseline_qps": serial["qps"],
        "serial_p99_ms": serial["p99_ms"],
        "speedup_vs_serial": round(batched["qps"] /
                                   max(serial["qps"], 1e-9), 3),
        "paged_kv": _serving_paged_ab(),
        "radix_prefix": _serving_radix_ab(),
        "speculative": _serving_speculative_ab(),
        "tp_decode": _serving_tp_decode_ab(),
        "int8_paged": _serving_int8_ab(),
    }
    print(json.dumps(result))


def _serving_paged_ab():
    """Paged-vs-fixed-slot generation A/B at EQUAL KV HBM: the planner
    (`static.page_budget`, the HBM-walker sizing path) chooses the page
    budget; the fixed-slot baseline gets the SAME kv byte budget spent
    as dense worst-case max-context slots (generously uncharged for
    workspace, biasing the comparison AGAINST paging).  Both engines
    drain an identical shared-system-prompt workload; reported are peak
    concurrent sequences (the capacity claim), QPS/chip, p50/p95/p99,
    page-occupancy/sharing stats, and token-equality vs per-sequence
    generate()."""
    import threading
    import paddle_tpu.dygraph as dg
    from paddle_tpu.models import GPTConfig, GPTModel, GPTForGeneration
    from paddle_tpu.serving import ContinuousBatchingEngine, PagedKVPool
    from paddle_tpu.serving.metrics import (percentiles,
                                            reset_serving_stats)
    from paddle_tpu.static import page_budget
    import jax

    n_req = int(os.environ.get("BENCH_SERVING_GEN_REQUESTS", 24))
    kv_hbm = int(os.environ.get("BENCH_SERVING_GEN_HBM", 1 << 20))
    max_new = 8
    rng = np.random.RandomState(7)
    with dg.guard():
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, max_position=128, dropout=0.0)
        m = GPTForGeneration(GPTModel(cfg))
        m.eval()
        weight_bytes = int(sum(np.asarray(p.numpy()).nbytes
                               for p in m.gpt.parameters()))
        # planner-chosen budget: weights + the KV grant, never hand-set
        plan = page_budget(m, page_tokens=16, max_context=128,
                           hbm_bytes=weight_bytes + kv_hbm)
        token_bytes = plan["page_bytes"] // plan["page_tokens"]
        # fixed-slot capacity at the same kv budget: every slot commits
        # a dense max-context buffer up front
        fixed_slots = max(1, plan["kv_bytes"] //
                          (token_bytes * plan["max_context"]))
        # shared 16-token system prompt + unique 8-token user tail
        head = rng.randint(2, 64, (16,)).astype(np.int64)
        prompts = [np.concatenate([head,
                                   rng.randint(2, 64, (8,))
                                   .astype(np.int64)])
                   for _ in range(n_req)]
        refs = [np.asarray(m.generate(p[None], max_length=max_new,
                                      decode_strategy="greedy_search")[0])
                for p in prompts[:3]]

        def drain(eng, pool=None):
            reset_serving_stats()
            peak = {"slots": 0, "pages": 0}
            done = threading.Event()

            def poll():
                while not done.is_set():
                    peak["slots"] = max(peak["slots"], eng.active_slots)
                    if pool is not None:
                        peak["pages"] = max(
                            peak["pages"],
                            pool.num_pages - pool.pages_free)
                    time.sleep(0.001)

            eng.start()
            t = threading.Thread(target=poll, daemon=True)
            t.start()
            t0 = time.time()
            try:
                futs = [eng.submit(p, max_length=max_new)
                        for p in prompts]
                outs = [np.asarray(f.result(timeout=300)) for f in futs]
            finally:
                done.set()
                eng.stop()
            dt = time.time() - t0
            t.join(timeout=1.0)
            lat = percentiles()
            return outs, dt, peak, lat

        pool = PagedKVPool.from_plan(plan)
        paged_eng = ContinuousBatchingEngine(m, max_slots=n_req,
                                             kv_pool=pool)
        p_outs, p_dt, p_peak, p_lat = drain(paged_eng, pool)
        pool_stats = pool.stats()
        pool.assert_drained()
        fixed_eng = ContinuousBatchingEngine(m, max_slots=fixed_slots)
        f_outs, f_dt, f_peak, f_lat = drain(fixed_eng)

    token_equal = all(
        np.array_equal(p_outs[i], refs[i]) for i in range(len(refs))
    ) and all(np.array_equal(f_outs[i], p_outs[i])
              for i in range(len(p_outs)))
    chips = max(1, jax.device_count())

    def _side(outs, dt, peak, lat):
        return {
            "qps": round(len(outs) / dt, 2),
            "qps_per_chip": round(len(outs) / dt / chips, 2),
            "tokens_per_s": round(len(outs) * max_new / dt, 1),
            "wall_s": round(dt, 2),
            "peak_concurrent_seqs": peak["slots"],
            "p50_ms": round(lat.get("p50", 0.0), 3),
            "p95_ms": round(lat.get("p95", 0.0), 3),
            "p99_ms": round(lat.get("p99", 0.0), 3),
        }

    paged_side = _side(p_outs, p_dt, p_peak, p_lat)
    paged_side["peak_pages_used"] = p_peak["pages"]
    paged_side["page_occupancy_peak"] = round(
        p_peak["pages"] / max(1, plan["pages"]), 4)
    fixed_side = _side(f_outs, f_dt, f_peak, f_lat)
    return {
        "requests": n_req,
        "max_new_tokens": max_new,
        "kv_budget_bytes": plan["kv_bytes"],
        "plan": {k: plan[k] for k in
                 ("pages", "page_tokens", "max_slots", "max_context",
                  "kv_bytes", "workspace_bytes", "source")},
        "fixed_slots_at_equal_hbm": fixed_slots,
        "paged": paged_side,
        "fixed": fixed_side,
        "pool": pool_stats,
        "capacity_ratio": round(
            paged_side["peak_concurrent_seqs"] /
            max(1, fixed_side["peak_concurrent_seqs"]), 2),
        "token_equal_vs_generate": bool(token_equal),
    }


def _serving_radix_ab():
    """Retained-prefix generation A/B on a repeated-system-prompt
    trace: a few long system prompts recur across the request stream
    with unique user tails, so after each head's first retirement the
    radix tree serves its pages back and prefill runs only the
    uncovered suffix.  The cold side is an identical engine with no
    prefix cache.  Requests drain sequentially (each retires before the
    next prefills) so the hit pattern is the trace's, not a scheduling
    race's.  Reported are the retained-hit rate, prefill tokens skipped
    vs actually run, tokens/s on both sides, and token-equality — a
    radix hit must never change output."""
    import paddle_tpu.dygraph as dg
    from paddle_tpu.models import GPTConfig, GPTModel, GPTForGeneration
    from paddle_tpu.serving import (ContinuousBatchingEngine,
                                    PagedKVPool, RadixPrefixCache,
                                    metrics)
    from paddle_tpu.serving.metrics import reset_serving_stats
    from paddle_tpu.static import page_budget

    n_req = int(os.environ.get("BENCH_SERVING_RADIX_REQUESTS", 24))
    kv_hbm = int(os.environ.get("BENCH_SERVING_GEN_HBM", 1 << 20))
    n_heads, head_tokens, max_new = 3, 32, 8
    rng = np.random.RandomState(17)
    with dg.guard():
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, max_position=128, dropout=0.0)
        m = GPTForGeneration(GPTModel(cfg))
        m.eval()
        weight_bytes = int(sum(np.asarray(p.numpy()).nbytes
                               for p in m.gpt.parameters()))
        plan = page_budget(m, page_tokens=16, max_context=128,
                           hbm_bytes=weight_bytes + kv_hbm)
        heads = [rng.randint(2, 64, (head_tokens,)).astype(np.int64)
                 for _ in range(n_heads)]
        prompts = [np.concatenate([heads[i % n_heads],
                                   rng.randint(2, 64, (8,))
                                   .astype(np.int64)])
                   for i in range(n_req)]

        def drain_seq(eng):
            reset_serving_stats()
            eng.start()
            t0 = time.time()
            try:
                outs = [np.asarray(eng.submit(p, max_length=max_new)
                                   .result(timeout=300))
                        for p in prompts]
            finally:
                eng.stop()
            return outs, time.time() - t0

        cold_pool = PagedKVPool.from_plan(plan)
        c_outs, c_dt = drain_seq(
            ContinuousBatchingEngine(m, max_slots=4, kv_pool=cold_pool))
        c_prefill = metrics.counter("gen.prefill_tokens")
        cold_pool.assert_drained()

        pool = PagedKVPool.from_plan(plan)
        radix = RadixPrefixCache.from_plan(pool)
        w_outs, w_dt = drain_seq(
            ContinuousBatchingEngine(m, max_slots=4, kv_pool=pool,
                                     prefix_cache=radix))
        w_prefill = metrics.counter("gen.prefill_tokens")
        hit_tokens = metrics.counter("kv.radix_hit_tokens")
        retained = pool.pages_retained
        pool.assert_drained()
        radix.clear()
        pool.assert_drained()

    token_equal = all(np.array_equal(a, b)
                      for a, b in zip(w_outs, c_outs))
    return {
        "requests": n_req,
        "distinct_heads": n_heads,
        "head_tokens": head_tokens,
        "watermarks": [radix.low_watermark, radix.high_watermark],
        "radix_hits": radix.hits,
        "hit_rate": round(radix.hits / max(1, n_req), 3),
        "prefill_tokens_skipped": int(hit_tokens),
        "prefill_tokens_cold": int(c_prefill),
        "prefill_tokens_warm": int(w_prefill),
        "retained_pages_at_drain": int(retained),
        "evicted_pages": radix.evicted_pages,
        "tokens_per_s_warm": round(n_req * max_new / w_dt, 1),
        "tokens_per_s_cold": round(n_req * max_new / c_dt, 1),
        "speedup_vs_cold": round(c_dt / max(w_dt, 1e-9), 3),
        "token_equal_vs_cold": bool(token_equal),
    }


def _serving_speculative_ab():
    """Speculative-decode generation A/B: a 2-layer stamped sibling
    proposes k tokens per slot and the target verifies the whole batch
    in one step; the plain side is the same paged engine with no draft.
    The stamp here is full-depth (the target IS 2 layers) so acceptance
    is total and accepted-tokens/step approaches 1 + k — the machinery
    ceiling; production drafts are shallower and land in between.  Both
    sides drain the same concurrent greedy workload; reported are
    accepted/step, proposal/rollback totals, wall-clock on both sides,
    and token-equality — rejection sampling must be invisible in
    output."""
    import paddle_tpu.dygraph as dg
    from paddle_tpu.models import GPTConfig, GPTModel, GPTForGeneration
    from paddle_tpu.serving import (ContinuousBatchingEngine,
                                    PagedKVPool, SpeculativeDecoder,
                                    metrics, stamp_draft)
    from paddle_tpu.serving.metrics import reset_serving_stats
    from paddle_tpu.static import page_budget

    n_req = int(os.environ.get("BENCH_SERVING_SPEC_REQUESTS", 8))
    kv_hbm = int(os.environ.get("BENCH_SERVING_GEN_HBM", 1 << 20))
    max_new, k = 16, 3
    rng = np.random.RandomState(19)
    with dg.guard():
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, max_position=128, dropout=0.0)
        m = GPTForGeneration(GPTModel(cfg))
        m.eval()
        weight_bytes = int(sum(np.asarray(p.numpy()).nbytes
                               for p in m.gpt.parameters()))
        plan = page_budget(m, page_tokens=16, max_context=128,
                           hbm_bytes=weight_bytes + kv_hbm,
                           draft_layers=2)
        prompts = [rng.randint(2, 64, (8 + (i % 4),)).astype(np.int64)
                   for i in range(n_req)]

        def drain(eng):
            reset_serving_stats()
            eng.start()
            t0 = time.time()
            try:
                futs = [eng.submit(p, max_length=max_new)
                        for p in prompts]
                outs = [np.asarray(f.result(timeout=300))
                        for f in futs]
            finally:
                eng.stop()
            return outs, time.time() - t0

        plain_pool = PagedKVPool.from_plan(plan)
        p_outs, p_dt = drain(
            ContinuousBatchingEngine(m, max_slots=4,
                                     kv_pool=plain_pool))
        plain_pool.assert_drained()

        spec = SpeculativeDecoder(stamp_draft(m, num_layers=2), k=k)
        pool = PagedKVPool.from_plan(plan)
        s_outs, s_dt = drain(
            ContinuousBatchingEngine(m, max_slots=4, kv_pool=pool,
                                     speculative=spec))
        steps = metrics.counter("spec.steps")
        proposed = metrics.counter("spec.proposed")
        accepted = metrics.counter("spec.accepted")
        rolled = metrics.counter("spec.rollback_cols")
        # per-ROW commit depth (the engine observes each row's committed
        # count every verify step) — gen.tokens / spec.steps would
        # conflate batch occupancy with speculation depth
        per_row = metrics.percentiles("spec.accepted_per_step")
        pool.assert_drained()

    token_equal = all(np.array_equal(a, b)
                      for a, b in zip(s_outs, p_outs))
    return {
        "requests": n_req,
        "max_new_tokens": max_new,
        "draft_layers": 2,
        "k": k,
        "draft_kv_bytes": plan["draft_kv_bytes"],
        "accepted_per_step": round(per_row.get("mean", 0.0), 2),
        "verify_steps": int(steps),
        "proposed": int(proposed),
        "accepted": int(accepted),
        "rollback_cols": int(rolled),
        "draft_tokens": int(spec.draft_tokens),
        "wall_s_spec": round(s_dt, 2),
        "wall_s_plain": round(p_dt, 2),
        "speedup_vs_plain": round(p_dt / max(s_dt, 1e-9), 3),
        "token_equal_vs_plain": bool(token_equal),
    }


def _serving_tp_decode_ab():
    """tp-sharded decode A/B at EQUAL per-chip HBM: the same model, the
    same pinned per-chip budget, page pools carved by
    `static.page_budget` at tp=1 and tp=2.  At tp=2 each chip holds
    half the Megatron-splittable weights and half of every KV byte
    (heads shard), so the per-chip budget carves more pages — reported
    as page capacity and peak concurrent sequences — while the decode
    itself runs `serving.TPShardedDecoder`'s CompiledProgram across the
    dp×mp mesh.  Both sides drain the same greedy workload;
    token-equality vs the tp=1 engine is ASSERTED (sharded math must be
    invisible in output), tokens/s measures what the mp collectives
    cost on this host."""
    import paddle_tpu.dygraph as dg
    from paddle_tpu.models import GPTConfig, GPTModel, GPTForGeneration
    from paddle_tpu.serving import ContinuousBatchingEngine, PagedKVPool
    from paddle_tpu.serving.metrics import reset_serving_stats
    from paddle_tpu.static import page_budget

    n_req = int(os.environ.get("BENCH_SERVING_TP_REQUESTS", 8))
    tp = int(os.environ.get("BENCH_SERVING_TP_DEGREE", 2))
    kv_hbm = int(os.environ.get("BENCH_SERVING_TP_HBM", 1 << 18))
    max_new = 8
    rng = np.random.RandomState(23)
    with dg.guard():
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_position=128, dropout=0.0)
        m = GPTForGeneration(GPTModel(cfg))
        m.eval()
        weight_bytes = int(sum(np.asarray(p.numpy()).nbytes
                               for p in m.gpt.parameters()))
        # the PINNED per-chip budget both sides must live inside —
        # weights + a thin KV grant, so the tp=1 pool is starved and
        # the tp=2 per-chip savings convert into pages
        hbm = weight_bytes + kv_hbm
        plan1 = page_budget(m, page_tokens=16, max_context=128,
                            hbm_bytes=hbm)
        plan2 = page_budget(m, page_tokens=16, max_context=128,
                            hbm_bytes=hbm, tp_degree=tp)
        prompts = [rng.randint(2, 64, (6 + (i % 5),)).astype(np.int64)
                   for i in range(n_req)]

        def drain(eng):
            reset_serving_stats()
            eng.start()
            t0 = time.time()
            try:
                futs = [eng.submit(p, max_length=max_new)
                        for p in prompts]
                outs = [np.asarray(f.result(timeout=600))
                        for f in futs]
            finally:
                eng.stop()
            return outs, time.time() - t0

        pool1 = PagedKVPool.from_plan(plan1)
        outs1, dt1 = drain(ContinuousBatchingEngine(
            m, max_slots=4, kv_pool=pool1))
        pool1.assert_drained()

        pool2 = PagedKVPool.from_plan(plan2)
        eng2 = ContinuousBatchingEngine(m, max_slots=4, kv_pool=pool2)
        outs2, dt2 = drain(eng2)
        pool2.assert_drained()

    # the tp A/B's contract: sharding must be invisible in output
    assert all(np.array_equal(a, b) for a, b in zip(outs1, outs2)), \
        "tp-sharded decode diverged from single-chip greedy"
    tok = n_req * max_new
    return {
        "requests": n_req,
        "max_new_tokens": max_new,
        "tp_degree": eng2.tp_degree,
        "hbm_per_chip_bytes": hbm,
        "pages_tp1": plan1["pages"],
        "pages_tp2": plan2["pages"],
        "page_capacity_ratio": round(plan2["pages"] /
                                     max(1, plan1["pages"]), 2),
        "max_slots_tp1": plan1["max_slots"],
        "max_slots_tp2": plan2["max_slots"],
        "tokens_per_s_tp1": round(tok / dt1, 1),
        "tokens_per_s_tp2": round(tok / dt2, 1),
        "wall_s_tp1": round(dt1, 2),
        "wall_s_tp2": round(dt2, 2),
        "token_equal": True,
    }


def _serving_int8_ab():
    """int8-vs-fp32 generation A/B at EQUAL per-chip HBM: the same
    model, the same pinned budget (weights + a thin KV grant), pools
    carved by `static.page_budget` at fp32 and at
    kv_dtype/weight_dtype="int8".  int8 KV pages store half the bytes
    (plus the fp32 scale sidecar, which the planner charges) and int8
    weights return 3 of every 4 weight bytes to the carve, so the int8
    side holds ~2-4x the pages and concurrent sequences — the capacity
    claim is ASSERTED at >= 1.9x, and so is token-equality: on this
    model the per-channel weight grid plus per-page KV scales leave
    greedy argmax unchanged (the tested contract; see docs/serving.md
    for the tolerance rule if a future model breaks it).  tokens/s on
    both sides measures what dynamic activation quant costs on a host
    CPU where int8 has no MXU to win back — the 2x rate claim is the
    queued on-chip row, not this number."""
    import threading
    import paddle_tpu.dygraph as dg
    from paddle_tpu.models import GPTConfig, GPTModel, GPTForGeneration
    from paddle_tpu.serving import ContinuousBatchingEngine, PagedKVPool
    from paddle_tpu.serving.metrics import reset_serving_stats
    from paddle_tpu.static import page_budget

    n_req = int(os.environ.get("BENCH_SERVING_INT8_REQUESTS", 16))
    tp = int(os.environ.get("BENCH_SERVING_INT8_TP", 1))
    kv_hbm = int(os.environ.get("BENCH_SERVING_INT8_HBM", 1 << 18))
    max_new = 8
    rng = np.random.RandomState(29)
    with dg.guard():
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_position=128, dropout=0.0)
        m = GPTForGeneration(GPTModel(cfg))
        m.eval()
        weight_bytes = int(sum(np.asarray(p.numpy()).nbytes
                               for p in m.gpt.parameters()))
        # the PINNED per-chip budget both sides must live inside
        hbm = weight_bytes + kv_hbm
        plan_f = page_budget(m, page_tokens=16, max_context=128,
                             hbm_bytes=hbm, tp_degree=tp)
        plan_i = page_budget(m, page_tokens=16, max_context=128,
                             hbm_bytes=hbm, tp_degree=tp,
                             kv_dtype="int8", weight_dtype="int8")
        prompts = [rng.randint(2, 64, (6 + (i % 5),)).astype(np.int64)
                   for i in range(n_req)]

        def drain(eng, pool):
            reset_serving_stats()
            peak = {"slots": 0, "pages": 0}
            done = threading.Event()

            def poll():
                while not done.is_set():
                    peak["slots"] = max(peak["slots"], eng.active_slots)
                    peak["pages"] = max(peak["pages"],
                                        pool.num_pages - pool.pages_free)
                    time.sleep(0.001)

            eng.start()
            t = threading.Thread(target=poll, daemon=True)
            t.start()
            t0 = time.time()
            try:
                futs = [eng.submit(p, max_length=max_new)
                        for p in prompts]
                outs = [np.asarray(f.result(timeout=600))
                        for f in futs]
            finally:
                done.set()
                eng.stop()
            dt = time.time() - t0
            t.join(timeout=1.0)
            return outs, dt, peak

        pool_f = PagedKVPool.from_plan(plan_f)
        f_outs, f_dt, f_peak = drain(ContinuousBatchingEngine(
            m, max_slots=n_req, kv_pool=pool_f), pool_f)
        pool_f.assert_drained()

        pool_i = PagedKVPool.from_plan(plan_i)
        eng_i = ContinuousBatchingEngine(m, max_slots=n_req,
                                         kv_pool=pool_i)
        i_outs, i_dt, i_peak = drain(eng_i, pool_i)
        i_stats = pool_i.stats()
        pool_i.assert_drained()

    # the int8 A/B's two contracts
    page_ratio = plan_i["pages"] / max(1, plan_f["pages"])
    assert page_ratio >= 1.9, \
        f"int8 carve only {page_ratio:.2f}x fp32 pages at equal HBM"
    assert all(np.array_equal(a, b) for a, b in zip(f_outs, i_outs)), \
        "int8 decode diverged from fp32 greedy"
    tok = n_req * max_new
    return {
        "requests": n_req,
        "max_new_tokens": max_new,
        "tp_degree": tp,
        "hbm_per_chip_bytes": hbm,
        "kv_dtype": i_stats["kv_dtype"],
        "weight_dtype": eng_i.weight_dtype,
        "pages_fp32": plan_f["pages"],
        "pages_int8": plan_i["pages"],
        "page_capacity_ratio": round(page_ratio, 2),
        "peak_concurrent_seqs_fp32": f_peak["slots"],
        "peak_concurrent_seqs_int8": i_peak["slots"],
        "peak_pages_used_int8": i_peak["pages"],
        "quant_scale_clips": i_stats["quant_scale_clips"],
        "tokens_per_s_fp32": round(tok / f_dt, 1),
        "tokens_per_s_int8": round(tok / i_dt, 1),
        "wall_s_fp32": round(f_dt, 2),
        "wall_s_int8": round(i_dt, 2),
        "token_equal": True,
    }


def _argv_value(flag):
    """Optional value following `flag` in argv (None when the flag is
    absent, "" when it is last or followed by another --option)."""
    if flag not in sys.argv:
        return None
    i = sys.argv.index(flag)
    if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("--"):
        return sys.argv[i + 1]
    return ""


def _bench_knobs():
    """Shared --remat / --grad-merge / --ring knob parsing (argv wins
    over env).  Returns (remat_mode, grad_merge_k, use_ring) where
    remat_mode is "" / "always" / "auto".  Both `--remat` and
    `--remat auto` work, matching the BENCH_REMAT=1|auto spellings."""
    remat = _argv_value("--remat")
    if remat is None:
        remat = os.environ.get("BENCH_REMAT", "")
    elif remat == "":
        remat = os.environ.get("BENCH_REMAT", "") or "1"
    if remat in ("0", "false"):
        remat = ""
    remat_mode = "" if not remat else ("auto" if remat == "auto"
                                       else "always")
    gm_raw = _argv_value("--grad-merge")
    if gm_raw is None or gm_raw == "":
        if gm_raw == "":
            raise SystemExit("bench: --grad-merge needs a step count "
                             "(e.g. --grad-merge 2)")
        gm_raw = os.environ.get("BENCH_GRAD_MERGE", "0")
    gm = int(gm_raw or 0)
    ring = os.environ.get("BENCH_RING", "") not in ("", "0", "false") \
        or "--ring" in sys.argv
    return remat_mode, gm, ring


def _dp_shard_knob():
    """--dp-shard [N] / BENCH_DP_SHARD=N: ZeRO optimizer-state sharding
    A/B (distributed/sharding.py).  A bare --dp-shard targets the
    v5e-32 pod slice's 8-chip host world."""
    raw = _argv_value("--dp-shard")
    if raw is None:
        raw = os.environ.get("BENCH_DP_SHARD", "0")
    elif raw == "":
        raw = os.environ.get("BENCH_DP_SHARD", "") or "8"
    ds = int(raw or 0)
    if ds < 0:
        raise SystemExit("bench: --dp-shard needs a non-negative world "
                         "size (e.g. --dp-shard 8)")
    return ds


def _zero_stage_knob():
    """--zero-stage S / BENCH_ZERO_STAGE=S: which ZeRO stage the
    --dp-shard rewrite applies (1 = optimizer slots, 2 = + sharded
    gradient accumulation under --grad-merge, 3 = full parameter
    sharding with JIT gathers).  Default 1; ignored without a dp_shard
    world."""
    raw = _argv_value("--zero-stage")
    if raw is None or raw == "":
        raw = os.environ.get("BENCH_ZERO_STAGE", "1")
    zs = int(raw or 1)
    if zs == 0:
        return 1  # 0 = "unset", mirroring BENCH_DP_SHARD=0 (ignored
        # anyway without a dp_shard world)
    if zs not in (1, 2, 3):
        raise SystemExit("bench: --zero-stage must be 1, 2 or 3")
    return zs


def _tp_knob():
    """--tp [N] / BENCH_TP_DEGREE=N: Megatron tensor-parallel A/B — the
    model builds through the tensor_parallel builders at degree N
    (models.build_transformer_lm).  On this bench's single-device
    Executor path the Megatron collectives degrade to identity, so
    tokens/s measures the tp build's dispatch/fusion overhead while
    predicted_peak_bytes (walker tp division) and wire_bytes_per_axis
    (mp ring at its own degree, batch-bound) report the dp×tp mesh
    story — the mesh numbers need CompiledProgram over real chips.
    A bare --tp targets degree 2 (the v5e 4×2 host split)."""
    raw = _argv_value("--tp")
    if raw is None:
        raw = os.environ.get("BENCH_TP_DEGREE", "0")
    elif raw == "":
        raw = os.environ.get("BENCH_TP_DEGREE", "") or "2"
    tp = int(raw or 0)
    if tp < 0:
        raise SystemExit("bench: --tp needs a non-negative degree "
                         "(e.g. --tp 2)")
    return 0 if tp == 1 else tp


def seq_ladder_main():
    """Sequence-length ladder (`python bench.py --seq-ladder` or
    BENCH_MODE=seq_ladder): builds the bench model at each rung —
    optionally with remat (BENCH_REMAT=1/auto) and/or ring attention
    (BENCH_RING=1) — and emits the HBM estimator's PREDICTED peak
    alongside measured tokens/s, one JSON line with the whole ladder.
    On chip, rungs the estimator predicts to OOM are SKIPPED instead of
    burning chip minutes on an allocator error; on CPU the rungs
    shrink so the mode runs end-to-end in CI.  Token budget per rung is
    constant (BENCH_LADDER_TOKENS) so batch = tokens/seq."""
    import jax
    _require_tpu_or_forced_cpu()
    import paddle_tpu.static as static
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.core.program import _reset_unique_names

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    default_ladder = "512,1024,2048,4096" if on_tpu else "64,128"
    seqs = [int(s) for s in os.environ.get(
        "BENCH_SEQ_LADDER", default_ladder).split(",") if s]
    # estimator-only extension rungs: planned (knobs chosen by
    # static.plan_program) and verdicted but NEVER executed — the
    # long-context regime where even one step would burn chip time
    default_est = "8192,16384,32768" if on_tpu else "256"
    est_seqs = [int(s) for s in os.environ.get(
        "BENCH_SEQ_LADDER_EST", default_est).split(",") if s]
    tokens = int(os.environ.get("BENCH_LADDER_TOKENS",
                                32768 if on_tpu else 512))
    layers_n = int(os.environ.get("BENCH_LAYERS", 12 if on_tpu else 2))
    hidden = int(os.environ.get("BENCH_HIDDEN", 768 if on_tpu else 128))
    heads = int(os.environ.get("BENCH_HEADS", 12 if on_tpu else 4))
    vocab = int(os.environ.get("BENCH_VOCAB", 30522 if on_tpu else 1024))
    steps = int(os.environ.get("BENCH_STEPS", 10 if on_tpu else 5))
    use_amp = os.environ.get("BENCH_NO_AMP", "") in ("", "0", "false")
    remat_mode, _, use_ring = _bench_knobs()

    rng = np.random.RandomState(0)
    rows = []
    for seq in seqs:
        batch = max(1, tokens // seq)
        _reset_unique_names()
        if remat_mode:
            set_flags({"recompute": remat_mode, "hbm_assume_batch": batch})
        try:
            main_p, startup_p, loss = build_bert_base(
                vocab, seq, hidden, layers_n, heads, batch,
                use_amp=use_amp, use_ring=use_ring)
        finally:
            set_flags({"recompute": "", "hbm_assume_batch": 0})
        mem = static.analyze_program(main_p, batch=batch)
        row = {"seq": seq, "batch": batch,
               "predicted_peak_bytes": mem["peak_bytes"],
               "predicted_peak_gib": round(mem["peak_bytes"] / 2 ** 30, 2),
               "predicted_fits": mem["fits"],
               "remat": remat_mode or "off", "ring": use_ring}
        if on_tpu and not mem["fits"]:
            # the whole point of compile-time accounting: a predicted
            # OOM costs zero chip seconds
            row["skipped"] = "predicted OOM at " + \
                f"{mem['budget_bytes'] / 2 ** 30:.2f} GiB budget"
            rows.append(row)
            continue
        idt = np.int64 if jax.config.jax_enable_x64 else np.int32
        feed = {
            "ids": rng.randint(0, vocab, (batch, seq)).astype(idt),
            "pos": np.tile(np.arange(seq), (batch, 1)).astype(idt),
            "labels": rng.randint(0, vocab, (batch, seq, 1)).astype(idt),
        }
        exe, scope = static.Executor(), static.Scope()
        with static.scope_guard(scope):
            exe.run(startup_p)
            exe.run(main_p, feed=feed, fetch_list=[loss])   # warm/compile
            exe.run(main_p, feed=feed, fetch_list=[])
            t0 = time.time()
            for _ in range(steps - 1):
                exe.run(main_p, feed=feed, fetch_list=[])
            out = exe.run(main_p, feed=feed, fetch_list=[loss])
            np.asarray(out[0])
            dt = time.time() - t0
        exe.close()
        row["tokens_per_sec"] = round(steps * batch * seq / dt, 2)
        rows.append(row)
    # -- estimator-only rungs: plan, verdict, never execute ----------------
    for seq in est_seqs:
        batch = max(1, tokens // seq)
        variants = {}

        def _build(ring):
            _reset_unique_names()
            return build_bert_base(vocab, seq, hidden, layers_n, heads,
                                   batch, use_amp=use_amp, use_ring=ring)
        main_p, startup_p, _ = _build(False)
        ring_main, ring_startup, _ = _build(True)
        variants["ring"] = (ring_main, ring_startup)
        # estimator sweep: many rungs x full lattice — remat/ring are
        # the long-seq knobs; verification is skipped for wall time
        # (plan_smoke + tests gate the verified path)
        plan = static.plan_program(
            main_p, startup_p, world=1, batch=batch, variants=variants,
            knobs={"grad_merge": (1,), "dp_shard": (0,)}, verify=False)
        rows.append({
            "seq": seq, "batch": batch,
            "estimator_only": True,
            "planned_knobs": dict(plan.knobs),
            "predicted_peak_bytes": plan.predicted_peak_bytes,
            "predicted_peak_gib":
                round(plan.predicted_peak_bytes / 2 ** 30, 2),
            "predicted_fits": plan.predicted_fits,
            "predicted_step_ms": round(plan.predicted_step_ms, 2),
        })
    measured = [r for r in rows if "tokens_per_sec" in r]
    result = {
        "metric": "seq_ladder_tokens_per_sec",
        "value": measured[-1]["tokens_per_sec"] if measured else 0.0,
        "unit": "tokens/s",
        "on_tpu": on_tpu,
        "remat": remat_mode or "off",
        "ring": use_ring,
        "hbm_budget_bytes": static.hbm_budget_bytes(),
        "ladder": rows,
    }
    if not on_tpu:
        result["failed"] = True
        result["note"] = "CPU run; predicted peaks are the deliverable"
    print(json.dumps(result))


def tp_main():
    """Tensor-parallel A/B (`python bench.py --tp N` or
    BENCH_TP_DEGREE=N): builds the bench geometry through the
    tensor_parallel builders (models.build_transformer_lm) and trains it
    over a dp×tp CompiledProgram mesh on the local devices — the tp
    shards need a real mesh (the per-head reshapes bake local dims, so
    the single-device Executor path cannot run this build).  On a CPU
    host the mesh is the virtual 8-device test mesh; on chip it is the
    host's local chips.  Emits ONE JSON line with tokens/s, the tp walker
    verdict (`analyze_program(tp_degree=)`), and the per-axis wire
    split (`collective_wire_bytes_by_axis`, mp ring at its own degree,
    batch-bound) riding ``memory_knobs``."""
    tp = _tp_knob()
    if tp <= 1:
        raise SystemExit("bench --tp: a tensor-parallel degree >= 2 is "
                         "required in this mode (use the default bench "
                         "for the tp-off baseline)")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    if os.environ.get("BENCH_FORCE_CPU") or not os.environ.get(
            "BENCH_AUTO_TPU"):
        jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static
    from paddle_tpu.core import compile_cache
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.distributed.compiled_program import (CompiledProgram,
                                                         BuildStrategy,
                                                         insert_grad_allreduce)

    devices = jax.devices()
    on_tpu = devices[0].platform != "cpu"
    want_world = int(os.environ.get("BENCH_WORLD", "0"))
    world = min(want_world, len(devices)) if want_world else len(devices)
    if world % tp != 0 or world < tp:
        raise SystemExit(
            f"bench --tp: world {world} does not hold a tp={tp} mesh")
    dp_world = world // tp
    seq = int(os.environ.get("BENCH_SEQ", 512 if on_tpu else 32))
    layers_n = int(os.environ.get("BENCH_LAYERS", 12 if on_tpu else 2))
    hidden = int(os.environ.get("BENCH_HIDDEN", 768 if on_tpu else 64))
    heads = int(os.environ.get("BENCH_HEADS", 12 if on_tpu else 4))
    vocab = int(os.environ.get("BENCH_VOCAB", 30522 if on_tpu else 256))
    batch = int(os.environ.get("BENCH_BATCH", 64 if on_tpu else 4))
    steps = int(os.environ.get("BENCH_STEPS", 20 if on_tpu else 6))

    from paddle_tpu.models import build_transformer_lm
    _reset_unique_names()
    main_p, startup_p, loss, _ = build_transformer_lm(
        vocab_size=vocab, hidden=hidden, num_layers=layers_n,
        num_heads=heads, seq_len=seq, tensor_parallel_degree=tp)
    with static.program_guard(main_p, startup_p):
        static.Adam(learning_rate=1e-4).minimize(loss)

    # compile-time story: tp walker verdict + per-axis wire, recorded
    # before a single device cycle is spent
    _mem = static.analyze_program(main_p, batch=batch, tp_degree=tp)
    reduced = insert_grad_allreduce(main_p)
    wire_axis = static.collective_wire_bytes_by_axis(reduced, dp_world,
                                                     batch=batch)

    bs = BuildStrategy()
    bs.tensor_parallel_degree = tp
    cp = CompiledProgram(main_p).with_data_parallel(
        loss_name=loss.name, build_strategy=bs,
        places=list(devices)[:world])
    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(0)
    idt = np.int64 if jax.config.jax_enable_x64 else np.int32
    gb = batch * dp_world
    feed = {"ids": rng.randint(0, vocab, (gb, seq)).astype(idt),
            "pos": np.tile(np.arange(seq), (gb, 1)).astype(idt),
            "labels": rng.randint(0, vocab, (gb, seq, 1)).astype(idt)}
    with static.scope_guard(scope):
        exe.run(startup_p)
        exe.run(cp, feed=feed, fetch_list=[loss])      # warm/compile
        exe.run(cp, feed=feed, fetch_list=[])
        warm_traces = compile_cache.cache_stats()["traces"]
        t0 = time.time()
        for _ in range(steps - 1):
            exe.run(cp, feed=feed, fetch_list=[])
        out = exe.run(cp, feed=feed, fetch_list=[loss])
        np.asarray(out[0])
        dt = time.time() - t0
    retraces = compile_cache.cache_stats()["traces"] - warm_traces
    tokens_per_sec = steps * gb * seq / dt / world  # per chip
    result = {
        "metric": "tp_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s/chip",
        "on_tpu": on_tpu,
        "mesh": {"dp": dp_world, "tp": tp},
        "seq": seq,
        "global_batch": gb,
        "measured_step_ms": round(dt / steps * 1e3, 2),
        "retraces_after_warmup": int(retraces),
        "predicted_peak_bytes": _mem["peak_bytes"],
        "predicted_fits": _mem["fits"],
        "hbm_budget_bytes": _mem["budget_bytes"],
        "memory_knobs": {"remat": "off", "grad_merge_k": 0,
                         "ring": False, "dp_shard": 0, "zero_stage": 0,
                         "tp_degree": tp},
        "collective_bytes_per_step": {"wire_bytes_per_axis": wire_axis},
    }
    assert retraces == 0, "bench --tp: recompile inside the timed loop"
    if not on_tpu:
        result["failed"] = True
        result["note"] = ("CPU mesh run; the walker/wire predictions "
                          "are the deliverable")
    print(json.dumps(result))


def auto_main():
    """Auto-parallel planner mode (`python bench.py --auto` or
    BENCH_MODE=auto): build the bench model, let
    `static.plan_program` search the knob lattice (batch x remat x
    dp_shard x grad_merge x bucket-MB x ring variant) against the
    three-substrate cost model, APPLY the chosen plan
    (`static.apply_plan` — recorded in the applied-passes registry, so
    the verifier's V504 drift check guards later hand-edits), and run
    it data-parallel over the local mesh — the timed loop rides the
    SCANNED micro-step window (`Executor.run_steps`, K steps per device
    dispatch, commit tail hoisted when the plan says so) unless
    BENCH_AUTO_SCAN=0.  Every record stamps predicted_vs_measured_pct,
    the calibrated roofline's wall-clock error on this host
    (tools/calibrate_roofline.py).  `--dry-run` (BENCH_AUTO_DRY=1)
    stops after plan+apply and prints the plan — the path
    tools/plan_smoke.py gates.  Prints ONE JSON line."""
    dry = "--dry-run" in sys.argv or \
        os.environ.get("BENCH_AUTO_DRY", "") not in ("", "0", "false")
    want_world = int(os.environ.get("BENCH_WORLD", "0"))
    # the mode targets the LOCAL mesh; on a CPU host grow a virtual
    # 8-device mesh (same as the test conftest) — a no-op if jax
    # already initialized its backend, and ignored on TPU hosts where
    # jax.devices() is the real slice
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            f"{want_world or 8}").strip()
    import jax
    if os.environ.get("BENCH_FORCE_CPU") or not os.environ.get(
            "BENCH_AUTO_TPU"):
        jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static
    from paddle_tpu.core import compile_cache
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.distributed.compiled_program import CompiledProgram

    devices = jax.devices()
    on_tpu = devices[0].platform != "cpu"
    world = min(want_world, len(devices)) if want_world else len(devices)
    seq = int(os.environ.get("BENCH_SEQ", 512 if on_tpu else 64))
    layers_n = int(os.environ.get("BENCH_LAYERS", 12 if on_tpu else 2))
    hidden = int(os.environ.get("BENCH_HIDDEN", 768 if on_tpu else 128))
    heads = int(os.environ.get("BENCH_HEADS", 12 if on_tpu else 4))
    vocab = int(os.environ.get("BENCH_VOCAB", 30522 if on_tpu else 1024))
    use_amp = os.environ.get("BENCH_NO_AMP", "") in ("", "0", "false")
    batch = int(os.environ.get("BENCH_BATCH", "0")) or None
    steps = int(os.environ.get("BENCH_STEPS", 20 if on_tpu else 8))

    # BENCH_TP=1 / BENCH_TP_DEGREES=2,4 put the tensor-parallel axis on
    # the lattice: tp variants are auto-generated from the model config
    # through the tensor_parallel builders (no hand-feeding the winner),
    # so the BASE build uses the same static LM builder for an
    # apples-to-apples trace.  BENCH_GLOBAL_BATCH=G arms the
    # effective-global-batch constraint (gm×tp candidates can win).
    tp_env = os.environ.get("BENCH_TP_DEGREES", "")
    want_tp = tuple(int(x) for x in tp_env.split(",") if x.strip())
    use_tp_lattice = bool(want_tp) or \
        os.environ.get("BENCH_TP", "") not in ("", "0", "false")
    global_batch = int(os.environ.get("BENCH_GLOBAL_BATCH", "0")) or None

    def build(use_ring):
        _reset_unique_names()
        if use_tp_lattice:
            from paddle_tpu.models import build_transformer_lm
            main_b, startup_b, loss_b, _ = build_transformer_lm(
                vocab_size=vocab, hidden=hidden, num_layers=layers_n,
                num_heads=heads, seq_len=seq)
            with static.program_guard(main_b, startup_b):
                static.Adam(learning_rate=1e-4).minimize(loss_b)
            return main_b, startup_b, loss_b
        return build_bert_base(vocab, seq, hidden, layers_n, heads,
                               batch or 8, use_amp=use_amp,
                               use_ring=use_ring)

    from paddle_tpu.core.pass_framework import applied_passes
    t_plan = time.time()
    main_p, startup_p, loss = build(use_ring=False)
    variants = {}
    if seq >= 2048 and not use_tp_lattice:
        # the long-seq regime where the ring knob is worth searching;
        # ring attention is emitted at BUILD time, so it enters the
        # lattice as a program variant
        ring_main, ring_startup, ring_loss = build(use_ring=True)
        variants["ring"] = (ring_main, ring_startup)
    # CPU lattice keeps batches small so the proof run stays cheap;
    # the chip lattice searches the full default buckets
    knobs = None
    if not on_tpu and batch is None:
        knobs = {"batch": (2, 4, 8)}
    model_config = None
    if use_tp_lattice:
        model_config = dict(vocab_size=vocab, hidden=hidden,
                            num_layers=layers_n, num_heads=heads,
                            seq_len=seq, learning_rate=1e-4)
        if want_tp:
            knobs = dict(knobs or {})
            knobs["tp_degree"] = (0,) + want_tp
    plan = static.plan_program(main_p, startup_p, world=world,
                               batch=batch, knobs=knobs,
                               variants=variants or None,
                               model_config=model_config,
                               global_batch=global_batch)
    if plan.knobs["ring"]:
        main_p, startup_p, loss = ring_main, ring_startup, ring_loss
    tp_chosen = int(plan.knobs.get("tp_degree") or 0)
    if tp_chosen > 1:
        main_p, startup_p, loss = plan.build_variants[tp_chosen]
    static.apply_plan(main_p, startup_p, plan)
    plan_wall = time.time() - t_plan

    result = {
        "metric": "auto_plan_tokens_per_sec",
        "value": 0.0,
        "unit": "tokens/s/chip",
        "on_tpu": on_tpu,
        "world": world,
        "seq": seq,
        "plan": plan.to_dict(),
        "plan_wall_s": round(plan_wall, 2),
        "applied_passes": [e["pass"] for e in applied_passes(main_p)],
    }
    if dry:
        result["dry_run"] = True
        print(json.dumps(result))
        return

    b = plan.batch
    dp_world = world // tp_chosen if tp_chosen > 1 else world
    gb = b * dp_world
    loss_name = loss if isinstance(loss, str) else loss.name
    bs_build = None
    if tp_chosen > 1:
        from paddle_tpu.distributed.compiled_program import BuildStrategy
        bs_build = BuildStrategy()
        bs_build.tensor_parallel_degree = tp_chosen
        result["mesh"] = {"dp": dp_world, "tp": tp_chosen}
    cp = CompiledProgram(main_p).with_data_parallel(
        loss_name=loss_name, build_strategy=bs_build,
        places=list(devices)[:world])
    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(0)
    idt = np.int64 if jax.config.jax_enable_x64 else np.int32
    feed = {"ids": rng.randint(0, vocab, (gb, seq)).astype(idt),
            "pos": np.tile(np.arange(seq), (gb, 1)).astype(idt),
            "labels": rng.randint(0, vocab, (gb, seq, 1)).astype(idt)}
    # the scanned micro-step window is the DEFAULT timed hot path: K
    # steps ride ONE jitted lax.scan dispatch (Executor.run_steps), and
    # when the plan chose scan_hoist the window's commit tail (optimizer
    # update + publish allgather) runs once per window instead of once
    # per masked micro-step.  K follows the gm window so the hoist gate
    # engages; BENCH_AUTO_SCAN=0 falls back to the per-step loop.
    use_scan = os.environ.get("BENCH_AUTO_SCAN", "") not in ("0", "false")
    gm_k = max(1, int(plan.knobs.get("grad_merge") or 1))
    scan_k = gm_k if gm_k > 1 else min(4, steps)
    windows = max(1, steps // scan_k)
    with static.scope_guard(scope):
        exe.run(startup_p)
        if use_scan:
            steps = windows * scan_k
            sfeed = {n: np.stack([v] * scan_k) for n, v in feed.items()}
            outs = exe.run_steps(cp, feed=sfeed, fetch_list=[loss])
            warm_traces = compile_cache.cache_stats()["traces"]
            t0 = time.time()
            for _ in range(windows):
                outs = exe.run_steps(cp, feed=sfeed, fetch_list=[loss])
            np.asarray(outs[0])
            dt = time.time() - t0
        else:
            exe.run(cp, feed=feed, fetch_list=[loss])      # warm/compile
            exe.run(cp, feed=feed, fetch_list=[])
            warm_traces = compile_cache.cache_stats()["traces"]
            t0 = time.time()
            for _ in range(steps - 1):
                exe.run(cp, feed=feed, fetch_list=[])
            out = exe.run(cp, feed=feed, fetch_list=[loss])
            np.asarray(out[0])
            dt = time.time() - t0
    retraces = compile_cache.cache_stats()["traces"] - warm_traces
    tokens_per_sec = steps * gb * seq / dt / world  # per chip
    result["value"] = round(tokens_per_sec, 2)
    result["measured_step_ms"] = round(dt / steps * 1e3, 2)
    result["retraces_after_warmup"] = int(retraces)
    if use_scan:
        result["scan"] = {
            "k": scan_k, "windows": windows,
            "hoisted": "scan_hoist" in result["applied_passes"],
        }
    # calibration loop closure (tools/calibrate_roofline.py): when the
    # checked-in fit is trusted, predicted_step_ms is a wall-clock
    # estimate of THIS host class — stamp its error on every record so
    # drift between the fit and reality is visible in the artifact
    result["predicted_vs_measured_pct"] = round(
        abs(plan.predicted_step_ms - dt / steps * 1e3)
        / max(dt / steps * 1e3, 1e-9) * 100, 1)
    assert retraces == 0, "bench --auto: recompile inside the timed loop"
    if not on_tpu:
        result["failed"] = True
        result["note"] = ("CPU mesh run; the planner's predicted "
                          "numbers are the deliverable")
    print(json.dumps(result))


def scan_main():
    """Scanned-window A/B (`python bench.py --scan` or BENCH_MODE=scan):
    build the bench model under ZeRO (BENCH_DP_SHARD / BENCH_ZERO_STAGE,
    default stage-2 over 8 ranks) x gradient merge (BENCH_GRAD_MERGE,
    default K=4) and measure the SAME window both ways — K looped
    `Executor.run` dispatches vs ONE `Executor.run_steps` scanned
    dispatch with the commit tail (optimizer update + publish
    allgather) hoisted out of the scan body
    (distributed/scan_window).  Stamps the ring-accounted per-step wire
    of both paths (`scan_window_wire_bytes`: the looped path re-publishes
    masked-out state K times per window, the hoisted path once) and the
    dispatch counts.  Prints ONE JSON line."""
    dp = int(os.environ.get("BENCH_DP_SHARD", "8"))
    stage = int(os.environ.get("BENCH_ZERO_STAGE", "2"))
    gm_k = max(2, int(os.environ.get("BENCH_GRAD_MERGE", "4")))
    want_world = int(os.environ.get("BENCH_WORLD", "8"))
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            f"{want_world}").strip()
    import jax
    if os.environ.get("BENCH_FORCE_CPU") or not os.environ.get(
            "BENCH_SCAN_TPU"):
        jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static
    from paddle_tpu.core import compile_cache
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.distributed import scan_window_wire_bytes
    from paddle_tpu.distributed.compiled_program import CompiledProgram
    from paddle_tpu.distributed.sharding import shard_optimizer_states

    devices = jax.devices()
    on_tpu = devices[0].platform != "cpu"
    world = min(want_world, len(devices))
    seq = int(os.environ.get("BENCH_SEQ", 512 if on_tpu else 64))
    layers_n = int(os.environ.get("BENCH_LAYERS", 12 if on_tpu else 2))
    hidden = int(os.environ.get("BENCH_HIDDEN", 768 if on_tpu else 128))
    heads = int(os.environ.get("BENCH_HEADS", 12 if on_tpu else 4))
    vocab = int(os.environ.get("BENCH_VOCAB", 30522 if on_tpu else 1024))
    batch = int(os.environ.get("BENCH_BATCH", 32 if on_tpu else 2))
    windows = int(os.environ.get("BENCH_SCAN_WINDOWS", 8 if on_tpu else 3))
    use_amp = os.environ.get("BENCH_NO_AMP", "") in ("", "0", "false")

    _reset_unique_names()
    main_p, startup_p, loss = build_bert_base(
        vocab, seq, hidden, layers_n, heads, batch, use_amp=use_amp)
    if dp > 1:
        shard_optimizer_states(main_p, startup_p,
                               dp_degree=min(dp, world), stage=stage)
    static.gradient_merge(main_p, gm_k, startup_program=startup_p)
    gb = batch * world
    wire = scan_window_wire_bytes(main_p, world, batch=gb)

    cp = CompiledProgram(main_p).with_data_parallel(
        loss_name=loss.name, places=list(devices)[:world])
    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(0)
    idt = np.int64 if jax.config.jax_enable_x64 else np.int32
    feed = {"ids": rng.randint(0, vocab, (gb, seq)).astype(idt),
            "pos": np.tile(np.arange(seq), (gb, 1)).astype(idt),
            "labels": rng.randint(0, vocab, (gb, seq, 1)).astype(idt)}
    sfeed = {n: np.stack([v] * gm_k) for n, v in feed.items()}
    with static.scope_guard(scope):
        exe.run(startup_p)
        # looped side: K host dispatches per window.  Warm a full gm
        # window so the host micro-step counter stays window-aligned —
        # the hoist gate only engages at a window boundary.
        exe.run(cp, feed=feed, fetch_list=[loss])
        for _ in range(gm_k - 2):
            exe.run(cp, feed=feed, fetch_list=[])
        exe.run(cp, feed=feed, fetch_list=[])
        d0 = cp._dispatches
        t0 = time.time()
        for _ in range(windows * gm_k - 1):
            exe.run(cp, feed=feed, fetch_list=[])
        out = exe.run(cp, feed=feed, fetch_list=[loss])
        np.asarray(out[0])
        looped_ms = (time.time() - t0) / (windows * gm_k) * 1e3
        looped_disp = cp._dispatches - d0
        # scanned-hoisted side: ONE dispatch per window
        outs = exe.run_steps(cp, feed=sfeed, fetch_list=[loss])  # warm
        warm_traces = compile_cache.cache_stats()["traces"]
        d0 = cp._dispatches
        t0 = time.time()
        for _ in range(windows):
            outs = exe.run_steps(cp, feed=sfeed, fetch_list=[loss])
        np.asarray(outs[0])
        scanned_ms = (time.time() - t0) / (windows * gm_k) * 1e3
        scanned_disp = cp._dispatches - d0
    retraces = compile_cache.cache_stats()["traces"] - warm_traces

    result = {
        "metric": "scan_hoist_wire_ratio",
        "value": round(wire["per_step_looped"]
                       / max(wire["per_step_hoisted"], 1e-9), 4),
        "unit": "looped/hoisted per-step ICI bytes",
        "on_tpu": on_tpu,
        "world": world, "seq": seq, "batch": batch,
        "dp_shard": min(dp, world), "zero_stage": stage,
        "grad_merge": gm_k, "windows": windows,
        "wire_bytes": {k: round(v, 1) if isinstance(v, float) else v
                       for k, v in wire.items()},
        "looped_step_ms": round(looped_ms, 2),
        "scanned_step_ms": round(scanned_ms, 2),
        "dispatches_per_window": {"looped": looped_disp // windows,
                                  "scanned": scanned_disp // windows},
        "retraces_after_warmup": int(retraces),
    }
    assert retraces == 0, "bench --scan: recompile inside the timed loop"
    if not on_tpu:
        result["failed"] = True
        result["note"] = ("CPU mesh run; the wire accounting and "
                          "dispatch counts are the deliverable")
    print(json.dumps(result))


def main():
    if "--serving" in sys.argv or \
            os.environ.get("BENCH_MODE") == "serving":
        serving_main()
        return
    if "--checkpoint" in sys.argv or \
            os.environ.get("BENCH_MODE") == "checkpoint":
        checkpoint_main()
        return
    if "--elastic" in sys.argv or \
            os.environ.get("BENCH_MODE") == "elastic":
        elastic_main()
        return
    if "--seq-ladder" in sys.argv or \
            os.environ.get("BENCH_MODE") == "seq_ladder":
        seq_ladder_main()
        return
    if "--auto" in sys.argv or os.environ.get("BENCH_MODE") == "auto":
        auto_main()
        return
    if "--scan" in sys.argv or os.environ.get("BENCH_MODE") == "scan" \
            or os.environ.get("BENCH_SCAN", "") not in ("", "0", "false"):
        scan_main()
        return
    # --tp 1 / --tp 0 explicitly ask for the NO-tensor-parallel
    # baseline: fall through to the default bench instead of silently
    # measuring a tp mesh
    if _tp_knob() > 1:
        tp_main()
        return
    import jax
    _require_tpu_or_forced_cpu()
    import jax.numpy as jnp
    import paddle_tpu.static as static
    from paddle_tpu.core import compile_cache
    from paddle_tpu.ops.attention import enable_flash_attention

    # persistent XLA cache (JAX_COMPILATION_CACHE_DIR, else
    # <repo>/.jax_cache): a warm second run loads serialized executables
    # instead of re-compiling
    compile_cache.initialize()
    warm_entries = compile_cache.persistent_entries()

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    # batch 64 is the measured single-chip sweet spot (r5 sweep: b32
    # 35.9k tok/s, b64 85k, b96/b128 OOM 15.75G HBM)
    seq, batch = (512, 64) if on_tpu else (128, 2)
    layers_n = 12 if on_tpu else 2
    hidden = 768 if on_tpu else 256
    heads = 12 if on_tpu else 4
    vocab = 30522 if on_tpu else 1024
    batch = int(os.environ.get("BENCH_BATCH", batch))
    seq = int(os.environ.get("BENCH_SEQ", seq))
    # model-shape overrides (e.g. ERNIE-large: LAYERS=24 HIDDEN=1024
    # HEADS=16 BATCH=16 — BASELINE.md config 5's model on one chip)
    layers_n = int(os.environ.get("BENCH_LAYERS", layers_n))
    hidden = int(os.environ.get("BENCH_HIDDEN", hidden))
    heads = int(os.environ.get("BENCH_HEADS", heads))
    use_amp = os.environ.get("BENCH_NO_AMP", "") in ("", "0", "false")

    # Flash dispatch is seq-length AUTO by default (crossover flag
    # flash_min_seq_len).  r5 on-chip A/Bs: XLA attention wins at every
    # length where both fit (512/2048/4096), so auto selects flash only
    # from 8192 up, where materialized scores OOM.  BENCH_FLASH=1/0
    # forces it for A/B runs.
    if os.environ.get("BENCH_FLASH", "") != "":
        enable_flash_attention(
            os.environ["BENCH_FLASH"] not in ("0", "false"))
    # BENCH_FUSED_CE=1: route the [tokens, vocab] cross-entropy through
    # the Pallas online fused kernel for A/B (tools/tune_fused_xent.py)
    if os.environ.get("BENCH_FUSED_CE", "") not in ("", "0", "false"):
        from paddle_tpu.ops.fused_xent import enable_fused_xent
        enable_fused_xent(True)

    # BENCH_REMAT=1/auto (--remat): activation checkpointing at
    # transformer-layer boundaries (static/recompute_rewrite.py) — the
    # memory-for-throughput knob the b96/b128 A/B decides.  "auto"
    # rewrites only when the HBM estimator predicts this batch exceeds
    # PADDLE_TPU_HBM_BYTES.  BENCH_GRAD_MERGE=K (--grad-merge K):
    # k-step gradient accumulation (static.gradient_merge), the OTHER
    # way to trade per-step memory for effective batch.  BENCH_RING=1
    # (--ring): ring-attention op in every layer.  NOTE on one chip
    # (this bench's Executor path) the op degrades to plain attention —
    # the A/B measures the op's dispatch overhead and composes with
    # remat; the estimator charges the degraded kernel's full S² scores
    # (memory_analysis._op_internal_bytes), and the true sp-sharded
    # numbers need CompiledProgram over a multi-chip mesh.
    remat_mode, grad_merge_k, use_ring = _bench_knobs()
    # BENCH_DP_SHARD=N (--dp-shard [N]) + BENCH_ZERO_STAGE=S
    # (--zero-stage S): ZeRO sharding A/B at stages 1-3.  The rewrite is
    # applied for an N-rank dp world; on this bench's single-device
    # Executor path every collective degrades to identity, so tokens/s
    # measures the rewrite's dispatch/fusion overhead while
    # predicted_peak_bytes and collective_bytes report the N-chip story
    # (the mesh numbers need CompiledProgram over real chips).
    dp_shard = _dp_shard_knob()
    zero_stage = _zero_stage_knob()
    if remat_mode:
        from paddle_tpu.core.flags import set_flags
        set_flags({"recompute": remat_mode, "hbm_assume_batch": batch,
                   "hbm_dp_shard": dp_shard,
                   "hbm_zero_stage": zero_stage if dp_shard > 1 else 0})

    main_p, startup_p, loss = build_bert_base(vocab, seq, hidden, layers_n,
                                              heads, batch, use_amp=use_amp,
                                              use_ring=use_ring)
    if remat_mode:
        from paddle_tpu.core.flags import set_flags
        set_flags({"recompute": "", "hbm_assume_batch": 0,
                   "hbm_dp_shard": 0, "hbm_zero_stage": 0})
    _collective_bytes = None
    if dp_shard > 1:
        from paddle_tpu.distributed.compiled_program import \
            insert_grad_allreduce
        from paddle_tpu.distributed.sharding import shard_optimizer_states
        # wire accounting rides the verifier's ring-accounted extractor
        # (static.collective_wire_bytes — the planner's wire substrate;
        # ring 0 = the dist-pass gradient/param collectives, matching
        # the A/B's historical scope; the per-bucket
        # sharding.collective_bytes_per_step shim is retired).
        # plain-DP wire bytes: what insert_grad_allreduce WOULD emit for
        # this program on an N-rank mesh (per-param allreduce)
        plain_bytes = static.collective_wire_bytes(
            insert_grad_allreduce(main_p), dp_shard, ring_id=0)
        shard_optimizer_states(main_p, startup_p, dp_degree=dp_shard,
                               stage=zero_stage)
        reduced = insert_grad_allreduce(main_p)
        zero_bytes = static.collective_wire_bytes(reduced, dp_shard,
                                                 ring_id=0)
        # every ring (dist-pass rs/ag plus forward model-parallel
        # collectives) — reported alongside the ring-0 A/B numbers so
        # the full wire story stays visible
        wire_all = static.collective_wire_bytes(reduced, dp_shard)
        # per-mesh-axis split: each ring priced at its OWN degree
        # (tensor-ring collectives never pay the dp world) — the wire
        # substrate the 2-D planner consumes; batch bound so mp-ring
        # activation collectives price
        wire_axis = static.collective_wire_bytes_by_axis(reduced, dp_shard,
                                                         batch=batch)
        _collective_bytes = {"allreduce": plain_bytes,
                             f"zero{zero_stage}": zero_bytes,
                             f"zero{zero_stage}_all_rings": wire_all,
                             "wire_bytes_per_axis": wire_axis}
    if grad_merge_k > 1:
        static.gradient_merge(main_p, grad_merge_k, startup_p)
    # compile-time HBM verdict rides every bench record: the number that
    # decides fits-or-OOMs before chip time is spent
    _mem = static.analyze_program(main_p, batch=batch,
                                  dp_shard=dp_shard or None,
                                  zero_stage=(zero_stage
                                              if dp_shard > 1 else None))
    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(0)

    # int32 feeds on x64-disabled backends (the default): int64 would be
    # truncated on device anyway, each transfer paying a UserWarning +
    # an extra cast
    idt = np.int64 if jax.config.jax_enable_x64 else np.int32

    def batch_feed():
        return {
            "ids": rng.randint(0, vocab, (batch, seq)).astype(idt),
            "pos": np.tile(np.arange(seq), (batch, 1)).astype(idt),
            "labels": rng.randint(0, vocab,
                                  (batch, seq, 1)).astype(idt),
        }

    # Megastep: scan K training steps inside ONE jitted dispatch
    # (Executor.run_steps), so per-step host dispatch cannot leave the
    # device idle.  BENCH_MEGASTEP=0 selects one-dispatch-per-step.
    # 30 CPU steps: the 10-step window was ~1s of wall and swung ±10%
    # run-to-run, drowning real deltas in noise
    n_steps = int(os.environ.get("BENCH_STEPS", 20 if on_tpu else 30))
    megastep = int(os.environ.get("BENCH_MEGASTEP",
                                  n_steps if on_tpu else 0))
    device_feed = os.environ.get("BENCH_DEVICE_FEED", "") not in ("", "0")
    compile_time_s = 0.0
    with static.scope_guard(scope):
        exe.run(startup_p)
        feed = batch_feed()
        if device_feed and megastep <= 0:
            # pre-stage the feed on device ONCE: isolates per-step
            # host->device transfer cost from compute
            feed = {k: jax.device_put(jnp.asarray(v), dev)
                    for k, v in feed.items()}
        prof_dir = os.environ.get("BENCH_PROFILE", "")
        if megastep > 0:
            sfeed = {k: np.broadcast_to(np.asarray(v),
                                        (megastep,) + np.shape(v)).copy()
                     for k, v in feed.items()}
            if device_feed:
                sfeed = {k: jax.device_put(jnp.asarray(v), dev)
                         for k, v in sfeed.items()}
            # warmup compiles the scan; timed run is ONE dispatch.  A
            # scanned path that fails fails the run.
            tc = time.time()
            exe.run_steps(main_p, feed=sfeed, fetch_list=[loss])
            compile_time_s = time.time() - tc
        if megastep > 0:
            n_steps = megastep
            if prof_dir:
                jax.profiler.start_trace(prof_dir)
            t0 = time.time()
            out = exe.run_steps(main_p, feed=sfeed, fetch_list=[loss])
            np.asarray(out[0])
            dt = time.time() - t0
        else:
            # warmup/compile BOTH step signatures (fetch + no-fetch differ
            # in cache key; compiling inside the timed loop poisons dt —
            # and poisons the HEADLINE: compile_time_s is reported as its
            # own JSON field so a cold cache can't drag down tokens/s)
            tc = time.time()
            exe.run(main_p, feed=feed, fetch_list=[loss])
            exe.run(main_p, feed=feed, fetch_list=[])
            compile_time_s = time.time() - tc
            warm_traces = exe.cache_stats()["traces"]
            if prof_dir:
                jax.profiler.start_trace(prof_dir)
            t0 = time.time()
            # steps WITHOUT per-step fetches: state buffers are donated
            # and stay on device, dispatch runs ahead of the chip; only
            # the last step fetches the loss (forces completion).  Feeds
            # ride the async Prefetcher: batch N+1's host-side cast +
            # device_put overlaps batch N's step (reader/prefetcher.py).
            # BENCH_PREFETCH=auto: on-chip the host is idle during the
            # step so overlap is free; on CPU the worker thread would
            # STEAL cores from XLA compute (measured -25% on a 2-core
            # box), so the plain loop wins there.
            prefetch = os.environ.get("BENCH_PREFETCH", "auto")
            use_prefetch = on_tpu if prefetch == "auto" \
                else prefetch not in ("0", "false")
            if use_prefetch:
                feeds = (feed for _ in range(n_steps - 1))
                for _ in exe.run_prefetched(main_p, feeds, fetch_list=[],
                                            return_numpy=False):
                    pass
            else:
                for _ in range(n_steps - 1):
                    exe.run(main_p, feed=feed, fetch_list=[])
            out = exe.run(main_p, feed=feed, fetch_list=[loss])
            np.asarray(out[0])
            dt = time.time() - t0
            assert exe.cache_stats()["traces"] == warm_traces, \
                "recompile inside the timed loop"
        if prof_dir:
            jax.profiler.stop_trace()

    tokens_per_sec = n_steps * batch * seq / dt

    # MFU accounting, twice over and cross-checked:
    #   analytic — 6 * params * tokens (fwd+bwd matmul flops) PLUS the
    #   attention score/context matmuls the params-only count misses —
    #   QK^T and PV are each 2*s*hidden flops per token per layer
    #   forward, 3x that with backward: 12 * L * s * hidden per token;
    #   exact — static.analyze_flops walks the ACTUAL op list (so remat
    #   replays, ring degradation, AMP rewrites are all priced).  Both
    #   ride the JSON; >10% drift on a plain build means either the
    #   walker regressed or the analytic constants went stale, and the
    #   bench says so instead of silently reporting two truths.
    n_params = sum(
        int(np.prod(v.shape)) for v in main_p.all_parameters()
        if v.shape is not None)
    flops_per_token = 6 * n_params + 12 * layers_n * seq * hidden
    analytic_step_flops = flops_per_token * batch * seq
    walker_step_flops = static.analyze_flops(
        main_p, batch=batch)["total_flops"]
    flops_drift = walker_step_flops / analytic_step_flops - 1.0
    if abs(flops_drift) > 0.10 and not remat_mode:
        sys.stderr.write(
            f"bench: WARNING analyze_flops ({walker_step_flops:.3e}) "
            f"drifts {flops_drift * 100:+.1f}% from the analytic "
            f"estimate ({analytic_step_flops:.3e}) — walker regression "
            f"or stale analytic constants?\n")
    achieved = tokens_per_sec * flops_per_token
    peak = static.peak_flops_per_chip()
    mfu = achieved / peak if peak else 0.0
    mfu_exact = (tokens_per_sec / (batch * seq)) * walker_step_flops \
        / peak if peak else 0.0

    stats = exe.cache_stats()
    result = {
        "metric": "bert_base_pretrain_tokens_per_sec_per_chip"
                  if on_tpu else "bert_tiny_cpu_tokens_per_sec",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s/chip",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "vs_baseline": round(mfu / 0.35, 4) if peak else 0.0,
        # steady-state vs compile split: `value` is measured AFTER warmup;
        # a cold persistent cache shows up here, not in the headline
        "compile_time_s": round(compile_time_s, 2),
        # compile-time HBM accounting (static/memory_analysis.py)
        "predicted_peak_bytes": _mem["peak_bytes"],
        "predicted_fits": _mem["fits"],
        "hbm_budget_bytes": _mem["budget_bytes"],
        # per-op FLOPs accounting (static/flops_analysis.py): the exact
        # walked step cost next to the analytic formula, + their drift
        "flops_per_step_walked": walker_step_flops,
        "flops_per_step_analytic": analytic_step_flops,
        "flops_drift_pct": round(flops_drift * 100, 2),
        "cache": {
            "persistent_dir": stats["persistent_dir"],
            "warm_start": bool(warm_entries),
            "traces": stats["traces"],
            "hits": stats["hits"],
        },
    }
    if remat_mode or grad_merge_k > 1 or use_ring or dp_shard > 1:
        # self-describing A/B records: the JSON says what memory knobs
        # produced the number
        result["memory_knobs"] = {"remat": remat_mode or "off",
                                  "grad_merge_k": grad_merge_k,
                                  "ring": use_ring,
                                  "dp_shard": dp_shard,
                                  "zero_stage": (zero_stage
                                                 if dp_shard > 1 else 0)}
    if _collective_bytes is not None:
        # per-rank ICI bytes per step: bucketed reduce-scatter+allgather
        # vs the per-param allreduce baseline (ring accounting)
        result["collective_bytes_per_step"] = _collective_bytes
        result["optimizer_slot_bytes"] = _mem["optimizer_slot_bytes"]
        result["parameter_bytes"] = _mem["parameter_bytes"]
    if on_tpu:
        result["mfu"] = round(mfu, 4)
        result["mfu_exact"] = round(mfu_exact, 4)
    else:
        # a CPU rehearsal is not a perf record and says so explicitly —
        # the driver must not read CPU tokens/s as the perf headline
        result["failed"] = True
        result["note"] = \
            "CPU run (TPU not used); not comparable to the baseline"
    print(json.dumps(result))


if __name__ == "__main__":
    main()
