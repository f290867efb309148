"""chip_smoke.py — the standing proof that the system starts on the chip.

    python chip_smoke.py

Drives the two paths the benchmark is built on, once, through their normal
entry points, at the full width of models the repo supports, with random
weights made from seeds:

  train          Program IR -> Executor: the BERT-base pretraining program
                 (models.build_bert_base: bf16 AMP, Adam), 5 per-step
                 dispatches.
  train-scanned  the same program and scope through one Executor.run_steps
                 window, compared with the looped steps at a tolerance.
  kernels        the Pallas flash-attention kernel compiled (never
                 interpreted) against the repo's own reference.
  serve          InferenceServer -> ContinuousBatchingEngine over a paged KV
                 pool: a GPT-2-small-width model answering concurrent HTTP
                 /generate posts, checked against per-sequence generate().
  multi          (>= 4 devices) the train program data-parallel over every
                 local chip, then __graft_entry__.dryrun_multichip.

One process touches JAX exactly once; no child needs the device.  JAX runs
with its defaults (x64 off) and no platform is set here.  The run exits
non-zero — before building anything — unless `jax.devices()[0].platform` is
`tpu`, and any phase that raises ends it non-zero.  The last line of stdout
is one JSON object naming the device as JAX reports it.

Each phase is a function of its sizes so tests/test_chip_smoke.py can
rehearse it tiny on the CPU; this file's own entry takes no switches.  The
per-phase seconds printed at the end are smoke timings for a builder's
notes, never metrics.
"""
import json
import math
import sys
import threading
import time

import numpy as np

# ---------------------------------------------------------------------------
# full sizes: what `python chip_smoke.py` runs
# ---------------------------------------------------------------------------
BERT_BASE = dict(vocab=30522, seq=512, hidden=768, layers_n=12, heads=12,
                 batch=32)
ATTN_SHAPE = (4, 12, 4096, 64)          # B, H, S, D — bf16
GPT2_SMALL = dict(vocab_size=50257, hidden_size=768, num_layers=12,
                  num_heads=12, max_position=1024)
SERVE = dict(n_requests=4, prompt_tokens=64, new_tokens=16, page_tokens=16,
             pool_extra_bytes=256 << 20)


# ---------------------------------------------------------------------------
# device + compile accounting
# ---------------------------------------------------------------------------
def device_report():
    """What JAX says the first device is (the one backend touch)."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    stats = dev.memory_stats() or {}
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs), "bytes_limit": stats.get("bytes_limit")}


def _peak_bytes():
    """(peak_bytes_in_use, peak_bytes_reserved) of the first device.  On
    the v5e the first counts live arrays only; an executable's temporaries
    show up in the second."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("peak_bytes_reserved")


class CompileClock:
    """Seconds JAX spent obtaining executables (XLA compile, or the load
    from the persistent cache) and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class TrainRun:
    """What the train phase hands to train-scanned and multi: the SAME
    program, scope and executor continue."""

    def __init__(self, main, startup, loss, exe, scope, feed, losses):
        self.main, self.startup, self.loss = main, startup, loss
        self.exe, self.scope, self.feed = exe, scope, feed
        self.losses = losses


def _assert_state_live(main, scope, platform, sample=4):
    """Every persistable is a live jax.Array on a `platform` device; read a
    few back — a deleted (donated) buffer raises here."""
    import jax
    from paddle_tpu.static.executor import _persistable_names
    names = [n for n in _persistable_names(main) if scope.get(n) is not None]
    assert names, "no persistable state in the scope"
    for n in names:
        v = scope.get(n)
        assert isinstance(v, jax.Array), f"{n}: {type(v).__name__}"
        assert not v.is_deleted(), f"{n}: donated buffer left in the scope"
        plats = {d.platform for d in v.devices()}
        assert plats == {platform}, f"{n} lives on {plats}, not {platform}"
    step = max(1, len(names) // sample)
    for n in names[::step]:
        assert np.isfinite(np.asarray(scope.get(n), np.float32)).all(), \
            f"{n}: non-finite after training"
    return len(names)


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------
def phase_train(vocab, seq, hidden, layers_n, heads, batch, steps=5):
    import jax
    import paddle_tpu.static as static
    from paddle_tpu.models import build_bert_base

    main, startup, loss = build_bert_base(
        vocab, seq, hidden, layers_n, heads, batch, use_amp=True)
    rng = np.random.RandomState(0)
    feed = {  # one fixed seeded batch, int32 (x64 is off)
        "ids": rng.randint(0, vocab, (batch, seq)).astype(np.int32),
        "pos": np.tile(np.arange(seq, dtype=np.int32), (batch, 1)),
        "labels": rng.randint(0, vocab, (batch, seq, 1)).astype(np.int32),
    }
    exe, scope = static.Executor(), static.Scope()
    losses = []
    with static.scope_guard(scope):
        exe.run(startup)
        for i in range(steps):
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
            losses.append(float(np.asarray(lv)))
            if i == 0:
                warm_traces = exe.cache_stats()["traces"]
    assert all(math.isfinite(v) for v in losses), losses
    ln_v = math.log(vocab)
    assert ln_v - 0.85 <= losses[0] <= ln_v + 1.2, \
        f"first loss {losses[0]:.4f} is not near ln(vocab) = {ln_v:.2f}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert exe.cache_stats()["traces"] == warm_traces, \
        f"retrace after warm-up: {exe.cache_stats()}"
    n_state = _assert_state_live(main, scope, jax.default_backend())
    print(f"train: losses {[round(v, 4) for v in losses]}, "
          f"{n_state} persistables live on {jax.default_backend()}, "
          f"traces {warm_traces}")
    return TrainRun(main, startup, loss, exe, scope, feed, losses)


# ---------------------------------------------------------------------------
# phase: train-scanned
# ---------------------------------------------------------------------------
def phase_train_scanned(run, k=4):
    import jax
    import jax.numpy as jnp
    import paddle_tpu.static as static
    from paddle_tpu.core.monitor import stat_get
    from paddle_tpu.static.executor import _persistable_names

    exe, scope, main, loss = run.exe, run.scope, run.main, run.loss
    names = [n for n in _persistable_names(main) if scope.get(n) is not None]
    # device-side copy of the state the window starts from, so the same K
    # steps can be replayed looped for the comparison
    snapshot = {n: jnp.copy(scope.get(n)) for n in names}
    stacked = {n: np.broadcast_to(v, (k,) + v.shape).copy()
               for n, v in run.feed.items()}
    with static.scope_guard(scope):
        traces0 = exe.cache_stats()["traces"]
        dispatches0 = stat_get("executor_run_times")
        (scanned,) = exe.run_steps(main, feed=stacked, fetch_list=[loss])
        dispatches = stat_get("executor_run_times") - dispatches0
        scan_traces = exe.cache_stats()["traces"] - traces0
        scanned = np.asarray(scanned, np.float64).reshape(-1)
        assert scanned.shape == (k,) and np.isfinite(scanned).all(), scanned
        assert dispatches == 1, f"{k} scanned steps cost {dispatches} " \
                                "dispatches, not 1"
        assert scan_traces == 1, f"scan traced {scan_traces} times"
        assert scanned[-1] < run.losses[-1], \
            f"scanned losses {scanned} do not continue the fall from " \
            f"{run.losses[-1]}"
        _assert_state_live(main, scope, jax.default_backend())
        # replay the window looped from the snapshot
        for n, v in snapshot.items():
            scope.set(n, v)
        del snapshot
        looped = [float(np.asarray(exe.run(main, feed=run.feed,
                                           fetch_list=[loss])[0]))
                  for _ in range(k)]
    # bf16 AMP: a tolerance, never bitwise
    np.testing.assert_allclose(scanned, looped, rtol=1e-2)
    print(f"train-scanned: K={k} losses {np.round(scanned, 4).tolist()} in "
          f"1 dispatch; looped {np.round(looped, 4).tolist()}")
    return scanned


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------
def _assert_lowering(fn, args, what):
    """The compiled Mosaic path is taken exactly when the backend is not
    the CPU: its custom call is in the lowered text or it is not."""
    import jax
    text = jax.jit(fn).lower(*args).as_text()
    compiled = "tpu_custom_call" in text
    want = jax.default_backend() != "cpu"
    assert compiled == want, \
        f"{what}: pallas_call {'compiled' if compiled else 'interpreted'} " \
        f"on backend {jax.default_backend()}"
    return compiled


def _max_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max()), float(np.abs(want).max())


def phase_kernels(attn_shape, attn_dtype="bfloat16"):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import flash_attention, reference_attention

    b, h, s, d = attn_shape
    dt = jnp.dtype(attn_dtype)
    atol = 2e-2 if dt == jnp.bfloat16 else 1e-4
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(kk, attn_shape, jnp.float32).astype(dt)
                  for kk in keys)
    report = {}
    for causal in (False, True):
        def flash_loss(q, k, v, causal=causal):
            out = flash_attention(q, k, v, causal=causal)
            return (out.astype(jnp.float32) * g.astype(jnp.float32)).sum(), \
                out

        flash_vg = jax.value_and_grad(flash_loss, argnums=(0, 1, 2),
                                      has_aux=True)
        _assert_lowering(flash_vg, (q, k, v), f"flash causal={causal}")
        (_, out), grads = jax.jit(flash_vg)(q, k, v)
        def ref_loss(q, k, v, g_row, causal=causal):
            out = reference_attention(q, k, v, causal=causal)
            return (out.astype(jnp.float32)
                    * g_row.astype(jnp.float32)).sum(), out

        # the reference materializes [H, S, S] scores: one batch row at a
        # time keeps it a fraction of HBM
        ref_vg = jax.jit(jax.value_and_grad(ref_loss, argnums=(0, 1, 2),
                                            has_aux=True))
        errs = {}
        for i in range(b):
            sl = slice(i, i + 1)
            (_, r_out), r_grads = ref_vg(q[sl], k[sl], v[sl], g[sl])
            for name, got, want in (("out", out[sl], r_out),
                                    ("dq", grads[0][sl], r_grads[0]),
                                    ("dk", grads[1][sl], r_grads[1]),
                                    ("dv", grads[2][sl], r_grads[2])):
                err, scale = _max_err(got, want)
                prev = errs.get(name, (0.0, 0.0))
                errs[name] = (max(prev[0], err), max(prev[1], scale))
        for name, (err, scale) in errs.items():
            assert math.isfinite(err) and err <= atol * max(1.0, scale), \
                f"flash causal={causal} {name}: max err {err:.3e} " \
                f"(ref scale {scale:.3e}, atol {atol})"
        report[f"flash_causal_{causal}"] = {
            n: round(e, 5) for n, (e, _) in errs.items()}

    print(f"kernels: compiled={jax.default_backend() != 'cpu'} "
          f"max errors {report}")
    return report


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------
def _save_stub_predictor(model_dir):
    """InferenceServer fronts a saved inference model (/predict); the
    generator rides beside it.  A one-fc program is the smallest one."""
    import paddle_tpu.static as static
    from paddle_tpu.io.framework_io import save_inference_model
    from paddle_tpu.static import layers
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 8])
        out = layers.fc(x, 2)
    exe, scope = static.Executor(), static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        save_inference_model(model_dir, ["x"], [out], exe, main)


def _post_json(url, payload, timeout):
    import urllib.request
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _near_tie_check(model, served, n_prompt, tol_sigma=0.05):
    """A served sequence that left generate()'s greedy chain must have done
    so at a numeric tie: under teacher forcing on the served tokens, every
    served token's reference logit is within `tol_sigma` standard
    deviations of that position's maximum.  (Two fp32 forwards of
    different shapes do not round alike on the chip — bitwise-style
    equality does not survive the dtype regime; a wrong KV column moves
    logits by whole sigmas.)  Returns the largest margin seen, in sigmas."""
    import paddle_tpu
    logits = np.asarray(model.gpt(paddle_tpu.to_tensor(
        np.asarray(served[:-1], np.int32)[None])).numpy())[0]
    worst = 0.0
    for t in range(n_prompt - 1, len(served) - 1):
        row = logits[t]
        margin = float(row.max() - row[served[t + 1]]) / float(row.std())
        worst = max(worst, margin)
        assert margin <= tol_sigma, \
            f"served token at position {t + 1} is {margin:.3f} sigma " \
            f"below the reference argmax — not a numeric tie"
    return worst


def phase_serve(model_cfg, n_requests, prompt_tokens, new_tokens,
                page_tokens, pool_extra_bytes, request_timeout_s=600.0):
    import tempfile
    import paddle_tpu
    import paddle_tpu.dygraph as dg
    import paddle_tpu.static as static
    from paddle_tpu.inference.server import InferenceServer
    from paddle_tpu.models import GPTConfig, GPTForGeneration, GPTModel
    from paddle_tpu.serving import budget_drift

    rng = np.random.RandomState(7)
    vocab = model_cfg["vocab_size"]
    prompts = [rng.randint(2, vocab, (prompt_tokens,)).astype(np.int32)
               for _ in range(n_requests)]
    with dg.guard(), tempfile.TemporaryDirectory() as model_dir:
        paddle_tpu.seed(1234)               # pins the weight draw
        m = GPTForGeneration(GPTModel(GPTConfig(dropout=0.0, **model_cfg)))
        m.eval()
        weight_bytes = int(sum(int(np.prod(p.shape)) * 4
                               for p in m.gpt.parameters()))
        # an explicit, small budget: the pool's slabs are host numpy today,
        # so the default 15.75 GiB would be allocated in host RAM.  The
        # context is what these requests need, which leaves the engine
        # room to batch all of them in one decode step.
        ctx = 1 << (prompt_tokens + new_tokens - 1).bit_length()
        plan = static.page_budget(
            m, page_tokens=page_tokens, max_context=ctx,
            hbm_bytes=weight_bytes + pool_extra_bytes)
        assert plan["max_slots"] >= n_requests, plan
        # the repo's own reference: per-sequence greedy generate()
        refs = [np.asarray(m.generate(p[None], max_length=new_tokens,
                                      decode_strategy="greedy_search")[0])
                for p in prompts]
        _save_stub_predictor(model_dir)
        srv = InferenceServer(model_dir, generator=m, gen_kv_pool=plan)
        srv.start()
        outs, errors = [None] * n_requests, []

        def client(i):
            try:
                reply = _post_json(
                    f"http://{srv.host}:{srv.port}/generate",
                    {"input_ids": prompts[i].tolist(),
                     "max_length": new_tokens}, request_timeout_s)
                outs[i] = np.asarray(reply["output_ids"][0])
            except Exception as e:          # re-raised on the main thread
                errors.append((i, e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_requests)]
        try:
            for th in threads:
                th.start()
            for th in threads:
                th.join(request_timeout_s + 30.0)
            assert not any(th.is_alive() for th in threads), \
                "a /generate client is still waiting"
            stats = srv.stats()
        finally:
            srv.stop()
        if errors:
            raise errors[0][1]
        pool = srv.engine.kv_pool
        pool.assert_drained()
        assert budget_drift(pool, m) == []
        equal, worst_tie = 0, 0.0
        for ref, out, p in zip(refs, outs, prompts):
            assert out.shape == (prompt_tokens + new_tokens,), out.shape
            assert (out[:prompt_tokens] == p).all()
            assert ((0 <= out) & (out < vocab)).all()
            if np.array_equal(ref, out):
                equal += 1
            else:
                worst_tie = max(worst_tie, _near_tie_check(
                    m, out.tolist(), prompt_tokens))
    print(f"serve: {equal}/{n_requests} sequences token-equal to "
          f"generate()" + (f", the rest diverge at ties <= {worst_tie:.4f} "
                           f"sigma" if equal < n_requests else "") +
          f"; plan pages={plan['pages']} max_slots={plan['max_slots']} "
          f"kv_bytes={plan['kv_bytes']}; pool drained; "
          f"kv_buckets={stats['gen_kv_buckets']}")
    return {"token_equal": equal, "requests": n_requests,
            "worst_tie_sigma": worst_tie}


# ---------------------------------------------------------------------------
# phase: multi
# ---------------------------------------------------------------------------
def phase_multi(run, steps=3):
    """The train program data-parallel over every local device (same
    programs, so the same seeded init as the train phase), then the five
    dryrun_multichip configurations on the real devices."""
    import jax
    import paddle_tpu.static as static
    from paddle_tpu.distributed.compiled_program import CompiledProgram
    from paddle_tpu.static.executor import _persistable_names
    import __graft_entry__

    devs = jax.devices()
    n = len(devs)
    exe, scope = static.Executor(), static.Scope()
    cp = CompiledProgram(run.main).with_data_parallel(
        loss_name=run.loss.name)
    placed = cp.place_feed(run.feed)
    for name, arr in placed.items():
        shard_devs = {s.device for s in arr.addressable_shards}
        assert len(shard_devs) == n and \
            all(s.data.shape[0] * n == arr.shape[0]
                for s in arr.addressable_shards), \
            f"feed {name}: shards on {len(shard_devs)} of {n} devices"
    losses = []
    with static.scope_guard(scope):
        exe.run(run.startup)
        for i in range(steps):
            # host feeds first (the placement _run does itself), then the
            # pre-placed ones
            (lv,) = exe.run(cp, feed=run.feed if i == 0 else placed,
                            fetch_list=[run.loss])
            losses.append(float(np.asarray(lv)))
    assert all(math.isfinite(v) for v in losses), losses
    rel = abs(losses[0] - run.losses[0]) / abs(run.losses[0])
    assert rel <= 1e-2, \
        f"first dp loss {losses[0]} vs one-chip {run.losses[0]} ({rel:.2e})"
    assert losses[-1] < losses[0], losses
    for pname in _persistable_names(run.main):
        v = scope.get(pname)
        if v is not None:
            assert set(v.sharding.device_set) == set(devs), \
                f"{pname} covers {len(v.sharding.device_set)} of {n} devices"
    print(f"multi: dp over {n} devices, losses "
          f"{[round(v, 4) for v in losses]} (one chip first loss "
          f"{run.losses[0]:.4f}, rel {rel:.1e}); parameters on all {n}, "
          f"feed shards on {n} distinct devices")
    del scope
    __graft_entry__.dryrun_multichip(4)
    print("multi: dryrun_multichip(4) ran dp, dp x sp, pipeline, dp x ep, "
          "dp x tp on the real devices")
    return losses


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------
def main():
    report = device_report()
    print(f"device: platform={report['platform']} kind={report['kind']!r} "
          f"count={report['count']} bytes_limit={report['bytes_limit']}",
          flush=True)
    if report["platform"] != "tpu":
        print(f"chip_smoke: platform is {report['platform']!r}, not 'tpu' — "
              "nothing was built", file=sys.stderr)
        return 1

    import jax
    from paddle_tpu.core import compile_cache
    cache_dir = compile_cache.initialize()
    entries0 = compile_cache.persistent_entries()
    clock = CompileClock()
    timings = []

    def timed(name, fn, *args, **kw):
        t0, c0, h0 = time.perf_counter(), clock.seconds, clock.hits
        out = fn(*args, **kw)
        timings.append((name, time.perf_counter() - t0,
                        clock.seconds - c0, clock.hits - h0, _peak_bytes()))
        return out

    run = timed("train", phase_train, **BERT_BASE)
    timed("train-scanned", phase_train_scanned, run)
    # the later phases get the HBM back; multi keeps only the programs
    run.exe.close()
    run.exe = run.scope = None
    timed("kernels", phase_kernels, ATTN_SHAPE)
    timed("serve", phase_serve, GPT2_SMALL, **SERVE)
    if report["count"] >= 4:
        timed("multi", phase_multi, run)
    else:
        print(f"multi: {report['count']} device, not run")

    assert jax.config.jax_compilation_cache_dir == cache_dir
    print(f"compile cache: {cache_dir}, entries {entries0} -> "
          f"{compile_cache.persistent_entries()}")
    print("phase            wall_s  compile_s  cache_hits  "
          "peak_bytes_in_use  peak_bytes_reserved")
    for name, wall, comp, hits, (in_use, reserved) in timings:
        print(f"{name:<15} {wall:7.1f} {comp:10.1f} {hits:11d}  "
              f"{in_use:>17}  {reserved:>19}")
    print(json.dumps({"ok": True, "device": {
        "platform": report["platform"], "kind": report["kind"],
        "count": report["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
