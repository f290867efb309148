"""Driver `serve_closed_moe_hybrid`: `serve_closed_cached`'s measurement
for the `nemotron_h` decoder with latent routed experts, one chip's share
of it (`serving_moe_hybrid.Served` in place of `serving_cached.Served`,
which is `GraniteHybridConfig` and `reference/granite_hybrid.py` by
name).

It measures exactly as `serve_closed_cached` does: a fixed number of
callers each post their next request when the reply to the last one
arrives; the window is `--seconds` long; the metric is the median latency
from send to full reply of the replies that arrived inside it.  That
driver's `_measure` builds `serving_cached.Served` and ends in
`serving_cached.conclude`, both granite's, so the loop is written out here
once more (with the routed experts' counters read at the window's edges);
everything the loop calls is imported.
"""
import threading
import time

import paddle_tpu.dygraph as dg

from benchmark import loadgen, serving, serving_moe_hybrid, stats


def run(run):
    with dg.guard():
        served = serving_moe_hybrid.Served(run)
        try:
            _warm_up(served, run)
            _measure(run, served, int(run.traffic["callers"]))
        finally:
            served.close()


def _warm_up(served, run):
    """`serving.warm_up`, with the engine's request timeout lifted while it
    runs (a cold (phase, bucket) traces and compiles its whole program,
    longer than the 120 s the HTTP handler waits): as
    `serve_closed_cached._warm_up`."""
    engine = served.server.engine
    keep, engine.default_timeout_s = engine.default_timeout_s, 3600.0
    try:
        serving.warm_up(served, run)
    finally:
        engine.default_timeout_s = keep


def _measure(run, served, callers):
    done, failed, lock = [], [], threading.Lock()
    stop = threading.Event()
    stream = loadgen.closed_loop_requests(
        run.traffic, served.cfg["vocab_size"], run.seed)

    def caller(t0):
        while not stop.is_set():
            with lock:
                req = next(stream)
            t_sent = time.perf_counter() - t0
            try:
                out = served.post(req.prompt, req.max_new,
                                  timeout_s=run.seconds + 120.0)
            except Exception as e:  # noqa: BLE001 — counted as failed
                with lock:
                    failed.append(req)
                run.log(f"request {req.index} failed: "
                        f"{type(e).__name__}: {e}")
                continue
            with lock:
                done.append((req, out, t_sent, time.perf_counter() - t0))

    t0 = run.begin_window()
    moe_first = serving_moe_hybrid.moe_counters()
    sampler = serving_moe_hybrid.Sampler(run, t0)
    threads = [threading.Thread(target=caller, args=(t0,), daemon=True)
               for _ in range(callers)]
    for th in threads:
        th.start()
    time.sleep(run.seconds)
    last = sampler.stop()
    stop.set()
    for th in threads:              # each finishes the request it is in
        th.join(timeout=120.0)
    run.end_window(run.seconds)
    if any(th.is_alive() for th in threads):
        raise RuntimeError("a closed-loop caller never got its reply")

    inside = [d for d in done if d[3] <= run.seconds]
    tokens = sum(len(out) for _, out, _, _ in inside)
    run.attempted = len(done) + len(failed)
    run.failed = len(failed)
    latency = [t1 - ts for _, _, ts, t1 in inside]
    run.samples["latency_s"] = latency
    run.counters["tokens_inside"] = tokens
    if inside:
        run.end_to_end["serve_closed_latency_p50_s"] = \
            stats.percentile(latency, 50)
    run.log(f"closed loop, {callers} callers: {len(inside)} replies and "
            f"{tokens} tokens inside {run.seconds} s = "
            f"{tokens / run.seconds:.2f} tok/s, "
            f"{len(inside) / run.seconds:.3f} req/s; latency p50 "
            + (f"{stats.percentile(latency, 50):.4f} s, mean "
               f"{sum(latency) / len(latency):.4f} s, p10 "
               f"{stats.percentile(latency, 10):.4f} s, p90 "
               f"{stats.percentile(latency, 90):.4f} s" if inside else "none")
            + f"; {run.failed} failed")
    serving_moe_hybrid.conclude(run, served, sampler, last,
                                [(r, out) for r, out, _, _ in done],
                                moe_first)
    run.correct = bool(inside)
