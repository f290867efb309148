"""Driver `serve_closed_cached`: `serve_closed`'s measurement for a model
that states its cache and serves through the engine's compiled step route
(`serving_cached.Served` in place of `serving.Served`, which is
`GPTConfig` and `reference/gpt2.py` by name).

It measures exactly as `serve_closed` does: a fixed number of callers each
post their next request when the reply to the last one arrives; the
window is `--seconds` long; the metric is the median latency from send to
full reply of the replies that arrived inside it.  `serve_closed._measure`
ends in `serving.conclude`, which is GPT's, so the loop is written out
here once more; everything it calls is imported.
"""
import threading
import time

import paddle_tpu.dygraph as dg

from benchmark import loadgen, serving, serving_cached, stats


def run(run):
    with dg.guard():
        served = serving_cached.Served(run)
        try:
            _warm_up(served, run)
            _measure(run, served, int(run.traffic["callers"]))
        finally:
            served.close()


def _warm_up(served, run):
    """`serving.warm_up`, with the engine's request timeout lifted while it
    runs: the first request of a (phase, bucket) traces and compiles that
    bucket's whole program, which in a cold checkout takes longer than the
    120 s the HTTP handler waits for a reply.  The window runs under the
    deployment's own timeout."""
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
    sampler = serving_cached.Sampler(run, t0)
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
    serving_cached.conclude(run, served, sampler, last,
                            [(r, out) for r, out, _, _ in done])
    run.correct = bool(inside)
