"""Driver `serve_closed_cohere2_moe`: `serve_closed_moe_hybrid`'s
measurement for the `cohere2_moe` decoder (window and full attention over
a device-only KV cache, gated routed experts), one chip's share of it
(`serving_cohere2_moe.Served`, its warm-up and its `conclude` in place of
`serving_moe_hybrid`'s, which are `NemotronHConfig` and
`reference/nemotron_h.py` by name).

It measures exactly as that driver does: a fixed number of callers each
post their next request when the reply to the last one arrives; the window
is `--seconds` long; the metric is the median latency from send to full
reply of the replies that arrived inside it.  That driver's `_measure`
names its serving module, so the loop is written out here once more;
everything the loop calls is imported.  The device's memory peak is read
BEFORE the reference check (which brings float32 temporaries of its own)
and logged: what the served system itself reached.
"""
import threading
import time

import paddle_tpu.dygraph as dg

from benchmark import loadgen, serving_cohere2_moe, stats


def run(run):
    with dg.guard():
        served = serving_cohere2_moe.Served(run)
        try:
            _warm_up(served, run)
            _measure(run, served, int(run.traffic["callers"]))
        finally:
            served.close()


def _warm_up(served, run):
    """The serving module's warm-up, with the engine's request timeout
    lifted while it runs (a cold (phase, bucket) traces and compiles its
    whole program, longer than the 120 s the HTTP handler waits): as
    `serve_closed_cached._warm_up`."""
    engine = served.server.engine
    keep, engine.default_timeout_s = engine.default_timeout_s, 3600.0
    try:
        serving_cohere2_moe.warm_up(served, run)
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
    moe_first = serving_cohere2_moe.moe_counters()
    sampler = serving_cohere2_moe.Sampler(run, t0)
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
    from benchmark import harness
    peak = harness.memory_peak_bytes(run.devices)
    run.counters["memory_peak_bytes_served"] = peak
    run.log(f"memory peak before the reference check: {peak} B")
    serving_cohere2_moe.conclude(run, served, sampler, last,
                                [(r, out) for r, out, _, _ in done],
                                moe_first)
    run.correct = bool(inside)
