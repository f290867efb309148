"""Driver `serve_open`: open-loop traffic against `InferenceServer`.

Requests are due on a schedule drawn from the seed at the rate fixed in
the cell file, whether or not earlier ones have finished; each is timed
from the instant it was due to its full reply at the HTTP client.  A
request that fails, is refused or has no reply by the end of the drain
counts as the window's length.  The metric is seconds per answer token:
those latencies summed over every request of the window, divided by the
answer tokens they asked for — continuous, over all requests, and the
statistic that depends least on which lengths the seed drew.
"""
import time
from concurrent.futures import ThreadPoolExecutor

import paddle_tpu.dygraph as dg

from benchmark import loadgen, serving, stats


def run(run):
    mix, cell = run.traffic, run.cell.cell
    rate = float(cell["rate_per_s"])
    with dg.guard():
        served = serving.Served(run)
        try:
            serving.warm_up(served, run)
            requests = loadgen.open_loop_requests(
                mix, served.cfg["vocab_size"], run.seed, rate, run.seconds)
            _measure(run, served, requests, float(mix["drain_s"]))
        finally:
            served.close()


def _measure(run, served, requests, drain_s):
    sent_at, replies = {}, {}       # by request index, seconds from t0

    def call(req, t0):
        sent_at[req.index] = time.perf_counter() - t0
        try:
            out = served.post(req.prompt, req.max_new,
                              timeout_s=run.seconds + drain_s)
        except Exception as e:      # noqa: BLE001 — counted as failed
            run.log(f"request {req.index} failed: {type(e).__name__}: {e}")
            return
        replies[req.index] = (time.perf_counter() - t0, out)

    pool = ThreadPoolExecutor(max_workers=int(run.traffic["client_threads"]))
    futures, nxt = [], 0
    t0 = run.begin_window()
    sampler = serving.Sampler(run, t0)
    try:
        while True:
            now = time.perf_counter() - t0
            while nxt < len(requests) and requests[nxt].due_s <= now:
                futures.append(pool.submit(call, requests[nxt], t0))
                nxt += 1
            if now >= run.seconds:
                break
            due = requests[nxt].due_s if nxt < len(requests) else run.seconds
            time.sleep(max(0.0, min(due, run.seconds)
                           - (time.perf_counter() - t0)))
        last = sampler.stop()
        serving.drain(futures, drain_s)
    finally:
        sampler.stop()
        pool.shutdown(wait=False, cancel_futures=True)
    run.end_window(run.seconds)

    # -- what the window did ------------------------------------------------
    sent = requests[:nxt]
    done = [(r, replies[r.index][1]) for r in sent if r.index in replies]
    latency = [replies[r.index][0] - r.due_s if r.index in replies
               else run.seconds for r in sent]
    late = [sent_at[r.index] - r.due_s for r in sent if r.index in sent_at]
    run.attempted, run.failed = len(sent), len(sent) - len(done)
    run.samples.update(latency_s=latency, send_late_s=late)
    if latency:
        per_token = sum(latency) / sum(r.max_new for r in sent)
        run.end_to_end["serve_s_per_answer_token"] = per_token
        run.log(f"open loop at {len(sent) / run.seconds:.3f} req/s: "
                f"{len(sent)} sent, {len(done)} replied, {run.failed} "
                f"failed; {per_token:.4f} s per answer token; latency over "
                f"{len(latency)} samples: p50 "
                f"{stats.percentile(latency, 50):.4f} s, p90 "
                f"{stats.percentile(latency, 90):.4f} s, mean "
                f"{sum(latency) / len(latency):.4f} s")
    serving.conclude(run, served, sampler, last, done, sent)
    run.checks["generator_kept_up"] = not late or \
        stats.percentile(late, 90) < float(run.traffic["late_limit_s"])
