"""Time the train loop waited for its next batch (the benchmark's
`feed_wait` span around the prefetcher), per train step."""
LAYER, SOURCE, UNIT, BETTER = "input", "host_clock", "ms", "lower"


def reduce(run):
    steps = run.counters.get("window_steps")
    if not steps:
        return None
    spent = sum(t1 - t0 for n, t0, t1 in run.spans if n == "feed_wait")
    return 1e3 * spent / steps
