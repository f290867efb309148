"""Host time inside `Executor.run_steps` / `run` until it returns (the
benchmark's `dispatch` span), per train step, over the whole window."""
LAYER, SOURCE, UNIT, BETTER = "executor", "host_clock", "ms", "lower"


def reduce(run):
    steps = run.counters.get("window_steps")
    if not steps:
        return None
    spent = sum(t1 - t0 for n, t0, t1 in run.spans if n == "dispatch")
    return 1e3 * spent / steps
