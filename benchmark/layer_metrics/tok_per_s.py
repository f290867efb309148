"""Prompt + generated tokens of the requests whose reply arrived inside
the window, per second of it (moves in steps of one request)."""
LAYER, SOURCE, UNIT, BETTER = \
    "entry_serve", "host_clock", "tokens/s", "higher"


def reduce(run):
    if "tokens_inside" not in run.counters:
        return None
    return run.counters["tokens_inside"] / run.window_s
