"""Share of the engine thread's time spent in its own host work: the self
time of every `engine/...` span but forward, fetch and idle — numpy build
and gather, upload, sampling, KV install and append, finish, admission —
over its accounted stretch of the traced slice
(`program_spans.assemble`)."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "engine", "program_span", "%", "lower"
ELSEWHERE = ("engine/forward", "engine/fetch", "engine/idle")


def reduce(run):
    return program_spans.share_of_loop(
        run, lambda name: name.startswith("engine/")
        and name not in ELSEWHERE, self_time=True)
