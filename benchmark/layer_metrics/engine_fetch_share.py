"""Share of the engine thread's time spent inside `engine/fetch` (`numpy()`
of the logits and new KV: the wait for the device, then the download), over
its accounted stretch of the traced slice (`program_spans.assemble`)."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "engine", "program_span", "%", "lower"


def reduce(run):
    return program_spans.share_of_loop(
        run, lambda name: name == "engine/fetch")
