"""Share of the engine thread's time spent inside `engine/forward`
(`model.forward`: the host dispatching the pass op by op), over its
accounted stretch of the traced slice — from its first whole loop span to
its last (`program_spans.assemble`)."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "engine", "program_span", "%", "lower"


def reduce(run):
    return program_spans.share_of_loop(
        run, lambda name: name == "engine/forward")
