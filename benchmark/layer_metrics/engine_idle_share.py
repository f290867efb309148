"""Share of the engine thread's time spent inside `engine/idle` (waiting
for work with no slot active), over its accounted stretch of the traced
slice (`program_spans.assemble`)."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "engine", "program_span", "%", "higher"


def reduce(run):
    return program_spans.share_of_loop(
        run, lambda name: name == "engine/idle")
