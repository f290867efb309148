"""Median `waited_ms` of the `engine/prefill` spans that began in the
traced slice: from `submit` until the engine took the prompt up."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "engine", "program_span", "ms", "lower"


def reduce(run):
    return program_spans.median(
        float(sp.fields["waited_ms"])
        for sp in program_spans.of(run)["whole"]
        if sp.name == "engine/prefill" and "waited_ms" in sp.fields)
