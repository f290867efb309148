"""Median duration of the `engine/prefill` spans that began in the traced
slice."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "engine", "program_span", "ms", "lower"


def reduce(run):
    return program_spans.median(
        sp.ns / 1e6 for sp in program_spans.of(run)["whole"]
        if sp.name == "engine/prefill")
