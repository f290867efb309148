"""Median duration of the `engine/step` spans that began in the traced
slice: one decode step as the engine's thread sees it (gather, upload,
forward, fetch, append, sample)."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "engine", "program_span", "ms", "lower"


def reduce(run):
    return program_spans.median(
        sp.ns / 1e6 for sp in program_spans.of(run)["whole"]
        if sp.name == "engine/step")
