"""Seconds of self time in the Python that builds or checks Program IR:
`program/build` and its children (`amp/rewrite`,
`static/head_loss_rewrite`, `static/backward`), `jit/record` (a dygraph
step recorded into a Program) and `executor/trace_compile` (the miss
path's verification and wrapping)."""
from benchmark import setup_phases

LAYER, SOURCE, UNIT, BETTER = "executor", "program_span", "s", "lower"


def reduce(run):
    return setup_phases.self_s(run, setup_phases.named(
        "program/build", "amp/rewrite", "static/head_loss_rewrite",
        "static/backward", "jit/record", "executor/trace_compile"))
