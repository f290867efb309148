"""Seconds of self time in `jax/trace` records (JAX's
`jaxpr_trace_duration`): on the program's paths `BlockTracer` walking
Program IR into a jaxpr, the program's own Python."""
from benchmark import setup_phases

LAYER, SOURCE, UNIT, BETTER = "executor", "program_span", "s", "lower"


def reduce(run):
    return setup_phases.self_s(run, setup_phases.named("jax/trace"))
