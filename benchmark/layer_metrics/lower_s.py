"""Seconds of self time in `jax/lower` records (JAX's
`jaxpr_to_mlir_module_duration`): a jaxpr written out as StableHLO, paid
whether or not the executable is then found in the cache."""
from benchmark import setup_phases

LAYER, SOURCE, UNIT, BETTER = "compile_cache", "program_span", "s", "lower"


def reduce(run):
    return setup_phases.self_s(run, setup_phases.named("jax/lower"))
