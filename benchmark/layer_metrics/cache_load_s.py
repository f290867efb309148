"""Seconds in `jax/cache_load` records (JAX's `cache_retrieval_time_sec`):
executables read back from the persistent cache; `compile_s` less this is
the time in XLA's compiler proper (and the cache key's hashing)."""
from benchmark import setup_phases

LAYER, SOURCE, UNIT, BETTER = "compile_cache", "program_span", "s", "lower"


def reduce(run):
    return setup_phases.self_s(run, setup_phases.named("jax/cache_load"))
