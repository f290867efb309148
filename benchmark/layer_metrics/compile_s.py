"""Seconds JAX spent obtaining executables during set-up (compiling, or
loading from the persistent cache), from JAX's monitoring events."""
LAYER, SOURCE, UNIT, BETTER = "compile_cache", "program_counter", "s", "lower"


def reduce(run):
    return run.setup_compile["seconds"]
