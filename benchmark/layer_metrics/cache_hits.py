"""Persistent compilation cache hits during set-up."""
LAYER, SOURCE, UNIT, BETTER = \
    "compile_cache", "program_counter", "count", "higher"


def reduce(run):
    return run.setup_compile["hits"]
