"""Seconds of the longest single `jit/program` or `executor/first_launch`
inside set-up; which program it was (its fields) is logged."""
from benchmark import setup_phases

LAYER, SOURCE, UNIT, BETTER = "compile_cache", "program_span", "s", "lower"


def reduce(run):
    parsed = setup_phases.of(run)
    if parsed is None:
        return None
    programs = [sp for sp in parsed["spans"]
                if sp.name in setup_phases.PROGRAMS]
    if not programs:
        return 0.0
    slowest = max(programs, key=lambda sp: sp.ns)
    run.log("setup.slowest_program_s: " + setup_phases.describe(slowest))
    return slowest.ns / 1e9
