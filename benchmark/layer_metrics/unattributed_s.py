"""Seconds of set-up, after the program's first kept record began, with no
kept record open on any thread: the benchmark's own stubs, warm-up
requests running programs already obtained, downloads for the reference
check."""
from benchmark import setup_phases

LAYER, SOURCE, UNIT, BETTER = "process", "program_span", "s", "lower"


def reduce(run):
    parsed = setup_phases.of(run)
    return None if parsed is None else parsed["unattributed_s"]
