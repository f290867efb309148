"""Decode steps the engine completed per second of the window."""
LAYER, SOURCE, UNIT, BETTER = "engine", "program_counter", "1/s", "higher"


def reduce(run):
    if "gen.steps" not in run.counters:
        return None
    return run.counters["gen.steps"] / run.window_s
