"""Rows decoded per decode step over the slots a step always pays for:
delta gen.tokens / (delta gen.steps x max_slots) over the window."""
LAYER, SOURCE, UNIT, BETTER = "engine", "program_counter", "%", "higher"


def reduce(run):
    steps = run.counters.get("gen.steps")
    if not steps:
        return None
    return 100.0 * run.counters["gen.tokens"] / (
        steps * run.counters["max_slots"])
