"""Host time inside `Executor::Run` / `Executor::RunSteps` spans that is
not the `executor/launch` child (the call of the jitted function): feed
coercion, cache key, state hand-over, telemetry, hooks — per train step of
the traced slice."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "executor", "program_span", "ms", "lower"


def reduce(run):
    whole = program_spans.total_ms(run, program_spans.DISPATCH)
    if whole is None or not run.slice_units:
        return None
    launch = program_spans.total_ms(run, ("executor/launch",)) or 0.0
    return (whole - launch) / run.slice_units
