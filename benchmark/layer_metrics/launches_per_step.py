"""XLA executables launched on chip 0 in the traced slice, per unit of
work the driver counted there: train steps, or the engine's forward passes
(decode steps + prefills)."""
LAYER, SOURCE, UNIT, BETTER = "executor", "device_trace", "count", "lower"


def reduce(run):
    if not run.slice_units:
        return None
    run.log(f"launches_per_step: {run.trace['launches']} launches over "
            f"{run.slice_units} steps; most frequent "
            f"{run.trace['modules'][:6]}")
    return run.trace["launches"] / run.slice_units
