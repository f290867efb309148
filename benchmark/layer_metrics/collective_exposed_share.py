"""Share of the traced slice in which chip 0 ran a collective operation
and nothing else."""
LAYER, SOURCE, UNIT, BETTER = "mesh", "device_trace", "%", "lower"


def reduce(run):
    return 100.0 * run.trace["collective_exposed_s"] / run.trace["window_s"]
