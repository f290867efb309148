"""Share of the traced slice chip 0 spent in collective operations."""
LAYER, SOURCE, UNIT, BETTER = "mesh", "device_trace", "%", "lower"


def reduce(run):
    return 100.0 * run.trace["collective_s"] / run.trace["window_s"]
