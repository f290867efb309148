"""Share of the traced slice in which no operation ran on chip 0."""
LAYER, SOURCE, UNIT, BETTER = "device", "device_trace", "%", "lower"


def reduce(run):
    return 100.0 * run.trace["idle_share"]
