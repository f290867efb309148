"""Share of chip 0's busy time in the traced slice spent in the
state-space layers' own ops: instructions under the `forward/mamba2_*`
and `forward/causal_conv1d` scopes (`device_scopes`) — the scan, the
one-token update and the conv, not the projections around them."""
from benchmark import device_scopes

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "lower"


def reduce(run):
    got = device_scopes.of(run)
    if got is None:
        return None
    spent = sum(ns for scope, ns in got["ops"].items()
                if scope.startswith("forward/mamba2_")
                or scope == "forward/causal_conv1d")
    return 100.0 * spent / got["busy_ns"] if spent else None
