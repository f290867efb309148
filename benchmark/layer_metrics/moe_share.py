"""Share of chip 0's busy time in the traced slice spent in the routed
experts' own ops: instructions under the `forward/moe_router_topk` and
`forward/moe_grouped_experts` scopes (`device_scopes`) — the gate, the
sort and the grouped expert matmuls, not the latent projections or the
shared expert around them (`forward/matmul_v2`)."""
from benchmark import device_scopes

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "lower"


def reduce(run):
    got = device_scopes.of(run)
    if got is None:
        return None
    spent = sum(ns for scope, ns in got["ops"].items()
                if scope.startswith("forward/moe_"))
    return 100.0 * spent / got["busy_ns"] if spent else None
