"""Share of chip 0's busy time in the traced slice spent in instructions
whose `tf_op` has `/optimize/` as a path component: the `jax.named_scope`
`BlockTracer.run_op` stamps from the IR op's `op_role`."""
from benchmark import device_scopes

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "lower"


def reduce(run):
    return device_scopes.role_share(run, "optimize")
