"""Share of chip 0's busy time in the traced slice spent in instructions
whose `tf_op` has `/forward/` as a path component: the `jax.named_scope`
`BlockTracer.run_op` stamps from the IR op's `op_role`.  Rematerialized forward work (`.remat` clones keep
their original's scope) counts here."""
from benchmark import device_scopes

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "lower"


def reduce(run):
    return device_scopes.role_share(run, "forward")
