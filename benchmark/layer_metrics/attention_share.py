"""Share of chip 0's busy time in the traced slice spent in the attention
layers' own ops: instructions under the `forward/rotary_embedding`,
`forward/windowed_prefill_attention`, `forward/kv_ring_pack` and
`forward/cached_decode_attention` scopes (`device_scopes`) — positions, a
prompt's blocked attention and the ring it leaves, a decode step's write
and read of the cache; not the q / k / v / o projections around them
(`forward/matmul_v2`)."""
from benchmark import device_scopes

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "lower"
SCOPES = ("forward/rotary_embedding", "forward/windowed_prefill_attention",
          "forward/kv_ring_pack", "forward/cached_decode_attention")


def reduce(run):
    got = device_scopes.of(run)
    if got is None:
        return None
    spent = sum(got["ops"].get(scope, 0) for scope in SCOPES)
    return 100.0 * spent / got["busy_ns"] if spent else None
