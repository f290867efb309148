"""A decode step's cached attention's required time over its own device
time, per step launch: `work_cohere2_moe.decode_attention_work` at the
`kv_columns` (valid columns its rows read, summed over rows and layers) and
`active` its own `engine/step` span carries — each column's K and V read
once — over the `XLA Ops` events under `forward/cached_decode_attention`
inside that launch's own module event (the in-place write of the new
column among them), median over the steps begun in the traced slice.  A
program whose spans carry no `kv_columns` gives None."""
from benchmark import launch_events, work, work_cohere2_moe

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "higher"
SCOPE = "forward/cached_decode_attention"


def reduce(run):
    if "sliding_window" not in run.config:      # another configuration
        return None
    peak = work.peaks(run.devices[0].device_kind)

    def required_s(launch):
        asked = launch["span"].parent.fields
        if "kv_columns" not in asked:
            return None
        return work.roofline_seconds(
            *work_cohere2_moe.decode_attention_work(
                run.config, int(asked["kv_columns"]),
                int(asked["active"])), peak)[0]

    return launch_events.shares(run, "engine/step", required_s,
                                lambda launch: launch["scoped"].get(SCOPE))
