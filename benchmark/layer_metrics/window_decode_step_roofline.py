"""One decode step's required time over its own device event, for the
decoder with window and full attention layers over a device-only KV cache
and gated routed experts: the larger of required FLOPs over the bf16 peak
and required bytes over the HBM peak (`work_cohere2_moe.decode_step_work`
at the `active` rows, `kv_columns`, `moe_pairs` and `moe_touched` the
`engine/step` span carries) over the duration of that step's `XLA Modules`
event, median over the steps begun in the traced slice
(`decode_step_roofline`'s and `moe_decode_step_roofline`'s twin: those
readers charge granite's and nemotron's layers; `decode_step_roofline`
takes any configuration with `layer_types` for granite's, so this one has
a name of its own)."""
from benchmark import launch_events, work, work_cohere2_moe

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "higher"


def reduce(run):
    if "sliding_window" not in run.config:      # another configuration
        return None
    peak = work.peaks(run.devices[0].device_kind)

    def required_s(launch):
        step = launch["span"].parent.fields
        if "kv_columns" not in step or "moe_pairs" not in step:
            return None
        return work.roofline_seconds(*work_cohere2_moe.decode_step_work(
            run.config, int(step["active"]), int(step["kv_columns"]),
            int(step["moe_pairs"]), int(step["moe_touched"])), peak)[0]

    return launch_events.shares(
        run, "engine/step", required_s,
        lambda launch: launch["module"][1] - launch["module"][0])
