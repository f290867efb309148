"""One decode step's required time over its own device event, for the
short-convolution decoder: the larger of required FLOPs over the bf16
peak and required bytes over the HBM peak (`work_lfm2_moe.
decode_step_work`: every always-read weight the step touches, the experts'
at `moe_pairs` / `moe_touched`, the KV columns read at `kv_columns`, the
conv tails, for the `active` rows the `engine/step` span carries) over the
duration of that step's `XLA Modules` event, median over the steps begun
in the traced slice (`decode_step_roofline`'s twin: that reader takes any
configuration with `layer_types` for granite's)."""
from benchmark import launch_events, work, work_lfm2_moe

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "higher"


def reduce(run):
    if "conv_L_cache" not in run.config:        # another configuration
        return None
    peak = work.peaks(run.devices[0].device_kind)

    def required_s(launch):
        step = launch["span"].parent.fields
        if "kv_columns" not in step or "moe_pairs" not in step:
            return None
        return work.roofline_seconds(*work_lfm2_moe.decode_step_work(
            run.config, int(step["active"]), int(step["kv_columns"]),
            int(step["moe_pairs"]), int(step["moe_touched"])), peak)[0]

    return launch_events.shares(
        run, "engine/step", required_s,
        lambda launch: launch["module"][1] - launch["module"][0])
