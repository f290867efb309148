"""One decode step's required time over its own device event, for the
decoder with routed experts: the larger of required FLOPs over the bf16
peak and required bytes over the HBM peak (`work_nemotron_h.
decode_step_work` at the `active` rows, `context`, `moe_pairs` and
`moe_touched` the `engine/step` span carries) over the duration of that
step's `XLA Modules` event, median over the steps begun in the traced
slice (`decode_step_roofline`'s twin: that reader charges an MLP in
every block and a tied table)."""
from benchmark import launch_events, work, work_nemotron_h

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "higher"


def reduce(run):
    if "moe_latent_size" not in run.config:     # another configuration
        return None
    peak = work.peaks(run.devices[0].device_kind)

    def required_s(launch):
        step = launch["span"].parent.fields
        if "active" not in step or "moe_pairs" not in step:
            return None
        return work.roofline_seconds(*work_nemotron_h.decode_step_work(
            run.config, int(step["active"]), int(step.get("context", 0)),
            int(step["moe_pairs"]), int(step["moe_touched"])), peak)[0]

    return launch_events.shares(
        run, "engine/step", required_s,
        lambda launch: launch["module"][1] - launch["module"][0])
