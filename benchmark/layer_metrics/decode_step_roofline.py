"""One decode step's required time over its own device event: the larger
of required FLOPs over the bf16 peak and required bytes over the HBM peak
(`work_hybrid.decode_step_work` at the `active` rows and `context` the
`engine/step` span carries) over the duration of that step's `XLA
Modules` event, median over the steps begun in the traced slice."""
from benchmark import launch_events, work, work_hybrid

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "higher"


def reduce(run):
    if "layer_types" not in run.config:     # another configuration's cell
        return None
    peak = work.peaks(run.devices[0].device_kind)

    def required_s(launch):
        step = launch["span"].parent.fields
        if "active" not in step:
            return None
        return work.roofline_seconds(*work_hybrid.decode_step_work(
            run.config, int(step["active"]), int(step.get("context", 0))),
            peak)[0]

    return launch_events.shares(
        run, "engine/step", required_s,
        lambda launch: launch["module"][1] - launch["module"][0])
