"""The one-token state update's required time over its own device time,
per decode step: the Mamba layers' `work_hybrid.ssm_update_work` at the
step's `active` rows (state read once and written once: memory-bound)
over the `XLA Ops` events under `forward/mamba2_state_update` inside that
step's module event, median over the steps begun in the traced slice."""
from benchmark import launch_events, work, work_hybrid

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "higher"
SCOPE = "forward/mamba2_state_update"


def reduce(run):
    layers = run.config.get("layer_types", []).count("mamba")
    if not layers:          # another configuration's cell: nothing to read
        return None
    peak = work.peaks(run.devices[0].device_kind)

    def required_s(launch):
        step = launch["span"].parent.fields
        if "active" not in step:
            return None
        return layers * work.roofline_seconds(*work_hybrid.ssm_update_work(
            run.config, int(step["active"])), peak)[0]

    return launch_events.shares(
        run, "engine/step", required_s,
        lambda launch: launch["scoped"].get(SCOPE))
