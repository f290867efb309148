"""The chunked state-space scan's required time over its own device
time, per prefill: the Mamba layers' `work_hybrid.ssm_scan_work` at the
prompt's own length (the recurrence's count, not the chunked form's, and
no padding) over the `XLA Ops` events under `forward/mamba2_chunk_scan`
inside that prefill's module event, median over the prefills begun in the
traced slice."""
from benchmark import launch_events, work, work_hybrid

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "higher"
SCOPE = "forward/mamba2_chunk_scan"


def reduce(run):
    layers = run.config.get("layer_types", []).count("mamba")
    if not layers:          # another configuration's cell: nothing to read
        return None
    peak = work.peaks(run.devices[0].device_kind)

    def required_s(launch):
        prefill = launch["span"].parent.fields
        if "prompt" not in prefill:
            return None
        return layers * work.roofline_seconds(*work_hybrid.ssm_scan_work(
            run.config, int(prefill["prompt"])), peak)[0]

    return launch_events.shares(
        run, "engine/prefill", required_s,
        lambda launch: launch["scoped"].get(SCOPE))
