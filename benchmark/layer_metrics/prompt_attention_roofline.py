"""The blocked prompt attention's required time over its own device time
at head dim 64, per prefill launch: `work_lfm2_moe.prompt_attention_work`
at the `prompt` (valid tokens) its own `engine/prefill` span carries —
8,192 FLOPs a causal (query, key) pair a layer — over the `XLA Ops`
events under `forward/windowed_prefill_attention` inside that launch's own
module event, median over the prefills begun in the traced slice
(`prefill_attention_roofline`'s twin: that reader charges command-a-plus's
window and heads).  A program without that scope, or another
configuration, gives None."""
from benchmark import launch_events, work, work_lfm2_moe

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "higher"
SCOPE = "forward/windowed_prefill_attention"


def reduce(run):
    if "conv_L_cache" not in run.config:        # another configuration
        return None
    peak = work.peaks(run.devices[0].device_kind)

    def required_s(launch):
        asked = launch["span"].parent.fields
        if "prompt" not in asked:
            return None
        return work.roofline_seconds(*work_lfm2_moe.prompt_attention_work(
            run.config, int(asked["prompt"])), peak)[0]

    return launch_events.shares(run, "engine/prefill", required_s,
                                lambda launch: launch["scoped"].get(SCOPE))
