"""A prefill launch's model FLOPs over the chip's bf16 peak and its own
device event: `work_cohere2_moe.prefill_work`'s FLOPs at the `prompt`
(VALID tokens: the bucket's pads earn nothing), `moe_pairs` and
`moe_touched` its own `engine/prefill` span carries, over the peak FLOP/s
times the duration of that launch's `XLA Modules` event, median over the
prefills begun in the traced slice.  The whole prompt program's share of
the peak: matmuls, attention, experts and what the power-of-two bucket
wastes."""
from benchmark import launch_events, work, work_cohere2_moe

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "higher"


def reduce(run):
    if "sliding_window" not in run.config:      # another configuration
        return None
    peak = work.peaks(run.devices[0].device_kind)

    def required_s(launch):
        asked = launch["span"].parent.fields
        if "prompt" not in asked or "moe_pairs" not in asked:
            return None
        return work_cohere2_moe.prefill_work(
            run.config, int(asked["prompt"]), int(asked["moe_pairs"]),
            int(asked["moe_touched"]))[0] / peak["bf16_flops_per_s"]

    return launch_events.shares(
        run, "engine/prefill", required_s,
        lambda launch: launch["module"][1] - launch["module"][0])
