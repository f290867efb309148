"""A prompt's blocked attention's required time over its own device time,
per prefill launch: `work_cohere2_moe.prefill_attention_work` at the
`prompt` (valid tokens) its own `engine/prefill` span carries — 4 FLOPs a
head dim a visible (query, key) pair, sum_i min(i + 1, window) a window
layer — over the `XLA Ops` events under `forward/windowed_prefill_
attention` inside that launch's own module event, median over the prefills
begun in the traced slice.  The kernel runs over the bucket, pads
included: the share says what the padding and the masked halves of the
diagonal blocks cost too.  A program without that scope gives None."""
from benchmark import launch_events, work, work_cohere2_moe

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "higher"
SCOPE = "forward/windowed_prefill_attention"


def reduce(run):
    if "sliding_window" not in run.config:      # another configuration
        return None
    peak = work.peaks(run.devices[0].device_kind)

    def required_s(launch):
        asked = launch["span"].parent.fields
        if "prompt" not in asked:
            return None
        return work.roofline_seconds(
            *work_cohere2_moe.prefill_attention_work(
                run.config, int(asked["prompt"])), peak)[0]

    return launch_events.shares(run, "engine/prefill", required_s,
                                lambda launch: launch["scoped"].get(SCOPE))
