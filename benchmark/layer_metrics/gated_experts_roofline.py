"""The gated grouped expert computation's required time over its own
device time, per launch (decode steps and prefills alike):
`work_cohere2_moe.experts_work` at the `moe_pairs` and `moe_touched` the
launch's own `engine/step` or `engine/prefill` span carries (the touched
experts' three matrices read once + the pairs' activations; 2 FLOPs a
weight a pair) over the `XLA Ops` events under `forward/moe_grouped_
experts` inside that launch's own module event, median over the launches
begun in the traced slice (`moe_experts_roofline`'s twin: that reader
charges two matrices an expert in a latent space).  A program whose spans
carry no such fields gives None."""
from benchmark import launch_events, program_spans, work, work_cohere2_moe

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "higher"
SCOPE = "forward/moe_grouped_experts"


def reduce(run):
    if "num_shared_experts" not in run.config:  # another configuration
        return None
    peak = work.peaks(run.devices[0].device_kind)
    got = []
    for launch in launch_events.of(run):
        asked = launch["span"].parent.fields
        took = launch["scoped"].get(SCOPE)
        if "moe_pairs" not in asked or not took:
            continue
        need = work.roofline_seconds(*work_cohere2_moe.experts_work(
            run.config, int(asked["moe_pairs"]),
            int(asked["moe_touched"])), peak)[0]
        got.append(100.0 * need * 1e9 / took)
    return program_spans.median(got)
