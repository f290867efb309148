"""Share of the held experts a decode step touches, for a configuration
whose every layer has experts: the `moe_touched` field of the `engine/step`
spans begun in the traced slice over the experts held (`num_experts`) x the
layers run (`num_hidden_layers`), median over the steps
(`moe_touched_share`'s twin: that reader counts expert layers as the `E`s
of a `hybrid_override_pattern`).  The share sets how much of the experts'
weights a step streams."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "kernels", "program_span", "%", "lower"


def reduce(run):
    cfg = run.config
    if "num_shared_experts" not in cfg:         # another configuration
        return None
    slots = cfg["num_experts"] * cfg["num_hidden_layers"]
    return program_spans.median(
        100.0 * int(sp.fields["moe_touched"]) / slots
        for sp in program_spans.of(run)["whole"]
        if sp.name == "engine/step" and "moe_touched" in sp.fields)
