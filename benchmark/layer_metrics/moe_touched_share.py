"""Share of the held experts a decode step touches: the `moe_touched`
field of the `engine/step` spans begun in the traced slice over the
experts held x the expert layers, median over the steps.  The share sets
how much of the experts' weights a step streams."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "kernels", "program_span", "%", "lower"


def reduce(run):
    cfg = run.config
    slots = cfg.get("n_routed_experts", 0) \
        * cfg.get("hybrid_override_pattern", "").count("E")
    if not slots:
        return None
    return program_spans.median(
        100.0 * int(sp.fields["moe_touched"]) / slots
        for sp in program_spans.of(run)["whole"]
        if sp.name == "engine/step" and "moe_touched" in sp.fields)
