"""How uneven a decode step's routing is: the largest held expert's load
over the mean load of the held experts (`moe_max_load`, the layers' largest
loads summed, x experts held / `moe_pairs`, both fields of the `engine/
step` span), median over the steps begun in the traced slice.  1 is even;
the grouped matmul's row tiles are sized by the mean."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "kernels", "program_span", "ratio", "lower"


def reduce(run):
    held = run.config.get("n_routed_experts", 0)
    return program_spans.median(
        int(sp.fields["moe_max_load"]) * held / int(sp.fields["moe_pairs"])
        for sp in program_spans.of(run)["whole"]
        if sp.name == "engine/step" and int(sp.fields.get("moe_pairs", 0)))
