"""Share of a decode step's active rows whose window ring has wrapped
(their cache is longer than the window, so the window layers read a full
ring and overwrite its oldest column): the `ring_rows` field of the
`engine/step` spans begun in the traced slice over their `active`, median
over the steps."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "kv_pool", "program_span", "%", "lower"


def reduce(run):
    return program_spans.median(
        100.0 * int(sp.fields["ring_rows"]) / int(sp.fields["active"])
        for sp in program_spans.of(run)["whole"]
        if sp.name == "engine/step" and "ring_rows" in sp.fields
        and int(sp.fields.get("active", 0)))
