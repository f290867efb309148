"""What power-of-two prompt buckets waste: 1 - `prompt` / `bucket` of the
`engine/prefill` spans begun in the traced slice, their mean (the share of
a prefill program's rows that are pads)."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "engine", "program_span", "%", "lower"


def reduce(run):
    got = [100.0 * (1.0 - int(sp.fields["prompt"]) / int(sp.fields["bucket"]))
           for sp in program_spans.of(run)["whole"]
           if sp.name == "engine/prefill" and "bucket" in sp.fields
           and "prompt" in sp.fields]
    return sum(got) / len(got) if got else None
