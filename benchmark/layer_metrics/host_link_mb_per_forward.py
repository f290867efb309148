"""Megabytes the engine moved around a forward pass in the traced slice:
the `bytes` of `engine/upload`, `engine/fetch` and `engine/kv_install`
(over the host link) and of `engine/gather` and `engine/kv_append` (pool
to dense cache and back, in host memory), over the `engine/forward`
spans."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "kv_pool", "program_span", "MB", "lower"
MOVES = ("engine/upload", "engine/fetch", "engine/kv_install",
         "engine/gather", "engine/kv_append")


def reduce(run):
    spans = program_spans.of(run)["whole"]
    forwards = sum(sp.name == "engine/forward" for sp in spans)
    if not forwards:
        return None
    moved = sum(int(sp.fields.get("bytes", 0)) for sp in spans
                if sp.name in MOVES)
    return moved / 1e6 / forwards
