"""Time the prefetcher's producer thread spends in `prefetcher/build` (the
source making a batch) and `prefetcher/place` (its placement on the
device or the mesh), per train step of the traced slice: how far the
feeder is from mattering against the step."""
from benchmark import program_spans

LAYER, SOURCE, UNIT, BETTER = "input", "program_span", "ms", "lower"


def reduce(run):
    busy = program_spans.total_ms(run, ("prefetcher/build",
                                        "prefetcher/place"))
    if busy is None or not run.slice_units:
        return None
    return busy / run.slice_units
