"""Seconds inside the `import/paddle_tpu` phase: the package's first line
to its last (kernel registry, every subpackage)."""
from benchmark import setup_phases

LAYER, SOURCE, UNIT, BETTER = "process", "program_span", "s", "lower"


def reduce(run):
    parsed = setup_phases.of(run)
    if parsed is None:
        return None
    return sum(sp.ns for sp in parsed["spans"]
               if sp.name == "import/paddle_tpu") / 1e9
