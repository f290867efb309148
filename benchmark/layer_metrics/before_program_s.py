"""Seconds from the process's start (`run.py`'s `T_PROCESS`) to the start
of the program's first kept record (`import/paddle_tpu`): the interpreter,
`import jax`, the harness's `jax.devices()` — the TPU client's start — all
of it before the program is imported."""
from benchmark import setup_phases

LAYER, SOURCE, UNIT, BETTER = "process", "program_span", "s", "lower"


def reduce(run):
    parsed = setup_phases.of(run)
    return None if parsed is None else parsed["before_s"]
