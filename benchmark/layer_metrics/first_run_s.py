"""Seconds of self time in `jit/program` and in `executor/first_launch`
with `startup` = 0, their recording and JAX's stages taken out: the first
execution of each new program — its dispatch, argument hand-over and
whatever of it the caller waits for."""
from benchmark import setup_phases

LAYER, SOURCE, UNIT, BETTER = "engine", "program_span", "s", "lower"


def reduce(run):
    return setup_phases.self_s(
        run, lambda sp: sp.name == "jit/program"
        or (sp.name == "executor/first_launch"
            and int(sp.fields.get("startup", 0)) == 0))
