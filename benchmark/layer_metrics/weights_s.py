"""Seconds of self time in the phases that make and place what the model
holds: `model/build` (a served model's weights drawn from the seed),
`executor/first_launch` with `startup` = 1 (a start-up Program's run: a
static model's), `kv_pool/allocate` (the pool's and the state's arrays).
The executables those draws obtain are JAX's stages, counted there."""
from benchmark import setup_phases

LAYER, SOURCE, UNIT, BETTER = "model", "program_span", "s", "lower"


def reduce(run):
    return setup_phases.self_s(
        run, lambda sp: sp.name in ("model/build", "kv_pool/allocate")
        or (sp.name == "executor/first_launch"
            and int(sp.fields.get("startup", 0)) == 1))
