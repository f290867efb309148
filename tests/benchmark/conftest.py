"""For the benchmark's tests only: leave the rest of tests/ as it ran
before these files existed.

Several tests elsewhere build models without seeding them, so their
weights — and for some their outcome — depend on the process-global
generator as earlier tests in the same xdist worker left it.  These files
sort first ("benchmark" < "test_"), which would hand every worker another
sequence of files; so they are moved to the end of the collection, and
each of their tests puts the generator back as it found it.
"""
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def pytest_collection_modifyitems(items):
    mine = [i for i in items if str(i.fspath).startswith(HERE + os.sep)]
    if mine and len(mine) < len(items):
        rest = [i for i in items if not str(i.fspath).startswith(
            HERE + os.sep)]
        items[:] = rest + mine


@pytest.fixture(autouse=True)
def generator_left_as_found():
    """`paddle_tpu.seed` (the drivers make the weights from `--seed`) sets
    the process-global generator and the default programs' seeds."""
    from paddle_tpu.core import generator
    from paddle_tpu.core.program import (default_main_program,
                                         default_startup_program)
    state = generator.get_rng_state()
    seeds = (default_main_program().random_seed,
             default_startup_program().random_seed)
    yield
    generator.set_rng_state(state)
    default_main_program().random_seed, \
        default_startup_program().random_seed = seeds
