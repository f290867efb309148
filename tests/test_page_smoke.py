"""Tier-1 paged-KV gate (NOT marked slow — a regression in planner
sizing, prefix sharing, COW isolation, paged decode equality, or the
bounded-compiled-shapes contract must fail the suite, not wait for a
perf round).

Drives tools/page_smoke.py in-process: pool allocated at the
planner-chosen budget (page_budget, never hand-set), two prompts
sharing a head occupying fewer pages than 2x solo, token-equal greedy
decode through the paged engine, and zero post-warmup KV-bucket growth.
Mirrors the mem_smoke/serve_smoke gate pattern; the CLI round-trip is
`slow` (a fresh interpreter buys no extra coverage in-process).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_page_smoke_gate():
    import page_smoke
    import paddle_tpu
    from paddle_tpu.core import generator
    from paddle_tpu.core.program import (default_main_program,
                                         default_startup_program)
    # seeded: the warm-up request must reach a decode step, and with
    # weights whose first sampled token is EOS it ends at its prefill, so
    # the decode bucket reads as a retrace.  Drawn unseeded, the weights
    # hang on whatever ran before in this worker (about 1 state in 50
    # fails).  The generator is put back as found.
    state = generator.get_rng_state()
    seeds = (default_main_program().random_seed,
             default_startup_program().random_seed)
    paddle_tpu.seed(1234)
    try:
        result = page_smoke.run_smoke()
    finally:
        generator.set_rng_state(state)
        default_main_program().random_seed, \
            default_startup_program().random_seed = seeds
    assert result["traces_after_warmup"] == 0, result
    assert result["shared_pages_for_two"] < 2 * result["solo_pages"], \
        result
    assert result["prefix_hits"] == 2, result
    assert result["pages"] >= 1 and result["max_slots"] >= 1, result
    assert result["value"] < 60, result  # in-process gate stays fast


@pytest.mark.slow
def test_page_smoke_cli_prints_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "page_smoke.py")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["traces_after_warmup"] == 0
    assert result["shared_pages_for_two"] < 2 * result["solo_pages"]
