"""Fast CPU static-analysis gate: clean program verifies clean, seeded
deadlock + read-after-donate are caught, in seconds.

The cheap canary for the IR-verifier tier (tests/test_verify_smoke.py
runs it as a tier-1 test, mirroring mem_smoke/shard_smoke): builds a
small ZeRO-1-sharded training program and asserts the contract the
static-analysis gate rests on:

  * a CLEAN program (minimize + shard_optimizer_states on the 8-way
    plan) produces ZERO diagnostics at every level — the verifier must
    not cry wolf on the machinery the rewrite passes actually emit;
  * a seeded DEADLOCK (a collective hoisted into a control-flow
    sub-block — rank-divergent trip counts hang a real mesh) is caught
    with code V205;
  * a seeded READ-AFTER-DONATE (a forward-role op reading a parameter
    after its optimizer commit — the donated-buffer ordering bug) is
    caught with code V302.

Prints one JSON line; `value` is the wall time of the walk (three
full-program verifications, including the abstract-evaluation shape
check), reported and never asserted.

Usage: python tools/verify_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_sharded_program(dp_degree: int = 8):
    """A small minimized + ZeRO-1-sharded training program (main,
    startup, loss)."""
    import paddle_tpu.static as static
    from paddle_tpu.static import layers
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.distributed.sharding import shard_optimizer_states

    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 16])
        y = layers.data("y", [-1, 1])
        h = layers.fc(x, 32, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = static.Adam(learning_rate=1e-3)
        opt.minimize(loss)
    shard_optimizer_states(main, startup, dp_degree=dp_degree)
    return main, startup, loss


def run_smoke():
    """Run the gate; returns the result dict (AssertionError on any
    verifier regression)."""
    # every tier-1 smoke doubles as a verifier sweep (ISSUE 10):
    # armed here, the first-compile hook and the rewrite-pass
    # self-checks verify every program this gate builds, for free
    os.environ.setdefault("PADDLE_TPU_VERIFY", "warn")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static
    from paddle_tpu.core.program import OpDesc, OpRole

    t0 = time.time()

    # -- clean program: zero diagnostics ------------------------------------
    main, startup, loss = build_sharded_program()
    clean = static.check_program(main, level="all", startup=startup,
                                 fetch_list=[loss])
    assert not clean.diagnostics, (
        f"verify smoke FAILED: clean sharded program reported "
        f"{len(clean.diagnostics)} diagnostic(s):\n{clean.render()}")
    n_collectives = len(static.collective_sequence(main))
    assert n_collectives >= 2, (
        f"verify smoke FAILED: collective_sequence saw {n_collectives} "
        f"ops in a ZeRO-1 program (expected the rs/ag chain)")

    # -- seeded deadlock: collective under control flow ---------------------
    dead_main, dead_startup, dead_loss = build_sharded_program()
    sub = dead_main.create_block()
    dead_main.rollback()
    sub.ops.append(OpDesc("c_allreduce_sum", {"X": ["x"]}, {"Out": ["x"]},
                          {"ring_id": 0,
                           "op_uid": dead_main._next_uid()}))
    dead_main._fingerprint_cache = None
    dead = static.check_program(dead_main, level="all",
                                fetch_list=[dead_loss])
    assert any(d.code == "V205" for d in dead.errors), (
        f"verify smoke FAILED: seeded rank-conditional collective "
        f"(deadlock) not detected as V205; got {dead.codes()}")

    # -- seeded read-after-donate -------------------------------------------
    rad_main, rad_startup, rad_loss = build_sharded_program()
    blk = rad_main.global_block()
    param = rad_main.all_parameters()[0]
    blk.create_var(name="post_commit_read", shape=param.shape,
                   dtype=param.dtype, stop_gradient=True)
    blk.ops.append(OpDesc(
        "scale", {"X": [param.name]}, {"Out": ["post_commit_read"]},
        {"scale": 2.0, OpRole.KEY: OpRole.Forward,
         "op_uid": rad_main._next_uid()}))
    rad_main._fingerprint_cache = None
    rad = static.check_program(rad_main, level="all",
                               fetch_list=[rad_loss])
    assert any(d.code == "V302" for d in rad.errors), (
        f"verify smoke FAILED: seeded read-after-donate not detected "
        f"as V302; got {rad.codes()}")

    wall = time.time() - t0

    return {
        "metric": "verify_smoke_wall_s",
        "value": round(wall, 2),
        "clean_diagnostics": len(clean.diagnostics),
        "collectives_extracted": n_collectives,
        "deadlock_codes": dead.codes(),
        "read_after_donate_codes": rad.codes(),
    }


def main():
    print(json.dumps(run_smoke()))


if __name__ == "__main__":
    main()
