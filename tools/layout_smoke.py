"""Fast CPU layout-analysis gate: a clean col→row tensor-parallel
program infers correct SPMD layouts with zero diagnostics, a seeded
missing-reduction defect is caught, in seconds.

The cheap canary for the sharding-propagation tier
(tests/test_layout_smoke.py runs it as a tier-1 test, mirroring
verify_smoke/shard_smoke): builds a Megatron col→row fc pair on a 4×2
``dp × mp`` mesh and asserts the contract the layout gate rests on:

  * the CLEAN program infers the full layout — column weight
    ``P(None, 'mp')``, row weight ``P('mp')``, the hidden activation
    feature-sharded, the row output replicated again — with ZERO V6xx
    diagnostics, and its reshard table prices the mp-ring allreduce at
    exact ring accounting (2(g−1)/g × bytes);
  * a seeded V602 (the row-parallel ``mp_allreduce_sum`` dropped — the
    partial products read as if complete) is caught with op provenance.

Prints one JSON line; `value` is the wall time of the walk (two full
propagations + a level-"layout" check_program), reported and never
asserted.

Usage: python tools/layout_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MESH = {"dp": 4, "mp": 2}
BATCH = 16


def build_tp_program(tp_degree: int = 2):
    """A minimized Megatron col→row training program (main, startup,
    loss)."""
    import paddle_tpu.static as static
    from paddle_tpu.static import layers
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.distributed.tensor_parallel import (col_parallel_fc,
                                                        row_parallel_fc)

    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 8])
        y = layers.data("y", [-1, 1])
        h = col_parallel_fc(x, 16, act="relu", tp_degree=tp_degree)
        pred = row_parallel_fc(h, 16, tp_degree=tp_degree)
        loss = layers.mean(layers.square_error_cost(pred, y))
        static.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def run_smoke():
    """Run the gate; returns the result dict (AssertionError on any
    layout-analyzer regression)."""
    # every tier-1 smoke doubles as a verifier sweep — "all" now
    # includes the layout level, so arming warn here sweeps V6xx too
    os.environ.setdefault("PADDLE_TPU_VERIFY", "warn")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static

    t0 = time.time()

    # -- clean program: full inference, zero diagnostics --------------------
    main, startup, loss = build_tp_program()
    layout = static.propagate_shardings(main, mesh_shape=MESH, batch=BATCH)
    assert not layout.diagnostics, (
        f"layout smoke FAILED: clean col→row program reported "
        f"{len(layout.diagnostics)} diagnostic(s): {layout.codes()}")
    col_w = layout.spec("col_parallel_fc_0.w_0")
    row_w = layout.spec("row_parallel_fc_0.w_0")
    hidden = layout.spec("col_parallel_fc_0.tmp_2")  # post-bias activation
    assert col_w.spec == (None, "mp"), col_w.render()
    assert row_w.spec == ("mp",), row_w.render()
    assert "mp" in hidden.axes(), hidden.render()
    # the row output (post-allreduce) must be replicated again
    part = next(n for n, s in layout.specs.items() if s.partial)
    assert part == "row_parallel_fc_0.tmp_0", part

    # reshard table: ONE mp conversion, priced at exact ring accounting
    mp_rows = [r for r in layout.reshard_table if r["axis"] == "mp"]
    assert len(mp_rows) == 1, layout.reshard_table
    g = MESH["mp"]
    expected = int(2 * (g - 1) / g * (BATCH * 16 * 4))  # [B,16] f32
    assert mp_rows[0]["bytes"] == expected, (mp_rows, expected)
    assert layout.wire_bytes_per_axis().get("mp") == expected

    # the verifier's layout level sees the same cleanliness
    report = static.check_program(main, level="layout", startup=startup,
                                  fetch_list=[loss])
    v6 = [d for d in report.diagnostics if d.code.startswith("V6")]
    assert not v6, report.render()

    # -- seeded defect: drop the row-parallel allreduce → V602 --------------
    dead_main, _, dead_loss = build_tp_program()
    dropped = 0
    for op in dead_main.global_block().ops:
        if op.type == "mp_allreduce_sum":
            op.type = "assign"
            op.attrs.pop("ring_id", None)
            dropped += 1
    dead_main._fingerprint_cache = None
    assert dropped == 1, dropped
    dead = static.propagate_shardings(dead_main, mesh_shape=MESH)
    v602 = [d for d in dead.diagnostics if d.code == "V602"]
    assert v602, (
        f"layout smoke FAILED: dropped mp_allreduce_sum not detected as "
        f"V602; got {dead.codes()}")
    assert v602[0].var == "row_parallel_fc_0.tmp_0", v602[0]
    assert v602[0].op_uid is not None

    wall = time.time() - t0

    return {
        "metric": "layout_smoke_wall_s",
        "value": round(wall, 2),
        "clean_diagnostics": len(layout.diagnostics),
        "mp_reshard_bytes": mp_rows[0]["bytes"],
        "seeded_codes": dead.codes(),
        "iterations": layout.iterations,
    }


def main():
    print(json.dumps(run_smoke()))


if __name__ == "__main__":
    main()
