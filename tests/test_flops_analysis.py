"""static.analyze_flops — the per-op FLOPs walker (the MFU denominator
and the planner's compute substrate).

Covers: hand-counted matmul arithmetic on a toy, the 5%-of-analytic
acceptance on all five BASELINE transformer shapes, grad = 2x forward,
per-class/per-phase structure, remat pricing the replayed segments, and
collectives costing zero compute.
"""
import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu.core.program import _reset_unique_names
from paddle_tpu.models import build_bert_base  # the shape factory


def _build_mlp(in_dim=16, hidden=32, batch_dim=-1):
    from paddle_tpu.static import layers
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [batch_dim, in_dim])
        y = layers.data("y", [batch_dim, 1])
        h = layers.fc(x, hidden, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        static.SGD(learning_rate=1e-2).minimize(loss)
    return main, startup, loss


def test_hand_counted_matmul_flops_on_mlp():
    main, _, _ = _build_mlp(in_dim=16, hidden=32)
    b = 8
    rep = static.analyze_flops(main, batch=b)
    fwd = 2 * b * (16 * 32 + 32 * 1)
    # each mul_grad = dX + dW = 2x its forward matmul
    assert rep["by_class"]["matmul"] == fwd * 3, rep["by_class"]
    # per-op rows carry provenance and land in the right phase
    mm = [r for r in rep["per_op"] if r["class"] == "matmul"]
    assert {r["phase"] for r in mm} == {"forward", "backward"}
    assert all(r["type"] in ("mul", "mul_grad") for r in mm)
    fwd_rows = [r for r in mm if r["phase"] == "forward"]
    bwd_rows = [r for r in mm if r["phase"] == "backward"]
    assert sum(r["flops"] for r in bwd_rows) == \
        2 * sum(r["flops"] for r in fwd_rows)


def test_flops_scale_linearly_with_batch():
    main, _, _ = _build_mlp()
    f1 = static.analyze_flops(main, batch=2)["total_flops"]
    f2 = static.analyze_flops(main, batch=4)["total_flops"]
    # optimizer flops are batch-independent, scalar loss-head ops nearly
    # so; everything else doubles
    opt = static.analyze_flops(main, batch=2)["by_class"]["optimizer"]
    assert f2 - opt == pytest.approx(2 * (f1 - opt), rel=1e-3)


def test_estimate_step_flops_and_default_batch():
    main, _, _ = _build_mlp()
    assert static.estimate_step_flops(main, batch=4) == \
        static.analyze_flops(main, batch=4)["total_flops"]
    # no batch -> binds -1 dims to 1 (documented lower bound)
    assert static.estimate_step_flops(main) == \
        static.estimate_step_flops(main, batch=1)


# the five BASELINE transformer shapes (BASELINE.md configs 3-5 at their
# benched batch points; docs/perf.md decision table): the acceptance bar
# is the walker landing within 5% of the analytic 6*params + 12*L*s*h
# estimate the whole perf record is denominated in
BASELINE_SHAPES = [
    # (name,              vocab,  seq, hidden, L, heads, batch)
    ("bert_base_b32",     30522,  512,  768, 12, 12, 32),
    ("bert_base_b64",     30522,  512,  768, 12, 12, 64),
    ("ernie_large_b16",   30522,  512, 1024, 24, 16, 16),
    ("transformer_big",   32768,  256, 1024,  6, 16,  8),
    ("bert_base_seq2048", 30522, 2048,  768, 12, 12,  4),
]


@pytest.mark.parametrize(
    "name,vocab,seq,hidden,layers_n,heads,batch",
    BASELINE_SHAPES, ids=[s[0] for s in BASELINE_SHAPES])
def test_baseline_shapes_within_5pct_of_analytic(name, vocab, seq, hidden,
                                                 layers_n, heads, batch):
    _reset_unique_names()
    main, _, _ = build_bert_base(vocab, seq, hidden, layers_n,
                                 heads, batch, use_amp=False)
    rep = static.analyze_flops(main, batch=batch)
    n_params = sum(int(np.prod(v.shape)) for v in main.all_parameters()
                   if v.shape is not None)
    analytic = (6 * n_params + 12 * layers_n * seq * hidden) * batch * seq
    drift = rep["total_flops"] / analytic - 1.0
    assert abs(drift) < 0.05, (
        f"{name}: walker {rep['total_flops']:.3e} vs analytic "
        f"{analytic:.3e} -> {drift * 100:+.2f}% drift")
    assert rep["n_unknown_vars"] == 0, rep["n_unknown_vars"]
    # the per-op breakdown is the planner substrate: classes populated,
    # matmul dominates a transformer
    assert rep["by_class"]["matmul"] > 0
    assert rep["by_class"]["embedding"] > 0
    assert rep["matmul_fraction"] > 0.5


def test_remat_replay_is_priced():
    """A rematerialized program re-executes forward segments in the
    backward pass; the walker prices the replayed ops (hardware flops),
    so the rewritten program reports MORE flops than the plain build."""
    from paddle_tpu.core.flags import set_flags
    _reset_unique_names()
    plain, _, _ = build_bert_base(512, 64, 64, 2, 2, 4,
                                  use_amp=False)
    _reset_unique_names()
    set_flags({"recompute": "always", "hbm_assume_batch": 4})
    try:
        remat, _, _ = build_bert_base(512, 64, 64, 2, 2, 4,
                                      use_amp=False)
    finally:
        set_flags({"recompute": "", "hbm_assume_batch": 0})
    f_plain = static.analyze_flops(plain, batch=4)["total_flops"]
    f_remat = static.analyze_flops(remat, batch=4)["total_flops"]
    assert f_remat > f_plain


def test_ring_attention_op_priced_like_materialized_path():
    """The ring_attention op (one fused IR node) must price the same
    QK^T/PV work as the materialized matmul+softmax path it replaces."""
    _reset_unique_names()
    plain, _, _ = build_bert_base(512, 64, 64, 2, 2, 4,
                                  use_amp=False, use_ring=False)
    _reset_unique_names()
    ring, _, _ = build_bert_base(512, 64, 64, 2, 2, 4,
                                 use_amp=False, use_ring=True)
    rp = static.analyze_flops(plain, batch=4)
    rr = static.analyze_flops(ring, batch=4)
    att = rr["by_class"]["attention"]
    # fwd 4*B*S^2*H per layer, bwd 2x -> 12*B*S^2*H per layer
    assert att == 12 * 4 * 64 * 64 * 64 * 2
    # totals agree within the elementwise ops the fused node subsumes
    assert abs(rr["total_flops"] - rp["total_flops"]) / rp["total_flops"] \
        < 0.05


def test_collectives_cost_zero_compute():
    """Wire cost lives in collective_wire_bytes; the FLOPs walker must
    not double-charge collectives as compute."""
    from paddle_tpu.distributed.compiled_program import \
        insert_grad_allreduce
    main, _, _ = _build_mlp()
    reduced = insert_grad_allreduce(main)
    rep = static.analyze_flops(reduced, batch=4)
    assert "collective" not in rep["by_class"]
    rows = [r for r in rep["per_op"] if r["class"] == "collective"]
    assert rows and all(r["flops"] == 0 for r in rows)


def test_peak_flops_env_override(monkeypatch):
    from paddle_tpu.static.flops_analysis import peak_flops_per_chip
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "123e9")
    assert peak_flops_per_chip() == 123e9
    monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS")
    assert peak_flops_per_chip() == 0.0          # tests run on the CPU
    assert peak_flops_per_chip("cpu") == 0.0
    assert peak_flops_per_chip("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="device_kind"):
        peak_flops_per_chip("TPU v9")
