"""Regenerate the docs/perf.md decision table from the auto-parallel
planner (static/planner.py) — the ISSUE 10 "self-serve instead of
reviewer-tuned" loop closure.

For every row of the hand-tuned decision table (the five BASELINE
shapes — LeNet / ResNet-50 / Transformer-big / BERT-base / ERNIE-large
— at their recorded batches, plus the bert batch ladder the r5/r6
rounds measured) this tool:

  1. builds the shape's training program with the repo's own builders,
  2. runs `static.plan_program` over the knob lattice (the HAND-chosen
     knob point is always injected into the lattice so the comparison
     is apples-to-apples),
  3. prints planner knobs + predicted peak / fits / step time next to
     the hand verdict's priced record, and FAILS (exit 1) if the
     planner's choice is slower than the hand row or does not fit where
     the hand row fits — the ISSUE 10 acceptance gate.

Output: a markdown table for docs/perf.md (stdout).

Usage:
    python tools/plan_decision_table.py [--rows bert,ernie,...]
        [--fast]   # skip per-candidate verification (pricing only)
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _build_bert(batch, seq=512, layers_n=12, hidden=768, heads=12,
                vocab=30522, ring=False):
    import bench
    from paddle_tpu.core.program import _reset_unique_names
    _reset_unique_names()
    main, startup, _ = bench.build_bert_base(
        vocab, seq, hidden, layers_n, heads, batch, use_amp=True,
        use_ring=ring)
    return main, startup


def _build_ernie_large(batch):
    return _build_bert(batch, layers_n=24, hidden=1024, heads=16)


def _build_lenet(batch):
    import bench_lenet
    from paddle_tpu.core.program import _reset_unique_names
    _reset_unique_names()
    main, startup, _ = bench_lenet.build_lenet()
    return main, startup


def _build_resnet(batch):
    import bench_resnet
    from paddle_tpu.core.program import _reset_unique_names
    _reset_unique_names()
    main, startup = bench_resnet.build_resnet50(batch)[:2]
    return main, startup


def _build_transformer(batch):
    import bench_transformer
    from paddle_tpu.core.program import _reset_unique_names
    _reset_unique_names()
    out = bench_transformer.build_transformer_big(256, 256)
    return out[0], out[1]


# the tp row's geometry: the planner auto-generates dp×tp variants from
# this config (tensor_parallel builders), so the tp column is searched,
# never hand-fed
LM_TP_CONFIG = dict(vocab_size=1024, hidden=256, num_layers=4,
                    num_heads=8, seq_len=128, learning_rate=1e-4)


def _build_lm_tp_base(batch):
    import paddle_tpu.static as static
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.models import build_transformer_lm
    _reset_unique_names()
    main, startup, loss, _ = build_transformer_lm(
        vocab_size=LM_TP_CONFIG["vocab_size"],
        hidden=LM_TP_CONFIG["hidden"],
        num_layers=LM_TP_CONFIG["num_layers"],
        num_heads=LM_TP_CONFIG["num_heads"],
        seq_len=LM_TP_CONFIG["seq_len"])
    with static.program_guard(main, startup):
        static.Adam(
            learning_rate=LM_TP_CONFIG["learning_rate"]).minimize(loss)
    return main, startup


# (row key, label, builder, batch, world, hand knobs, hand-fits)
# Hand column = the human-tuned docs/perf.md verdicts (r5 on-chip ground
# truth where measured) kept as the cross-check.
ROWS = [
    ("lenet", "LeNet b256", _build_lenet, 256, 1,
     dict(remat=False, dp_shard=0, zero_stage=0, grad_merge=1,
          ring=False), True),
    ("resnet", "ResNet-50 b128", _build_resnet, 128, 1,
     dict(remat=False, dp_shard=0, zero_stage=0, grad_merge=1,
          ring=False), True),
    ("transformer", "Transformer-big s256 b16", _build_transformer, 16, 1,
     dict(remat=False, dp_shard=0, zero_stage=0, grad_merge=1,
          ring=False), True),
    ("bert32", "bert-base b32", _build_bert, 32, 1,
     dict(remat=False, dp_shard=0, zero_stage=0, grad_merge=1,
          ring=False), True),
    ("bert64", "bert-base b64", _build_bert, 64, 1,
     dict(remat=False, dp_shard=0, zero_stage=0, grad_merge=1,
          ring=False), True),
    ("bert96", "bert-base b96", _build_bert, 96, 1,
     dict(remat=True, dp_shard=0, zero_stage=0, grad_merge=1,
          ring=False), True),
    ("bert128", "bert-base b128 (N=8)", _build_bert, 128, 8,
     dict(remat=True, dp_shard=8, zero_stage=1, grad_merge=1,
          ring=False), True),
    ("ernie16", "ERNIE-large b16", _build_ernie_large, 16, 1,
     dict(remat=False, dp_shard=0, zero_stage=0, grad_merge=1,
          ring=False), True),
    ("ernie24", "ERNIE-large b24 (N=8)", _build_ernie_large, 24, 8,
     dict(remat=False, dp_shard=8, zero_stage=1, grad_merge=1,
          ring=False), True),
    # the tp column: the hand verdict is a hand-built 4×2 dp×tp config
    # (the PR-12 acceptance mesh); the planner searches the auto-
    # generated tp variants and must tie or beat it — on this
    # comfortably-fitting shape the honest answer is pure dp (no mp
    # wire), which beats the hand 2-D point
    ("lm_tp", "transformer-lm h256 s128 (N=8, dp×tp searched)",
     _build_lm_tp_base, 16, 8,
     dict(remat=False, dp_shard=0, zero_stage=0, grad_merge=1,
          ring=False, tp_degree=2), True),
]

# per-row model configs that put auto-generated tp variants on the
# lattice (rows absent here search the classic 1-D axes only)
ROW_CONFIGS = {"lm_tp": LM_TP_CONFIG}


def _fmt_knobs(k):
    parts = []
    if k.get("remat"):
        parts.append("remat")
    if k.get("dp_shard"):
        parts.append(f"zero{k.get('zero_stage') or 1}/{k['dp_shard']}")
    if int(k.get("grad_merge") or 1) > 1:
        parts.append(f"gm{k['grad_merge']}")
    if k.get("ring"):
        parts.append("ring")
    if int(k.get("tp_degree") or 0) > 1:
        parts.append(f"tp{k['tp_degree']}")
    return "+".join(parts) or "plain"


def main():
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static

    want = None
    if "--rows" in sys.argv:
        want = set(sys.argv[sys.argv.index("--rows") + 1].split(","))
    verify = "--fast" not in sys.argv

    lines = ["| config | planner choice | planned peak | fits | "
             "pred. step ms | hand verdict (cross-check) | "
             "planner ≤ hand? |",
             "|---|---|---|---|---|---|---|"]
    failures = []
    for key, label, builder, batch, world, hand, hand_fits in ROWS:
        if want and key not in want:
            continue
        t0 = time.time()
        main_p, startup_p = builder(batch)
        # inject the hand point into the lattice so it is always priced
        knobs = {
            "remat": (False, True),
            "dp_shard": tuple(sorted({0, world if world > 1 else 0,
                                      hand["dp_shard"]})),
            "grad_merge": tuple(sorted({1, hand["grad_merge"]})),
        }
        model_config = ROW_CONFIGS.get(key)
        if model_config is not None:
            knobs["tp_degree"] = tuple(sorted(
                {0, int(hand.get("tp_degree") or 0)} | {0, 2}))
        plan = static.plan_program(main_p, startup_p, world=world,
                                   batch=batch, knobs=knobs,
                                   model_config=model_config,
                                   verify=verify)
        hand_rec = next(
            (c for c in plan.trace
             if c["remat"] == hand["remat"]
             and c["dp_shard"] == hand["dp_shard"]
             and c["zero_stage"] == hand.get("zero_stage",
                                             1 if hand["dp_shard"] else 0)
             and c["grad_merge"] == hand["grad_merge"]
             and c["ring"] == hand["ring"]
             and int(c.get("tp_degree") or 0) ==
             int(hand.get("tp_degree") or 0)), None)
        beat = (plan.predicted_fits and hand_rec is not None and
                plan.predicted_step_ms <= hand_rec["step_ms"] + 1e-9)
        if hand_fits and not beat:
            failures.append(label)
        hand_txt = "?" if hand_rec is None else (
            f"{_fmt_knobs(hand)} — {hand_rec['peak_bytes'] / 2**30:.1f} "
            f"GiB, {'fits' if hand_rec['fits'] else 'OOM'}, "
            f"{hand_rec['step_ms']:.2f} ms")
        lines.append(
            f"| {label} | {_fmt_knobs(plan.knobs)} | "
            f"{plan.predicted_peak_bytes / 2**30:.1f} GiB | "
            f"{'yes' if plan.predicted_fits else 'no'} | "
            f"{plan.predicted_step_ms:.2f} | {hand_txt} | "
            f"{'yes' if beat else 'NO'} |")
        sys.stderr.write(
            f"{key}: planned in {time.time() - t0:.1f}s -> "
            f"{_fmt_knobs(plan.knobs)} "
            f"({json.dumps(plan.to_dict()['knobs'])})\n")

    print("\n".join(lines))
    if failures:
        sys.stderr.write(
            f"FAILED: planner worse than hand verdict on: {failures}\n")
        sys.exit(1)


if __name__ == "__main__":
    main()
