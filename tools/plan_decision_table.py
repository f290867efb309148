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


def _build_bert(batch, seq=512, layers_n=12, hidden=768, heads=12,
                vocab=30522, ring=False):
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.models import build_bert_base
    _reset_unique_names()
    main, startup, _ = build_bert_base(
        vocab, seq, hidden, layers_n, heads, batch, use_amp=True,
        use_ring=ring)
    return main, startup


def _build_ernie_large(batch):
    return _build_bert(batch, layers_n=24, hidden=1024, heads=16)


# The three non-BERT BASELINE shapes (LeNet-5; ResNet-50 v1.5;
# Transformer-big, 6+6 layers, masks as feed inputs): only this table
# builds them.
def build_lenet(use_amp=False):
    import paddle_tpu.static as static
    from paddle_tpu.static import layers

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        im = layers.data("image", [-1, 1, 28, 28])
        lbl = layers.data("label", [-1, 1], dtype="int64")
        h = layers.conv2d(im, 6, 5, padding=2, act="relu")
        h = layers.pool2d(h, 2, pool_type="max", pool_stride=2)
        h = layers.conv2d(h, 16, 5, act="relu")
        h = layers.pool2d(h, 2, pool_type="max", pool_stride=2)
        h = layers.fc(h, 120, act="relu")
        h = layers.fc(h, 84, act="relu")
        logits = layers.fc(h, 10)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, lbl))
        static.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def conv_bn(layers, x, filters, ksize, stride=1, act=None):
    y = layers.conv2d(x, filters, ksize, stride=stride,
                      padding=(ksize - 1) // 2, bias_attr=False)
    return layers.batch_norm(y, act=act)


def bottleneck(layers, x, filters, stride, downsample):
    out = conv_bn(layers, x, filters, 1, act="relu")
    out = conv_bn(layers, out, filters, 3, stride=stride, act="relu")
    out = conv_bn(layers, out, filters * 4, 1)
    if downsample:
        x = conv_bn(layers, x, filters * 4, 1, stride=stride)
    return layers.relu(layers.elementwise_add(out, x))


def build_resnet50(batch, img=224, classes=1000, use_amp=True):
    import paddle_tpu.static as static
    from paddle_tpu.static import layers
    from paddle_tpu import amp

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        im = layers.data("image", [-1, 3, img, img])
        label = layers.data("label", [-1, 1], dtype="int64")
        h = conv_bn(layers, im, 64, 7, stride=2, act="relu")
        h = layers.pool2d(h, 3, pool_type="max", pool_stride=2,
                          pool_padding=1)
        for stage, (filters, blocks) in enumerate(
                [(64, 3), (128, 4), (256, 6), (512, 3)]):
            for b in range(blocks):
                stride = 2 if (b == 0 and stage > 0) else 1
                h = bottleneck(layers, h, filters, stride, b == 0)
        h = layers.pool2d(h, pool_type="avg", global_pooling=True)
        logits = layers.fc(h, classes)
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits, label))
        opt = static.Momentum(learning_rate=0.1, momentum=0.9)
        if use_amp:
            opt = amp.decorate(opt, init_loss_scaling=1.0,
                               use_dynamic_loss_scaling=False,
                               dest_dtype="bfloat16")
        opt.minimize(loss)
    return main, startup, loss


def _mha(layers, q_in, kv_in, d_model, heads, bias=None):
    """Multi-head attention via raw static layers; bias is an additive
    [-1, 1, Tq, Tk] feed (None = unmasked)."""
    dk = d_model // heads

    def split_heads(x, t):
        y = layers.reshape(x, [-1, t, heads, dk])
        y.shape = (-1, t, heads, dk)
        return layers.transpose(y, [0, 2, 1, 3])

    tq, tk = q_in.shape[1], kv_in.shape[1]
    q = split_heads(layers.fc(q_in, d_model, num_flatten_dims=2), tq)
    k = split_heads(layers.fc(kv_in, d_model, num_flatten_dims=2), tk)
    v = split_heads(layers.fc(kv_in, d_model, num_flatten_dims=2), tk)
    logits = layers.matmul(layers.scale(q, scale=dk ** -0.5), k,
                           transpose_y=True)
    if bias is not None:
        logits = layers.elementwise_add(logits, bias)
    ctx = layers.matmul(layers.softmax(logits), v)
    ctx = layers.transpose(ctx, [0, 2, 1, 3])
    ctx = layers.reshape(ctx, [-1, tq, d_model])
    ctx.shape = (-1, tq, d_model)
    return layers.fc(ctx, d_model, num_flatten_dims=2)


def _block_post(layers, x, sub):
    return layers.layer_norm(layers.elementwise_add(x, sub),
                             begin_norm_axis=2)


def _ffn(layers, x, d_model, d_inner):
    h = layers.fc(x, d_inner, num_flatten_dims=2, act="relu")
    return layers.fc(h, d_model, num_flatten_dims=2)


def build_transformer_big(src_len, trg_len, vocab=32000, d_model=1024,
                          heads=16, n_layers=6, d_inner=4096,
                          use_amp=True):
    import paddle_tpu.static as static
    from paddle_tpu.static import layers
    from paddle_tpu import amp

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        src = layers.data("src_ids", [-1, src_len], dtype="int64")
        trg = layers.data("trg_ids", [-1, trg_len], dtype="int64")
        lbl = layers.data("labels", [-1, trg_len, 1], dtype="int64")
        causal = layers.data("trg_bias", [-1, 1, trg_len, trg_len])
        spos = layers.data("src_pos", [-1, src_len], dtype="int64")
        tpos = layers.data("trg_pos", [-1, trg_len], dtype="int64")

        enc = layers.elementwise_add(
            layers.embedding(src, size=[vocab, d_model]),
            layers.embedding(spos, size=[src_len, d_model]))
        for _ in range(n_layers):
            enc = _block_post(layers, enc,
                              _mha(layers, enc, enc, d_model, heads))
            enc = _block_post(layers, enc, _ffn(layers, enc, d_model,
                                                d_inner))

        dec = layers.elementwise_add(
            layers.embedding(trg, size=[vocab, d_model]),
            layers.embedding(tpos, size=[trg_len, d_model]))
        for _ in range(n_layers):
            dec = _block_post(layers, dec,
                              _mha(layers, dec, dec, d_model, heads,
                                   bias=causal))
            dec = _block_post(layers, dec,
                              _mha(layers, dec, enc, d_model, heads))
            dec = _block_post(layers, dec, _ffn(layers, dec, d_model,
                                                d_inner))

        logits = layers.fc(dec, vocab, num_flatten_dims=2)
        smoothed = layers.label_smooth(
            layers.one_hot(layers.reshape(lbl, [-1, trg_len]), vocab),
            epsilon=0.1)
        loss = layers.mean(layers.softmax_with_cross_entropy(
            logits, smoothed, soft_label=True))
        opt = static.Adam(learning_rate=2e-4)
        if use_amp:
            opt = amp.decorate(opt, init_loss_scaling=1.0,
                               use_dynamic_loss_scaling=False,
                               dest_dtype="bfloat16")
        opt.minimize(loss)
    return main, startup, loss


def _build_lenet(batch):
    from paddle_tpu.core.program import _reset_unique_names
    _reset_unique_names()
    main, startup, _ = build_lenet()
    return main, startup


def _build_resnet(batch):
    from paddle_tpu.core.program import _reset_unique_names
    _reset_unique_names()
    main, startup = build_resnet50(batch)[:2]
    return main, startup


def _build_transformer(batch):
    from paddle_tpu.core.program import _reset_unique_names
    _reset_unique_names()
    out = build_transformer_big(256, 256)
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
# Hand column = the human-tuned docs/perf.md verdicts, kept as the
# cross-check.
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
