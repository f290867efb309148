"""The LM head + loss rewrite (static/head_loss_rewrite.py) and its op
(`linear_softmax_xent`, ops/kernels/loss.py): same numbers as the three
ops it replaces, fires only on what the IR shows, leaves the collectives
alone, and is known to the memory and FLOPs walkers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.static as static
import paddle_tpu.static.optimizer as static_optimizer
from paddle_tpu import amp
from paddle_tpu.core import monitor
from paddle_tpu.core.program import _reset_unique_names
from paddle_tpu.models import build_bert_base
from paddle_tpu.ops.kernels import loss as loss_kernels
from paddle_tpu.static import layers
from paddle_tpu.static.head_loss_rewrite import fuse_head_loss

VOCAB, HIDDEN, BATCH, IGNORE = 101, 16, 4, 3


def _head(h, labels, **loss_kw):
    logits = layers.fc(h, VOCAB, num_flatten_dims=2)
    return logits, layers.softmax_with_cross_entropy(
        logits, labels, ignore_index=IGNORE, **loss_kw)


def _build(seq, use_amp=False, head=_head, optimizer=None, fuse=True,
           monkeypatch=None):
    """A small BERT-shaped program: embedding, layer norm, one FFN, the
    head under test.  `fuse=False` builds it with the rewrite stubbed
    out — there is no switch in the program to do that."""
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    main.random_seed = startup.random_seed = 7
    if not fuse:
        monkeypatch.setattr(static_optimizer, "fuse_head_loss",
                            lambda *a, **k: 0)
    with static.program_guard(main, startup):
        ids = layers.data("ids", [-1, seq], dtype="int64")
        labels = layers.data("labels", [-1, seq, 1], dtype="int64")
        h = layers.layer_norm(layers.embedding(ids, size=[VOCAB, HIDDEN]),
                              begin_norm_axis=2)
        h = layers.fc(h, HIDDEN, num_flatten_dims=2, act="gelu")
        loss = layers.mean(head(h, labels)[1])
        opt = optimizer or static.SGD(learning_rate=0.1)
        if use_amp:
            opt = amp.decorate(opt, init_loss_scaling=1.0,
                               use_dynamic_loss_scaling=False,
                               dest_dtype="bfloat16")
        _, params_grads = opt.minimize(loss)
    if not fuse:
        monkeypatch.undo()
    return main, startup, loss, params_grads


def _feed(seq, batch=BATCH):
    rng = np.random.RandomState(0)
    labels = rng.randint(0, VOCAB, (batch, seq, 1))
    labels[0, :3] = IGNORE                      # ignored rows are present
    return {"ids": rng.randint(0, VOCAB, (batch, seq)).astype(np.int64),
            "labels": labels.astype(np.int64)}


def _loss_and_grads(built, seq):
    main, startup, loss, params_grads = built
    exe, scope = static.Executor(), static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        out = exe.run(main, feed=_feed(seq),
                      fetch_list=[loss] + [g for _, g in params_grads])
    return [np.asarray(o, np.float64) for o in out]


def _op_types(program):
    return [op.type for op in program.global_block().ops]


def _blocks_forced(monkeypatch, seq, n):
    """Shrink the block budget so that `seq` positions fall into at
    least `n` blocks at the test's shapes."""
    monkeypatch.setattr(loss_kernels, "HEAD_BLOCK_BYTES",
                        BATCH * seq * VOCAB * 4 // n)


# -- (a) rewritten == unrewritten, (b) n > 1 == n = 1 -----------------------
@pytest.mark.parametrize("use_amp", [False, True], ids=["fp32", "amp"])
@pytest.mark.parametrize("seq,blocks", [(12, 1), (12, 3), (13, 3), (13, 5)])
def test_rewritten_program_matches_unrewritten(monkeypatch, seq, blocks,
                                               use_amp):
    want = _loss_and_grads(_build(seq, use_amp, fuse=False,
                                  monkeypatch=monkeypatch), seq)
    _blocks_forced(monkeypatch, seq, blocks)
    built = _build(seq, use_amp)
    types = _op_types(built[0])
    assert "linear_softmax_xent" in types
    assert "linear_softmax_xent_grad" in types
    assert "softmax_with_cross_entropy" not in types
    got = _loss_and_grads(built, seq)
    assert monitor.gauge_get("static.head_loss.token_blocks") >= blocks
    # fp32: the same mathematics in another order; AMP: the block's logits
    # are rounded to bf16 once where the three ops rounded them twice
    loss_tol, grad_tol = (1e-3, 5e-2) if use_amp else (1e-6, 1e-5)
    np.testing.assert_allclose(got[0], want[0], rtol=loss_tol)
    for g, w in zip(got[1:], want[1:]):
        assert np.abs(g - w).max() <= grad_tol * np.abs(w).max()


@pytest.mark.parametrize("seq", [12, 13])
def test_block_count_does_not_change_the_numbers(monkeypatch, seq):
    one = _loss_and_grads(_build(seq), seq)
    assert monitor.gauge_get("static.head_loss.token_blocks") == 1
    _blocks_forced(monkeypatch, seq, 4)
    many = _loss_and_grads(_build(seq), seq)
    assert monitor.gauge_get("static.head_loss.token_blocks") >= 4
    for a, b in zip(one, many):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("batch,seq,vocab,want", [
    (64, 512, 30522, (64, 8)),      # the one-chip cell: 8 blocks of 64
    (56, 512, 30522, (64, 8)),      # dp4's shard: 7 needed, 8 divides
    (4, 32, 512, (32, 1)),          # small programs: one piece
    (64, 509, 30522, (63, 9)),      # prime S: 8 full blocks and a tail
    (1, 4, 1 << 40, (1, 4)),        # never below one position
])
def test_head_token_blocks(batch, seq, vocab, want):
    blk, n = loss_kernels.head_token_blocks(batch, seq, vocab)
    assert (blk, n) == want
    assert (n - 1) * blk < seq <= n * blk


def test_kernel_matches_reference_gradients(monkeypatch):
    """The op alone against jax.grad of the plain formula, in float64."""
    from paddle_tpu.ops.registry import OpContext, get_op_info
    rng = np.random.RandomState(1)
    b, s, h, v = 3, 7, 5, 11
    monkeypatch.setattr(loss_kernels, "HEAD_BLOCK_BYTES", b * 2 * v * 4)
    x, w = rng.randn(b, s, h), rng.randn(h, v)
    bias, g = rng.randn(v), rng.randn(b, s, 1)
    lbl = rng.randint(0, v, (b, s, 1))
    lbl[1, 2:4] = -100
    attrs, ctx = {"ignore_index": -100}, OpContext(seed=0)

    def ref(x, w, bias):
        logp = jax.nn.log_softmax(x @ w + bias, axis=-1)
        picked = jnp.take_along_axis(logp, jnp.clip(lbl, 0, v - 1), -1)
        return jnp.where(lbl == -100, 0.0, -picked)

    ins = {"X": jnp.asarray(x), "W": jnp.asarray(w),
           "Bias": jnp.asarray(bias), "Label": jnp.asarray(lbl)}
    out = get_op_info("linear_softmax_xent").kernel(ins, attrs, ctx)
    np.testing.assert_allclose(out["Loss"], ref(x, w, bias), rtol=1e-12)
    np.testing.assert_allclose(
        out["Lse"], jax.nn.logsumexp(x @ w + bias, -1, keepdims=True),
        rtol=1e-12)
    want = jax.grad(lambda *a: jnp.sum(ref(*a) * g), (0, 1, 2))(x, w, bias)
    grad_kernel = get_op_info("linear_softmax_xent_grad").kernel
    # with the forward's Lse, as a program hands it over, and without
    for extra in ({"Lse": out["Lse"]}, {}):
        got = grad_kernel(dict(ins, **{"Loss@GRAD": jnp.asarray(g)}, **extra),
                          attrs, ctx)
        for name, ref_g in zip(("X@GRAD", "W@GRAD", "Bias@GRAD"), want):
            np.testing.assert_allclose(got[name], ref_g, rtol=1e-10,
                                       atol=1e-12)


# -- (c) the match fires only on what the IR shows ---------------------------
def _softmax_consumed(h, labels):
    logits = layers.fc(h, VOCAB, num_flatten_dims=2)
    loss, softmax = layers.softmax_with_cross_entropy(
        logits, labels, ignore_index=IGNORE, return_softmax=True)
    return logits, loss + layers.reduce_max(softmax, dim=-1, keep_dim=True)


def _logits_consumed(h, labels):
    logits, loss = _head(h, labels)
    return logits, loss + layers.reduce_max(logits, dim=-1, keep_dim=True)


def _soft_labels(h, labels):
    logits = layers.fc(h, VOCAB, num_flatten_dims=2)
    soft = layers.one_hot(labels, VOCAB)
    return logits, layers.softmax_with_cross_entropy(logits, soft,
                                                     soft_label=True)


def _no_bias(h, labels):
    logits = layers.fc(h, VOCAB, num_flatten_dims=2, bias_attr=False)
    return logits, layers.softmax_with_cross_entropy(logits, labels)


def _vocab_sharded(h, labels):
    from paddle_tpu.distributed.tensor_parallel import shard_param
    logits, loss = _head(h, labels)
    block = logits.block
    mul = next(op for op in reversed(block.ops) if op.type == "mul")
    shard_param(block.var(mul.inputs["Y"][0]), dim=1)
    return logits, loss


def _tp_stamped(h, labels):
    logits, loss = _head(h, labels)
    mul = next(op for op in reversed(logits.block.ops) if op.type == "mul")
    mul.attrs["mp_axis"] = "mp"
    return logits, loss


def _fetched(which):
    def head(h, labels):
        logits, loss = _head(h, labels)
        xent = logits.block.ops[-1]
        name = logits.name if which == "logits" else xent.outputs["Softmax"][0]
        logits.block.program._fetch_names = [name]
        return logits, loss
    return head


def _persistable_logits(h, labels):
    logits, loss = _head(h, labels)
    logits.persistable = True
    return logits, loss


@pytest.mark.parametrize("head", [
    _softmax_consumed, _logits_consumed, _soft_labels, _no_bias,
    _vocab_sharded, _tp_stamped, _fetched("logits"), _fetched("softmax"),
    _persistable_logits,
], ids=["softmax_consumed", "logits_consumed", "soft_label", "no_bias",
        "vocab_sharded_weight", "tp_stamped_mul", "logits_fetched",
        "softmax_fetched", "logits_persistable"])
@pytest.mark.parametrize("use_amp", [False, True], ids=["fp32", "amp"])
def test_rewrite_keeps_the_three_ops(head, use_amp):
    before = monitor.stat_get("static.head_loss.rewritten")
    main = _build(12, use_amp, head=head)[0]
    types = _op_types(main)
    assert "linear_softmax_xent" not in types
    assert "softmax_with_cross_entropy" in types
    assert "softmax_with_cross_entropy_grad" in types
    assert monitor.stat_get("static.head_loss.rewritten") == before


def test_rank2_classifier_is_left_alone():
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, HIDDEN])
        y = layers.data("y", [-1, 1], dtype="int64")
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.fc(x, 10), y))
        static.SGD(0.1).minimize(loss)
    assert "linear_softmax_xent" not in _op_types(main)


def test_recompute_checkpoints_are_kept():
    """A checkpoint the recompute pass will look for is not fused away."""
    holder = {}

    def head(h, labels):
        holder["logits"], loss = _head(h, labels)
        return holder["logits"], loss

    opt = static_optimizer.RecomputeOptimizer(static.SGD(0.1))
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        ids = layers.data("ids", [-1, 12], dtype="int64")
        labels = layers.data("labels", [-1, 12, 1], dtype="int64")
        h = layers.embedding(ids, size=[VOCAB, HIDDEN])
        loss = layers.mean(head(h, labels)[1])
        opt._set_checkpoints([holder["logits"]])
        opt.minimize(loss)
    assert "linear_softmax_xent" not in _op_types(main)


def test_counters_and_pass_record():
    from paddle_tpu.core.pass_framework import applied_passes
    before = monitor.stat_get("static.head_loss.rewritten")
    main = _build(12)[0]
    assert monitor.stat_get("static.head_loss.rewritten") == before + 1
    assert {"pass": "head_loss", "heads": 1} in applied_passes(main)
    # nothing left to match: a second call is a no-op
    assert fuse_head_loss(main) == 0
    assert monitor.stat_get("static.head_loss.rewritten") == before + 1


def test_fetching_a_fused_away_var_says_why():
    holder = {}

    def head(h, labels):
        holder["logits"], loss = _head(h, labels)
        return holder["logits"], loss

    main, startup, loss, _ = _build(12, head=head)
    exe, scope = static.Executor(), static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(KeyError, match="linear_softmax_xent.*_fetch_names"):
            exe.run(main, feed=_feed(12), fetch_list=[loss, holder["logits"]])


@pytest.mark.parametrize("use_amp", [False, True], ids=["fp32", "amp"])
def test_rewritten_program_verifies_clean(use_amp):
    main, startup, loss, _ = _build(12, use_amp)
    report = static.check_program(main, level="all", startup=startup,
                                  fetch_list=[loss])
    assert [d for d in report.diagnostics] == []


def test_rewritten_program_survives_serialization():
    main, startup, loss, params_grads = _build(12)
    again = static.Program.parse_from_string(main.serialize_to_string())
    assert _op_types(again) == _op_types(main)
    want = _loss_and_grads((main, startup, loss, params_grads), 12)
    got = _loss_and_grads((again, startup, loss, params_grads), 12)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)


def test_layout_analysis_flags_a_hand_sharded_fused_head():
    from paddle_tpu.distributed.tensor_parallel import shard_param
    main = _build(12)[0]
    block = main.global_block()
    op = next(o for o in block.ops if o.type == "linear_softmax_xent")
    shard_param(block.var(op.inputs["W"][0]), dim=1)
    layout = static.propagate_shardings(main, mesh_shape={"dp": 2, "mp": 2},
                                        batch=BATCH)
    assert any(d.code == "V601" and d.op_type == "linear_softmax_xent"
               for d in layout.diagnostics)


# -- (d) data parallel: the same collectives ---------------------------------
def _dp_allreduces(built, seq):
    """all-reduces in the lowered dp step of `built`, and its loss."""
    from paddle_tpu.distributed.compiled_program import CompiledProgram
    from paddle_tpu.static.executor import _persistable_names
    main, startup, loss, _ = built
    devices = jax.devices()[:4]
    cp = CompiledProgram(main).with_data_parallel(loss_name=loss.name,
                                                  places=devices)
    exe, scope = static.Executor(), static.Scope()
    feed = _feed(seq, batch=8)
    with static.scope_guard(scope):
        exe.run(startup)
        program, mesh = cp._get_program(), cp._get_mesh()
        names = [n for n in _persistable_names(program)
                 if scope.get(n) is not None]
        fn = cp._compile(program, names, sorted(feed), [loss.name], mesh)
        state = {n: scope.get(n) for n in names}
        feeds = cp.place_feed(feed)
        text = fn.lower(state, feeds, jnp.uint32(0)).as_text()
        out = exe.run(cp, feed=feed, fetch_list=[loss])
    return text.count("all_reduce"), float(np.asarray(out[0]).mean())


@pytest.mark.parametrize("use_amp", [False, True], ids=["fp32", "amp"])
def test_data_parallel_step_has_the_same_collectives(monkeypatch, use_amp):
    seq = 12
    want_n, want_loss = _dp_allreduces(
        _build(seq, use_amp, fuse=False, monkeypatch=monkeypatch), seq)
    _blocks_forced(monkeypatch, seq, 3)      # blocks on every shard
    built = _build(seq, use_amp)
    assert "linear_softmax_xent" in _op_types(built[0])
    got_n, got_loss = _dp_allreduces(built, seq)
    assert want_n > 0 and got_n == want_n
    assert got_loss == pytest.approx(want_loss, rel=1e-3 if use_amp else 1e-6)


# -- (e), (f) the walkers know the op, at the benchmark's size ---------------
@pytest.fixture(scope="module")
def bert_base_b64():
    _reset_unique_names()
    return build_bert_base(batch=64)[0]


def test_memory_walker_drops_the_head_tensors(bert_base_b64, monkeypatch):
    from paddle_tpu.static.memory_analysis import analyze_program
    monkeypatch.setattr(static_optimizer, "fuse_head_loss",
                        lambda *a, **k: 0)
    _reset_unique_names()
    before = analyze_program(build_bert_base(batch=64)[0], batch=64)
    # ISSUE 26: 18.40 GB, at the head's mul_grad, the 2.0 GB bf16 logits
    # gradient the largest live tensor
    assert before["peak_bytes"] == pytest.approx(18.40e9, rel=1e-3)
    assert before["peak_op_type"] == "mul_grad"
    report = analyze_program(bert_base_b64, batch=64)
    # now no [B, S, V] tensor is live anywhere: the live vars at the peak
    # are 2.0 GB fewer, and the peak is the grad op's own transient (two
    # blocks and the fp32 weight-gradient accumulator) on top of them
    assert report["peak_op_type"] == "linear_softmax_xent_grad"
    scratch = 2 * 64 * 64 * 30522 * 4 + 768 * 30522 * 4
    assert before["peak_bytes"] - (report["peak_bytes"] - scratch) >= 1.99e9
    assert report["peak_bytes"] <= before["peak_bytes"] - 0.9e9
    block = bert_base_b64.global_block()
    assert not any(v.shape is not None and len(v.shape) == 3
                   and v.shape[-1] == 30522 for v in block.vars.values())
    assert all(size < 0.5e9 for _, size in report["top_live"])


def test_flops_walker_counts_the_head(bert_base_b64):
    from paddle_tpu.static import analyze_flops
    report = analyze_flops(bert_base_b64, batch=64)
    # 27,976,463,878,250: what the walker counted with the three ops
    assert report["total_flops"] == pytest.approx(27976463878250, rel=0.01)
    head = [r for r in report["per_op"]
            if r["type"].startswith("linear_softmax_xent")]
    tokens, matmul = 64 * 512, 2 * 768 * 30522
    assert [r["class"] for r in head] == ["matmul", "matmul"]
    assert head[0]["flops"] >= tokens * matmul
    assert head[1]["flops"] == 2 * head[0]["flops"]
