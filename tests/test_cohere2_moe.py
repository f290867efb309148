"""The decoder with window and full attention layers over a device-only KV
cache with a ring, rotary positions, a parallel block and gated routed
experts beside averaged shared experts (one chip's share of an
expert-parallel deployment): the new ops against their formulas, the ring
a prompt leaves and the one a decode step writes, the eight shares adding
up to the uncut layer, the description against the plain float32 reference
(full pass; prefill then decode through the cache, logits, across ring
wraps and for prompts on both sides of the window), the cache description
with several `kv` groups through the pool and the planner, and the engine's
compiled step route — chosen by the step contract — with nothing of the KV
crossing the host link.

Tolerances are `tests/test_granite_hybrid.py`'s, for its reasons: model
and reference are both float32 here and differ only by the order of
float32 additions (blocks of queries and of cached columns with an online
softmax, a sorted grouped matmul, padded buckets, batched rows against one
softmax a row over one unpadded sequence), so logits agree to LOGIT_SIGMAS
= 1e-3 of their own spread (seen: <= 3e-6).
"""
import hashlib
import inspect
import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.dygraph as dg
import paddle_tpu.static as static
from paddle_tpu.models import (Cohere2MoeConfig, cohere2_moe_tiny,
                               granite_hybrid_tiny, nemotron_h_tiny)
from paddle_tpu.ops.registry import OpContext, get_op_info, run_kernel
from paddle_tpu.serving import ContinuousBatchingEngine, budget_drift
from paddle_tpu.serving.kv_pool import (PagedKVPool, device_kv_arrays,
                                        kv_geometry, retained_kv_groups,
                                        state_slot_bytes)
from paddle_tpu.serving.metrics import reset_serving_stats, serving_stats

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import cohere2_moe as reference  # noqa: E402

LOGIT_SIGMAS = 1e-3
CTX = OpContext(seed=0, is_test=True)
W = 8                           # the tiny window
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


@pytest.fixture(autouse=True)
def generator_left_as_found():
    """Every test here seeds its own weights (`paddle_tpu.seed`); the
    process-global generator goes back as it was (as
    `tests/test_nemotron_h.py` does)."""
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


def _assert_logits(got, want, spread=None):
    spread = float(np.std(want)) if spread is None else spread
    assert float(np.abs(np.asarray(got) - want).max()) \
        <= LOGIT_SIGMAS * spread


def _t(a, dtype=None):
    return paddle_tpu.to_tensor(np.asarray(a, dtype))


def _model(seed, **kw):
    paddle_tpu.seed(seed)            # every test seeds its own weights
    return cohere2_moe_tiny(**kw)


def _published(cfg):
    """The keys `reference.logits` reads, from a built config."""
    out = {k: getattr(cfg, k) for k in cfg._HF_KEYS if hasattr(cfg, k)}
    out.update(num_experts=cfg.held_experts,
               num_experts_per_tok=cfg.num_experts_per_tok,
               num_shared_experts=cfg.n_shared_experts,
               layer_norm_eps=cfg.rms_norm_eps,
               first_held_expert=cfg.first_held)
    return out


def _reference_logits(m, ids, **kw):
    ids = np.asarray(ids, np.int32)
    return np.asarray(reference.logits(
        reference.params_of(m), ids, _published(m.config), **kw))


def _plain_attention(q, k, v, window=0):
    """softmax(q k^T / sqrt(d) + mask) v, the mask token by token."""
    hq, hkv, d = q.shape[1], k.shape[1], q.shape[-1]
    k, v = (np.repeat(x, hq // hkv, axis=1) for x in (k, v))
    s = np.einsum("bhtd,bhsd->bhts", q, k) * d ** -0.5
    i, j = np.arange(q.shape[2])[:, None], np.arange(k.shape[2])[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhts,bhsd->bhtd", p / p.sum(-1, keepdims=True), v)


# -- the ops ------------------------------------------------------------------
@pytest.mark.parametrize("op", ["rotary_embedding",
                                "windowed_prefill_attention", "kv_ring_pack",
                                "cached_decode_attention"])
def test_attention_ops_are_registered_forward_only(op):
    info = get_op_info(op)
    assert info is not None and info.grad is None
    assert get_op_info(op + "_grad") is None


@pytest.mark.parametrize("t", [1, 5, 16, 40])
def test_rotary_turns_interleaved_pairs_by_the_position(t):
    rng = np.random.default_rng(t)
    x = rng.normal(size=(2, 3, t, 16)).astype(np.float32)
    pos = rng.integers(0, 5000, (2, t)).astype(np.int32)
    got = np.asarray(run_kernel("rotary_embedding", {
        "X": x, "Positions": pos}, {"theta": 50000.0}, CTX)["Out"])
    # float32 angles from the int32 positions, as the op states
    inv = np.float32(50000.0) ** (-np.arange(0, 16, 2, dtype=np.float32)
                                  / np.float32(16))
    angle = (pos.astype(np.float32)[:, None, :, None] * inv).astype(
        np.float64)                                         # [B,1,T,8]
    even, odd = x[..., 0::2], x[..., 1::2]
    want = np.stack([even * np.cos(angle) - odd * np.sin(angle),
                     odd * np.cos(angle) + even * np.sin(angle)],
                    -1).reshape(x.shape)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    # without positions: a prompt's 0..T-1
    got = np.asarray(run_kernel("rotary_embedding", {"X": x},
                                {"theta": 50000.0}, CTX)["Out"])
    plain = np.asarray(run_kernel("rotary_embedding", {
        "X": x, "Positions": np.broadcast_to(np.arange(t, dtype=np.int32),
                                             (2, t))},
        {"theta": 50000.0}, CTX)["Out"])
    np.testing.assert_array_equal(got, plain)
    np.testing.assert_allclose(got[:, :, 0], x[:, :, 0], atol=1e-6)


def _prompt_attention(q, k, v, window, lengths=None):
    """`lengths`: one for the only row, or one a row."""
    ins = {"Q": q, "K": k, "V": v}
    if lengths is not None:
        ins["Lengths"] = np.asarray(lengths, np.int32).reshape(-1)
    return np.asarray(run_kernel("windowed_prefill_attention", ins,
                                 {"window": window}, CTX)["Out"])


def _assert_prompt_attention(got, q, k, v, window, length, block_q):
    """Rows of the blocks of queries that begin before `length` are the
    plain attention's (the block the length falls in whole), rows of the
    blocks past it zeros; every row is finite."""
    t = q.shape[2]
    computed = t if length is None else min(-(-length // block_q) * block_q,
                                            t)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got[:, :, :computed], _plain_attention(q, k, v, window)[
            :, :, :computed], rtol=0, atol=2e-6)
    assert not got[:, :, computed:].any()


@pytest.mark.parametrize("window", [0, W, 3, 512])
@pytest.mark.parametrize("t,length", [
    (8, None), (21, None), (64, None), (384, None),     # every row valid
    # 384 tokens are three blocks of 128 queries: a length inside the
    # first, on a block's boundary, one past it, and the whole bucket
    (384, 5), (384, 128), (384, 129), (384, 256), (384, 384), (21, 9)])
def test_prompt_attention_is_the_masked_softmax_window_or_none(
        t, length, window):
    """window 0 (none), shorter than the prompt, and at least as long;
    with `Lengths`, blocks of queries past the length are left out."""
    from paddle_tpu.ops.kernels import window_attention
    rng = np.random.default_rng(t + window)
    q = rng.normal(size=(1, 4, t, 16)).astype(np.float32)
    k = rng.normal(size=(1, 2, t, 16)).astype(np.float32)
    v = rng.normal(size=(1, 2, t, 16)).astype(np.float32)
    got = _prompt_attention(q, k, v, window, length)
    block_q = window_attention._fit_block(t + -t % 8,
                                          window_attention._BLOCK_Q)
    assert block_q == min(128, t + -t % 8)
    _assert_prompt_attention(got, q, k, v, window, length, block_q)
    if length is not None:      # without Lengths every row is computed
        np.testing.assert_array_equal(
            got[:, :, :length],
            _prompt_attention(q, k, v, window)[:, :, :length])


@pytest.mark.parametrize("fold_rows", [16, 8])
@pytest.mark.parametrize("length", [None, 100, 41])
@pytest.mark.parametrize("window", [0, 48, 40, 12])
def test_prompt_attention_drives_every_phase_of_its_key_loop(
        window, length, fold_rows, monkeypatch):
    """Blocks of 8 queries against blocks of 16 keys over 128 tokens: a
    late block of queries of a window-48 layer reads a far-edge block
    (masked by the window alone), two interior blocks (no mask) and its
    diagonal block (causal); a window of 40 is not whole blocks (two edge
    blocks), one of 12 reaches into the diagonal block (both masks there,
    no interior); the full layer has interior blocks and the diagonal.
    The two heads of a kv head are folded together or (`fold_rows` 8) one
    at a time.  All equal the plain attention, with lengths (on no
    boundary) and without."""
    from paddle_tpu.ops.kernels import window_attention
    monkeypatch.setattr(window_attention, "_BLOCK_Q", 8)
    monkeypatch.setattr(window_attention, "_BLOCK_K", 16)
    monkeypatch.setattr(window_attention, "_FOLD_ROWS", fold_rows)
    t = 128
    rng = np.random.default_rng(window + 7)
    q = rng.normal(size=(2, 4, t, 16)).astype(np.float32)
    k = rng.normal(size=(2, 2, t, 16)).astype(np.float32)
    v = rng.normal(size=(2, 2, t, 16)).astype(np.float32)
    lengths = length and [length, length // 2]  # the second shorter still
    got = _prompt_attention(q, k, v, window, lengths)
    for row, n in enumerate(lengths or [None, None]):
        at = slice(row, row + 1)
        _assert_prompt_attention(got[at], q[at], k[at], v[at], window, n, 8)


@pytest.mark.parametrize("p", [1, 5, 8, 9, 13, 27, 32])
def test_a_prompts_ring_holds_its_last_window_at_position_mod_window(p):
    x = np.random.default_rng(p).normal(size=(1, 2, 32, 4)).astype(
        np.float32)
    ring = np.asarray(run_kernel("kv_ring_pack", {
        "X": x, "Lengths": np.asarray([p], np.int32)}, {"window": W},
        CTX)["Out"])
    assert ring.shape == (1, 2, W, 4)
    for position in range(max(0, p - W), p):
        np.testing.assert_array_equal(ring[0, :, position % W],
                                      x[0, :, position])
    # a prompt bucket shorter than the window: padded, each key at its own
    short = np.asarray(run_kernel("kv_ring_pack", {
        "X": x[:, :, :4], "Lengths": np.asarray([3], np.int32)},
        {"window": W}, CTX)["Out"])
    np.testing.assert_array_equal(short[:, :, :4], x[:, :, :4])


@pytest.mark.parametrize("window", [0, W])
def test_decode_attention_writes_in_place_and_reads_across_ring_wraps(window):
    """Three rows decode 30 tokens side by side from empty caches, one of
    them idle every third step: an active row's result is the plain masked
    softmax over all it has seen (the ring never unrolled), the column
    lands at `length mod window`, and an idle row reads nothing (its
    column lands where its own next token will overwrite it)."""
    rng = np.random.default_rng(window)
    rows, t, columns = 3, 30, window or 32
    q = rng.normal(size=(rows, 4, t, 16)).astype(np.float32)
    k = rng.normal(size=(rows, 2, t, 16)).astype(np.float32)
    v = rng.normal(size=(rows, 2, t, 16)).astype(np.float32)
    kc = np.zeros((2, rows, 2, columns, 16), np.float32)
    vc = np.zeros_like(kc)
    lengths = np.zeros(rows, np.int32)
    want = _plain_attention(q, k, v, window)
    fed = np.zeros(rows, np.int32)      # tokens each row has taken
    for step in range(2 * t):
        active = np.asarray([1, step % 3 != 0, 1], np.int32) \
            * (fed < t)
        if not active.any():
            continue
        take = np.minimum(fed, t - 1)
        pick = lambda x: np.stack(          # noqa: E731
            [x[r, :, take[r]] for r in range(rows)])[:, :, None]
        out = run_kernel("cached_decode_attention", {
            "Q": pick(q), "K": pick(k), "V": pick(v), "KCache": kc,
            "VCache": vc, "CacheLengths": fed.astype(np.int32),
            "Active": active},
            {"slab_index": 1, "window": window}, CTX)
        kc, vc = np.asarray(out["NewKCache"]), np.asarray(out["NewVCache"])
        got = np.asarray(out["Out"])
        for r in range(rows):
            if active[r]:
                np.testing.assert_allclose(got[r, :, 0], want[r, :, fed[r]],
                                           rtol=0, atol=3e-6)
                np.testing.assert_array_equal(
                    kc[1, r, :, fed[r] % columns], k[r, :, fed[r]])
            else:
                assert not got[r].any()
        assert not kc[0].any()              # the other layer's entry
        fed += active
    assert (fed == t).all()


@pytest.mark.parametrize("first,held", [(0, 16), (4, 4), (14, 2)])
@pytest.mark.parametrize("block", [None, 8, 64])
def test_gated_experts_are_the_per_expert_loop_held_first_or_not(
        first, held, block, monkeypatch):
    """`activation="silu_gated"`: W1 = [gate | up]; and a call of many
    pairs (`_BLOCK_ROWS`, shrunk here) sorts the held pairs first and
    computes them block by block to the same sums and the same counts."""
    from paddle_tpu.ops.kernels import moe
    if block:
        monkeypatch.setattr(moe, "_BLOCK_ROWS", block)
    rng = np.random.default_rng(first + held)
    b, t, d, f, e, k = 2, 24, 16, 8, 16, 4
    x = rng.normal(size=(b, t, d)).astype(np.float32)
    experts = np.stack([rng.choice(e, k, replace=False)
                        for _ in range(b * t)]).reshape(b, t, k).astype(
                            np.int32)
    w = rng.uniform(size=(b, t, k)).astype(np.float32)
    w1 = (rng.normal(size=(held, d, 2 * f)) * 0.3).astype(np.float32)
    w2 = (rng.normal(size=(held, f, d)) * 0.3).astype(np.float32)
    lengths = np.asarray([20, 24], np.int32)
    out = run_kernel("moe_grouped_experts", {
        "X": x, "Experts": experts, "Weights": w, "W1": w1, "W2": w2,
        "Lengths": lengths}, {"n_experts": e, "first_held": first,
                              "held": held, "activation": "silu_gated"}, CTX)
    want, loads = np.zeros((b, t, d), np.float32), np.zeros(held, np.int64)
    for i in range(b):
        for j in range(int(lengths[i])):
            for n in range(k):
                at = int(experts[i, j, n]) - first
                if 0 <= at < held:
                    h = x[i, j] @ w1[at]
                    h = h[:f] / (1 + np.exp(-h[:f])) * h[f:]
                    want[i, j] += w[i, j, n] * (h @ w2[at])
                    loads[at] += 1
    np.testing.assert_allclose(np.asarray(out["Out"]), want, rtol=0,
                               atol=5e-6)
    np.testing.assert_array_equal(
        np.asarray(out["Stats"]),
        [int(lengths.sum()) * k, loads.sum(), (loads > 0).sum(),
         loads.max()])
    with pytest.raises(ValueError, match="activation"):
        run_kernel("moe_grouped_experts", {
            "X": x, "Experts": experts, "Weights": w, "W1": w1, "W2": w2},
            {"n_experts": e, "first_held": first, "held": held,
             "activation": "gelu"}, CTX)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips of 2 experts each: what the shares' layers add to the
    residual, with what every chip computes alike — attention and the
    averaged shared experts — counted once, is the uncut reference's
    layer."""
    with dg.guard():
        whole = _model(11)
        ids = np.random.default_rng(2).integers(0, 126, 24).astype(np.int32)
        params = reference.params_of(whole)
        cfg = _published(whole.config)
        sizes = reference.sizes_of(cfg)
        h0 = np.asarray(params["embed"])[ids].astype(np.float32)
        layer = params["layers"][0]
        uncut = np.asarray(reference._layer_jit(
            h0, layer, True, sizes, None, None, True, True)[0])
        # a share's layer: the same arrays, its two experts' matrices
        total, alike = np.zeros_like(uncut), None
        for chip in range(8):
            mine = dict(layer, w1=layer["w1"][2 * chip:2 * chip + 2],
                        w2=layer["w2"][2 * chip:2 * chip + 2])
            share = sizes[:7] + (2 * chip,) + sizes[8:]
            out = np.asarray(reference._layer_jit(
                h0, mine, True, share, None, None, True, True)[0])
            total += out - h0
            # no expert held: attention and the shared experts alone
        none = dict(layer, w1=layer["w1"][:0], w2=layer["w2"][:0])
        alike = np.asarray(reference._layer_jit(
            h0, none, True, sizes, None, None, True, True)[0]) - h0
        np.testing.assert_allclose(h0 + total - 7 * alike, uncut, rtol=0,
                                   atol=1e-5)
        # and the model's own share is the reference's
        share = _model(11, held_experts=2, first_held=6)
        _assert_logits(
            np.asarray(share(_t(ids[None])).numpy())[0],
            _reference_logits(share, ids))


# -- the description ----------------------------------------------------------
def _catalog_row():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "command-a-plus-05-2026.json")) as f:
        file = json.load(f)
    return dict(file, **file["published"])      # the row as published


def test_from_published_reads_the_published_keys():
    cfg = Cohere2MoeConfig.from_published(
        _catalog_row(), held_experts=16, first_held=0, vocab_rows=32768,
        layers=(0, 4), max_position=8192, eos_id=32767)
    assert cfg.layer_types == PERIOD
    assert cfg.param_count() == 4_733_292_544
    assert (cfg.n_routed_experts, cfg.held_experts, cfg.num_experts_per_tok,
            cfg.n_shared_experts, cfg.moe_intermediate_size) \
        == (128, 16, 8, 4, 4096)
    assert (cfg.sliding_window, cfg.rope_theta, cfg.attention_multiplier) \
        == (4096, 50000.0, 128 ** -0.5)
    assert (cfg.block_form, cfg.norm_kind, cfg.expert_form) \
        == ("parallel", "layer", "gated_silu")
    assert [(s.window, s.rotary) for s in map(cfg.attention_spec, range(4))] \
        == [(4096, 50000.0)] * 3 + [(None, None)]
    assert cfg.cache_spec() == [
        {"kind": "kv", "layers": 3, "kv_heads": 8, "head_dim": 128,
         "dtype": "bfloat16", "retain": 4096},
        {"kind": "kv", "layers": 1, "kv_heads": 8, "head_dim": 128,
         "dtype": "bfloat16", "retain": "all"}]
    whole = Cohere2MoeConfig.from_published(_catalog_row())
    assert len(whole.blocks) == 32 and whole.vocab_size == 262144
    assert round(whole.param_count() / 1e9, 1) == 218.3


@pytest.mark.parametrize("key,value", [
    ("use_qk_norm", True), ("attention_bias", True), ("rotary_pct", 0.5),
    ("first_k_dense_replace", 1), ("use_parallel_block", False),
    ("expert_selection_fn", "softmax"),
    ("shared_expert_combination_strategy", "sum"),
    ("use_gated_activation", False), ("position_embedding_type", "rope"),
    ("tie_word_embeddings", False), ("vision_config", {"depth": 2}),
    ("rope_parameters", {"rope_type": "yarn", "rope_theta": 50000})])
def test_from_published_names_what_is_not_built(key, value):
    with pytest.raises(NotImplementedError, match=key.split("_")[0]):
        Cohere2MoeConfig.from_published(dict(_catalog_row(), **{key: value}))


def test_config_refuses_layer_kinds_and_shares_it_cannot_describe():
    with pytest.raises(NotImplementedError, match="chunked_attention"):
        Cohere2MoeConfig(layer_types=["chunked_attention"])
    with pytest.raises(ValueError, match="are not among"):
        Cohere2MoeConfig(held_experts=16, first_held=120)
    from paddle_tpu.models.hybrid_decoder import (AttentionSpec,
                                                  HybridDecoderConfig)
    bad = granite_hybrid_tiny().config
    bad.attention_specs = {bad.layers_of("mamba")[0]:
                           AttentionSpec(window=8)}
    with pytest.raises(ValueError, match="no attention to describe"):
        HybridDecoderConfig._check(bad)


def test_a_window_beside_a_recurrent_state_is_one_more_description():
    """No option picks the KV's path: whatever a description's attention
    layers state — here granite's tiny layers with a window of 8 and rotary
    positions given to its one attention layer — its cache groups say
    `retain`, the ring rides the step contract before the Mamba state, and
    the engine serves it through ONE decode program (a ring: no bound on
    the columns), token-equal to the model's own full forward on both
    sides of the window and past two wraps."""
    from paddle_tpu.models import GraniteHybridModel
    from paddle_tpu.models.hybrid_decoder import AttentionSpec
    reset_serving_stats()
    with dg.guard():
        paddle_tpu.seed(21)
        cfg = granite_hybrid_tiny().config
        assert not cfg.kv_ring
        cfg.attention_specs = {2: AttentionSpec(window=W, rotary=10000.0)}
        assert cfg.kv_ring and cfg.kv_groups() == [(W, [2])]
        assert cfg.cache_spec()[0] == {
            "kind": "kv", "layers": 1, "kv_heads": 2, "head_dim": 16,
            "dtype": "float32", "retain": W}
        m = GraniteHybridModel(cfg)
        plan = static.page_budget(m, page_tokens=4, max_context=64,
                                  hbm_bytes=16 << 20, max_slots_cap=2)
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        state = eng.kv_pool.state
        assert state.names == ["k0", "v0", "ssm", "conv"]
        assert state.arrays["k0"].shape == (1, 2, 2, W, 16)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 126, n) for n in (5, 19, 11)]
        news = (22, 6, 9)
        outs = [f.result(timeout=900) for f in
                [eng.submit(p, max_length=n) for p, n in zip(prompts, news)]]
        for prompt, n, out in zip(prompts, news, outs):
            ids = list(prompt)
            for _ in range(n):
                buf = np.zeros((1, 64), np.int32)
                buf[0, :len(ids)] = ids
                with dg.no_grad():
                    ids.append(int(m(_t(buf)).numpy()[0, len(ids) - 1]
                                   .argmax()))
            assert list(out) == ids
        (dec,) = eng._steps._decode_traces()             # ONE decode program
        ops = [op for op in dec.program.global_block().ops
               if op.type == "cached_decode_attention"]
        assert [(op.attrs["window"], op.attrs["columns"]) for op in ops] \
            == [(W, 0)]
        eng.stop()
        eng.kv_pool.assert_drained()
        assert budget_drift(eng.kv_pool, m) == []
    stats = serving_stats()
    assert stats["serving.gen.state_in_place"] == stats["serving.gen.steps"]
    assert stats["serving.kv.ring_wraps"] >= 2 + 2
    assert stats["serving.gen.kv_buckets"] == eng._steps.programs


def test_built_model_has_exactly_the_shapes_the_config_states():
    with dg.guard():
        m = _model(1, held_experts=4, first_held=8)
        got = {n: tuple(p.shape) for n, p in m.named_parameters()}
        assert got == {k: tuple(v)
                       for k, v in m.config.param_shapes().items()}
        assert "layers.0.norm2" not in got      # one norm a parallel block
        assert "layers.0.experts.router_b" not in got
        assert got["layers.0.experts.w1"] == (4, 64, 96)
        assert got["layers.0.experts.shared_in"] == (64, 2 * 2 * 48)
        assert m.step_counters == ("moe_routed", "moe_pairs", "moe_touched",
                                   "moe_max_load")


@pytest.mark.parametrize("first,held", [(0, 16), (4, 4)])
def test_full_forward_matches_the_reference(first, held):
    with dg.guard():
        m = _model(3, held_experts=held, first_held=first)
        ids = np.random.default_rng(0).integers(0, 126, (2, 40))
        got = np.asarray(m(_t(ids, np.int32)).numpy())
        for row in range(2):
            _assert_logits(got[row], _reference_logits(m, ids[row]))


def _prefill(m, prompt, bucket):
    p = len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :p] = prompt
    with dg.no_grad():
        out = m.prefill_step(_t(ids), _t([p], np.int32),
                             _t([p - 1], np.int32))
    return [np.asarray(o.numpy()) for o in out]


@pytest.mark.parametrize("p", [5, 8, 13, 27])
def test_prefill_then_decode_through_the_cache_matches_the_full_pass(p):
    """A padded prefill of a prompt shorter than, as long as and longer
    than the window of 8 (27: the ring has wrapped three times at
    install), then decode steps over three rows (one idle, one reused
    from a longer sequence's leftovers) to a context of 40 — the ring
    wraps again in decode: every step's logits equal the reference's full
    pass."""
    with dg.guard():
        m = _model(4, held_experts=4, first_held=4)
        c = m.config
        ids = np.random.default_rng(5).integers(0, 126, 40)
        picks = []
        want = _reference_logits(m, ids, picks=picks)
        picks = np.stack(picks)                         # [4, 40, k]
        held = (picks >= 4) & (picks < 8)
        logits, kr, vr, kg, vg, counts = _prefill(m, ids[:p], 32)
        _assert_logits(logits[0], want[p - 1], want.std())
        assert kr.shape == (3, 1, 2, W, 16) and kg.shape == (1, 1, 2, 32, 16)
        assert counts[0] == 4 * p * 4 and counts[1] == held[:, :p].sum()
        rows = 3
        arrays = {a["name"]: np.random.default_rng(9).normal(size=(
            a["layers"], rows) + tuple(a["shape"])).astype(np.float32)
            for a in device_kv_arrays(c.cache_spec(), 64)}  # leftovers
        for name, made in (("k0", kr), ("v0", vr), ("k1", kg), ("v1", vg)):
            arrays[name][:, 1, :, :made.shape[3]] = made[:, 0]
        before = {n: a.copy() for n, a in arrays.items()}
        for i in range(40 - p):
            step = np.zeros((rows, 1), np.int32)
            step[1, 0] = ids[p + i]
            with dg.no_grad():
                out = m.decode_step(
                    _t(step), _t([0, p + i, 0], np.int32),
                    _t([0, 1, 0], np.int32),
                    *[_t(a) for a in arrays.values()])
            logits, *new, counts = (np.asarray(o.numpy()) for o in out)
            _assert_logits(logits[1], want[p + i], want.std())
            assert counts[1] == held[:, p + i].sum()
            arrays = dict(zip(arrays, new))
        # an idle row's new column lands at column 0 of its own slot and
        # nowhere else: the rest of rows 0 and 2 is as it was
        for name, a in arrays.items():
            np.testing.assert_array_equal(a[:, [0, 2], :, 1:],
                                          before[name][:, [0, 2], :, 1:])


@pytest.mark.parametrize("p", [5, 13, 27])
def test_a_prompts_pads_are_nobodys_to_read(p, monkeypatch):
    """`prefill_step` on a prompt padded to twice its bucket, blocks of 8
    queries, so that the attention layers leave whole blocks of pads out
    (zeros where the smaller bucket computed rows: the op's own test):
    the logits row, the rings, the full layer's valid columns and the
    experts' counts are those of the prompt padded to its own bucket."""
    from paddle_tpu.ops.kernels import window_attention
    monkeypatch.setattr(window_attention, "_BLOCK_Q", 8)
    monkeypatch.setattr(window_attention, "_BLOCK_K", 16)
    with dg.guard():
        m = _model(4, held_experts=4, first_held=4)
        ids = np.random.default_rng(5).integers(0, 126, 40)[:p]
        bucket = 16 if p <= 16 else 32
        near = _prefill(m, ids, bucket)
        far = _prefill(m, ids, 2 * bucket)
        spread = float(near[0].std())
        _assert_logits(far[0], near[0], spread)
        for a, b in zip(near[1:3], far[1:3]):           # the rings
            at = slice(0, min(p, W))
            np.testing.assert_allclose(b[..., at, :], a[..., at, :],
                                       rtol=0, atol=1e-5)
        for a, b in zip(near[3:5], far[3:5]):           # every column
            assert b.shape[3] == 2 * a.shape[3]
            np.testing.assert_allclose(b[..., :p, :], a[..., :p, :],
                                       rtol=0, atol=1e-5)
        np.testing.assert_array_equal(far[5], near[5])


# -- the cache description: several kv groups ---------------------------------
def test_a_description_states_retain_on_every_kv_group_or_none():
    spec = cohere2_moe_tiny().config.cache_spec()
    assert [g["retain"] for g in retained_kv_groups(spec)] == [W, "all"]
    assert kv_geometry(spec) == (1, 2, 16)      # the pages count "all"
    assert kv_geometry(spec[:1]) == (3, 2, 16)  # every group a window
    arrays = device_kv_arrays(spec, 40)
    assert [(a["name"], a["layers"], a["shape"], a["window"])
            for a in arrays] == [
        ("k0", 3, [2, W, 16], W), ("v0", 3, [2, W, 16], W),
        ("k1", 1, [2, 64, 16], 0), ("v1", 1, [2, 64, 16], 0)]
    assert state_slot_bytes(spec, 40) == 2 * 2 * 16 * 4 * (3 * W + 64)
    # every description of the hybrid decoder states it; a GPT's does not
    assert [g["retain"] for g in retained_kv_groups(
        granite_hybrid_tiny().config.cache_spec())] == ["all"]
    assert retained_kv_groups([{"kind": "kv", "layers": 2, "kv_heads": 2,
                                "head_dim": 8}]) == []
    mixed = [dict(spec[0]), {k: v for k, v in spec[1].items()
                             if k != "retain"}]
    with pytest.raises(ValueError, match="every kv group or on none"):
        retained_kv_groups(mixed)
    with pytest.raises(ValueError, match="window in columns"):
        retained_kv_groups([dict(spec[0], retain=-3)])
    two = [{k: v for k, v in g.items() if k != "retain"} for g in spec]
    with pytest.raises(NotImplementedError, match="exactly one kv"):
        kv_geometry(two)


def test_page_budget_prices_the_ring_and_the_columns_a_slot():
    with dg.guard():
        m = _model(2)
        plan = static.page_budget(m, page_tokens=4, max_context=40,
                                  hbm_bytes=8 << 20, max_slots_cap=4)
        slot = 2 * 2 * 16 * 4 * (3 * W + 64)
        assert plan["max_slots"] == 4
        assert plan["kv_slot_bytes"] == slot      # and no recurrent state
        assert plan["state_slot_bytes"] == plan["state_bytes"] == 0
        assert plan["kv_bytes"] == 4 * slot
        assert plan["pages"] == 4 * (64 // 4 + 1)
        assert plan["page_bytes"] == 2 * 1 * 2 * 16 * 4 * 4
        assert (plan["num_layers"], plan["num_heads"], plan["head_dim"]) \
            == (1, 2, 16)
        pool = PagedKVPool.from_plan(plan)
        assert pool.device_only and pool.k is None and pool.v is None
        assert list(pool.state.arrays) == ["k0", "v0", "k1", "v1"]
        assert pool.state.arrays["k0"].shape == (3, 4, 2, W, 16)
        assert pool.state.arrays["v1"].shape == (1, 4, 2, 64, 16)
        assert pool.state.slot_bytes == slot == pool.state.kv_slot_bytes
        assert budget_drift(pool, m) == []
        # the tables account: pages by tokens, nothing stored or shared
        table = pool.reserve(pool.pages_for_request(9, 5))
        pool.account_prompt(table, 9)
        assert (table.length, len(table.pages)) == (9, 3)
        for _ in range(4):
            pool.account_column(table)
        assert (table.length, len(table.pages)) == (13, 4)
        with pytest.raises(RuntimeError, match="no pages to gather"):
            pool.gather(table)
        pool.close_sequence(table)
        pool.assert_drained()
        assert pool.prefix_hits == 0
        # half of what the weights leave stays free for a prefill
        tight = static.page_budget(m, page_tokens=4, max_context=40,
                                   hbm_bytes=(plan["weight_bytes"]
                                              + 6 * (slot + 512) + 64)
                                   / 0.92, max_slots_cap=4)
        assert tight["max_slots"] == 3
        for bad in (dict(tp_degree=2), dict(kv_dtype="int8"),
                    dict(weight_dtype="int8"), dict(draft_layers=2)):
            with pytest.raises(NotImplementedError, match="retain"):
                static.page_budget(m, hbm_bytes=8 << 20, **bad)
        # what the state slots hold says it: there is no option to differ
        assert not PagedKVPool(1, 2, 16).device_only
        assert "device_only" not in inspect.signature(PagedKVPool).parameters


# -- the step programs ---------------------------------------------------------
def _program_digest(program):
    ops = [(op.type, sorted((k, repr(v)) for k, v in op.attrs.items()),
            sorted((k, len(v) if isinstance(v, (list, tuple)) else 1)
                   for k, v in op.inputs.items()),
            sorted((k, len(v) if isinstance(v, (list, tuple)) else 1)
                   for k, v in op.outputs.items()))
           for op in program.global_block().ops]
    return len(ops), hashlib.sha256(json.dumps(ops).encode()).hexdigest()[:16]


def _step_programs(m):
    from paddle_tpu.dygraph.tensor import Tensor
    from paddle_tpu.serving.step_program import StepPrograms
    plan = static.page_budget(m, page_tokens=4, max_context=64,
                              hbm_bytes=64 << 20, max_slots_cap=4)
    state = PagedKVPool.from_plan(plan).state
    steps = StepPrograms(m)
    s, c = state.slots, len(steps.counters)
    pre = steps._prefill.concrete_program(
        _t(np.zeros((1, 16), np.int32)), _t([9], np.int32),
        _t([8], np.int32))
    # the engine's own choice: a ring's description takes no bound
    bound = None if m.config.kv_ring else 32
    dec = steps.decode_program(bound).concrete_program(
        _t(np.zeros(s + c, np.int32)), _t(np.zeros(s, np.int32)),
        _t(np.ones(s, np.int32)),
        *[Tensor(a) for a in state.arrays.values()])
    return pre, dec, state


@pytest.mark.parametrize("family,want", [
    (granite_hybrid_tiny, ((108, "669d0a323a557dc9"),
                           (118, "2d160e58eafa7c1c"))),
    (nemotron_h_tiny, ((109, "70337780e784aaf2"),
                       (119, "11a37cd67e3f8c30")))],
    ids=["granite", "nemotron"])
def test_the_other_families_step_programs_do_not_change_by_one_op(
        family, want):
    """The traced prefill and decode (bound 32) Programs of the other
    descriptions: op types, attrs and slot arities in order, as PR 34's
    tree records them (the digests are that tree's, taken by this
    function).  Against PR 32's: a prefill's ops are the same 108 / 109
    (the K / V stacks now come before the head); a decode step has 5 ops
    fewer — the view's `unstack`s and `cast`s and the columns' `stack`s
    gone, `cached_decode_attention` in `gqa_attention`'s place."""
    with dg.guard():
        paddle_tpu.seed(1)
        pre, dec, _ = _step_programs(family())
        assert (_program_digest(pre.program),
                _program_digest(dec.program)) == want
        ops = [op.type for op in dec.program.global_block().ops]
        assert ops.count("cached_decode_attention") == 1
        assert not {"gqa_attention", "unstack", "stack"} & set(ops)


def test_decode_program_donates_every_kv_array_and_writes_in_place():
    with dg.guard():
        pre, dec, state = _step_programs(_model(6))
        assert not pre.donated and len(dec.donated) == 4 == len(state.arrays)
        ops = [op.type for op in dec.program.global_block().ops]
        assert ops.count("cached_decode_attention") == 4
        assert ops.count("rotary_embedding") == 2 * 3   # q, k: 3 layers
        assert "gqa_attention" not in ops and "rms_norm" not in ops
        windows = [op.attrs["window"] for op in
                   dec.program.global_block().ops
                   if op.type == "cached_decode_attention"]
        entries = [op.attrs["slab_index"] for op in
                   dec.program.global_block().ops
                   if op.type == "cached_decode_attention"]
        assert windows == [W, W, W, 0] and entries == [0, 1, 2, 0]
        ops = [op.type for op in pre.program.global_block().ops]
        assert ops.count("windowed_prefill_attention") == 4
        # each is given the prompt's length: the feed the scan, the rings
        # and the experts read
        lengths = {tuple(op.inputs["Lengths"])
                   for op in pre.program.global_block().ops
                   if op.type in ("windowed_prefill_attention",
                                  "kv_ring_pack", "moe_grouped_experts")}
        assert len(lengths) == 1 and len(lengths.pop()) == 1
        assert ops.count("kv_ring_pack") == 2 * 3
        assert ops.count("layer_norm") == 5 and ops.count("moe_grouped_"
                                                          "experts") == 4


# -- the engine ---------------------------------------------------------------
def test_engine_serves_through_compiled_steps_with_the_kv_on_the_device():
    """More requests than the 4 slots, prompts on both sides of the window
    and contexts past three wraps, admitted at different steps: the served
    tokens are the reference's own greedy choices (margins of float32
    ties), a reused slot shows nothing of its last owner, and nothing but
    ids, lengths and counts crosses the host link."""
    import paddle_tpu.profiler as prof
    reset_serving_stats()
    slots = 4
    with dg.guard():
        m = _model(8, held_experts=4, first_held=4)
        plan = static.page_budget(m, page_tokens=4, max_context=64,
                                  hbm_bytes=16 << 20, max_slots_cap=slots)
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        assert eng._compiled and eng.kv_pool.device_only
        rng = np.random.default_rng(5)
        lengths = (5, 19, 30, 7, 12, 9, 26, 3)
        prompts = [rng.integers(0, 126, n) for n in lengths]
        news = (30, 14, 20, 25, 9, 12, 8, 33)
        prof.start_profiler(state="CPU")
        try:
            futs = [eng.submit(prompts[0], max_length=news[0])]
            while not eng.active_slots:         # the first is decoding ...
                time.sleep(0.01)
            futs += [eng.submit(p, max_length=n)    # ... when the rest come
                     for p, n in zip(prompts[1:], news[1:])]
            outs = [f.result(timeout=900) for f in futs]
            eng.stop()
        finally:
            prof.stop_profiler(profile_path=None)
        events = list(prof._state.events)
        for prompt, n, out in zip(prompts, news, outs):
            assert len(out) == len(prompt) + n or (
                out[-1] == m.config.eos_id and len(out) < len(prompt) + n)
            want = _reference_logits(m, np.asarray(out[:-1]))
            for at in range(len(prompt) - 1, len(out) - 1):
                row = want[at]
                assert float(row.max() - row[out[at + 1]]) \
                    <= LOGIT_SIGMAS * float(row.std())
        eng.kv_pool.assert_drained()
        assert budget_drift(eng.kv_pool, m) == []
        # two prefill buckets (16, 32) and ONE decode program
        assert eng._steps.programs == 3 and eng.kv_buckets == 3
        stats = serving_stats()
    assert stats["serving.gen.state_in_place"] == stats["serving.gen.steps"]
    assert stats.get("serving.gen.state_copied", 0) == 0
    assert stats["serving.gen.state_resets"] == len(lengths) - slots
    assert stats["serving.kv.device_bytes"] == plan["kv_bytes"]
    # every write at a position 8, 16, 24, ...: at install or in a step
    # (a row found at EOS a step late may have written one more)
    wraps = sum((len(o) - 2) // W for o in outs)
    assert wraps <= stats["serving.kv.ring_wraps"] <= wraps + len(outs)
    # every token but a sequence's last went through the 4 layers' gates
    # (a row found at EOS a step late once more)
    fed = sum(len(o) - 1 for o in outs)
    assert 16 * fed <= stats["serving.moe.pairs_routed"] \
        <= 16 * (fed + len(outs))
    steps = [e for e in events if e.name == "engine/step"
             and "active" in e.fields]
    assert steps and all(e.fields["lpad"] == 64 for e in steps)
    for e in steps:
        # rows past the window read a full ring: 3 x 8 + their length
        assert 0 <= e.fields["ring_rows"] <= e.fields["active"]
        assert e.fields["kv_columns"] <= e.fields["active"] * (3 * W + 64)
        assert e.fields["kv_columns"] >= e.fields["ring_rows"] * 4 * W
    assert any(e.fields["ring_rows"] >= 2 for e in steps)
    assert any(e.fields["ahead"] for e in steps)
    # the host link: a step's ids + counts down, a prefill's id + counts;
    # ids, lengths and active up; no KV, no logits
    fetches = [e.fields["bytes"] for e in events if e.name == "engine/fetch"]
    assert set(fetches) == {4 * (slots + 4), 4 * (1 + 4)}
    installs = [e.fields["bytes"] for e in events
                if e.name == "engine/kv_install"]
    appends = [e.fields["bytes"] for e in events
               if e.name == "engine/kv_append"]
    assert installs == [0] * len(lengths) and set(appends) == {0}
    uploads = [e.fields["bytes"] for e in events if e.name == "engine/upload"]
    assert max(uploads) <= 4 * 32 + 8
    assert stats.get("serving.kv.append_bytes", 0) == 0
    assert stats.get("serving.gen.logits_rows_fetched", 0) == 0
    prefills = [e for e in events if e.name == "engine/prefill"]
    assert sorted(e.fields["prompt"] for e in prefills) == sorted(lengths)
    # the rows of the prompt programs that were pads, counted
    assert stats["serving.gen.prefill_tokens"] == sum(lengths)
    assert stats["serving.gen.prefill_pad_tokens"] == sum(
        e.fields["bucket"] - e.fields["prompt"] for e in prefills) > 0
    assert {e.fields["bucket"] for e in prefills} == {16, 32}
    assert all("moe_pairs" in e.fields for e in prefills)


def test_the_compiled_route_is_chosen_by_the_step_contract():
    """A model with `prefill_step` / `decode_step` and no recurrent state
    lands on the compiled route; it needs the paged pool, and a ring can
    be neither shared by page nor truncated."""
    with dg.guard():
        m = _model(2)
        assert not [g for g in m.cache_spec() if g["kind"] == "state"]
        with pytest.raises(ValueError, match="paged pool"):
            ContinuousBatchingEngine(m)
        with pytest.raises(NotImplementedError, match="shared by page"):
            ContinuousBatchingEngine(m, kv_pool="auto", prefix_cache="auto")
        with pytest.raises(NotImplementedError, match="cannot be truncated"):
            ContinuousBatchingEngine(m, kv_pool="auto", speculative="auto")
        plan = static.page_budget(m, page_tokens=4, max_context=32,
                                  hbm_bytes=8 << 20, max_slots_cap=2)
        eng = ContinuousBatchingEngine(m, kv_pool=plan)
        assert eng._compiled and eng._steps is not None
        # a pool built for another description's slots is refused
        other = static.page_budget(granite_hybrid_tiny(), page_tokens=4,
                                   max_context=32, hbm_bytes=8 << 20,
                                   max_slots_cap=2)
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(m, kv_pool=other)
        # a recurrent model keeps its reasons
        with pytest.raises(NotImplementedError, match="recurrent state"):
            ContinuousBatchingEngine(granite_hybrid_tiny(), kv_pool="auto",
                                     prefix_cache="auto")


def test_a_sampling_row_reads_its_logits_beside_the_device_kv():
    """A request that samples is read at once, its logits row fetched:
    the seeded tokens are those of the same request served alone."""
    with dg.guard():
        m = _model(9)
        plan = static.page_budget(m, page_tokens=4, max_context=64,
                                  hbm_bytes=16 << 20, max_slots_cap=2)
        prompt = np.random.default_rng(1).integers(0, 126, 11)

        def serve(beside):
            eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
            futs = [eng.submit(prompt, max_length=12,
                               decode_strategy="sampling", top_k=5,
                               temperature=0.8, seed=3)]
            futs += [eng.submit(p, max_length=9) for p in beside]
            outs = [list(f.result(timeout=600)) for f in futs]
            eng.stop()
            eng.kv_pool.assert_drained()
            return outs[0]

        alone = serve([])
        assert len(alone) == 23
        assert serve([prompt[:7]]) == alone
