"""Gated short convolutions with their tails on the device, QK-normed
half-split-rotary attention and an expert layer with no shared expert
(`models/lfm2_moe.py` over `models/hybrid_decoder.py`), tiny, on the CPU,
against the plain float32 reference `benchmark/reference/lfm2_moe.py`: the
prompt path, prefill then decode through the KV columns and the conv tails
on the compiled route (an idle row keeping its tail), the two new attention
pieces against plain `jnp`, the expert layer without shared projections,
a pipeline stage's hidden state against the uncut model's, and the engine
end to end."""
import os
import sys

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.dygraph as dg

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from benchmark import serving_lfm2_moe as slm  # noqa: E402
from benchmark.reference import lfm2_moe as reference  # noqa: E402
from paddle_tpu.models import Lfm2MoeConfig, lfm2_moe_tiny  # noqa: E402
from paddle_tpu.models.lfm2_moe import _PATTERN  # noqa: E402
from paddle_tpu.ops.registry import OpContext, get_op_info  # noqa: E402

T = 32          # the tiny model's longest prompt bucket here


def _t(a, dtype=None):
    return paddle_tpu.to_tensor(np.asarray(a, dtype))


def _model(seed, **kw):
    paddle_tpu.seed(seed)            # every test seeds its own weights
    return lfm2_moe_tiny(**kw)


def _published(cfg):
    """The keys `reference.logits` reads, from a built config."""
    return {"layer_types": cfg.layer_types,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "rope_theta": cfg.rope_theta, "norm_eps": cfg.rms_norm_eps,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "first_held_expert": cfg.first_held,
            "num_dense_layers": len(cfg.layers_of("mlp"))}


def _distance(got, want):
    """|l_served - l_ref| / |l_ref - its mean|, a row: the cell's measure."""
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(
        want - want.mean(-1, keepdims=True), axis=-1)


def _ids(seed, n, vocab=127):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _run(op, ins, attrs):
    return get_op_info(op).kernel(ins, attrs, OpContext(seed=0))


# -- the prompt path ----------------------------------------------------------
@pytest.mark.parametrize("gqa_max", [512, 16], ids=["gqa", "blocked"])
def test_prefill_logits_match_the_references_forward(gqa_max, monkeypatch):
    """The prompt path through `gqa_attention` and, with the fork moved
    under the bucket, through the blocked kernel."""
    from paddle_tpu.models import hybrid_decoder
    monkeypatch.setattr(hybrid_decoder, "GQA_PROMPT_MAX", gqa_max)
    with dg.guard():
        m = _model(3)
        ids = _ids(0, T)
        want = np.asarray(reference.logits(reference.params_of(m), ids,
                                           _published(m.config)))
        with dg.no_grad():
            full = np.asarray(m(_t(ids[None])).numpy())[0]
            out = m.prefill_step(_t(ids[None]), _t([20], np.int32),
                                 _t([19], np.int32))
    assert float(np.abs(full - want).max()) < 1e-4 * want.std()
    assert float(_distance(np.asarray(out[0].numpy())[0], want[19])) < 1e-5
    # K, V [1 layer, 1, 2 kv heads, T, 16], the 5 conv layers' tails
    # [5, 1, 2, 64]: the last two u rows before position 20
    k, v, tails = (np.asarray(a.numpy()) for a in out[1:4])
    assert k.shape == v.shape == (1, 1, 2, T, 16)
    assert tails.shape == (5, 1, 2, 64) and len(out) == 5  # + the counts
    # layer 0's tail: u rows 18 and 19 of its own input
    p = reference.params_of(m)["layers"][0]
    y = reference._rms(np.asarray(m.embed.numpy())[ids],
                       np.asarray(p["norm1"]), 1e-5)
    b, _, x = np.split(y @ np.asarray(p["in_proj"]), 3, axis=-1)
    np.testing.assert_allclose(tails[0, 0], (b * x)[18:20], rtol=1e-5,
                               atol=1e-6)


def test_the_prompt_goes_through_the_blocked_kernel_with_no_ring(
        monkeypatch):
    """The description's forks: no window layer, so the decode step reads
    a bound on the columns (`kv_ring` False); a prompt bucket past
    `GQA_PROMPT_MAX` tokens goes through the blocked kernel at window 0,
    one of at most that many through `gqa_attention` — granite's and
    nemotron's buckets (512 and under) among them."""
    from paddle_tpu.models import GraniteHybridConfig, hybrid_decoder
    cfg = Lfm2MoeConfig()
    assert not cfg.kv_ring and hybrid_decoder.GQA_PROMPT_MAX == 512
    assert cfg.blocked_prompt(1024) and not cfg.blocked_prompt(512)
    granite = GraniteHybridConfig()
    assert not granite.kv_ring and not granite.blocked_prompt(512)
    from paddle_tpu.ops import registry
    seen = []
    kernels = {op: registry.get_op_info(op).kernel
               for op in ("windowed_prefill_attention", "gqa_attention")}

    def spy(op):
        def kernel(ins, attrs, ctx):
            seen.append((op, tuple(ins["Q"].shape), attrs.get("window")))
            return kernels[op](ins, attrs, ctx)
        return kernel

    for op in kernels:
        monkeypatch.setattr(registry.get_op_info(op), "kernel", spy(op))
    monkeypatch.setattr(hybrid_decoder, "GQA_PROMPT_MAX", 16)
    with dg.guard():
        m = _model(4)
        with dg.no_grad():
            for t in (16, T):
                m.prefill_step(_t(_ids(1, t)[None]), _t([t], np.int32),
                               _t([t - 1], np.int32))
    assert seen == [("gqa_attention", (1, 4, 16, 16), None),
                    ("windowed_prefill_attention", (1, 4, T, 16), 0)]


# -- prefill, then decode through the KV columns and the conv tails -----------
def _serve_side_by_side(m, prompts, steps, idle=()):
    """Each prompt through its prefill program into a slot, then `steps`
    decode steps of all rows side by side through the decode program of
    the bound the engine takes, teacher-forced with tokens of `_ids`; a
    row in `idle` sits out steps 2 and 3 (active 0) and takes its tokens
    after: (the sequences, the logits rows a sequence, the tails of the
    idle rows before and after their idle steps)."""
    import paddle_tpu.static as static
    from paddle_tpu.serving.kv_pool import PagedKVPool
    from paddle_tpu.serving.step_program import StepPrograms
    plan = static.page_budget(m, page_tokens=4, max_context=64,
                              hbm_bytes=16 << 20, max_slots_cap=3)
    state = PagedKVPool.from_plan(plan).state
    steps_ = StepPrograms(m)
    seqs = [np.concatenate([p, _ids(50 + i, steps)])
            for i, p in enumerate(prompts)]
    rows = []
    for slot, p in enumerate(prompts):
        ids = np.zeros((1, T), np.int32)
        ids[0, :len(p)] = p
        logits, _, *made = steps_.prefill(_t(ids), _t([len(p)], np.int32),
                                          _t([len(p) - 1], np.int32))
        state.install(slot, **{n: t._value for n, t in
                               zip(state.names, made)})
        rows.append([np.asarray(logits.numpy())[0]])
    extra = len(steps_.counters)
    done = [0] * len(prompts)           # tokens taken after the prompt
    kept = {}
    for t in range(steps + (2 if idle else 0)):
        ids = np.zeros(3 + extra, np.int32)
        lengths = np.zeros(3, np.int32)
        active = np.zeros(3, np.int32)
        for slot, p in enumerate(prompts):
            at = len(p) + done[slot]
            lengths[slot] = at
            if done[slot] < steps and not (slot in idle and t in (2, 3)):
                ids[slot], active[slot] = seqs[slot][at], 1
        if t == 2:
            kept["before"] = np.asarray(state.arrays["short_conv"])[
                :, list(idle)]
        old = dict(state.arrays)
        logits, _, *new = steps_.decode(
            _t(ids), _t(lengths), _t(active), *state.arrays.values(),
            columns=max(16, 1 << int(lengths.max() - 1).bit_length()))
        assert all(a.is_deleted() for a in old.values())    # in place
        state.rebind(**{n: a._value for n, a in zip(state.names, new)})
        if t == 3:
            kept["after"] = np.asarray(state.arrays["short_conv"])[
                :, list(idle)]
        for slot in np.flatnonzero(active):
            rows[slot].append(np.asarray(logits.numpy())[slot])
            done[slot] += 1
    return seqs, [np.stack(r) for r in rows], kept


def test_prefill_then_decode_through_the_cache_matches_the_full_pass():
    """The conv tail crosses the prompt / decode boundary (prompts of 5
    and 17: a tail from the prefill program, then step after step), and
    a row that sits out two steps (length 0 taken: active 0) keeps its
    tail bit for bit and decodes on as if it had not."""
    with dg.guard():
        m = _model(5)
        prompts = [_ids(10, 5), _ids(11, 17), _ids(12, 2)]
        seqs, rows, kept = _serve_side_by_side(m, prompts, 10, idle=(1,))
        params, pub = reference.params_of(m), _published(m.config)
        for p, seq, got in zip(prompts, seqs, rows):
            padded = np.zeros(64, np.int32)
            padded[:len(seq)] = seq
            want = np.asarray(reference.logits(params, padded, pub))
            want = want[len(p) - 1:len(p) + 10]
            assert got.shape == want.shape == (11, 128)
            assert float(_distance(got, want).max()) < 1e-5
    np.testing.assert_array_equal(kept["before"], kept["after"])


def test_the_float8_control_fails_the_cells_limits():
    """The sound path reads ~1e-6 here; the reference's float8 e4m3
    control reads past the cell's limits on the same served rows.  (The
    cell's control is int8, which fails those limits at the published
    widths on normal matrices; at these widths it reads 0.05-0.25, on
    either side of them.)"""
    with dg.guard():
        m = _model(6)
        prompts = [_ids(13, 20), _ids(14, 28)]
        seqs, rows, _ = _serve_side_by_side(m, prompts, 8)
        params, pub = reference.params_of(m), _published(m.config)
        for p, seq, got in zip(prompts, seqs, rows):
            padded = np.zeros(64, np.int32)
            padded[:len(seq)] = seq
            want = np.asarray(reference.logits(
                params, padded, pub,
                weights_as="float8_e4m3fn"))[len(p) - 1:len(p) + 8]
            assert float(_distance(got[0], want[0])) > slm.PREFILL_APART
            assert float(np.median(_distance(got[1:], want[1:]))) \
                > slm.DECODE_APART


def test_the_steps_return_their_picks_only_when_asked():
    """`picks=True` appends the experts every expert layer picked, what the
    cell's check gives the reference; the default result is unchanged."""
    with dg.guard():
        m = _model(13)
        ids, lengths, last = _t(_ids(3, T)[None]), _t([20], np.int32), \
            _t([19], np.int32)
        with dg.no_grad():
            plain = m.prefill_step(ids, lengths, last)
            more = m.prefill_step(ids, lengths, last, picks=True)
            routes = np.asarray(m.routes(ids, lengths).numpy())
        assert len(more) == len(plain) + 1
        for a, b in zip(plain, more):
            np.testing.assert_array_equal(np.asarray(a.numpy()),
                                          np.asarray(b.numpy()))
        # 4 expert layers, 1 prompt, T positions, top 4
        np.testing.assert_array_equal(np.asarray(more[-1].numpy()), routes)
        assert routes.shape == (4, 1, T, 4)
        state = [np.zeros((1, 3, 2, 64, 16), np.float32)] * 2 \
            + [np.zeros((5, 3, 2, 64), np.float32)]
        with dg.no_grad():
            out = m.decode_step(_t(_ids(4, 3)[:, None]), _t([2, 0, 5],
                                                            np.int32),
                                _t([1, 0, 1], np.int32),
                                *[_t(s) for s in state], columns=16,
                                picks=True)
        assert len(out) == 1 + 3 + 2 and tuple(out[-1].shape) == (4, 3, 1, 4)


# -- the new pieces against plain jnp -----------------------------------------
def test_half_split_rotary_and_qk_norm_against_plain_jnp():
    """`rotary_embedding` form "half" against ``x cos + rotate_half(x) sin``
    written out, at a prompt and at one position a row; the attention's
    q and k (per-head RMSNorm, then positions) against the same by hand."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 3, 40, 16)).astype(np.float32)
    theta = 1e6

    def by_hand(x, pos):
        d = x.shape[-1]
        inv = theta ** (-np.arange(0, d, 2) / d)
        ang = pos[..., None] * inv                              # [B, T, 8]
        cos = np.cos(np.concatenate([ang, ang], -1))[:, None]
        sin = np.sin(np.concatenate([ang, ang], -1))[:, None]
        half = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
        return x * cos + half * sin

    got = np.asarray(_run("rotary_embedding", {"X": x},
                          {"theta": theta, "form": "half"})["Out"])
    pos = np.broadcast_to(np.arange(40.0), (2, 40))
    np.testing.assert_allclose(got, by_hand(x, pos), rtol=1e-5, atol=1e-5)
    at = np.asarray([[7], [33]], np.int32)
    got = np.asarray(_run("rotary_embedding", {"X": x[:, :, :1],
                                               "Positions": at},
                          {"theta": theta, "form": "half"})["Out"])
    np.testing.assert_allclose(got, by_hand(x[:, :, :1], at), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(
        by_hand(x, pos), np.asarray(reference.rope(
            np.transpose(x, (2, 0, 1, 3)), np.arange(40), theta)).transpose(
                1, 2, 0, 3), rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="form"):
        _run("rotary_embedding", {"X": x}, {"theta": theta, "form": "x"})
    with dg.guard():
        m = _model(7)
        att = m.layers[2].mixer
        att.q_norm.set_value(rng.uniform(0.5, 1.5, 16).astype(np.float32))
        h = rng.normal(size=(1, 9, 64)).astype(np.float32)
        with dg.no_grad():
            q, k, _ = att._heads(_t(h))
        wq, wk = np.asarray(att.wq.numpy()), np.asarray(att.wk.numpy())
    for got, w, norm, n in ((q, wq, att.q_norm, 4), (k, wk, att.k_norm, 2)):
        y = (h[0] @ w).reshape(9, n, 16).transpose(1, 0, 2)[None]
        y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5) \
            * np.asarray(norm.numpy())
        np.testing.assert_allclose(np.asarray(got.numpy()),
                                   by_hand(y, np.arange(9.0)[None]),
                                   rtol=1e-4, atol=1e-5)


def test_the_short_conv_op_carries_its_tail_and_an_idle_row_keeps_it():
    """`short_conv` against numpy: the whole mixer over a prompt from an
    empty tail, then one token a row on the state array's entry (slab 1
    of 2), a row of length 0 keeping its tail and every other entry
    untouched."""
    rng = np.random.default_rng(9)
    c, k = 8, 3
    x = rng.normal(size=(2, 6, c)).astype(np.float32)
    w_in = rng.normal(size=(c, 3 * c)).astype(np.float32) * 0.3
    taps = rng.normal(size=(c, k)).astype(np.float32)
    w_out = rng.normal(size=(c, c)).astype(np.float32) * 0.3

    def by_hand(x, tail):
        b, gc, xs = np.split(x @ w_in, 3, axis=-1)
        u = np.concatenate([tail, b * xs], 1)
        conv = sum(u[:, j:j + x.shape[1]] * taps[:, j] for j in range(k))
        return (gc * conv) @ w_out, u

    lengths = np.asarray([6, 4], np.int32)
    got = _run("short_conv", {"X": x, "WIn": w_in, "Weight": taps,
                              "WOut": w_out, "Lengths": lengths}, {})
    want, u = by_hand(x, np.zeros((2, k - 1, c), np.float32))
    np.testing.assert_allclose(np.asarray(got["Out"]), want, rtol=1e-4,
                               atol=1e-5)
    tail = np.asarray(got["NewTail"])
    np.testing.assert_allclose(tail[0], u[0, 6:8], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(tail[1], u[1, 4:6], rtol=1e-5, atol=1e-6)
    slab = np.stack([rng.normal(size=(2, k - 1, c)).astype(np.float32),
                     tail])                             # [2 layers, 2, 2, c]
    step = rng.normal(size=(2, 1, c)).astype(np.float32)
    got = _run("short_conv", {"X": step, "WIn": w_in, "Weight": taps,
                              "WOut": w_out, "Tail": slab,
                              "Lengths": np.asarray([1, 0], np.int32)},
               {"slab_index": 1})
    want, u = by_hand(step, tail)
    np.testing.assert_allclose(np.asarray(got["Out"])[0], want[0],
                               rtol=1e-4, atol=1e-5)
    new = np.asarray(got["NewTail"])
    np.testing.assert_allclose(new[1, 0], u[0, 1:], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(new[1, 1], tail[1])      # length 0
    np.testing.assert_array_equal(new[0], slab[0])         # another layer


# -- the expert layer with no shared expert -----------------------------------
def test_an_expert_layer_with_zero_shared_experts():
    """No shared projections are built or counted; the layer is the held
    experts' weighted sum alone, as the reference's."""
    with dg.guard():
        m = _model(8)
        blk = m.layers[3].experts
        assert not hasattr(blk, "shared_in") and not hasattr(
            blk, "shared_out")
        shapes = m.config.param_shapes()
        assert not [n for n in shapes if "shared" in n]
        assert m.config.param_count() == sum(
            int(np.prod(p.shape)) for p in m.parameters())
        x = np.random.default_rng(4).normal(size=(1, 9, 64)).astype(
            np.float32)
        with dg.no_grad():
            got = np.asarray(blk(_t(x), None)[0].numpy())[0]
        p = reference.params_of(m)["layers"][3]
        sizes = reference.sizes_of(_published(m.config))
        import jax
        with jax.default_matmul_precision("highest"):
            want, _, _ = reference.experts(x[0], p, sizes)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4, atol=1e-5)


def test_the_routers_epsilon_is_far_under_bfloat16s_rounding():
    """The router op normalises the picks' weights with 1e-20, the
    reference with the family's 1e-6: over four sigmoid scores (a sum of
    at least 4 x sigmoid of the 4th-best logit) the two weights differ by
    under 1e-5 of a weight, a 300th of bfloat16's half step."""
    x = np.random.default_rng(1).normal(size=(64, 8)).astype(np.float32)
    w = np.random.default_rng(2).normal(size=(8, 32)).astype(np.float32)
    attrs = {"top_k": 4, "norm_topk_prob": True, "routed_scaling_factor": 1}
    got = np.asarray(_run("moe_router_topk", {"X": x, "Weight": w},
                          attrs)["Weights"])
    np.testing.assert_allclose(got.sum(-1), 1.0, rtol=1e-6)
    s = 1 / (1 + np.exp(-(x @ w)))
    picked = np.sort(s, -1)[:, ::-1][:, :4]
    family = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    assert float(np.abs(got - family).max()) < 1e-5 < 2.0 ** -9 / 100


# -- a pipeline stage ---------------------------------------------------------
def test_the_12_layer_stage_is_the_uncut_models_first_12_layers():
    """The same seed draws the same first layers: the stage's residual
    after its last layer (11) on the served prompt path equals the uncut
    24-layer reference's after layer 11."""
    with dg.guard():
        paddle_tpu.seed(21)
        full = lfm2_moe_tiny(layer_types=_PATTERN)
        paddle_tpu.seed(21)
        stage = lfm2_moe_tiny(layer_types=_PATTERN[:12])
        assert stage.config.num_layers == 12 and full.config.num_layers == 24
        ids = _ids(2, T)
        hidden = []
        reference.logits(reference.params_of(full), ids,
                         _published(full.config), hidden=hidden)
        with dg.no_grad():
            got = stage.hidden_row(_t(ids[None]), _t([T], np.int32),
                                   _t([T - 1], np.int32), 12)
    want = np.asarray(hidden[11][T - 1])
    np.testing.assert_allclose(np.asarray(got.numpy())[0], want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


def test_from_published_takes_the_first_layers_and_refuses_by_name():
    import json
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "lfm2-8b-a1b.json")) as f:
        published = json.load(f)
    cfg = Lfm2MoeConfig.from_published(published)
    assert cfg.num_layers == 12 and cfg.layers_of("attention") == [2, 6, 10]
    assert cfg.layers_of("mlp") == [0, 1] and cfg.expert_layers == 10
    assert cfg.head_dim == 64 and cfg.attention_multiplier == 0.125
    assert cfg.held_experts == cfg.n_routed_experts == 32
    assert cfg.attention_spec(2) == (None, 1e6, "half", True)
    assert Lfm2MoeConfig.from_published(published, layers=3).blocks[-1] \
        == ("attention", "experts")
    for key, value in (("conv_bias", True), ("tie_word_embeddings", False),
                       ("block_auto_adjust_ff_dim", True)):
        with pytest.raises(NotImplementedError, match=key):
            Lfm2MoeConfig.from_published(dict(published, **{key: value}))
    with pytest.raises(NotImplementedError, match="sliding"):
        Lfm2MoeConfig(layer_types=["conv", "sliding_attention"])
    spec = cfg.cache_spec()
    assert spec == [
        {"kind": "kv", "layers": 3, "kv_heads": 8, "head_dim": 64,
         "dtype": "bfloat16", "retain": "all"},
        {"kind": "state", "layers": 9, "arrays": [
            {"name": "short_conv", "dtype": "bfloat16",
             "shape": [2, 2048]}]}]


# -- the engine, end to end ---------------------------------------------------
def test_the_engine_serves_greedy_tokens_of_the_reference_in_place():
    import paddle_tpu.static as static
    from paddle_tpu.serving import ContinuousBatchingEngine
    from paddle_tpu.serving.metrics import gauge_value, serving_stats
    with dg.guard():
        m = _model(12)
        plan = static.page_budget(m, page_tokens=4, max_context=64,
                                  hbm_bytes=16 << 20, max_slots_cap=3)
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        before = serving_stats()
        try:
            prompts = [_ids(30 + i, 4 + 7 * i) for i in range(4)]
            futs = [eng.submit(p, max_length=6) for p in prompts]
            got = [np.asarray(f.result(timeout=600)) for f in futs]
        finally:
            eng.stop()
        after = serving_stats()
        assert gauge_value("state.conv_bytes") == 5 * 3 * 2 * 64 * 4
        steps = after["serving.gen.steps"] - before.get("serving.gen.steps",
                                                        0)
        assert steps > 0 and after["serving.gen.state_in_place"] \
            - before.get("serving.gen.state_in_place", 0) == steps
        eng.kv_pool.assert_drained()
        params, pub = reference.params_of(m), _published(m.config)
        for p, seq in zip(prompts, got):
            padded = np.zeros(64, np.int32)
            padded[:len(seq)] = seq
            logits = np.asarray(reference.logits(params, padded, pub))
            for t in range(len(p), len(seq)):
                row = logits[t - 1]
                assert row.max() - row[seq[t]] < 1e-4 * row.std(), t
        for kw in ({"prefix_cache": "auto"}, {"speculative": "auto"}):
            with pytest.raises(NotImplementedError):
                ContinuousBatchingEngine(m, kv_pool=plan, **kw)
