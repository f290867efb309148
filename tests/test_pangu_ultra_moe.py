"""Latent attention (MLA) over a latent cache, sandwich blocks and biased
gated experts (`models/pangu_ultra_moe.py` over `models/hybrid_decoder.py`,
`ops/kernels/latent_attention.py`), tiny, on the CPU, against the plain
float32 reference `benchmark/reference/pangu_ultra_moe.py`: the prompt
path, prefill then decode through the latent cache on the compiled route,
the absorbed form against the expanded one, the prompt kernel against a
dense masked softmax, the sandwich block, the expert shares adding up to
the uncut layer, a float8 cache failing the cell's limits, and the
refusals by name."""
import os
import sys

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.dygraph as dg

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from benchmark import serving_pangu_ultra_moe as spm  # noqa: E402
from benchmark.reference import pangu_ultra_moe as reference  # noqa: E402
from paddle_tpu.models import (PanguUltraMoeConfig,  # noqa: E402
                               pangu_ultra_moe_tiny)
from paddle_tpu.ops.registry import OpContext, get_op_info  # noqa: E402

T = 32          # the tiny model's context


def _t(a, dtype=None):
    return paddle_tpu.to_tensor(np.asarray(a, dtype))


def _model(seed, **kw):
    paddle_tpu.seed(seed)            # every test seeds its own weights
    return pangu_ultra_moe_tiny(**kw)


def _published(cfg):
    """The keys `reference.logits` reads, from a built config."""
    ls = cfg.latent_spec
    return {"num_attention_heads": cfg.num_attention_heads,
            "qk_nope_head_dim": ls.qk_nope_head_dim,
            "qk_rope_head_dim": ls.qk_rope_head_dim,
            "v_head_dim": ls.v_head_dim, "kv_lora_rank": ls.kv_lora_rank,
            "rope_theta": ls.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "first_held_expert": cfg.first_held,
            "first_k_dense_replace": len(cfg.layers_of("mlp"))}


def _distance(got, want):
    """|l_served - l_ref| / |l_ref - its mean|, a row: the cell's measure."""
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(
        want - want.mean(-1, keepdims=True), axis=-1)


def _ids(seed, n, vocab=127):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


# -- the prompt path -----------------------------------------------------------
def test_prefill_logits_match_the_references_forward():
    with dg.guard():
        m = _model(3, held_experts=8, first_held=4)
        ids = _ids(0, T)
        want = np.asarray(reference.logits(reference.params_of(m), ids,
                                           _published(m.config)))
        with dg.no_grad():
            full = np.asarray(m(_t(ids[None])).numpy())[0]
            out = m.prefill_step(_t(ids[None]), _t([20], np.int32),
                                 _t([19], np.int32))
    assert float(np.abs(full - want).max()) < 1e-4 * want.std()
    assert float(_distance(np.asarray(out[0].numpy())[0], want[19])) < 1e-5
    # the prefill leaves the latent rows [layers, 1, T, kv_rank + rope]
    assert tuple(out[1].shape) == (3, 1, T, 40) and len(out) == 3


def test_the_sandwich_block_matches_the_references():
    """The residual after the dense layer and after the first expert layer
    (four RMSNorms each) on the path `prefill_step` takes."""
    with dg.guard():
        m = _model(4)
        ids = _ids(1, T)
        hidden = []
        reference.logits(reference.params_of(m), ids, _published(m.config),
                         hidden=hidden)
        for upto in (1, 2):
            with dg.no_grad():
                got = m.hidden_row(_t(ids[None]), _t([T], np.int32),
                                   _t([T - 1], np.int32), upto)
            want = np.asarray(hidden[upto - 1][T - 1])
            assert np.abs(np.asarray(got.numpy())[0] - want).max() \
                < 1e-4 * np.abs(want).max(), upto
    blk = m.layers[1]
    assert blk.sandwich and blk.post_norm1.shape == [64] \
        and blk.post_norm2.shape == [64]


# -- prefill, then decode through the latent cache (the compiled route) -------
def _serve_side_by_side(m, prompts, steps, round_cache=None):
    """Each prompt through its prefill program into a slot of the latent
    array, then `steps` decode steps of all rows side by side through the
    ONE decode program, teacher-forced with tokens of `_ids`: (the
    sequences, the logits rows [prefill's, decode's...] a sequence)."""
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
        ids = np.zeros((1, 32), np.int32)
        ids[0, :len(p)] = p
        logits, _, *made = steps_.prefill(_t(ids), _t([len(p)], np.int32),
                                          _t([len(p) - 1], np.int32))
        state.install(slot, **{n: t._value for n, t in
                               zip(state.names, made)})
        rows.append([np.asarray(logits.numpy())[0]])
    if round_cache is not None:
        state.arrays["c0"] = round_cache(state.arrays["c0"])
    extra = len(steps_.counters)
    for t in range(steps):
        ids = np.zeros(3 + extra, np.int32)
        lengths = np.zeros(3, np.int32)
        active = np.zeros(3, np.int32)
        for slot, p in enumerate(prompts):
            at = len(p) + t
            ids[slot], lengths[slot], active[slot] = seqs[slot][at], at, 1
        old = dict(state.arrays)
        logits, _, *new = steps_.decode(_t(ids), _t(lengths), _t(active),
                                        *state.arrays.values())
        assert all(a.is_deleted() for a in old.values())    # in place
        state.rebind(**{n: a._value for n, a in zip(state.names, new)})
        for slot in range(len(prompts)):
            rows[slot].append(np.asarray(logits.numpy())[slot])
    return seqs, [np.stack(r) for r in rows]


def test_prefill_then_decode_through_the_latent_cache_matches_the_full_pass():
    with dg.guard():
        m = _model(5, held_experts=8, first_held=4)
        prompts = [_ids(10, 5), _ids(11, 17)]
        seqs, rows = _serve_side_by_side(m, prompts, 10)
        params, pub = reference.params_of(m), _published(m.config)
        for p, seq, got in zip(prompts, seqs, rows):
            padded = np.zeros(64, np.int32)
            padded[:len(seq)] = seq
            want = np.asarray(reference.logits(params, padded, pub))
            want = want[len(p) - 1:len(p) + 10]
            assert got.shape == want.shape == (11, 128)
            assert float(_distance(got, want).max()) < 1e-5


def test_a_float8_latent_cache_fails_the_cells_limits():
    """The sound path reads ~1e-6 here; the same decode steps over a latent
    cache rounded through float8_e4m3fn read past the cell's
    DECODE_APART, and so does the reference's own float8 control."""
    import jax.numpy as jnp

    def float8(a):
        return a.astype(jnp.float8_e4m3fn).astype(a.dtype)

    with dg.guard():
        m = _model(6, held_experts=8, first_held=4)
        prompts = [_ids(12, 20), _ids(13, 28)]
        seqs, rows = _serve_side_by_side(m, prompts, 8, round_cache=float8)
        params, pub = reference.params_of(m), _published(m.config)
        for p, seq, got in zip(prompts, seqs, rows):
            padded = np.zeros(64, np.int32)
            padded[:len(seq)] = seq
            want = np.asarray(reference.logits(params, padded, pub))[
                len(p) - 1:len(p) + 8]
            assert float(np.median(_distance(got[1:], want[1:]))) \
                > spm.DECODE_APART
            assert float(_distance(got[0], want[0])) < 1e-5   # prefill's
            rounded = np.asarray(reference.logits(
                params, padded, pub, latent_as="float8_e4m3fn"))[len(p) - 1]
            assert float(_distance(got[0], rounded)) > spm.PREFILL_APART


# -- the two forms and the kernels ----------------------------------------------
def test_the_absorbed_decode_equals_the_expanded_form_for_one_layer():
    """Row t of the expanded prompt pass of one latent layer against one
    absorbed step over a cache holding rows 0..t-1 (written by the prompt
    pass) — the same mathematics, matmuls regrouped."""
    with dg.guard():
        m = _model(7)
        mixer = m.layers[0].mixer
        x = np.random.default_rng(2).normal(size=(1, 12, 64)).astype(
            np.float32)
        with dg.no_grad():
            out, latent = mixer(_t(x))
            out, latent = np.asarray(out.numpy()), np.asarray(latent.numpy())
            cache = np.zeros((2, 2, 16, 128), np.float32)   # lanes-padded
            cache[1, 0, :12, :40] = latent[0]
            cache[1, 1, :12, :40] = latent[0]
            t = np.asarray([9, 4], np.int32)
            step, new = mixer.step(_t(x[0, [9, 4]][:, None]), _t(t),
                                   _t([1, 1], np.int32), _t(cache), 1)
    np.testing.assert_allclose(np.asarray(step.numpy())[:, 0],
                               out[0, [9, 4]], rtol=2e-4, atol=2e-5)
    new = np.asarray(new.numpy())
    np.testing.assert_allclose(new[1, 0, 9, :40], latent[0, 9], atol=1e-6)
    assert not new[1, :, :, 40:].any()          # the lanes' pad stays zero
    np.testing.assert_array_equal(new[0], cache[0])   # another layer's


def test_the_mla_ops_absorbed_query_scores_as_the_expanded_keys_do():
    """`mla` part `in`: the absorbed query [q~_h | q_pe_h] against a token's
    cache row [c_kv | k_pe] scores exactly as the expanded query [q_nope_h
    | q_pe_h] against that token's expanded key [k_nope_h | k_pe]."""
    with dg.guard():
        m = _model(11)
        mixer = m.layers[0].mixer
    kernel = get_op_info("mla").kernel
    x = np.random.default_rng(5).normal(size=(1, 6, 64)).astype(np.float32)
    weights = {"WqA": mixer.wq_a, "QNorm": mixer.q_norm, "WqB": mixer.wq_b,
               "WkvA": mixer.wkv_a, "KvNorm": mixer.kv_norm,
               "WkvB": mixer.wkv_b}
    weights = {k: np.asarray(v.numpy()) for k, v in weights.items()}
    expanded = kernel(dict(weights, X=x), mixer._attrs("in", "expanded"),
                      OpContext(seed=0))
    at = np.asarray([[5]], np.int32)
    absorbed = kernel(dict(weights, X=x[:, 5:], Positions=at),
                      mixer._attrs("in", "absorbed"), OpContext(seed=0))
    q, k = np.asarray(expanded["Q"]), np.asarray(expanded["K"])
    scores = np.einsum("hd,thd->ht", q[0, :, 5], np.transpose(k[0], (1, 0, 2)))
    rows = np.asarray(expanded["Latent"])[0]                 # [T, 40]
    absorbed_scores = np.asarray(absorbed["Q"])[0] @ rows.T   # [H, T]
    np.testing.assert_allclose(absorbed_scores, scores, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(absorbed["Latent"])[0], rows[5],
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("t,length", [
    (256, 100),             # one block of 256 queries, one fold
    # blocks of 512 queries, two folds of 256 of the one head each: every
    # row valid, a length inside a block's first fold, inside its second,
    # and on a block's edge
    (1024, None), (1024, 300), (1024, 700), (1024, 512)])
def test_latent_prefill_attention_with_lengths_is_a_masked_softmax(t, length):
    """q / k 24 wide, v 16: the kernel (interpreted, at the op's own block
    shape) against a dense causal softmax, rows inside the length; blocks
    of queries past it are zeros (the block the length falls in is
    computed whole)."""
    from paddle_tpu.ops.kernels.window_attention import prompt_blocks
    rng = np.random.default_rng(t + (length or 0))
    b, h, d, dv = 1, 2, 24, 16
    q, k = (rng.normal(size=(b, h, t, d)).astype(np.float32)
            for _ in range(2))
    v = rng.normal(size=(b, h, t, dv)).astype(np.float32)
    ins = {"Q": q, "K": k, "V": v}
    if length is not None:
        ins["Lengths"] = np.asarray([length], np.int32)
    kernel = get_op_info("latent_prefill_attention").kernel
    got = np.asarray(kernel(ins, {"scale": d ** -0.5},
                            OpContext(seed=0))["Out"])
    assert got.shape == (b, h, t, dv)
    s = np.einsum("bhid,bhjd->bhij", q, k) * d ** -0.5
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhij,bhjd->bhid", p / p.sum(-1, keepdims=True), v)
    block_q = prompt_blocks(t, 1)[0]
    computed = t if length is None else -(-length // block_q) * block_q
    np.testing.assert_allclose(got[:, :, :computed], want[:, :, :computed],
                               rtol=1e-4, atol=1e-5)
    assert not got[:, :, computed:].any()    # the blocks past the length


@pytest.mark.parametrize("t,groups,want", [
    # grouped-query attention (command-a-plus: 16 query heads a kv head)
    # keeps 128 queries a head against 512 keys, 256 rows a fold
    (8192, 16, (128, 512, 256)), (1024, 16, (128, 512, 256)),
    (64, 16, (64, 64, 256)),
    # one query head a kv head: 512 queries, two folds of 256 a key tile
    (16384, 1, (512, 512, 256)), (1024, 1, (512, 512, 256)),
    (256, 1, (256, 256, 256))])
def test_the_prompt_kernels_block_shape_follows_the_heads_a_kv_head(
        t, groups, want):
    from paddle_tpu.ops.kernels.window_attention import prompt_blocks
    assert prompt_blocks(t, groups) == want


# -- the expert share ------------------------------------------------------------
def test_the_expert_shares_of_four_chips_add_up_to_the_uncut_layer():
    """16 experts as 4 shares of 4: each share's expert layer (its held
    experts' part + the shared expert), the shared expert counted once,
    adds up to the layer that holds all 16."""
    with dg.guard():
        full = _model(8, held_experts=16)
        parts = [_model(8, held_experts=4, first_held=4 * i)
                 for i in range(4)]
        whole = full.layers[1].experts
        for i, part in enumerate(parts):
            mine = part.layers[1].experts
            for name in ("router_w", "router_b", "shared_in", "shared_out"):
                getattr(mine, name).set_value(getattr(whole, name))
            for name in ("w1", "w2"):
                getattr(mine, name).set_value(
                    getattr(whole, name)._value[4 * i:4 * i + 4])
        x = _t(np.random.default_rng(4).normal(size=(1, 9, 64)).astype(
            np.float32))
        with dg.no_grad():
            want = np.asarray(whole(x, None)[0].numpy())
            shared = np.asarray(parts[0].layers[1].experts.shared_out.numpy())
            got = sum(np.asarray(p.layers[1].experts(x, None)[0].numpy())
                      for p in parts)
            g, u = np.split(np.asarray(x.numpy()) @ np.asarray(
                whole.shared_in.numpy()), 2, axis=-1)
            once = (g / (1 + np.exp(-g)) * u) @ shared
    np.testing.assert_allclose(got - 3 * once, want, rtol=1e-4, atol=1e-5)


# -- the description, the cache, the refusals -------------------------------------
def test_the_cache_is_one_latent_group_priced_by_its_rows():
    import paddle_tpu.static as static
    from paddle_tpu.serving.kv_pool import (device_kv_arrays, kv_geometry,
                                            token_bytes)
    spec = PanguUltraMoeConfig().cache_spec()
    assert spec == [{"kind": "latent", "layers": 61, "width": 576,
                     "dtype": "bfloat16", "read_block": 1024}]
    assert kv_geometry(spec) == (61, 1, 576)
    assert token_bytes(spec) == 61 * 576 * 2
    (c,) = device_kv_arrays(spec, 16000)
    assert (c["name"], c["kind"], c["shape"], c["window"]) == \
        ("c0", "latent", [16384, 640], 0)          # 576 in whole lanes
    with dg.guard():
        m = _model(9)
        plan = static.page_budget(m, page_tokens=4, max_context=64,
                                  hbm_bytes=16 << 20, max_slots_cap=3)
    assert plan["kv_slot_bytes"] == 3 * 64 * 128 * 4
    assert plan["page_bytes"] == 3 * 40 * 4 * 4     # 40 values a row


def test_the_engine_refuses_by_name_what_a_latent_cache_has_not():
    import paddle_tpu.static as static
    from paddle_tpu.serving import ContinuousBatchingEngine
    with dg.guard():
        m = _model(10)
        plan = static.page_budget(m, page_tokens=4, max_context=64,
                                  hbm_bytes=16 << 20, max_slots_cap=3)
        for kw in ({"prefix_cache": "auto"}, {"speculative": "auto"}):
            with pytest.raises(NotImplementedError, match="latent"):
                ContinuousBatchingEngine(m, kv_pool=plan, **kw)


def test_from_published_refuses_each_key_not_built_by_name():
    published = {
        "vocab_size": 153600, "hidden_size": 7680, "num_hidden_layers": 61,
        "first_k_dense_replace": 3, "num_attention_heads": 128,
        "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "rope_theta": 25600000,
        "intermediate_size": 18432, "moe_intermediate_size": 2048,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "num_experts_per_tok": 8, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
        "sandwich_norm": True, "num_nextn_predict_layers": 0,
        "attention_bias": False, "hidden_act": "silu",
        "tie_word_embeddings": False}
    cfg = PanguUltraMoeConfig.from_published(
        published, held=8, vocab_rows=19200, layers=(1, 4))
    assert (cfg.num_layers, cfg.expert_layers, cfg.held_experts,
            cfg.vocab_size, cfg.block_form) == (5, 4, 8, 19200, "sandwich")
    assert cfg.param_count() == 3_409_191_424
    for key, bad in (("num_nextn_predict_layers", 1),
                     ("rope_scaling", {"type": "yarn", "factor": 4}),
                     ("scoring_func", "softmax"), ("n_group", 8),
                     ("topk_group", 4), ("attention_bias", True),
                     ("hidden_act", "gelu"), ("tie_word_embeddings", True),
                     ("q_lora_rank", None)):
        with pytest.raises(NotImplementedError, match=key):
            PanguUltraMoeConfig.from_published(dict(published, **{key: bad}))
