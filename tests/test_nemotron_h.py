"""The hybrid decoder with latent routed experts (one chip's share of an
expert-parallel deployment): the router and the grouped experts against
their formulas, the shares adding up to the uncut layer, the grouped
output norm, the model against the plain float32 reference (full pass;
prefill then decode through the caches), and the engine's compiled step
route with what a step counted riding behind the ids.

Tolerances are `tests/test_granite_hybrid.py`'s, for its reasons: model
and reference are both float32 here and differ only by the order of
float32 additions (chunked scan, sorted grouped matmul, padded buckets,
batched rows against a token-by-token, per-expert loop over one unpadded
sequence), so logits agree to LOGIT_SIGMAS = 1e-3 of their own spread
(seen: <= 5e-6).  A flipped pick would move a logit by tenths of a sigma:
it cannot hide in that.
"""
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.dygraph as dg
import paddle_tpu.static as static
from paddle_tpu.models import (GraniteHybridConfig, NemotronHConfig,
                               nemotron_h_tiny)
from paddle_tpu.ops.registry import OpContext, get_op_info, run_kernel
from paddle_tpu.serving import ContinuousBatchingEngine, budget_drift
from paddle_tpu.serving.metrics import reset_serving_stats, serving_stats

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import nemotron_h as reference  # noqa: E402

LOGIT_SIGMAS = 1e-3
CTX = OpContext(seed=0, is_test=True)
E, K = 16, 4                    # the tiny router: top 4 of 16


@pytest.fixture(autouse=True)
def generator_left_as_found():
    """Every test here seeds its own weights (`paddle_tpu.seed`); the
    process-global generator goes back as it was, so that tests elsewhere
    that build models without seeding them see what they saw before this
    file existed (as `tests/benchmark/conftest.py` does for its files)."""
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
    return nemotron_h_tiny(**kw)


def _published(cfg):
    """The keys `reference.logits` reads, from a built config."""
    out = {k: getattr(cfg, k) for k in cfg._HF_KEYS if hasattr(cfg, k)}
    out.update(mamba_num_heads=cfg.mamba_n_heads,
               mamba_head_dim=cfg.mamba_d_head,
               ssm_state_size=cfg.mamba_d_state, n_groups=cfg.mamba_n_groups,
               layer_norm_epsilon=cfg.rms_norm_eps,
               first_held_expert=cfg.first_held)
    return out


def _reference_logits(m, ids, **kw):
    return np.asarray(reference.logits(
        reference.params_of(m), np.asarray(ids, np.int32),
        _published(m.config), **kw))


# -- the ops ------------------------------------------------------------------
@pytest.mark.parametrize("op", ["moe_router_topk", "moe_grouped_experts"])
def test_expert_ops_are_registered_forward_only(op):
    info = get_op_info(op)
    assert info is not None and info.grad is None
    assert get_op_info(op + "_grad") is None
    assert "Forward only" in info.kernel.__doc__


def _route(x, w, b, k=K, scaling=5.0):
    s = 1 / (1 + np.exp(-(x.astype(np.float64) @ w.astype(np.float64))))
    pick = np.argsort(-(s + b), axis=-1, kind="stable")[:, :k]
    picked = np.take_along_axis(s, pick, -1)
    return pick, scaling * picked / (picked.sum(-1, keepdims=True) + 1e-20)


def test_router_selects_by_score_plus_bias_and_weighs_by_score():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(9, 24)).astype(np.float32)
    w = rng.normal(size=(24, E)).astype(np.float32) * 0.3
    b = np.zeros(E, np.float32)
    b[3] = 5.0              # expert 3 is always selected, whatever it scores
    got = run_kernel("moe_router_topk", {"X": x, "Weight": w, "Bias": b},
                     {"top_k": K, "routed_scaling_factor": 5.0}, CTX)
    pick, weights = np.asarray(got["Experts"]), np.asarray(got["Weights"])
    want_pick, want_w = _route(x, w, b)
    assert pick.dtype == np.int32 and weights.dtype == np.float32
    np.testing.assert_array_equal(pick, want_pick)
    assert (pick[:, 0] == 3).all()              # the bias selects ...
    np.testing.assert_allclose(weights, want_w, rtol=1e-5)
    s3 = 1 / (1 + np.exp(-(x @ w)[:, 3]))       # ... and does not weigh
    others = np.sort(1 / (1 + np.exp(-(x @ w))), -1)
    assert (weights[:, 0] < 5.0 * s3 / s3 + 1e-6).all()
    np.testing.assert_allclose(weights.sum(-1), 5.0, rtol=1e-5)
    assert (s3 < others[:, -1]).any()   # not the best score everywhere
    # without normalisation or scaling the weights are the raw scores
    raw = run_kernel("moe_router_topk", {"X": x, "Weight": w, "Bias": b},
                     {"top_k": K, "norm_topk_prob": False}, CTX)
    np.testing.assert_allclose(np.asarray(raw["Weights"])[:, 0], s3,
                               rtol=1e-5)
    # leading axes pass through: [B, T, hidden] -> [B, T, k]
    three = run_kernel("moe_router_topk",
                       {"X": x.reshape(3, 3, 24), "Weight": w, "Bias": b},
                       {"top_k": K, "routed_scaling_factor": 5.0}, CTX)
    np.testing.assert_array_equal(
        np.asarray(three["Experts"]).reshape(9, K), pick)


def _experts_loop(u, pick, weights, w1, w2, first, valid=None):
    """The share's sum, one pair at a time: expert `first + i` is w1[i]."""
    out = np.zeros((u.shape[0], w2.shape[2]), np.float64)
    loads = np.zeros(w1.shape[0], np.int64)
    for t in range(u.shape[0]):
        if valid is not None and not valid[t]:
            continue
        for j in range(pick.shape[1]):
            e = int(pick[t, j]) - first
            if 0 <= e < w1.shape[0]:
                loads[e] += 1
                hidden = np.maximum(u[t].astype(np.float64) @ w1[e], 0) ** 2
                out[t] += weights[t, j] * (hidden @ w2[e])
    return out, loads


def _grouped(u, pick, weights, w1, w2, first, total=E, lengths=None):
    ins = {"X": u, "Experts": pick, "Weights": weights, "W1": w1, "W2": w2}
    if lengths is not None:
        ins["Lengths"] = np.asarray(lengths, np.int32)
    got = run_kernel("moe_grouped_experts", ins, {
        "n_experts": total, "first_held": first, "held": w1.shape[0]}, CTX)
    return np.asarray(got["Out"]), np.asarray(got["Stats"])


def _expert_weights(rng, held=E, d=12, f=20):
    return (rng.normal(size=(held, d, f)).astype(np.float32) * 0.3,
            rng.normal(size=(held, f, d)).astype(np.float32) * 0.3)


@pytest.mark.parametrize("first, held", [(0, 16), (4, 4), (12, 4), (5, 3)])
def test_grouped_experts_are_the_per_expert_loop_over_the_share(first, held):
    """Uneven loads, empty experts, rows the lengths leave out; the counts
    are a numpy count."""
    rng = np.random.default_rng(first)
    n = 13
    u = rng.normal(size=(n, 12)).astype(np.float32)
    # uneven: two thirds of the tokens crowd experts 4-6
    pick = np.stack([rng.permutation(E)[:K] for _ in range(n)]).astype(
        np.int32)
    pick[: 2 * n // 3, 0] = 4 + np.arange(2 * n // 3) % 2
    pick[pick == 13] = 14                       # nobody picks expert 13
    pick = np.stack([np.unique(r)[:K] if len(np.unique(r)) >= K
                     else np.arange(K) for r in pick]).astype(np.int32)
    weights = rng.uniform(0.1, 1.0, (n, K)).astype(np.float32)
    a, b = _expert_weights(rng)
    out, stats = _grouped(u, pick, weights, a[first:first + held],
                          b[first:first + held], first)
    want, loads = _experts_loop(u, pick, weights, a[first:first + held],
                                b[first:first + held], first)
    assert out.dtype == np.float32
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        stats, [n * K, loads.sum(), (loads > 0).sum(), loads.max()])
    if (first, held) == (12, 4):
        assert loads[1] == 0                    # an empty held expert
    # [B, T, D] with lengths: the pads of a prompt route nothing
    lengths, valid = [4, 0], np.r_[np.ones(4), np.zeros(2), np.zeros(6)]
    out, stats = _grouped(u[:12].reshape(2, 6, 12),
                          pick[:12].reshape(2, 6, K),
                          weights[:12].reshape(2, 6, K),
                          a[first:first + held], b[first:first + held],
                          first, lengths=lengths)
    want, loads = _experts_loop(u[:12], pick[:12], weights[:12],
                                a[first:first + held], b[first:first + held],
                                first, valid)
    np.testing.assert_allclose(out.reshape(12, 12), want, rtol=2e-5,
                               atol=2e-5)
    assert not out.reshape(12, 12)[4:].any()
    np.testing.assert_array_equal(
        stats, [4 * K, loads.sum(), (loads > 0).sum(), loads.max()])


def test_experts_are_dropless_and_absent_experts_add_nothing():
    """Every token to ONE held expert: none is lost, whatever the load;
    every token to experts held elsewhere: zero pairs, zeros out."""
    rng = np.random.default_rng(1)
    n = 40
    u = rng.normal(size=(n, 12)).astype(np.float32)
    a, b = _expert_weights(rng, held=4)
    weights = np.ones((n, 1), np.float32)
    out, stats = _grouped(u, np.full((n, 1), 6, np.int32), weights, a, b, 4)
    want = (np.maximum(u.astype(np.float64) @ a[2], 0) ** 2) @ b[2]
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(stats, [n, n, 1, n])
    assert np.abs(out).min(axis=1).max() > 0 and (np.abs(out).sum(1) > 0).all()
    out, stats = _grouped(u, np.full((n, 1), 9, np.int32), weights, a, b, 4)
    assert not out.any()
    np.testing.assert_array_equal(stats, [n, 0, 0, 0])
    with pytest.raises(ValueError, match="holds 4 experts"):
        _grouped(u, np.zeros((n, 1), np.int32), weights, a, b, 14)


def test_the_four_quarter_shares_add_up_to_the_uncut_layer():
    """The routed parts of the four shares (experts 0-3, 4-7, 8-11, 12-15
    of one layer with the SAME router, latent pair and shared expert),
    plus the latent path and the shared expert counted once, equal the
    reference's uncut layer (all 16 held).  Through the model's own expert
    layer, so the latent pair and the shared expert are in it."""
    with dg.guard():
        whole = _model(21, hybrid_override_pattern="E").layers[0].experts
        rng = np.random.default_rng(2)
        x = rng.normal(size=(1, 11, 64)).astype(np.float32)
        full, stats, _ = whole(_t(x), None)
        full = np.asarray(full.numpy())[0]
        p = {n: np.asarray(getattr(whole, n).numpy(), np.float32)
             for n in ("router_w", "router_b", "w_down", "w_up", "w1", "w2",
                       "shared_in", "shared_out")}
        want, _, _ = reference._experts(x[0], p, K, 5.0, 0)
        _assert_logits(full, np.asarray(want))
        assert int(stats.numpy()[1]) == 11 * K          # all pairs held
        shared = np.maximum(x[0] @ p["shared_in"], 0) ** 2 @ p["shared_out"]
        routed = np.zeros_like(full)
        pairs = 0
        for first in (0, 4, 8, 12):
            share = _model(21, hybrid_override_pattern="E", held_experts=4,
                           first_held=first).layers[0].experts
            for name in ("router_w", "router_b", "w_down", "w_up",
                         "shared_in", "shared_out"):
                getattr(share, name).set_value(p[name])
            share.w1.set_value(p["w1"][first:first + 4])
            share.w2.set_value(p["w2"][first:first + 4])
            part, stats, _ = share(_t(x), None)
            routed += np.asarray(part.numpy())[0] - shared
            pairs += int(stats.numpy()[1])
        assert pairs == 11 * K                  # each pair on one chip
        _assert_logits(routed + shared, np.asarray(want))
        assert np.abs(routed).max() > 10 * LOGIT_SIGMAS * np.std(want)


def test_gated_rms_norm_in_groups_and_one_group_is_todays():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 5, 24)).astype(np.float32)
    g = rng.normal(size=(2, 5, 24)).astype(np.float32)
    w = rng.uniform(0.5, 1.5, 24).astype(np.float32)
    y = x * (g / (1 + np.exp(-g)))
    run = lambda attrs: np.asarray(run_kernel(  # noqa: E731
        "gated_rms_norm", {"X": x, "Gate": g, "Scale": w}, attrs,
        CTX)["Out"])
    y3 = y.reshape(2, 5, 3, 8)
    want = (y3 / np.sqrt((y3 ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(2, 5, 24) * w
    np.testing.assert_allclose(run({"epsilon": 1e-5, "groups": 3}), want,
                               rtol=1e-5, atol=1e-6)
    # groups=1 is bit-equal to the op without the attribute, which is the
    # formula over the whole axis
    one = run({"epsilon": 1e-5})
    np.testing.assert_array_equal(run({"epsilon": 1e-5, "groups": 1}), one)
    np.testing.assert_allclose(
        one, y / np.sqrt((y ** 2).mean(-1, keepdims=True) + 1e-5) * w,
        rtol=1e-5, atol=1e-6)
    assert np.abs(one - want).max() > 1e-2      # the groups matter


def test_slab_update_takes_128_heads_in_two_parts():
    """The one-token update's VMEM budget holds per part of a row's
    entry: granite's 64 heads go whole, Nemotron-H's 128 in two halves
    (declined before: XLA's form ran), and the cut kernel is the plain
    update (interpreted here)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import ssm
    assert ssm._slab_parts(64, 64, 128) == 1
    assert ssm._slab_parts(128, 64, 128) == 2
    assert ssm._slab_update_fits(
        jnp.zeros((1, 1, 128, 64, 128), jnp.float32), 128, 64, 128)
    old = ssm._SLAB_VMEM_BYTES
    ssm._SLAB_VMEM_BYTES = 4 * 8 * 128 * 128 * 4    # 8 blocks a part
    try:
        h, p, n, b = 32, 64, 128, 3                 # 16 blocks: two parts
        assert ssm._slab_parts(h, p, n) == 2
        rng = np.random.default_rng(4)
        f32 = lambda *s: rng.normal(size=s).astype(np.float32)  # noqa: E731
        ins = dict(X=f32(b, h, p), Dt=f32(b, h),
                   A=-rng.uniform(1, 4, h).astype(np.float32),
                   B=f32(b, 4, n), C=f32(b, 4, n), D=np.ones(h, np.float32),
                   DtBias=f32(h), Lengths=np.asarray([1, 0, 1], np.int32))
        slab = f32(2, b, h, p, n)
        run = lambda state, attrs: run_kernel(  # noqa: E731
            "mamba2_state_update", dict(ins, State=state), attrs, CTX)
        # keyed by the budget: `_slab_update` is a jit of its own
        ssm._slab_update.clear_cache()
        got, want = run(slab, {"slab_index": 1}), run(slab[1], {})
    finally:
        ssm._SLAB_VMEM_BYTES = old
        ssm._slab_update.clear_cache()
    new = np.asarray(got["NewState"])
    # (the read-out sums 128 products in another order: 4e-5 of a value
    # near zero was seen)
    np.testing.assert_allclose(new[1], np.asarray(want["NewState"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got["Y"]), np.asarray(want["Y"]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(new[0], slab[0])
    np.testing.assert_array_equal(new[1, 1], slab[1, 1])    # the idle row


# -- the description ----------------------------------------------------------
def test_parameter_counts_at_published_widths_from_shapes_alone():
    uncut = NemotronHConfig()       # 88 layers, 512 experts, no MTP module
    assert uncut.num_layers == 88 and uncut.expert_layers == 40
    assert len(uncut.layers_of("mamba")) == 40
    assert len(uncut.layers_of("attention")) == 8
    assert uncut.param_count() == 120_668_707_840           # 120,669 M
    cut = NemotronHConfig(hybrid_override_pattern="MEMEMEM*EME",
                          held_experts=128, vocab_size=32768)
    assert cut.param_count() == 4_648_163_712               # 4,648.2 M
    shapes = cut.param_shapes()
    count = lambda prefix: sum(                 # noqa: E731
        int(np.prod(s)) for k, s in shapes.items() if k.startswith(prefix))
    assert count("layers.0.") == 109_640_064                # a Mamba layer
    assert count("layers.1.") == 759_173_632                # an expert layer
    assert count("layers.7.") == 35_655_680                 # attention
    assert shapes["layers.1.experts.w1"] == (128, 1024, 2688)
    assert shapes["layers.1.experts.router_w"] == (4096, 512)
    assert shapes["head"] == (4096, 32768) and "layers.1.norm1" not in shapes
    spec = cut.cache_spec()
    assert [g["kind"] for g in spec] == ["kv", "state"]
    assert (spec[0]["layers"], spec[0]["kv_heads"], spec[0]["head_dim"]) \
        == (1, 2, 128)
    assert spec[1]["layers"] == 5 and spec[1]["arrays"][0]["shape"] \
        == [128, 64, 128]


def test_from_published_reads_the_published_keys_and_names_what_is_not_built():
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "nemotron-3-super-120b-a12b.json")) as f:
        import json
        file = json.load(f)
    published = dict(file, **file["published"])     # the row as published
    with pytest.raises(NotImplementedError, match="num_nextn_predict_layers"):
        NemotronHConfig.from_published(published)
    cfg = NemotronHConfig.from_published(
        published, held_experts=128, first_held=0, vocab_rows=32768,
        layers=(0, 11), drop_mtp=True, eos_id=32767)
    assert cfg.hybrid_override_pattern == "MEMEMEM*EME"
    assert cfg.param_count() == 4_648_163_712
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.mamba_norm_groups, cfg.mamba_d_conv,
            cfg.mamba_chunk_size) == (128, 64, 128, 8, 8, 4, 128)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.moe_latent_size) \
        == (512, 22, 5.0, 1024)
    assert cfg.attention_multiplier == 128 ** -0.5
    assert [tuple(b) for b in cfg.blocks[:2]] \
        == [("mamba", None), (None, "experts")]
    whole = NemotronHConfig.from_published(published, drop_mtp=True)
    assert whole.param_count() == 120_668_707_840
    for key, value, named in (
            ("hybrid_override_pattern", "ME-M", "'-'"),
            ("use_bias", True, "use_bias"), ("mlp_bias", True, "mlp_bias"),
            ("attention_bias", True, "attention_bias"),
            ("mamba_proj_bias", True, "mamba_proj_bias"),
            ("use_conv_bias", False, "use_conv_bias"),
            ("n_group", 2, "n_group"), ("topk_group", 2, "topk_group")):
        with pytest.raises(NotImplementedError, match=named):
            NemotronHConfig.from_published(
                dict(published, **{key: value}), drop_mtp=True)
    with pytest.raises(ValueError, match="are not among"):
        NemotronHConfig(held_experts=128, first_held=400)
    # the other family's message names what IS built now
    with pytest.raises(NotImplementedError, match="nemotron_h.py"):
        GraniteHybridConfig.from_published({"num_local_experts": 8})


def test_built_model_has_exactly_the_shapes_the_config_states():
    with dg.guard():
        m = _model(1, held_experts=4, first_held=8)
        built = {n: tuple(p.shape) for n, p in m.named_parameters()}
        assert built == m.config.param_shapes()
        assert m.config.param_count() == sum(
            int(np.prod(s)) for s in built.values())
        assert m.step_counters == ("moe_routed", "moe_pairs", "moe_touched",
                                   "moe_max_load")
        b = m.layers[1].experts.router_b.numpy()
        assert np.abs(b).max() <= 0.02 and np.abs(b).min() > 0 \
            and len(np.unique(b)) == E      # small, non-zero, distinct


# -- the model against the reference ------------------------------------------
@pytest.mark.parametrize("first, held", [(0, 16), (4, 4)])
def test_full_forward_matches_the_reference(first, held):
    with dg.guard():
        m = _model(2, held_experts=held, first_held=first)
        ids = np.random.default_rng(3).integers(0, 126, 21)
        with dg.no_grad():
            got = m(_t(ids[None], np.int32)).numpy()[0]
        picks = []
        want = _reference_logits(m, ids, picks=picks)
        _assert_logits(got, want)
        # the served gate's sets are the reference's at float32
        mine = np.asarray(m.routes(_t(ids[None], np.int32),
                                   _t([21], np.int32)).numpy())[:, 0]
        assert mine.shape == (3, 21, K)
        np.testing.assert_array_equal(np.sort(mine, -1),
                                      np.sort(np.stack(picks), -1))


def _prefill(m, ids, bucket):
    p = len(ids)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :p] = ids
    with dg.no_grad():
        out = m.prefill_step(_t(padded), _t([p], np.int32),
                             _t([p - 1], np.int32))
    return [np.asarray(o.numpy()) for o in out]


def test_prefill_then_decode_through_the_caches_matches_the_full_pass():
    """A padded prefill, then decode steps over three rows (one live) on
    the KV and state arrays given whole, each at the engine's bound on the
    columns (11..18 columns: 16, then 32 from 17 on): every step's logits
    equal the reference's full pass, the counts are a numpy count of the
    reference's picks, the arrays come back with the live row's column
    written at its own position, and an idle row routes nothing."""
    from paddle_tpu.core.compile_cache import next_pow2
    with dg.guard():
        m = _model(4, held_experts=4, first_held=4)
        c = m.config
        ids = np.random.default_rng(5).integers(0, 126, 19)
        p, n = 11, 8
        picks = []
        want = _reference_logits(m, ids, picks=picks)
        picks = np.stack(picks)                             # [3, 19, K]
        held = (picks >= 4) & (picks < 8)

        def counted(rows):
            mine = held[:, rows]
            loads = np.stack([np.bincount(
                picks[layer, rows][mine[layer]] - 4, minlength=4)
                for layer in range(3)])
            return [3 * len(rows) * K, mine.sum(), (loads > 0).sum(),
                    loads.max(1).sum()]

        logits, k, v, ssm, conv, counts = _prefill(m, ids[:p], 16)
        _assert_logits(logits[0], want[p - 1], want.std())
        np.testing.assert_array_equal(counts, counted(list(range(p))))
        rows, lpad = 3, 32
        kc = np.zeros((1, rows, c.num_key_value_heads, lpad, c.head_dim),
                      np.float32)
        vc = np.zeros_like(kc)
        kc[:, 1, :, :16], vc[:, 1, :, :16] = k[:, 0], v[:, 0]   # pads too
        s = np.zeros((3, rows) + ssm.shape[2:], np.float32)
        t = np.zeros((3, rows) + conv.shape[2:], np.float32)
        s[:, 1], t[:, 1] = ssm[:, 0], conv[:, 0]
        for i in range(n):
            step = np.zeros((rows, 1), np.int32)
            step[1, 0] = ids[p + i]
            with dg.no_grad():
                out = m.decode_step(
                    _t(step), _t([0, p + i, 0], np.int32),
                    _t([0, 1, 0], np.int32), _t(kc), _t(vc), _t(s), _t(t),
                    columns=next_pow2(p + i, 16))
            logits, kn, vn, s, t, counts = (np.asarray(o.numpy())
                                            for o in out)
            _assert_logits(logits[1], want[p + i], want.std())
            np.testing.assert_array_equal(counts, counted([p + i]))
            assert kn.shape == kc.shape
            np.testing.assert_array_equal(kn[:, 1, :, :p + i],
                                          kc[:, 1, :, :p + i])
            assert kn[:, 1, :, p + i].any()     # past it: the prompt's pads
            assert not kn[:, 1, :, max(16, p + i + 1):].any()
            assert not kn[:, [0, 2], :, 1:].any()   # idle: their column 0
            kc, vc = kn, vn
            assert not s[:, [0, 2]].any()       # idle rows: state untouched


# -- the engine ---------------------------------------------------------------
def _greedy(m, prompt, n, width=64):
    """One sequence, no cache: the full forward again for every token."""
    ids = list(prompt)
    for _ in range(n):
        buf = np.zeros((1, width), np.int32)
        buf[0, :len(ids)] = ids
        with dg.no_grad():
            row = m(_t(buf)).numpy()[0, len(ids) - 1]
        ids.append(int(row.argmax()))
        if ids[-1] == m.config.eos_id:
            break
    return ids


def test_engine_serves_the_share_token_equal_and_counts_what_it_routed():
    """More requests than the 8 slots, admitted at different steps: greedy
    tokens equal one-sequence decoding with no cache; the spans' `moe_*`
    fields add up to the `serving.moe.*` counters, which equal a numpy
    count over the reference's picks of every token that went through an
    expert layer; a greedy step still downloads ids and KV columns only."""
    import paddle_tpu.profiler as prof
    reset_serving_stats()
    slots = 8
    with dg.guard():
        m = _model(8, held_experts=4, first_held=4)
        plan = static.page_budget(m, page_tokens=4, max_context=128,
                                  hbm_bytes=16 << 20, max_slots_cap=slots)
        assert plan["max_slots"] == slots
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        assert eng._steps.counters == m.step_counters
        rng = np.random.default_rng(5)
        lengths = (5, 19, 33, 7, 12, 9, 26, 14, 6, 17)
        prompts = [rng.integers(0, 126, n) for n in lengths]
        news = (9, 4, 6, 5, 7, 3, 8, 5, 6, 4)
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
        programs = eng._steps.programs
        for prompt, n, out in zip(prompts, news, outs):
            assert list(out) == _greedy(m, prompt, n)
        eng.kv_pool.assert_drained()
        assert budget_drift(eng.kv_pool, m) == []
        assert eng._steps.programs == programs
        # every token that went through the expert layers: each prompt's
        # tokens once (its prefill) and each generated token but the last
        # (a decode step each) - under the reference's picks
        want = np.zeros(4, np.int64)
        for out, prompt in zip(outs, prompts):
            picks = []
            _reference_logits(m, np.asarray(out[:-1]), picks=picks)
            picks = np.stack(picks)
            mine = (picks >= 4) & (picks < 8)
            want[0] += picks.size
            want[1] += mine.sum()
            # touched experts and largest loads are per call: the prefill
            # is one call over the prompt, every later token its own step
            # SHARED with other rows - only the first two are additive
        stats = serving_stats()
        assert stats["serving.moe.pairs_routed"] == want[0]
        assert stats["serving.moe.pairs_held"] == want[1]
        calls = stats["serving.gen.steps"] + stats["serving.gen.prefills"]
        assert stats["serving.moe.expert_steps"] == 3 * calls
        assert 0 < stats["serving.moe.experts_touched"] <= 4 * 3 * calls
        assert stats["serving.moe.load_max_over_mean"] >= 1.0
    spans = [e for e in events if e.name in ("engine/step", "engine/prefill")]
    assert len([e for e in spans if e.name == "engine/prefill"]) == 10
    # a prefill's counts are on its own span; a decode step is read after
    # the next launch was dispatched, so its counts are on the next step
    # span to open after its own: only the last step's are on no span
    for name, key in (("moe_routed", "pairs_routed"),
                      ("moe_pairs", "pairs_held"),
                      ("moe_touched", "experts_touched")):
        got = sum(e.fields[name] for e in spans if name in e.fields)
        assert 0 <= stats["serving.moe." + key] - got <= slots * 3 * K
    assert sum(e.fields["moe_routed"] for e in spans
               if e.name == "engine/prefill") == 3 * K * sum(lengths)
    steps = [e for e in spans if e.name == "engine/step"
             and "active" in e.fields]
    assert "moe_routed" not in steps[0].fields and len(steps) >= 5
    for before, step in zip(steps, steps[1:]):
        # one step behind its own launch: the rows of the step before
        assert step.fields["moe_routed"] == 3 * K * before.fields["active"]
        assert step.fields["moe_touched"] <= min(12,
                                                 step.fields["moe_pairs"])
        assert step.fields["moe_max_load"] >= 1
    assert any(step.fields["active"] >= 2 and step.fields["ahead"]
               for step in steps)
    # no further download: ids (+ the 4 counts, 16 bytes in the same
    # array) a step - read under the next step's span or under a prefill's
    # - and 4 + 16 bytes a prefill; no KV byte moves: the one attention
    # layer's columns are written where the device arrays lie
    fetches = [e.fields["bytes"] for e in events if e.name == "engine/fetch"]
    assert set(fetches) == {4 * (slots + 4), 4 * (1 + 4)}
    assert fetches.count(4 * (1 + 4)) == 10
    assert not any(e.fields["bytes"] for e in events
                   if e.name in ("engine/kv_install", "engine/kv_append"))
    assert stats.get("serving.gen.logits_rows_fetched", 0) == 0
    assert stats["serving.gen.state_in_place"] == stats["serving.gen.steps"]
    assert stats.get("serving.gen.state_copied", 0) == 0
    assert stats["serving.gen.kv_buckets"] == eng._steps.programs
    state = eng.kv_pool.state
    assert state.names == ["k0", "v0", "ssm", "conv"]
    assert stats["serving.kv.device_bytes"] == 2 * state.arrays["k0"].nbytes
    ops = {op.type for cp in eng._steps._decode_traces()
           for op in cp.program.global_block().ops}
    assert {"moe_router_topk", "moe_grouped_experts", "mamba2_state_update",
            "cached_decode_attention", "gated_rms_norm"} <= ops
    bounds = {op.attrs["columns"] for cp in eng._steps._decode_traces()
              for op in cp.program.global_block().ops
              if op.type == "cached_decode_attention"}
    assert 0 not in bounds and len(bounds) == len(eng._steps._decode_traces())
    groups = {op.attrs.get("groups") for cp in eng._steps._decode_traces()
              for op in cp.program.global_block().ops
              if op.type == "gated_rms_norm"}
    assert groups == {2}


def test_eos_is_found_a_step_late_beside_the_counts():
    """Three greedy requests over two slots, the first ending on EOS
    mid-answer (an id from its recorded stream) while the step dispatched
    ahead - from ids AND counts as the last step returned them, on the
    device - had already computed its next row: that row is booked nowhere
    (`rows_past_end` 1), the third request takes the slot over, tokens
    equal one-sequence decoding, and the device's count of routed pairs
    is every token fed plus that one row."""
    reset_serving_stats()
    with dg.guard():
        m = _model(9, held_experts=4, first_held=4)
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 126, n) for n in (7, 15, 10)]
        news = (10, 12, 3)
        streams = [_greedy(m, p, n)[len(p):] for p, n in zip(prompts, news)]
        eos = streams[0][3]
        assert eos not in streams[0][:3] + streams[1] + streams[2]
        m.config.eos_id = eos
        plan = static.page_budget(m, page_tokens=4, max_context=128,
                                  hbm_bytes=16 << 20, max_slots_cap=2)
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        outs = [f.result(timeout=900) for f in
                [eng.submit(p, max_length=n) for p, n in zip(prompts, news)]]
        programs = eng._steps.programs
        assert list(outs[0]) == list(prompts[0]) + streams[0][:4]
        for prompt, n, out in zip(prompts, news, outs):
            assert list(out) == _greedy(m, prompt, n)
        again = eng.submit(prompts[1], max_length=news[1]).result(900)
        assert list(again) == list(outs[1])
        assert eng._steps.programs == programs
        assert {cp.composed()._cache_size() for cp in
                eng._steps._decode_traces()} == {1}
        eng.stop()
        eng.kv_pool.assert_drained()
    stats = serving_stats()
    assert stats["serving.gen.rows_past_end"] == 1
    assert 0 < stats["serving.gen.steps_ahead"] < stats["serving.gen.steps"]
    fed = sum(len(o) - 1 for o in outs + [again])   # all but the last token
    assert stats["serving.moe.pairs_routed"] == 3 * K * (fed + 1)


def test_a_sampling_row_keeps_its_seeded_tokens_beside_the_counts():
    """A request that samples downloads the logits beside the ids (whose
    array carries the counts): its tokens are `_sample`'s over the
    no-cache logits with the same seed."""
    with dg.guard():
        m = _model(9, held_experts=4, first_held=4)
        plan = static.page_budget(m, page_tokens=4, max_context=128,
                                  hbm_bytes=16 << 20, max_slots_cap=2)
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        prompt = np.random.default_rng(6).integers(0, 126, 7)
        out = eng.submit(prompt, max_length=5, decode_strategy="sampling",
                         seed=3).result(timeout=600)
        greedy = eng.submit(prompt, max_length=5).result(timeout=600)
        eng.stop()
        assert list(greedy) == _greedy(m, prompt, 5)
        # the same draws over full-forward logits
        from paddle_tpu.serving.generation import GenerationRequest
        req = GenerationRequest(prompt, 5, "sampling", 0, 1.0, seed=3,
                                timeout_s=60.0)
        ids = list(prompt)
        for _ in range(5):
            buf = np.zeros((1, 64), np.int32)
            buf[0, :len(ids)] = ids
            with dg.no_grad():
                row = m(_t(buf)).numpy()[0, len(ids) - 1]
            ids.append(eng._sample(req, row))
        assert list(out) == ids
