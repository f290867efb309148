"""The grouped expert matmul's tiling (`moe.grouped_tiling`): the k tile is
the whole contraction wherever its blocks fit the VMEM budget with an n
tile of at least 512 lanes, so an expert's weight block keeps its index
across the expert's row tiles and is fetched once a column tile.  The rule
on the served configurations' widths, the op in interpret mode against a
float32 reference where experts span several row tiles (on the plain path
and on the held-first path), and the counter a trace counts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import monitor
from paddle_tpu.ops.kernels import moe
from paddle_tpu.ops.registry import OpContext, run_kernel

CTX = OpContext(seed=0, is_test=True)
PROMPT_ROWS = 16384         # a block of sorted pairs (`moe._BLOCK_ROWS`)


# [held, K, N] of each served configuration's W1 (gate | up, or up) and
# W2, as one chip holds them, and the tiling the rule gives a prompt's
# block (where nothing fits, (128, _tile(K), _tile(N)))
@pytest.mark.parametrize("held,k,n,tiling", [
    (32, 2048, 3584, (128, 2048, 896)),     # lfm2-8b-a1b W1
    (32, 1792, 2048, (128, 1792, 1024)),    # lfm2-8b-a1b W2
    (16, 4096, 8192, (128, 4096, 512)),     # command-a-plus W1: tn narrowed
    (16, 4096, 4096, (128, 4096, 512)),     # command-a-plus W2
    (128, 1024, 2688, (128, 1024, 896)),    # nemotron W1: K already whole
    (128, 2688, 1024, (128, 2688, 1024)),   # nemotron W2
    (8, 7680, 4096, (128, 768, 1024)),      # openpangu W1: does not fit
    (8, 2048, 7680, (128, 2048, 768)),      # openpangu W2
], ids=["lfm2-w1", "lfm2-w2", "command-a-plus-w1", "command-a-plus-w2",
        "nemotron-w1", "nemotron-w2", "openpangu-w1", "openpangu-w2"])
def test_rule_gives_each_configuration_its_tiling(held, k, n, tiling):
    assert moe.grouped_tiling(PROMPT_ROWS, k, n) == tiling
    tm, tk, tn = tiling
    if tk == k:
        assert moe._vmem_bytes(tm, tk, tn, 2) <= moe._VMEM_BYTES
        assert tn >= moe._TILE_N_MIN and n % tn == 0
        # no wider lane tile up to the cap fits with the whole contraction
        assert all(moe._vmem_bytes(tm, k, wider, 2) > moe._VMEM_BYTES
                   for wider in moe._tiles(n) if wider > tn)
    else:
        assert (tk, tn) == (moe._tile(k), moe._tile(n))
        assert all(moe._vmem_bytes(tm, k, t, 2) > moe._VMEM_BYTES
                   for t in moe._tiles(n) if t >= moe._TILE_N_MIN)
    # a decode step's few rows take the same k and n tiles
    assert moe.grouped_tiling(256, k, n)[1:] == tiling[1:]


def test_rule_keeps_the_whole_of_narrow_axes_and_counts_the_dtype():
    assert moe.grouped_tiling(40, 16, 24) == (40, 16, 24)
    assert moe.grouped_tiling(1024, 1280, 256) == (128, 1280, 256)
    # float32 operands take twice the VMEM: the n tile narrows first
    assert moe.grouped_tiling(PROMPT_ROWS, 2048, 3584, 4) == (128, 2048, 512)
    assert moe.grouped_tiling(PROMPT_ROWS, 4096, 8192, 4) == \
        (128, 1024, 1024)


def _reference(x, experts, weights, w1, w2, first, activation):
    """The weighted sum over the held picks, one expert at a time, in
    float32 numpy."""
    held, f = w1.shape[0], w2.shape[1]
    flat, d = x.reshape(-1, x.shape[-1]), x.shape[-1]
    ids, w = experts.reshape(len(flat), -1), weights.reshape(len(flat), -1)
    out = np.zeros((len(flat), d), np.float32)
    for at in range(held):
        token, slot = np.nonzero(ids == first + at)
        h = flat[token] @ w1[at]
        if activation == "silu_gated":
            h = h[:, :f] / (1 + np.exp(-h[:, :f])) * h[:, f:]
        else:
            h = np.square(np.maximum(h, 0))
        np.add.at(out, token, w[token, slot][:, None] * (h @ w2[at]))
    loads = np.bincount(ids.reshape(-1), minlength=first + held)
    return out.reshape(x.shape), loads[first:first + held]


@pytest.mark.parametrize("block", [None, 256], ids=["plain", "held_first"])
@pytest.mark.parametrize("activation", ["relu2", "silu_gated"])
def test_whole_contraction_matches_the_reference_across_row_tiles(
        block, activation, monkeypatch):
    """K = 1,280 takes two k tiles under the old rule and one here; 4 of 6
    experts are held and each holds 170-300 of the 1,024 pairs, so every
    held expert spans two or more 128-row tiles and most tiles are shared
    by two experts."""
    if block:
        monkeypatch.setattr(moe, "_BLOCK_ROWS", block)
    rng = np.random.default_rng(7)
    tokens, k, d, f, e, first, held = 512, 2, 1280, 128, 6, 1, 4
    x = rng.normal(size=(tokens, d)).astype(np.float32)
    experts = np.stack([rng.choice(e, k, replace=False, p=[.1, .2, .2, .2,
                                                           .2, .1])
                        for _ in range(tokens)]).astype(np.int32)
    weights = rng.uniform(size=(tokens, k)).astype(np.float32)
    width = 2 * f if activation == "silu_gated" else f
    w1 = (rng.normal(size=(held, d, width)) * d ** -0.5).astype(np.float32)
    w2 = (rng.normal(size=(held, f, d)) * f ** -0.5).astype(np.float32)
    assert moe._tile(d) < d                 # `_tile` alone would split K
    monitor.stat_reset("ops.moe.gmm_whole_k")
    out = run_kernel("moe_grouped_experts", {
        "X": x, "Experts": experts, "Weights": weights, "W1": w1,
        "W2": w2}, {"n_experts": e, "first_held": first, "held": held,
                    "activation": activation}, CTX)
    want, loads = _reference(x, experts, weights, w1, w2, first, activation)
    assert (loads > moe._TILE_M).all()
    got = np.asarray(out["Out"])
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * np.abs(want).max())
    np.testing.assert_array_equal(
        np.asarray(out["Stats"]),
        [tokens * k, loads.sum(), held, loads.max()])
    # both matmuls took the whole contraction (the held-first blocks are
    # one loop body, traced once)
    assert monitor.stat_get("ops.moe.gmm_whole_k") == 2


def test_counter_reads_what_was_traced():
    """A trace of the op at LFM2's widths takes the whole contraction in
    both matmuls; openPangu's W1 keeps ten k tiles and counts nothing."""
    monitor.stat_reset("ops.moe.gmm_whole_k")

    def experts(x, picked, weights, w1, w2):
        return moe.moe_grouped_experts(
            {"X": x, "Experts": picked, "Weights": weights, "W1": w1,
             "W2": w2}, {"n_experts": 32, "first_held": 0, "held": 32,
                         "activation": "silu_gated"}, CTX)["Out"]

    bf16 = jnp.bfloat16
    jax.eval_shape(experts, jax.ShapeDtypeStruct((64, 1, 2048), bf16),
                   jax.ShapeDtypeStruct((64, 1, 4), jnp.int32),
                   jax.ShapeDtypeStruct((64, 1, 4), jnp.float32),
                   jax.ShapeDtypeStruct((32, 2048, 3584), bf16),
                   jax.ShapeDtypeStruct((32, 1792, 2048), bf16))
    assert monitor.stat_get("ops.moe.gmm_whole_k") == 2
    jax.eval_shape(lambda r, w, s: moe._grouped(r, w, s, 0),
                   jax.ShapeDtypeStruct((4096, 7680), bf16),
                   jax.ShapeDtypeStruct((8, 7680, 4096), bf16),
                   jax.ShapeDtypeStruct((8,), jnp.int32))
    assert monitor.stat_get("ops.moe.gmm_whole_k") == 2
