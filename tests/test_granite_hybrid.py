"""The hybrid decoder (Mamba-2 layers beside grouped-query attention
layers): its ops, the model against the plain float32 reference, the
state slots beside the page pool, and the engine's compiled step route.

Tolerances.  Model and reference are both float32 here (conftest turns
x64 on; neither uses it), so they differ only by the order of float32
additions: the system scans in chunks, pads prompts to a bucket and
batches rows where the reference steps token by token over one unpadded
sequence.  Logits are compared in units of their own spread: the largest
error must stay under LOGIT_SIGMAS = 1e-3 standard deviations of the
reference's logits (seen: <= 3e-6) — three orders under the spread, and
well under the ~sigma/50 that separates neighbours in a 128-way argmax, so
a wrong column, a missed mask or a lower precision (bfloat16 rounds at
4e-3 relative) cannot hide in it.  State and padded-vs-unpadded
comparisons are between two float32 runs of the same system: a few ulps,
rtol 1e-5 with STATE_ATOL = 1e-6 for values near zero.
"""
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.dygraph as dg
import paddle_tpu.static as static
from paddle_tpu.models import (GPTConfig, GPTModel, GraniteHybridConfig,
                               GraniteHybridModel, granite_hybrid_tiny)
from paddle_tpu.ops.registry import OpContext, get_op_info, run_kernel
from paddle_tpu.serving import (ContinuousBatchingEngine, PagedKVPool,
                                StateSlots, budget_drift)
from paddle_tpu.serving.metrics import reset_serving_stats, serving_stats

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import granite_hybrid as reference  # noqa: E402

LOGIT_SIGMAS = 1e-3
STATE_ATOL = 1e-6


def _assert_logits(got, want, spread=None):
    spread = float(np.std(want)) if spread is None else spread
    assert float(np.abs(np.asarray(got) - want).max()) \
        <= LOGIT_SIGMAS * spread


def _assert_state(got, want):
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=STATE_ATOL)
NEW_OPS = ("rms_norm", "gated_rms_norm", "causal_conv1d",
           "mamba2_chunk_scan", "mamba2_state_update", "gqa_attention")


def _t(a, dtype=None):
    return paddle_tpu.to_tensor(np.asarray(a, dtype))


def _model(seed, **kw):
    paddle_tpu.seed(seed)            # every test seeds its own weights
    return granite_hybrid_tiny(**kw)


def _published(cfg):
    return {k: getattr(cfg, k) for k in cfg._HF_KEYS}


def _reference_logits(m, ids):
    return np.asarray(reference.logits(
        reference.params_of(m), np.asarray(ids, np.int32),
        _published(m.config)))


def _prefill(m, ids, bucket):
    p = len(ids)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :p] = ids
    with dg.no_grad():
        out = m.prefill_step(_t(padded), _t([p], np.int32),
                             _t([p - 1], np.int32))
    return [np.asarray(o.numpy()) for o in out]


# -- ops --------------------------------------------------------------------
@pytest.mark.parametrize("op", NEW_OPS)
def test_new_ops_are_registered_forward_only(op):
    info = get_op_info(op)
    assert info is not None and info.grad is None
    assert get_op_info(op + "_grad") is None
    assert "Forward only" in info.kernel.__doc__


def test_rms_norms_against_their_formulas():
    rng = np.random.default_rng(0)
    x, g, w = rng.normal(size=(3, 8)), rng.normal(size=(3, 8)), \
        rng.normal(size=8)
    x, g, w = (a.astype(np.float32) for a in (x, g, w))
    ctx = OpContext()
    got = run_kernel("rms_norm", {"X": x, "Scale": w}, {"epsilon": 1e-5},
                     ctx)["Out"]
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(got, want, rtol=1e-5)
    got = run_kernel("gated_rms_norm", {"X": x, "Gate": g, "Scale": w},
                     {"epsilon": 1e-5}, ctx)["Out"]
    y = x * (g / (1 + np.exp(-g)))          # the gate comes BEFORE the norm
    want = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_causal_conv_carries_its_tail_by_length():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 6, 5)).astype(np.float32)
    w = rng.normal(size=(5, 4)).astype(np.float32)
    b = rng.normal(size=5).astype(np.float32)
    ctx = OpContext()
    whole = run_kernel("causal_conv1d", {"X": x, "Weight": w, "Bias": b},
                       {"activation": ""}, ctx)
    padded = np.concatenate([np.zeros((2, 3, 5), np.float32), x], 1)
    want = sum(padded[:, j:j + 6] * w[:, j] for j in range(4)) + b
    np.testing.assert_allclose(whole["Out"], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(whole["NewTail"], x[:, 3:])
    # in two pieces through the tail, the second row stopping early
    first = run_kernel("causal_conv1d",
                       {"X": x[:, :4], "Weight": w, "Bias": b,
                        "Lengths": np.array([4, 2], np.int32)},
                       {"activation": ""}, ctx)
    np.testing.assert_array_equal(first["NewTail"][0], x[0, 1:4])
    np.testing.assert_array_equal(first["NewTail"][1][1:], x[1, :2])
    assert not first["NewTail"][1][0].any()       # still the zero start
    rest = run_kernel("causal_conv1d",
                      {"X": x[:, 4:], "Weight": w, "Bias": b,
                       "Tail": first["NewTail"],
                       "Lengths": np.array([2, 0], np.int32)},
                      {"activation": ""}, ctx)
    np.testing.assert_allclose(rest["Out"][0], want[0, 4:], rtol=1e-5,
                               atol=1e-6)
    # a row of length 0 keeps its tail bit for bit
    np.testing.assert_array_equal(rest["NewTail"][1], first["NewTail"][1])
    # slab_index: the tail is entry 1 of a pool's whole array, replaced
    slab = np.stack([np.full_like(first["NewTail"], 7.0),
                     np.asarray(first["NewTail"])])
    in_slab = run_kernel("causal_conv1d",
                         {"X": x[:, 4:], "Weight": w, "Bias": b,
                          "Tail": slab,
                          "Lengths": np.array([2, 0], np.int32)},
                         {"activation": "", "slab_index": 1}, ctx)
    np.testing.assert_array_equal(in_slab["Out"], rest["Out"])
    np.testing.assert_array_equal(in_slab["NewTail"][1], rest["NewTail"])
    np.testing.assert_array_equal(in_slab["NewTail"][0], slab[0])


def _scan_inputs(rng, b=2, t=19, h=4, p=8, n=6):
    f = np.float32
    return {"X": rng.normal(size=(b, t, h, p)).astype(f),
            "Dt": rng.normal(size=(b, t, h)).astype(f),
            "A": -rng.uniform(1, 16, h).astype(f),
            "B": rng.normal(size=(b, t, 1, n)).astype(f),
            "C": rng.normal(size=(b, t, 1, n)).astype(f),
            "D": np.ones(h, f),
            "DtBias": rng.normal(size=h).astype(f) - 3.0}


@pytest.mark.parametrize("chunk", [4, 8, 0])
def test_chunk_scan_equals_the_token_by_token_update(chunk):
    """Chunks of 4 / 8 / the whole sequence against each other through
    the one-token recurrence, which is the definition."""
    ins = _scan_inputs(np.random.default_rng(2))
    ctx = OpContext()
    got = run_kernel("mamba2_chunk_scan", ins, {"chunk_size": chunk}, ctx)
    state = np.zeros((2, 4, 8, 6), np.float32)
    ys = []
    for t in range(19):
        step = run_kernel("mamba2_state_update", {
            **{k: ins[k][:, t] for k in ("X", "Dt", "B", "C")},
            **{k: ins[k] for k in ("A", "D", "DtBias")}, "State": state},
            {}, ctx)
        state = step["NewState"]
        ys.append(np.asarray(step["Y"]))
    np.testing.assert_allclose(got["Y"], np.stack(ys, 1), atol=2e-5)
    _assert_state(got["FinalState"], state)
    # slab_index: the state is entry 0 of a pool's whole array, replaced
    slab = np.stack([np.asarray(state), np.full_like(state, 3.0)])
    one = {**{k: ins[k][:, 0] for k in ("X", "Dt", "B", "C")},
           **{k: ins[k] for k in ("A", "D", "DtBias")}}
    plain = run_kernel("mamba2_state_update", {**one, "State": state}, {},
                       ctx)
    in_slab = run_kernel("mamba2_state_update", {**one, "State": slab},
                         {"slab_index": 0}, ctx)
    np.testing.assert_array_equal(in_slab["Y"], plain["Y"])
    np.testing.assert_array_equal(in_slab["NewState"][0], plain["NewState"])
    np.testing.assert_array_equal(in_slab["NewState"][1], slab[1])


def test_scan_stops_at_each_rows_length_and_resumes_from_a_state():
    ins = _scan_inputs(np.random.default_rng(3))
    ctx = OpContext()
    lengths = np.array([19, 11], np.int32)
    masked = run_kernel("mamba2_chunk_scan", {**ins, "Lengths": lengths},
                        {"chunk_size": 8}, ctx)
    short = run_kernel(
        "mamba2_chunk_scan",
        {k: (v[1:, :11] if v.ndim > 1 else v) for k, v in ins.items()},
        {"chunk_size": 8}, ctx)
    _assert_state(masked["FinalState"][1], short["FinalState"][0])
    np.testing.assert_allclose(masked["Y"][1, :11], short["Y"][0],
                               atol=2e-5)
    # the other half of row 1, from the state the first half left
    tail = run_kernel(
        "mamba2_chunk_scan",
        {**{k: (v[1:, 11:] if v.ndim > 1 else v) for k, v in ins.items()},
         "InitialState": short["FinalState"]}, {"chunk_size": 8}, ctx)
    whole = run_kernel("mamba2_chunk_scan", ins, {"chunk_size": 8}, ctx)
    _assert_state(tail["FinalState"][0], whole["FinalState"][1])


def test_gqa_attention_shares_kv_heads_and_reads_a_masked_cache():
    rng = np.random.default_rng(4)
    f = np.float32
    q = rng.normal(size=(2, 4, 3, 8)).astype(f)
    k, v = (rng.normal(size=(2, 2, 3, 8)).astype(f) for _ in range(2))
    kc, vc = (rng.normal(size=(2, 2, 5, 8)).astype(f) for _ in range(2))
    seen = np.array([5, 2], np.int32)
    got = run_kernel("gqa_attention", {
        "Q": q, "K": k, "V": v, "KCache": kc, "VCache": vc,
        "CacheLengths": seen}, {"scale": 0.3}, OpContext())["Out"]
    for b in range(2):
        keys = np.concatenate([kc[b, :, :seen[b]], k[b]], 1)
        vals = np.concatenate([vc[b, :, :seen[b]], v[b]], 1)
        for h in range(4):
            s = q[b, h] @ keys[h // 2].T * 0.3      # [3, seen + 3]
            for t in range(3):
                s[t, seen[b] + t + 1:] = -np.inf    # causal among the new
            p = np.exp(s - s.max(-1, keepdims=True))
            want = p / p.sum(-1, keepdims=True) @ vals[h // 2]
            np.testing.assert_allclose(got[b, h], want, rtol=2e-5,
                                       atol=2e-6)


# -- the model against the reference ----------------------------------------
def test_parameter_count_at_published_widths_is_the_issues_sum():
    cfg = GraniteHybridConfig()                 # shapes only: no allocation
    assert cfg.layers_of("attention") == [5, 15, 25, 35]
    assert cfg.param_count() == 3_191_396_096           # 3,191 M
    assert round(cfg.param_count() * 2 / 1e9, 2) == 6.38    # GB, bfloat16
    kv, state = cfg.cache_spec()
    assert (kv["layers"], kv["kv_heads"], kv["head_dim"]) == (4, 8, 64)
    assert state["layers"] == 36
    from paddle_tpu.serving.kv_pool import state_slot_bytes
    assert kv["retain"] == "all" and kv["dtype"] == "bfloat16"
    assert state_slot_bytes([state]) == 75_497_472 + 940_032
    # with its KV at the context the cell serves: 4 x 8 x 1,024 x 64, K + V
    assert state_slot_bytes(cfg.cache_spec(), 1024) \
        == 75_497_472 + 940_032 + 2 * 4 * 8 * 1024 * 64 * 2


def test_built_model_has_exactly_the_shapes_the_config_states():
    with dg.guard():
        m = _model(1)
        built = {n: tuple(p.shape) for n, p in m.named_parameters()}
    assert built == {n: tuple(s) for n, s in
                     m.config.param_shapes().items()}
    assert all(p.stop_gradient for p in m.parameters())


def test_full_forward_matches_the_reference():
    with dg.guard():
        m = _model(2)
        ids = np.random.default_rng(0).integers(0, 127, 21)
        with dg.no_grad():
            got = m(_t(ids[None], np.int32)).numpy()[0]
    want = _reference_logits(m, ids)
    _assert_logits(got, want)


def test_prefill_then_decode_through_the_cache_matches_the_full_pass():
    """Prefill of p tokens (padded to a bucket of 16) into row 1 of 3, then
    n decode steps over the KV and state arrays given whole, against the
    reference's full forward of p + n: every step at the engine's bound on
    the columns (the power of two over the row, floor 16: 13..15 columns
    read to 16, 16 itself too, 17.. to 32, so the steps cross a bucket)
    and, beside it, with no bound (what a row holds, block by block); row
    2 is idle and holds a made-up state and made-up columns, which every
    step must hand back bit for bit but for the one column it names."""
    from paddle_tpu.core.compile_cache import next_pow2
    p, n, rows, cache_len = 13, 8, 3, 32
    with dg.guard():
        m = _model(3)
        c = m.config
        assert not c.kv_ring and c.cache_spec()[0] == {
            "kind": "kv", "layers": 1, "kv_heads": 2, "head_dim": 16,
            "dtype": "float32", "retain": "all"}
        ids = np.random.default_rng(1).integers(0, 127, p + n)
        want = _reference_logits(m, ids)
        logits, k, v, ssm, conv = _prefill(m, ids[:p], 16)
        _assert_logits(logits[0], want[p - 1], want.std())
        kc = np.zeros((1, rows, 2, cache_len, 16), np.float32)
        vc = np.zeros_like(kc)
        kc[:, 1, :, :16], vc[:, 1, :, :16] = k[:, 0], v[:, 0]   # pads too
        kc[:, 2], vc[:, 2] = 7.0, -7.0
        ssm_s = np.zeros((3, rows) + ssm.shape[2:], np.float32)
        conv_s = np.zeros((3, rows) + conv.shape[2:], np.float32)
        ssm_s[:, 1], conv_s[:, 1] = ssm[:, 0], conv[:, 0]
        ssm_s[:, 2], conv_s[:, 2] = 0.5, 0.25
        bounds = []
        for t in range(n):
            step_ids = np.zeros((rows, 1), np.int32)
            step_ids[1, 0] = ids[p + t]
            args = [_t(step_ids), _t([0, p + t, 5], np.int32),
                    _t([0, 1, 0], np.int32), _t(kc), _t(vc), _t(ssm_s),
                    _t(conv_s)]
            bounds.append(next_pow2(p + t, 16))
            with dg.no_grad():
                out = m.decode_step(*args, columns=bounds[-1])
                free = m.decode_step(*args)
            logits, kn, vn, ssm_n, conv_n = (np.asarray(o.numpy())
                                             for o in out)
            _assert_logits(logits[1], want[p + t], want.std())
            _assert_logits(free[0].numpy()[1], want[p + t], want.std())
            for a, b in zip(out[1:3], free[1:3]):   # K, V: the same bits
                np.testing.assert_array_equal(a.numpy(), b.numpy())
            for a, b in zip(out[3:], free[3:]):     # behind the attention
                _assert_state(a.numpy(), b.numpy())
            for new, old in ((ssm_n, ssm_s), (conv_n, conv_s)):
                np.testing.assert_array_equal(new[:, 2], old[:, 2])
                np.testing.assert_array_equal(new[:, 0], old[:, 0])
            # the arrays come back whole: the live row's new column at its
            # own position, an idle row's at the position it names (5, a
            # slot without a sequence 0), and nothing else touched
            for new, old in ((kn, kc), (vn, vc)):
                assert new.shape == old.shape
                changed = set(map(tuple, np.argwhere(
                    (new != old).any(axis=(0, 2, 4)))))
                named = {(0, 0), (1, p + t), (2, 5)}
                # (an idle row writes the same column again every step)
                assert changed == (named if t == 0 else {(1, p + t)})
            kc, vc, ssm_s, conv_s = kn, vn, ssm_n, conv_n
        assert bounds == [16] * 4 + [32] * 4
    assert c.layers_of("mamba") == [0, 1, 3]


def test_padded_prompt_leaves_the_unpadded_prompts_state_and_logits():
    with dg.guard():
        m = _model(4)
        ids = np.random.default_rng(2).integers(0, 127, 13)
        exact = _prefill(m, ids, 13)
        for bucket in (16, 32):
            padded = _prefill(m, ids, bucket)
            _assert_logits(padded[0], exact[0])
            for got, want in zip(padded[3:], exact[3:]):    # ssm, conv
                _assert_state(got, want)
            for got, want in zip(padded[1:3], exact[1:3]):  # K, V columns
                _assert_state(got[:, :, :, :13], want)


def test_chunk_size_does_not_change_the_model():
    ids = np.random.default_rng(3).integers(0, 127, 21)
    got = []
    for chunk in (4, 8, 64):
        with dg.guard():
            m = _model(5, mamba_chunk_size=chunk)
            got.append(_prefill(m, ids, 32))
    for other in got[1:]:
        _assert_logits(other[0], got[0][0])
        _assert_state(other[3], got[0][3])


# -- the manager: pages and state slots --------------------------------------
def test_state_slots_reserve_install_release():
    reset_serving_stats()
    spec = GraniteHybridConfig(
        vocab_size=8, hidden_size=8, layer_types=["mamba"],
        num_attention_heads=1, num_key_value_heads=1,
        shared_intermediate_size=8, mamba_n_heads=2, mamba_d_head=8,
        mamba_d_state=4).cache_spec()
    slots = StateSlots(spec, 2)
    assert slots.names == ["ssm", "conv"] and slots.used == 0
    a, b = slots.reserve(), slots.reserve()
    assert {a, b} == {0, 1} and not slots.can_reserve()
    with pytest.raises(RuntimeError):
        slots.reserve()
    new = {n: np.full((v.shape[0], 1) + v.shape[2:], 3.0, np.float32)
           for n, v in slots.arrays.items()}
    slots.install(b, **new)
    assert (slots.row(b)["ssm"] == 3).all() and not slots.row(a)["ssm"].any()
    slots.release(b)
    with pytest.raises(ValueError):
        slots.release(b)
    assert slots.reserve() == b
    slots.install(b, **new)                     # a reused slot: overwritten
    stats = serving_stats()
    assert stats["serving.gen.state_resets"] == 1
    assert stats["serving.state.slots_total"] == 2
    assert stats["serving.state.slots_used"] == 2
    assert stats["serving.state.bytes"] == slots.nbytes == 2 * slots.slot_bytes


def test_state_slots_hold_the_kv_arrays_before_the_state_on_the_device():
    """A description of one attention layer (1 kv head x 4) beside one
    Mamba layer, 3 slots, 16 columns: the KV arrays come first among the
    slots' arrays, in the step contract's order; a prefill's prompt columns
    go in with its state, into its slot alone and as far as its bucket
    reaches; a slot's bytes count the KV; nothing of it is a second copy."""
    from paddle_tpu.serving.kv_pool import (device_kv_arrays, state_groups,
                                            state_slot_bytes)
    reset_serving_stats()
    spec = GraniteHybridConfig(
        vocab_size=8, hidden_size=8, layer_types=["mamba", "attention"],
        num_attention_heads=2, num_key_value_heads=1,
        shared_intermediate_size=8, mamba_n_heads=2, mamba_d_head=8,
        mamba_d_state=4, dtype="float32").cache_spec()
    kv = device_kv_arrays(spec, 12)         # the power of two over it
    assert [(a["name"], a["layers"], a["shape"], a["window"]) for a in kv] \
        == [("k0", 1, [1, 16, 4], 0), ("v0", 1, [1, 16, 4], 0)]
    slots = StateSlots(state_groups(spec), 3, device_kv=kv)
    assert slots.names == ["k0", "v0", "ssm", "conv"]
    assert slots.arrays["k0"].shape == (1, 3, 1, 16, 4)
    state = {n: np.ones((v.shape[0], 1) + v.shape[2:], np.float32)
             for n, v in slots.arrays.items() if n in ("ssm", "conv")}
    prompt = np.arange(1, 1 * 1 * 1 * 5 * 4 + 1, dtype=np.float32).reshape(
        1, 1, 1, 5, 4)
    with pytest.raises(ValueError, match="install needs"):
        slots.install(1, **state)               # the prompt's KV is missing
    slots.install(1, **state, k0=prompt, v0=-prompt)
    k, v = (np.asarray(slots.arrays[n]) for n in ("k0", "v0"))
    np.testing.assert_array_equal(k[:, 1, :, :5], prompt[:, 0])
    np.testing.assert_array_equal(v[:, 1, :, :5], -prompt[:, 0])
    assert not k[:, 1, :, 5:].any() and not k[:, [0, 2]].any()
    # a shorter prompt into the same slot: its bucket's columns are
    # overwritten, the last owner's later columns stay (no row reads past
    # its own length, and each of its columns is written before it is read)
    slots.install(1, **state, k0=9 * prompt[:, :, :, :2],
                  v0=prompt[:, :, :, :2])
    k = np.asarray(slots.arrays["k0"])
    np.testing.assert_array_equal(k[:, 1, :, :2], 9 * prompt[:, 0, :, :2])
    np.testing.assert_array_equal(k[:, 1, :, 2:5], prompt[:, 0, :, 2:5])
    assert slots.kv_slot_bytes == 2 * 1 * 16 * 4 * 4
    assert slots.slot_bytes == sum(
        a.nbytes for a in slots.arrays.values()) // 3 \
        == state_slot_bytes(spec, 12)
    stats = serving_stats()
    assert stats["serving.kv.device_bytes"] == 3 * slots.kv_slot_bytes
    assert stats["serving.gen.state_resets"] == 1


def test_page_budget_sizes_from_the_cache_description():
    with dg.guard():
        m = _model(6)
        plan = static.page_budget(m, page_tokens=4, max_context=64,
                                  hbm_bytes=8 << 20, max_slots_cap=3)
        assert (plan["num_layers"], plan["num_heads"], plan["head_dim"]) \
            == (1, 2, 16)                     # the ONE attention layer, GQA
        assert plan["page_bytes"] == 2 * 1 * 2 * 16 * 4 * 4
        # a slot holds its KV (every column of the context served) beside
        # its state, ONCE: the decode step writes both where they lie
        kv_slot = 2 * 1 * 2 * 64 * 16 * 4
        assert plan["kv_slot_bytes"] == kv_slot
        assert plan["state_slot_bytes"] == 3 * (8 * 16 * 16 + 3 * 160) * 4
        assert plan["state_bytes"] == 3 * plan["state_slot_bytes"]
        assert plan["kv_bytes"] == 3 * kv_slot
        assert plan["workspace_bytes"] == 3 * 128 * 4      # a logits row
        # the pages only account: every slot's worst case, nothing carved
        assert plan["max_slots"] == 3
        assert plan["pages"] == 3 * (64 // 4 + 1)
        assert "kv_on_device" not in plan
        pool = PagedKVPool.from_plan(plan)
        assert pool.device_only and pool.k is None and pool.v is None
        assert pool.state.names == ["k0", "v0", "ssm", "conv"]
        assert pool.state.slots == 3 and budget_drift(pool, m) == []
        pool.state = StateSlots(plan["cache"][1:], 2)
        drift = budget_drift(pool, m)
        assert any("state slots" in d for d in drift)
        assert any("state_slot_bytes" in d for d in drift)  # its KV is gone
        for bad in (dict(tp_degree=2), dict(draft_layers=1),
                    dict(kv_dtype="int8")):
            with pytest.raises(NotImplementedError):
                static.page_budget(m, hbm_bytes=8 << 20, **bad)
        # a recurrent state beside KV in host pages is no route's: the
        # description must say what its kv groups retain
        no_retain = [{k: v for k, v in plan["cache"][0].items()
                      if k != "retain"}, plan["cache"][1]]
        with pytest.raises(NotImplementedError, match="retain"):
            static.page_budget(
                config=dict(plan["config"], cache=no_retain),
                hbm_bytes=8 << 20, weight_bytes=plan["weight_bytes"])
        # a GPT states one kv group of layers x heads: as before
        g = static.page_budget(GPTModel(GPTConfig(
            vocab_size=50, hidden_size=16, num_layers=2, num_heads=2,
            max_position=32)), page_tokens=4, hbm_bytes=4 << 20)
        assert (g["num_layers"], g["num_heads"], g["head_dim"]) == (2, 2, 8)
        assert g["state_slot_bytes"] == 0 and g["cache"] == [
            {"kind": "kv", "layers": 2, "kv_heads": 2, "head_dim": 8}]
        assert not PagedKVPool.from_plan(g).device_only


def test_page_budget_sizes_granite_h_micro_by_slots_at_the_published_widths():
    """`granite-4.0-h-micro` on a v5e's 16.9 GB, shapes only: 16 slots as
    before, each of its 76.4 MB of state and 8.4 MB of bf16 KV (4 layers x
    8 heads x 1,024 columns x 64), 134 MB of KV in all; no host pool is
    carved and no gather view rented (PR 28: 29,281 float32 pages and a
    1.07 GB view), the pages account for 16 worst cases."""
    cfg = GraniteHybridConfig(dtype="bfloat16")
    sizing = dict(config=cfg, page_tokens=16, max_context=1024,
                  max_slots_cap=16, weight_bytes=cfg.param_count() * 2)
    plan = static.page_budget(hbm_bytes=16_909_336_064, **sizing)
    kv_slot = 2 * 4 * 8 * 1024 * 64 * 2
    assert (plan["max_slots"], plan["state_slot_bytes"],
            plan["kv_slot_bytes"]) == (16, 76_437_504, kv_slot)
    assert plan["state_bytes"] == 16 * 76_437_504 == 1_223_000_064
    slot = 76_437_504 + kv_slot
    assert plan["kv_bytes"] == 16 * kv_slot == 134_217_728
    assert plan["workspace_bytes"] == 16 * 100_352 * 4
    assert plan["pages"] == 16 * (1024 // 16 + 1)
    assert plan["page_bytes"] == 2 * 4 * 8 * 64 * 2 * 16
    # the slots may take half of what the weights leave (the other half
    # is a prefill's workspace): one slot and its logits row are enough
    # to start, a little less is not
    ws = 100_352 * 4
    least = int((plan["weight_bytes"] + 2 * (slot + ws))
                / (1.0 - plan["headroom"])) + 2
    tight = static.page_budget(hbm_bytes=least, **sizing)
    assert tight["max_slots"] == 1 and tight["pages"] == 1024 // 16 + 1
    with pytest.raises(ValueError, match="not enough for one slot"):
        static.page_budget(hbm_bytes=least - slot // 2, **sizing)


def test_pool_reserves_and_releases_the_state_slot_with_the_pages():
    with dg.guard():
        m = _model(7)
        pool = PagedKVPool.from_plan(static.page_budget(
            m, page_tokens=4, max_context=64, hbm_bytes=8 << 20,
            max_slots_cap=2))
    t1, t2 = pool.reserve(3), pool.reserve(3)
    assert {t1.state_slot, t2.state_slot} == {0, 1}
    assert not pool.can_reserve(1)          # pages are there, slots are not
    with pytest.raises(AssertionError, match="state leak"):
        pool.assert_drained()
    pool.close_sequence(t1)
    assert t1.state_slot is None and pool.can_reserve(1)
    pool.close_sequence(t2)
    pool.assert_drained()
    assert pool.stats()["state_slots_used"] == 0


# -- the decode step owns the state arrays ------------------------------------
# the tiny model's state tiles ([16, 16]) take the plain jnp update; with
# [8, 128] tiles `_slab_update` (Pallas, interpreted on the CPU) takes them
SLAB_TILES = dict(mamba_n_heads=16, mamba_d_head=8, mamba_d_state=128)


def _pool_and_steps(m, slots=3):
    from paddle_tpu.serving.step_program import StepPrograms
    plan = static.page_budget(m, page_tokens=4, max_context=64,
                              hbm_bytes=16 << 20, max_slots_cap=slots)
    return PagedKVPool.from_plan(plan).state, StepPrograms(m)


def _decode_args(ids, lengths, active):
    """`StepPrograms.decode`'s arguments before the cache arrays: `ids` [S]
    int32 as a step returns them (or such a result itself, on the
    device)."""
    ids = ids if isinstance(ids, dg.Tensor) else _t(ids, np.int32)
    return [ids, _t(lengths, np.int32), _t(active, np.int32)]


def _install(state, slot, made):
    """A prefill's `*kv, *state` results into `slot`."""
    state.install(slot, **{n: t._value for n, t in zip(state.names, made)})


def test_decode_program_aliases_every_cache_feed_to_its_result():
    """Read from the lowered module, not from a timing: the four cache
    feeds of the step contract (`DECODE_CACHE_AT`..: K, V, ssm, conv) are
    donated and each is aliased to a result; ids, lengths and `active` are
    not, and the prefill program donates nothing.  A bound on the columns
    is a static of the trace: a program a bound, the same feeds."""
    import re
    import jax.numpy as jnp
    from paddle_tpu.serving import step_program
    with dg.guard():
        m = _model(11)
        state, steps = _pool_and_steps(m)
        args = _decode_args(np.zeros(3), [0, 0, 0], [0, 0, 0]) \
            + [dg.to_variable(a) for a in state.arrays.values()]
        with dg.no_grad():
            cp = steps.decode_program(32).concrete_program(*args)
            pre = steps._prefill.concrete_program(
                _t(np.zeros((1, 16)), np.int32), _t([3], np.int32),
                _t([2], np.int32))
            assert steps.programs == 2
            steps.decode_program(16).concrete_program(*args)
            steps.decode_program(None).concrete_program(*args)
        assert steps.programs == 4 and len(steps._decode_traces()) == 3
    assert step_program.DECODE_CACHE_AT == 3 and cp.donated == (3, 4, 5, 6)
    assert pre.donated == ()
    kept, donated = cp.split_feeds([a._value for a in args])
    assert (len(kept), len(donated)) == (3, 4)
    text = cp.composed().lower(
        jnp.uint32(0), tuple(t._value for t in cp.params.values()), kept,
        True, donated).as_text()
    aliased = re.findall(r"%arg\d+: tensor<([0-9x]+)x[a-z0-9]+> "
                         r"\{[^}]*tf.aliasing_output = (\d+)", text)
    shapes = ["x".join(map(str, a.shape)) for a in state.arrays.values()]
    assert [a[0] for a in aliased] == shapes    # k0, v0, ssm, conv: no other
    # (logits, next_ids, K, V, ssm, conv)
    assert [int(a[1]) for a in aliased] == [2, 3, 4, 5]
    bounds = sorted(op.attrs["columns"] for t in steps._decode_traces()
                    for op in t.program.global_block().ops
                    if op.type == "cached_decode_attention")
    assert bounds == [0, 16, 32]


@pytest.mark.parametrize("tiles", [{}, SLAB_TILES], ids=["jnp", "slab"])
def test_decode_consumes_the_cache_it_is_given_and_rebind_takes_its_result(
        tiles):
    """20 decode steps through `StepPrograms.decode` and `StateSlots`, as
    the engine makes them, the bound doubling on the way (16 -> 32: 13..32
    columns): after every step the arrays that went in — the KV among them
    — are dead and the rebound ones carry the sequence on (logits match
    the reference's full forward at every step); an idle row's made-up
    state and tail come back bit for bit, and of its made-up KV all but
    the one column the step names; both counters count."""
    from paddle_tpu.core.compile_cache import next_pow2
    from paddle_tpu.ops.kernels import ssm as ssm_kernels
    p, n = 13, 20
    reset_serving_stats()
    with dg.guard():
        m = _model(12, **tiles)
        c = m.config
        state, steps = _pool_and_steps(m)
        assert ssm_kernels._slab_update_fits(
            state.arrays["ssm"], c.mamba_n_heads, c.mamba_d_head,
            c.mamba_d_state) == bool(tiles)
        ids = np.random.default_rng(6).integers(0, 127, p + n)
        want = _reference_logits(m, ids)
        padded = np.zeros((1, 16), np.int32)
        padded[0, :p] = ids[:p]
        logits, _, *made = steps.prefill(
            _t(padded), _t([p], np.int32), _t([p - 1], np.int32))
        _assert_logits(logits.numpy()[0], want[p - 1], want.std())
        _install(state, 1, made)
        made_up = {name: np.full((a.shape[0], 1) + a.shape[2:], fill,
                                 np.float32)
                   for (name, a), fill in zip(state.arrays.items(),
                                              (3.0, -3.0, 0.5, 0.25))}
        state.install(2, **made_up)
        for t in range(n):
            step_ids = np.zeros(3, np.int32)
            step_ids[1] = ids[p + t]
            old = list(state.arrays.values())
            logits, _, *new = steps.decode(
                *_decode_args(step_ids, [0, p + t, 40], [0, 1, 0]), *old,
                columns=next_pow2(p + t, 16))
            assert all(a.is_deleted() for a in old)
            state.rebind(**{name: tensor._value
                            for name, tensor in zip(state.names, new)})
            assert not any(a.is_deleted() for a in state.arrays.values())
            _assert_logits(logits.numpy()[1], want[p + t], want.std())
        assert steps.programs == 1 + 2          # bounds 16 and 32
        for name, a in state.row(2).items():
            if name in ("ssm", "conv"):
                np.testing.assert_array_equal(a, made_up[name][:, 0])
            else:       # a column of its own named, the rest as it was
                same = (a == made_up[name][:, 0]).all(axis=(0, 1, 3))
                assert list(np.flatnonzero(~same)) == [40]
        assert not state.row(0)["ssm"].any()
        assert not state.row(0)["k0"][:, :, 1:].any()
    stats = serving_stats()
    assert stats["serving.gen.state_in_place"] == n
    assert stats.get("serving.gen.state_copied", 0) == 0
    # a step whose arrays were NOT given away (a backend that ignored the
    # donation looks like this) is counted as a copy
    state.rebind(**{name: a + 0 for name, a in state.arrays.items()})
    assert serving_stats()["serving.gen.state_copied"] == 1
    with pytest.raises(ValueError, match="rebind needs"):
        state.rebind(ssm=state.arrays["ssm"])


def _tied_halves(m):
    """The vocabulary's upper half made a copy of its lower half (the head
    is tied to the table): every logit then occurs twice, so EVERY argmax
    is a tie between v and v + V/2, and the first index must win.  Prompts
    and pending tokens come from the lower half alone."""
    table = np.array(m.embed.numpy())
    half = table.shape[0] // 2
    table[half:] = table[:half]
    m.embed.set_value(table)
    return half


@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_step_programs_pick_the_first_argmax_of_the_logits_they_return(
        phase, tied):
    """`StepPrograms` returns, after the logits, their argmax as int32:
    what `np.argmax` gives on the same float32 values, the first index
    where the maximum occurs twice.  Prefill: five prompts; decode: 20
    steps over three rows of which one is idle, each step taking the ids
    in the form the last one returned them - the first an upload from the
    host, every later one that result itself, on the device: one trace
    and one executable serve both."""
    with dg.guard():
        m = _model(14)
        vocab = _tied_halves(m) if tied else m.config.vocab_size - 1
        state, steps = _pool_and_steps(m)
        rng = np.random.default_rng(15)
        picks, rows = [], []
        for slot, p in enumerate((13, 9, 16, 3, 11)):
            padded = np.zeros((1, 16), np.int32)
            padded[0, :p] = rng.integers(0, vocab, p)
            logits, nxt, *made = steps.prefill(
                _t(padded), _t([p], np.int32), _t([p - 1], np.int32))
            assert nxt.numpy().dtype == np.int32 and nxt.shape == [1]
            picks.append(nxt.numpy())
            rows.append(logits.numpy())
            if slot < 2:
                _install(state, slot, made)
        lengths = np.asarray([13, 9, 0], np.int32)
        pending = np.concatenate(picks[:2] + [[0]]).astype(np.int32)
        for _ in range(20 * (phase == "decode")):
            logits, nxt, *new = steps.decode(
                *_decode_args(pending, lengths, [1, 1, 0]),
                *state.arrays.values(), columns=64)
            state.rebind(**{name: tensor._value
                            for name, tensor in zip(state.names, new)})
            assert nxt.numpy().dtype == np.int32 and nxt.shape == [3]
            picks.append(nxt.numpy()[:2])       # the idle row's is ignored
            rows.append(logits.numpy()[:2])
            pending = nxt
            lengths[:2] += 1
        if phase == "decode":
            (traced,) = steps._decode_traces()
            assert traced.composed()._cache_size() == 1
    picks, rows = np.concatenate(picks), np.concatenate(rows)
    assert len(picks) == (45 if phase == "decode" else 5)
    np.testing.assert_array_equal(picks, rows.argmax(-1))
    twice = (rows == rows.max(-1, keepdims=True)).sum(-1)
    if tied:
        np.testing.assert_array_equal(rows[:, :vocab], rows[:, vocab:])
        assert (twice == 2).all() and (picks < vocab).all()
    else:
        assert (twice == 1).all()


@pytest.mark.parametrize("groups, h, p, n", [
    (1, 16, 8, 128),        # 16 heads share a [128, 128] block
    (2, 16, 8, 128),        # ... and B, C by halves
    (2, 4, 64, 256),        # two heads a block, two blocks of lanes
    (1, 2, 256, 128)])      # a head spans two blocks
def test_slab_update_is_the_plain_update_in_one_pass(groups, h, p, n):
    """`_slab_update` (the Pallas kernel) against the jnp form of the same
    op on the same inputs: entry 1 of a [3, B, H, P, N] array, B and C
    shared by H / groups heads; an idle row and the other layers' entries
    come back bit for bit."""
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import ssm as ssm_kernels
    rng = np.random.default_rng(groups)
    b = 3
    f32 = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa
    ins = dict(X=f32(b, h, p), Dt=f32(b, h), A=-rng.uniform(1, 4, h).astype(
        np.float32), B=f32(b, groups, n), C=f32(b, groups, n),
        D=np.ones(h, np.float32), DtBias=f32(h),
        Lengths=np.asarray([1, 0, 1], np.int32))
    slab = f32(3, b, h, p, n)
    assert ssm_kernels._slab_update_fits(jnp.asarray(slab), h, p, n)
    assert not ssm_kernels._slab_update_fits(jnp.asarray(slab), h, p, 16)
    assert not ssm_kernels._slab_update_fits(jnp.asarray(slab), 1, 8, n)
    assert not ssm_kernels._slab_update_fits(
        jnp.asarray(slab, jnp.bfloat16), h, p, n)
    run = lambda state, attrs: run_kernel(  # noqa: E731
        "mamba2_state_update", dict(ins, State=state), attrs,
        OpContext(seed=0, is_test=True))
    got, want = run(slab, {"slab_index": 1}), run(slab[1], {})
    new = np.asarray(got["NewState"])
    _assert_state(new[1], np.asarray(want["NewState"]))
    _assert_state(np.asarray(got["Y"]), np.asarray(want["Y"]))
    np.testing.assert_array_equal(new[[0, 2]], slab[[0, 2]])
    np.testing.assert_array_equal(new[1, 1], slab[1, 1])    # the idle row


@pytest.mark.parametrize("fails_at", [1, 2], ids=["alone", "one_in_flight"])
def test_a_step_that_raises_leaves_zeroed_state_and_a_live_engine(fails_at):
    """A decode step that fails AFTER it has given its state arrays away -
    the first, or the second, dispatched while the first is still unread:
    every sequence is failed, the step in flight is dropped, the pool
    gets fresh zeroed arrays in place of the dead ones, and the next
    request is served as ever."""
    with dg.guard():
        m = _model(13)
        plan = static.page_budget(m, page_tokens=4, max_context=128,
                                  hbm_bytes=8 << 20, max_slots_cap=2)
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        state, real = eng.kv_pool.state, eng._steps.decode
        calls, in_flight = [], []

        def failing(*args, **bound):
            calls.append(real(*args, **bound))
            if len(calls) == fails_at:
                in_flight.append(eng._in_flight)
                raise RuntimeError("the device fell over")
            return calls[-1]

        prompt = np.random.default_rng(7).integers(0, 126, 9)
        eng._steps.decode = failing
        with pytest.raises(RuntimeError, match="fell over"):
            eng.submit(prompt, max_length=6).result(timeout=600)
        eng._steps.decode = real
        assert len(calls) == fails_at and eng._in_flight is None
        assert (in_flight[0] is not None) == (fails_at == 2)
        for a in state.arrays.values():     # the step had donated these,
            assert not a.is_deleted() and not np.asarray(a).any()   # KV too
        out = eng.submit(prompt, max_length=6).result(timeout=600)
        assert list(out) == _greedy(m, prompt, 6)
        eng.stop()
        eng.kv_pool.assert_drained()


# -- through the engine -------------------------------------------------------
def _greedy(m, prompt, n, width=64, pick=lambda row: int(row.argmax())):
    """One sequence, no cache: the full forward again for every token
    (`pick`: logits row -> token, greedy unless given).  The sequence sits
    in a buffer of one width (the model is causal: what follows a position
    cannot reach it), so one shape is compiled."""
    ids = list(prompt)
    for _ in range(n):
        buf = np.zeros((1, width), np.int32)
        buf[0, :len(ids)] = ids
        with dg.no_grad():
            row = m(_t(buf)).numpy()[0, len(ids) - 1]
        ids.append(pick(row))
        if ids[-1] == m.config.eos_id:
            break
    return ids


def test_engine_serves_the_hybrid_model_token_equal_to_one_sequence():
    """Two requests of different lengths admitted at different steps, then
    more requests than slots so that slots are reused after retirement:
    greedy tokens equal one-sequence decoding with no cache at all."""
    reset_serving_stats()
    with dg.guard():
        m = _model(8)
        plan = static.page_budget(m, page_tokens=4, max_context=128,
                                  hbm_bytes=8 << 20, max_slots_cap=2)
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 126, n) for n in (5, 19, 33, 7)]
        news = (9, 4, 6, 5)
        futs = [eng.submit(prompts[0], max_length=news[0])]
        while not eng.active_slots:           # the first is decoding ...
            time.sleep(0.01)
        futs += [eng.submit(p, max_length=n)  # ... when the others arrive
                 for p, n in zip(prompts[1:], news[1:])]
        outs = [f.result(timeout=600) for f in futs]
        programs = eng._steps.programs
        for prompt, n, out in zip(prompts, news, outs):
            assert list(out) == _greedy(m, prompt, n)
        # a fifth request changes no shape: nothing is traced again
        again = eng.submit(prompts[1], max_length=news[1]).result(600)
        assert list(again) == list(outs[1])
        assert eng._steps.programs == programs
        eng.stop()
        eng.kv_pool.assert_drained()
        assert budget_drift(eng.kv_pool, m) == []
    stats = serving_stats()
    # ONE cache, on the device: nothing is gathered from pages or appended
    # to them (there are none: the tables account), no KV byte is fetched,
    # every step's arrays — the KV among them — were written in place, and
    # the engine's buckets are the programs traced
    assert stats.get("serving.kv.gather_bytes", 0) == 0
    assert stats.get("serving.kv.append_bytes", 0) == 0
    assert eng.kv_pool.device_only and eng.kv_pool.k is None
    assert stats["serving.gen.state_in_place"] == stats["serving.gen.steps"]
    assert stats.get("serving.gen.state_copied", 0) == 0
    assert stats["serving.gen.kv_buckets"] == programs
    assert stats["serving.kv.device_bytes"] \
        == 2 * eng.kv_pool.state.arrays["k0"].nbytes
    assert stats["serving.gen.state_resets"] >= 2    # 5 sequences, 2 slots
    assert stats["serving.state.slots_used"] == 0
    prompt_ops = {op.type for cp in eng._steps._prefill._cache.values()
                  for op in cp.program.global_block().ops}
    step_ops = {op.type for cp in eng._steps._decode_traces()
                for op in cp.program.global_block().ops}
    assert {"mamba2_chunk_scan", "gqa_attention", "rms_norm"} <= prompt_ops
    assert {"mamba2_state_update", "causal_conv1d", "gated_rms_norm",
            "cached_decode_attention"} <= step_ops
    assert "gqa_attention" not in step_ops
    assert "windowed_prefill_attention" not in prompt_ops


def test_one_launch_in_flight_finds_eos_a_step_late_and_books_no_column():
    """Three greedy requests of different budgets over two slots, the
    third waiting for a slot.  The first ends on EOS mid-answer (an id
    taken from its recorded stream): the step dispatched ahead had
    computed a row for it (`rows_past_end` 1) that goes to no page and no
    sequence - a sequence's pages hold its prompt and every token fed, the
    EOS never - and its slot is taken over by the third request while
    that step is still unread.  Tokens stay equal to one-sequence
    decoding; steps follow steps from the device's ids (`steps_ahead`),
    and neither traces nor compiles anything once every bucket is warm."""
    reset_serving_stats()
    with dg.guard():
        m = _model(8)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 126, n) for n in (5, 19, 11)]
        news = (10, 12, 6)
        streams = [_greedy(m, p, n)[len(p):] for p, n in zip(prompts, news)]
        eos = streams[0][3]
        assert eos not in streams[0][:3] + streams[1] + streams[2]
        m.config.eos_id = eos
        plan = static.page_budget(m, page_tokens=4, max_context=128,
                                  hbm_bytes=8 << 20, max_slots_cap=2)
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        booked, finish = [], eng._finish

        def finish_and_note(slot):
            booked.append((len(slot.tokens), slot.table.length))
            finish(slot)

        eng._finish = finish_and_note
        outs = [f.result(timeout=600) for f in
                [eng.submit(p, max_length=n) for p, n in zip(prompts, news)]]
        assert list(outs[0]) == list(prompts[0]) + streams[0][:4]
        for prompt, n, out in zip(prompts, news, outs):
            assert list(out) == _greedy(m, prompt, n)
        assert sorted(booked) == sorted((len(o), len(o) - 1) for o in outs)
        stats = serving_stats()
        assert stats["serving.gen.rows_past_end"] == 1
        assert stats["serving.gen.state_resets"] == 1   # its slot, re-used
        assert stats["serving.state.slots_used"] == 0
        assert 0 < stats["serving.gen.steps_ahead"] < stats["serving.gen.steps"]
        programs = eng._steps.programs
        again = eng.submit(prompts[1], max_length=news[1]).result(600)
        assert list(again) == list(outs[1])
        assert eng._steps.programs == programs
        assert {cp.composed()._cache_size() for cp in
                eng._steps._decode_traces()} == {1}
        eng.stop()
        eng.kv_pool.assert_drained()
        assert budget_drift(eng.kv_pool, m) == []


def test_a_step_with_a_row_that_samples_is_read_before_the_next_goes_out():
    """While a request that samples is decoding no step is dispatched
    ahead - its next id is made on the host, from that step's logits -
    and it draws the tokens its seed draws; the greedy request that
    follows it alone is served from the device's ids again."""
    reset_serving_stats()
    with dg.guard():
        m = _model(8)
        plan = static.page_budget(m, page_tokens=4, max_context=128,
                                  hbm_bytes=8 << 20, max_slots_cap=2)
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 126, n) for n in (5, 19, 7)]
        how = dict(seed=3, top_k=5, temperature=0.8)
        futs = [eng.submit(prompts[0], max_length=9,
                           decode_strategy="sampling", **how),
                eng.submit(prompts[1], max_length=4)]
        outs = [f.result(timeout=600) for f in futs]
        assert list(outs[0]) == _sampled(eng, m, prompts[0], 9, **how)
        assert list(outs[1]) == _greedy(m, prompts[1], 4)
        stats = serving_stats()
        assert stats["serving.gen.steps"] == 8      # the sampler's tokens
        assert stats["serving.gen.steps_ahead"] == 0
        out = eng.submit(prompts[2], max_length=5).result(timeout=600)
        assert list(out) == _greedy(m, prompts[2], 5)
        stats = serving_stats()
        assert stats["serving.gen.steps"] == 8 + 4
        assert stats["serving.gen.steps_ahead"] == 3
        assert stats["serving.gen.rows_past_end"] == 0
        eng.stop()
        eng.kv_pool.assert_drained()


def test_stop_drains_with_a_launch_in_flight():
    """`stop(drain=True)` called while a decode step is in flight and a
    request still queued: every future resolves with its whole answer,
    and nothing is left in flight."""
    with dg.guard():
        m = _model(8)
        plan = static.page_budget(m, page_tokens=4, max_context=128,
                                  hbm_bytes=8 << 20, max_slots_cap=2)
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 126, n) for n in (5, 19, 11)]
        news = (40, 30, 6)
        futs = [eng.submit(p, max_length=n) for p, n in zip(prompts, news)]
        deadline = time.monotonic() + 600
        while eng._in_flight is None and time.monotonic() < deadline:
            time.sleep(0.001)
        assert eng._in_flight is not None
        eng.stop(drain=True, timeout=600)
        assert all(f.done() for f in futs) and eng._in_flight is None
        for prompt, n, f in zip(prompts, news, futs):
            assert list(f.result(timeout=0)) == _greedy(m, prompt, n)
        eng.kv_pool.assert_drained()


def _sampled(eng, m, prompt, n, seed, top_k=0, temperature=1.0):
    """`_greedy` for a request that samples: the engine's own `_sample`
    over each full-forward logits row with the request's own seeded RNG —
    what the engine did with every row's logits before greedy picks moved
    onto the device."""
    from paddle_tpu.serving.generation import GenerationRequest
    req = GenerationRequest(prompt, n, "sampling", top_k, temperature,
                            seed, 600)
    return _greedy(m, prompt, n, pick=lambda row: eng._sample(req, row))


def test_sampling_rows_beside_greedy_rows_keep_their_seeded_tokens():
    """A batch of greedy and sampling requests: the greedy rows take the
    device's argmax; a step with a row that samples brings the logits down
    whole, as every step did before, and that row draws the tokens its
    seed drew then."""
    reset_serving_stats()
    with dg.guard():
        m = _model(8)
        plan = static.page_budget(m, page_tokens=4, max_context=128,
                                  hbm_bytes=8 << 20, max_slots_cap=3)
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 126, n) for n in (5, 19, 11, 7, 9, 6)]
        hows = [None, dict(seed=3, top_k=5, temperature=0.8),
                dict(seed=4, temperature=1.3), dict(seed=5, top_k=3),
                None, dict(seed=6, temperature=2.0)]
        news = (3, 8, 12, 10, 5, 7)
        futs = [eng.submit(p, max_length=n, **(
            dict(decode_strategy="sampling", **how) if how else {}))
            for p, n, how in zip(prompts, news, hows)]
        outs = [f.result(timeout=600) for f in futs]
        for prompt, n, how, out in zip(prompts, news, hows, outs):
            want = _sampled(eng, m, prompt, n, **how) if how \
                else _greedy(m, prompt, n)
            assert list(out) == want
        eng.stop()
        eng.kv_pool.assert_drained()
    stats = serving_stats()
    generated = [len(o) - len(p) for o, p in zip(outs, prompts)]
    assert stats["serving.gen.sampled_on_device"] == sum(
        g for g, how in zip(generated, hows) if not how)
    # one row at each sampling prefill, all 3 slots' rows in every step
    # that held a sampling row: no sampled token without its row
    fetched = stats["serving.gen.logits_rows_fetched"]
    sampled = sum(g for g, how in zip(generated, hows) if how)
    prefills = sum(1 for how in hows if how)
    assert fetched >= sampled and (fetched - prefills) % 3 == 0


def test_a_greedy_step_fetches_ids_and_no_kv_column_and_no_logits():
    """Under a profiler session: the `engine/fetch` span of a step of
    greedy rows carries the bytes of `next_ids` and nothing else (the KV
    columns stay where the step wrote them: `engine/kv_install` and
    `engine/kv_append` carry `bytes` 0),
    and `serving.gen.logits_rows_fetched` stays 0 while
    `serving.gen.sampled_on_device` grows by the step's active rows; a
    row that samples adds the whole logits to the step's bytes and every
    slot's row to the fetched count, and its prefill fetches its one
    logits row in place of the id."""
    import paddle_tpu.profiler as prof
    slots, vocab = 3, 128
    with dg.guard():
        m = _model(8)
        plan = static.page_budget(m, page_tokens=4, max_context=128,
                                  hbm_bytes=8 << 20, max_slots_cap=slots)
        eng = ContinuousBatchingEngine(m, kv_pool=plan).start()
        greedy_step, one_row = 4 * slots, 4 * vocab
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 126, n) for n in (5, 7)]

        def serve(**how):
            reset_serving_stats()
            prof.start_profiler(state="CPU")
            try:
                futs = [eng.submit(prompts[0], max_length=6),
                        eng.submit(prompts[1], max_length=6, **how)]
                outs = [f.result(timeout=600) for f in futs]
            finally:
                prof.stop_profiler(profile_path=None)
            events = list(prof._state.events)
            # (the last `engine/step` span ends after its futures resolve:
            # the session may close first, so steps are read off children)
            active = [e.fields["rows"] for e in events
                      if e.name == "engine/forward"
                      and e.parent == "engine/step"]
            # a step is read under the next step's span or under a
            # prefill's, after that program's dispatch: told apart from a
            # prefill's own fetch (an id, or one logits row) by its size
            fetched = [e.fields["bytes"] for e in events
                       if e.name == "engine/fetch"]
            fetches = [b for b in fetched if b not in (4, one_row)]
            prefills = [b for b in fetched if b in (4, one_row)]
            assert len(fetches) == len(active) and len(prefills) == 2
            moved = [e.fields["bytes"] for e in events
                     if e.name in ("engine/kv_install", "engine/kv_append")]
            assert len(moved) == 2 + len(active) and not any(moved)
            tokens = [len(o) - len(p) for o, p in zip(outs, prompts)]
            return active, fetches, prefills, tokens, serving_stats()

        active, fetches, prefills, tokens, stats = serve()
        assert set(fetches) == {greedy_step} and set(prefills) == {4}
        assert stats.get("serving.gen.logits_rows_fetched", 0) == 0
        assert stats["serving.gen.sampled_on_device"] == sum(tokens) \
            == 2 + sum(active)

        active, fetches, prefills, tokens, stats = serve(
            decode_strategy="sampling", seed=2)
        # the steps that held the sampling row (one a token after its
        # prefill's, whenever its neighbour was admitted) brought the
        # logits of all the slots down, the others none
        with_logits = tokens[1] - 1
        assert sorted(fetches) == sorted(
            [greedy_step + slots * one_row] * with_logits
            + [greedy_step] * (len(active) - with_logits))
        assert sorted(prefills) == [4, one_row]
        assert stats["serving.gen.logits_rows_fetched"] \
            == 1 + slots * with_logits
        assert stats["serving.gen.sampled_on_device"] == tokens[0]
        eng.stop()
        eng.kv_pool.assert_drained()


def test_engine_refuses_what_a_recurrent_state_cannot_have_yet():
    with dg.guard():
        m = _model(9)
        plan = static.page_budget(m, page_tokens=4, max_context=64,
                                  hbm_bytes=8 << 20, max_slots_cap=2)
        with pytest.raises(NotImplementedError, match="snapshot"):
            ContinuousBatchingEngine(m, kv_pool=plan, prefix_cache="auto")
        with pytest.raises(NotImplementedError, match="rollback"):
            ContinuousBatchingEngine(m, kv_pool=plan, speculative="auto")
        with pytest.raises(ValueError, match="paged pool"):
            ContinuousBatchingEngine(m)
        for heads, why in ((4, "geometry"), (2, "no state slots")):
            gpt_plan = static.page_budget(GPTModel(GPTConfig(
                vocab_size=128, hidden_size=32, num_layers=1,
                num_heads=heads, max_position=64)), page_tokens=4,
                hbm_bytes=4 << 20)
            with pytest.raises(ValueError, match=why):
                ContinuousBatchingEngine(m, kv_pool=gpt_plan)


def test_abstract_trace_records_the_same_program_without_running_it():
    """`StaticFunction(abstract_trace=True)` (what `StepPrograms` uses):
    the Program is recorded from shapes, the compiled run gives what the
    eagerly traced one gives, and a function that reads a tensor's value
    while it is traced is refused by JAX, not silently mis-traced."""
    import jax
    from paddle_tpu.jit import StaticFunction
    with dg.guard():
        w = _t(np.arange(6, dtype=np.float32).reshape(2, 3))

        def f(x):
            return paddle_tpu.matmul(x, w) * 2.0 + 1.0

        x = _t(np.ones((4, 2), np.float32))
        eager, abstract = StaticFunction(f), StaticFunction(
            f, abstract_trace=True)
        with dg.no_grad():
            np.testing.assert_array_equal(abstract(x).numpy(),
                                          eager(x).numpy())
        ops = [[op.type for op in sf.concrete_program(x).program
                .global_block().ops] for sf in (eager, abstract)]
        assert ops[0] == ops[1] and ops[0]
        assert not isinstance(x._value, jax.core.Tracer)    # put back

        def peeks(x):
            return x * float(x.numpy().sum())

        with pytest.raises(Exception), dg.no_grad():
            StaticFunction(peeks, abstract_trace=True)(x)
