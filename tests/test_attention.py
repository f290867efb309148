"""Long-context attention tests: Pallas flash kernel + ring attention
sequence parallelism (SURVEY.md §5.7 — the TPU-native capability the
reference lacks)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.attention import (flash_attention, reference_attention,
                                      ring_attention,
                                      enable_flash_attention)


def _qkv(B=2, H=2, S=128, D=32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.rand(B, H, S, D).astype(np.float32))
    return mk(), mk(), mk()


def test_flash_matches_reference():
    q, k, v = _qkv()
    np.testing.assert_allclose(np.asarray(flash_attention(q, k, v)),
                               np.asarray(reference_attention(q, k, v)),
                               rtol=1e-4, atol=1e-5)


def test_flash_causal_and_grads():
    q, k, v = _qkv(S=256, D=64)
    ref = reference_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)

    def loss_flash(q):
        return (flash_attention(q, k, v, causal=True) ** 2).sum()

    def loss_ref(q):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(loss_flash)(q)
    g2 = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(128, 128), (64, 128)])
def test_flash_blockwise_backward_qkv(causal, sq, sk):
    """The Pallas blockwise backward (dq/dk/dv kernels) must match the
    reference vjp for every input, incl. cross-attention shapes."""
    rng = np.random.RandomState(1)
    B, H, D = 2, 2, 32
    q = jnp.asarray(rng.rand(B, H, sq, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, H, sk, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, H, sk, D).astype(np.float32))
    g = jnp.asarray(rng.rand(B, H, sq, D).astype(np.float32))

    _, vjp_f = jax.vjp(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=causal,
                                           block_q=32, block_k=32),
        q, k, v)
    _, vjp_r = jax.vjp(
        lambda q_, k_, v_: reference_attention(q_, k_, v_, causal=causal),
        q, k, v)
    for name, a, b in zip("qkv", vjp_f(g), vjp_r(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4,
                                   err_msg=f"d{name} causal={causal}")


def test_flash_backward_bf16():
    """bf16 inputs (the AMP path) go through the Pallas backward with f32
    accumulation; compare against the f32 reference loosely."""
    rng = np.random.RandomState(2)
    B, H, S, D = 1, 2, 128, 32
    qf = rng.rand(B, H, S, D).astype(np.float32)
    kf = rng.rand(B, H, S, D).astype(np.float32)
    vf = rng.rand(B, H, S, D).astype(np.float32)
    q, k, v = (jnp.asarray(a, jnp.bfloat16) for a in (qf, kf, vf))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True).astype(jnp.float32)
                ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True)
                .astype(jnp.float32) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.05, atol=0.1, err_msg=f"d{name} bf16")


def test_flash_irregular_len_raises():
    """A length no multiple-of-8 block tiles is an error, never a silent
    detour to the O(S^2) reference."""
    q, k, v = _qkv(S=100)
    with pytest.raises(ValueError, match="do not tile"):
        flash_attention(q, k, v)


def test_ring_attention_sharded():
    from jax.sharding import Mesh, PartitionSpec as P
    q, k, v = _qkv(S=128, D=32)
    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))

    for causal in (False, True):
        ref = reference_attention(q, k, v, causal=causal)

        def fn(q, k, v, causal=causal):
            return ring_attention(q, k, v, "sp", causal=causal)

        sharded = jax.shard_map(
            fn, mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None), check_vma=False)
        out = jax.jit(sharded)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=f"causal={causal}")


def test_ring_attention_grads_sharded():
    from jax.sharding import Mesh, PartitionSpec as P
    q, k, v = _qkv(S=64, D=16)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))

    def ring_loss(q, k, v):
        def fn(q, k, v):
            return ring_attention(q, k, v, "sp", causal=True)
        f = jax.shard_map(fn, mesh=mesh,
                          in_specs=(P(None, None, "sp", None),) * 3,
                          out_specs=P(None, None, "sp", None),
                          check_vma=False)
        return (f(q, k, v) ** 2).sum()

    def ref_loss(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(ring_loss)(q, k, v)
    g2 = jax.grad(ref_loss)(q, k, v)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-3, atol=1e-4)


def test_mha_flash_path_matches():
    import paddle_tpu
    import paddle_tpu.nn as nn
    layer = nn.MultiHeadAttention(32, 4, dropout=0.0)
    layer.eval()
    x = paddle_tpu.to_tensor(
        np.random.RandomState(0).rand(2, 16, 32).astype(np.float32))
    base = layer(x).numpy()
    enable_flash_attention(True)
    try:
        fl = layer(x).numpy()
    finally:
        enable_flash_attention(False)
    np.testing.assert_allclose(fl, base, rtol=1e-4, atol=1e-5)


def test_mha_flash_backward():
    import paddle_tpu
    import paddle_tpu.nn as nn
    layer = nn.MultiHeadAttention(32, 4, dropout=0.0)
    x = paddle_tpu.to_tensor(
        np.random.RandomState(0).rand(2, 16, 32).astype(np.float32))
    enable_flash_attention(True)
    try:
        out = layer(x)
        out.sum().backward()
    finally:
        enable_flash_attention(False)
    assert layer.q_proj.weight.grad is not None


def test_static_ring_attention_op_sequence_parallel():
    """Static program using the ring_attention op under a (dp=2, sp=4)
    mesh; loss must match the single-device run."""
    import paddle_tpu.static as static
    from paddle_tpu.static import layers
    from paddle_tpu.static.layer_helper import LayerHelper
    from paddle_tpu.distributed import CompiledProgram, BuildStrategy

    B, H, S, D = 4, 2, 32, 16

    def build():
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            q = layers.data("q", [-1, S, H * D])
            helper = LayerHelper("ring_attention")
            out = helper.create_variable_for_type_inference("float32")
            out.shape = (-1, S, H * D)
            helper.append_op("ring_attention",
                             inputs={"Q": [q], "K": [q], "V": [q]},
                             outputs={"Out": [out]},
                             attrs={"causal": True, "ring_id": 101,
                                    "num_heads": H})
            loss = layers.mean(layers.square(out))
        return main, startup, loss

    rng = np.random.RandomState(0)
    qb = rng.rand(B, S, H * D).astype(np.float32)

    main, startup, loss = build()
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        (single,) = exe.run(main, feed={"q": qb}, fetch_list=[loss])

    main2, startup2, loss2 = build()
    bs = BuildStrategy()
    bs.sequence_parallel_degree = 4
    cp = CompiledProgram(main2, build_strategy=bs).with_data_parallel()
    exe2 = static.Executor()
    scope2 = static.Scope()
    with static.scope_guard(scope2):
        exe2.run(startup2)
        (sharded,) = exe2.run(cp, feed={"q": qb}, fetch_list=[loss2])
    np.testing.assert_allclose(float(sharded), float(single),
                               rtol=1e-4, atol=1e-6)


def test_flash_cross_length_causal():
    """sq != sk causal must be bottom-right aligned like the reference
    (decode-with-KV-prefix shape)."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.rand(1, 2, 128, 32).astype(np.float32))
    k = jnp.asarray(rng.rand(1, 2, 256, 32).astype(np.float32))
    v = jnp.asarray(rng.rand(1, 2, 256, 32).astype(np.float32))
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_flash_sq_gt_sk_causal_valid_rows():
    """Bottom-right causal with MORE queries than keys: the first sq-sk
    rows see no key at all (undefined — flash outputs zero); every valid
    row must match the reference exactly."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    sq, sk = 128, 64
    q = jnp.asarray(rng.randn(1, 2, sq, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, sk, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, sk, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    # valid rows (those with >= 1 visible key) agree
    np.testing.assert_allclose(np.asarray(out[:, :, sq - sk:]),
                               np.asarray(ref[:, :, sq - sk:]),
                               rtol=1e-5, atol=1e-5)
    # undefined rows are zero by convention
    np.testing.assert_allclose(np.asarray(out[:, :, : sq - sk]), 0.0,
                               atol=1e-6)
    # dq, dk AND dv agree (the dkv kernel's causal start index goes
    # through its negative sk-sq branch exactly in this configuration)
    gs = jax.grad(lambda a, b, c: flash_attention(
        a, b, c, causal=True)[:, :, sq - sk:].sum(),
        argnums=(0, 1, 2))(q, k, v)
    grs = jax.grad(lambda a, b, c: reference_attention(
        a, b, c, causal=True)[:, :, sq - sk:].sum(),
        argnums=(0, 1, 2))(q, k, v)
    for g, gr, tag in zip(gs, grs, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=1e-4, atol=1e-5, err_msg=tag)


def test_wired_sequence_parallel_transformer_lm():
    """The PUBLIC long-seq wiring: build_transformer_lm(
    sequence_parallel=True) emits ring_attention ops per layer, runs
    single-device (ring degrades to plain attention), matches the
    non-sp build numerically there, and composes with FLAGS_recompute
    auto-remat (barriers + ring op in the same block).  The dp×sp mesh
    execution of the ring op itself is pinned by
    test_static_ring_attention_op_sequence_parallel above."""
    import paddle_tpu.static as static
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.core.program import _reset_unique_names
    from paddle_tpu.models.static_lm import build_transformer_lm

    VOCAB, HID, SEQ, B = 64, 32, 16, 4
    rng = np.random.RandomState(0)
    feed = {"ids": rng.randint(0, VOCAB, (B, SEQ)).astype(np.int32),
            "pos": np.tile(np.arange(SEQ), (B, 1)).astype(np.int32),
            "labels": rng.randint(0, VOCAB,
                                  (B, SEQ, 1)).astype(np.int32)}

    def build(sp, remat=False):
        _reset_unique_names()
        if remat:
            set_flags({"recompute": "always"})
        try:
            main, startup, loss, _ = build_transformer_lm(
                VOCAB, HID, 2, 2, SEQ, sequence_parallel=sp)
            with static.program_guard(main, startup):
                static.SGD(learning_rate=0.0).minimize(loss)
        finally:
            set_flags({"recompute": ""})
        return main, startup, loss

    def run_single(main, startup, loss):
        exe, sc = static.Executor(), static.Scope()
        with static.scope_guard(sc):
            exe.run(startup)
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
        return float(lv)

    main_sp, startup_sp, loss_sp = build(sp=True)
    ring_ops = [op for op in main_sp.global_block().ops
                if op.type == "ring_attention"]
    assert len(ring_ops) == 2  # one per layer
    l_sp = run_single(main_sp, startup_sp, loss_sp)
    main_plain, startup_plain, loss_plain = build(sp=False)
    l_plain = run_single(main_plain, startup_plain, loss_plain)
    np.testing.assert_allclose(l_sp, l_plain, rtol=1e-4, atol=1e-6)

    # remat × ring compose in one block, numerics preserved
    main_r, startup_r, loss_r = build(sp=True, remat=True)
    ops_r = [op.type for op in main_r.global_block().ops]
    assert "optimization_barrier" in ops_r and "ring_attention" in ops_r
    l_r = run_single(main_r, startup_r, loss_r)
    np.testing.assert_allclose(l_r, l_plain, rtol=1e-4, atol=1e-6)
