"""Switch-MoE + expert parallelism (incubate/moe.py; SURVEY §5.7 alltoall
expert path).  The decisive check: the ep-sharded shard_map result equals
the single-device dense result bit-for-bit-ish."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.incubate.moe import (switch_moe, init_moe_params,
                                     moe_aux_loss)


def _params(E=4, D=8, H=16, seed=0):
    return init_moe_params(jax.random.PRNGKey(seed), D, H, E)


def test_moe_forward_shapes_and_capacity():
    gw, w1, b1, w2, b2 = _params()
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    out, aux = switch_moe(x, gw, w1, b1, w2, b2, capacity_factor=1.25)
    assert out.shape == (32, 8)
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) > 0
    # capacity so tight that most tokens drop -> many zero rows
    out2, _ = switch_moe(x, gw, w1, b1, w2, b2, capacity_factor=0.05)
    zero_rows = (np.abs(np.asarray(out2)).sum(-1) < 1e-9).sum()
    assert zero_rows > 16


def test_moe_grads_flow_and_training():
    gw, w1, b1, w2, b2 = _params()
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 8))
    y = jnp.tanh(x @ jax.random.normal(jax.random.PRNGKey(3), (8, 8)))

    def loss_fn(params):
        out, aux = switch_moe(x, *params, capacity_factor=2.0)
        return jnp.mean((out - y) ** 2) + 0.01 * aux

    params = (gw, w1, b1, w2, b2)
    g = jax.grad(loss_fn)(params)
    assert all(np.isfinite(np.asarray(gi)).all() for gi in g)
    assert float(jnp.abs(g[0]).sum()) > 0  # gate receives gradient
    assert float(jnp.abs(g[1]).sum()) > 0  # experts receive gradient
    l0 = float(loss_fn(params))
    step = jax.jit(lambda p: jax.tree.map(
        lambda a, b: a - 0.5 * b, p, jax.grad(loss_fn)(p)))
    for _ in range(40):
        params = step(params)
    assert float(loss_fn(params)) < l0 * 0.7


def test_moe_expert_parallel_matches_dense():
    """dp x ep shard_map with tokens sharded over BOTH axes: sharded
    experts + all_to_all dispatch must equal the single-device dense
    computation, and expert-weight grads must match the dense grads (the
    a2a vjp accumulates the ep row — no ep over-counting)."""
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    E, D, H, N = 4, 8, 16, 64
    gw, w1, b1, w2, b2 = _params(E, D, H)
    x = jax.random.normal(jax.random.PRNGKey(5), (N, D))
    # generous capacity so no token drops (local capacity differs from
    # global: N/8 tokens per device vs N)
    dense, _ = switch_moe(x, gw, w1, b1, w2, b2, capacity_factor=8.0)

    mesh = Mesh(np.array(devs[:8]).reshape(2, 4), ("dp", "ep"))

    def fn(xl, gwl, w1l, b1l, w2l, b2l):
        out, aux = switch_moe(xl, gwl, w1l, b1l, w2l, b2l,
                              capacity_factor=8.0, axis_name="ep")
        return out

    sharded = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(("dp", "ep")), P(), P("ep"), P("ep"), P("ep"),
                  P("ep")),
        out_specs=P(("dp", "ep")), check_vma=False)
    out = sharded(x, gw, w1, b1, w2, b2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=2e-4, atol=2e-5)

    # gradient parity: mean-squared output loss, dense vs sharded
    def dense_loss(p):
        o, _ = switch_moe(x, *p, capacity_factor=8.0)
        return jnp.mean(o ** 2)

    g_dense = jax.grad(dense_loss)((gw, w1, b1, w2, b2))

    def sharded_step(p, xl):
        def loss_fn(pl):
            o, _ = switch_moe(xl, *pl, capacity_factor=8.0,
                              axis_name="ep")
            return jax.lax.pmean(jnp.mean(o ** 2), ("dp", "ep"))
        g = jax.grad(loss_fn)(p)
        world = jax.lax.psum(1, ("dp", "ep"))
        return (jax.lax.pmean(g[0], ("dp", "ep")),) + tuple(
            jax.lax.psum(gi, "dp") / world for gi in g[1:])

    specs_p = (P(), P("ep"), P("ep"), P("ep"), P("ep"))
    g_sh = jax.shard_map(
        sharded_step, mesh=mesh, in_specs=(specs_p, P(("dp", "ep"))),
        out_specs=specs_p, check_vma=False)((gw, w1, b1, w2, b2), x)
    for a, b in zip(g_dense, g_sh):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=1e-6)


def test_moe_aux_loss_balance():
    g_uniform = jnp.full((100, 4), 0.25)
    idx = jnp.arange(100) % 4
    balanced = float(moe_aux_loss(g_uniform, idx))
    g_skew = jnp.asarray(np.eye(4, dtype=np.float32)[np.zeros(100, int)])
    skewed = float(moe_aux_loss(g_skew, jnp.zeros(100, jnp.int32)))
    assert skewed > balanced  # imbalance is penalized
    np.testing.assert_allclose(balanced, 1.0, rtol=1e-5)


# ---------------------------------------------------------------------------
# MoE as a framework citizen (VERDICT r3 weak #8): switch_moe op +
# static.layers wrapper + nn.SwitchMoE all share the incubate core
# ---------------------------------------------------------------------------

def test_switch_moe_op_registered_and_matches_core():
    from paddle_tpu.ops.registry import run_kernel, OpContext, get_op_info
    assert get_op_info("switch_moe") is not None
    gw, w1, b1, w2, b2 = _params()
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 8))
    out = run_kernel("switch_moe",
                     {"X": x, "GateW": gw, "W1": w1, "B1": b1,
                      "W2": w2, "B2": b2},
                     {"capacity_factor": 1.25}, OpContext(seed=0))
    ref_out, ref_aux = switch_moe(x, gw, w1, b1, w2, b2,
                                  capacity_factor=1.25)
    np.testing.assert_allclose(np.asarray(out["Out"]),
                               np.asarray(ref_out), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["AuxLoss"]),
                               np.asarray(ref_aux), atol=1e-6)


def test_static_moe_transformer_block_trains():
    """A static-graph MoE FFN block (attention-free book-size version)
    must train: loss + aux_weight*aux falls on a fixed batch."""
    import paddle_tpu.static as static
    from paddle_tpu.static import layers

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 6, 16])
        y = layers.data("y", [-1, 6, 16])
        h = layers.fc(x, 16, num_flatten_dims=2, act="relu")
        moe_out, aux = layers.switch_moe(h, num_experts=4, d_hidden=32,
                                         capacity_factor=2.0)
        h = layers.layer_norm(layers.elementwise_add(h, moe_out),
                              begin_norm_axis=2)
        mse = layers.mean(layers.square(layers.elementwise_sub(h, y)))
        loss = layers.elementwise_add(
            mse, layers.scale(aux, scale=0.01))
        static.Adam(learning_rate=5e-3).minimize(loss)

    exe = static.Executor()
    scope = static.Scope()
    rng = np.random.RandomState(0)
    xb = rng.randn(8, 6, 16).astype(np.float32)
    yb = np.tanh(xb[:, :, ::-1]).astype(np.float32)
    with static.scope_guard(scope):
        exe.run(startup)
        losses = []
        for _ in range(40):
            (lv,) = exe.run(main, feed={"x": xb, "y": yb},
                            fetch_list=[loss])
            losses.append(float(np.asarray(lv)))
    assert losses[-1] < losses[0] * 0.6, losses[::8]


def test_nn_switch_moe_layer_dygraph():
    """nn.SwitchMoE forwards and backprops in dygraph; grads reach the
    gate and every expert weight."""
    import paddle_tpu
    import paddle_tpu.nn as nn
    from paddle_tpu.dygraph.base import guard

    with guard():
        layer = nn.SwitchMoE(d_model=8, d_hidden=16, num_experts=4,
                             capacity_factor=2.0)
        x = paddle_tpu.dygraph.to_variable(
            np.random.RandomState(0).randn(16, 8).astype(np.float32))
        out, aux = layer(x)
        assert tuple(out.shape) == (16, 8)
        loss = (out * out).sum() + aux * 0.01
        loss.backward()
        assert layer.gate_w.grad is not None
        assert np.abs(np.asarray(layer.w1.grad)).sum() > 0
        assert np.abs(np.asarray(layer.w2.grad)).sum() > 0
