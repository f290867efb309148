"""Collective ops (reference: /root/reference/paddle/fluid/operators/collective/
c_allreduce_op.h:124 ncclAllReduce dispatch, c_broadcast_op, c_allgather_op,
c_reducescatter_op, barrier_op; ring ids from
platform/collective_helper.h:62 NCCLCommContext).

TPU-native lowering: when the executor traces the program under shard_map over
a jax.sharding.Mesh, ctx.collective_axes(ring_id) names the mesh axes and the
ops become XLA collectives over ICI (psum/all_gather/psum_scatter/ppermute).
Outside any mesh (single-chip), world size is 1 and they are identities —
the same degenerate behaviour the reference has with one trainer.

The c_sync_*_stream ops are no-ops: XLA owns scheduling, there are no user
streams to sync (reference needed them because NCCL ran on separate CUDA
streams).  c_comm_init/c_gen_nccl_id have no TPU equivalent: mesh formation is
jax.distributed initialization; they are registered as no-ops for program
compatibility."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..registry import register_op


def _axes(ctx, attrs):
    return ctx.collective_axes(attrs.get("ring_id", 0))


def _axis_size(ax) -> int:
    """Static size of a mesh axis from inside shard_map."""
    return jax.lax.axis_size(ax)


def _c_allreduce(name, op):
    @register_op(name, inputs=["X"], outputs=["Out"], grad="auto",
                 side_effect=True)
    def kernel(ins, attrs, ctx, _op=op):
        from ...core.selected_rows import SelectedRows
        x = ins["X"]
        axes = _axes(ctx, attrs)
        if not axes:
            return {"Out": x}
        if isinstance(x, SelectedRows):
            # sparse allreduce (reference allgathers SelectedRows grads):
            # psum would sum the int32 row INDICES across replicas —
            # all_gather rows+values instead; concatenation is the sum
            # under scatter-add semantics
            if _op != "sum":
                raise NotImplementedError(
                    f"{_op} allreduce over SelectedRows")
            rows, vals = x.rows, x.values
            for ax in ([axes] if isinstance(axes, str) else axes):
                rows = jax.lax.all_gather(rows, ax, tiled=True)
                vals = jax.lax.all_gather(vals, ax, tiled=True)
            return {"Out": SelectedRows(rows, vals, x.height)}
        if _op == "sum":
            return {"Out": jax.lax.psum(x, axes)}
        if _op == "max":
            return {"Out": jax.lax.pmax(x, axes)}
        if _op == "min":
            return {"Out": jax.lax.pmin(x, axes)}
        if _op == "prod":
            return {"Out": jnp.exp(jax.lax.psum(jnp.log(x), axes))}
        raise ValueError(_op)
    return kernel


_c_allreduce("c_allreduce_sum", "sum")
_c_allreduce("c_allreduce_max", "max")
_c_allreduce("c_allreduce_min", "min")
_c_allreduce("c_allreduce_prod", "prod")
_c_allreduce("allreduce", "sum")  # legacy distributed_ops/allreduce_op
_c_allreduce("c_reduce_sum", "sum")   # reduce-to-root approximated as
_c_allreduce("c_reduce_max", "max")   # allreduce (root semantics preserved
_c_allreduce("c_reduce_min", "min")   # for the root rank's value)
_c_allreduce("c_reduce_prod", "prod")


@register_op("c_broadcast", inputs=["X"], outputs=["Out"], side_effect=True)
def c_broadcast(ins, attrs, ctx):
    x = ins["X"]
    axes = _axes(ctx, attrs)
    if not axes:
        return {"Out": x}
    root = attrs.get("root", 0)
    # broadcast root's value: select root's shard and psum the rest to it
    idx = jax.lax.axis_index(axes if isinstance(axes, str) else axes[0])
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return {"Out": jax.lax.psum(masked, axes)}


@register_op("broadcast", inputs=["X"], outputs=["Out"], side_effect=True)
def broadcast_legacy(ins, attrs, ctx):
    return c_broadcast(ins, attrs, ctx)


@register_op("c_allgather", inputs=["X"], outputs=["Out"], side_effect=True)
def c_allgather(ins, attrs, ctx):
    x = ins["X"]
    axes = _axes(ctx, attrs)
    if not axes:
        return {"Out": x}
    ax = axes if isinstance(axes, str) else axes[0]
    out = jax.lax.all_gather(x, ax, axis=0, tiled=True)
    return {"Out": out}


@register_op("c_reducescatter", inputs=["X"], outputs=["Out"],
             side_effect=True)
def c_reducescatter(ins, attrs, ctx):
    x = ins["X"]
    axes = _axes(ctx, attrs)
    if not axes:
        return {"Out": x}
    ax = axes if isinstance(axes, str) else axes[0]
    return {"Out": jax.lax.psum_scatter(x, ax, scatter_dimension=0,
                                        tiled=True)}


@register_op("c_scatter", inputs=["X"], outputs=["Out"], side_effect=True)
def c_scatter(ins, attrs, ctx):
    x = ins["X"]
    axes = _axes(ctx, attrs)
    if not axes:
        return {"Out": x}
    ax = axes if isinstance(axes, str) else axes[0]
    n = _axis_size(ax)
    idx = jax.lax.axis_index(ax)
    # only the root's buffer is meaningful — broadcast it first so non-root
    # ranks may contribute an arbitrary (e.g. zero) full-shaped buffer
    root = attrs.get("root", 0)
    x = jax.lax.psum(jnp.where(idx == root, x, jnp.zeros_like(x)), axes)
    shard = x.shape[0] // n
    return {"Out": jax.lax.dynamic_slice_in_dim(x, idx * shard, shard, 0)}


@register_op("barrier", inputs=["X?"], outputs=["Out?"], grad=None,
             side_effect=True)
def barrier(ins, attrs, ctx):
    # XLA collectives synchronise implicitly; a psum of a scalar is a true
    # cross-replica barrier when one is explicitly requested
    axes = _axes(ctx, attrs)
    x = ins.get("X")
    if x is None:
        x = jnp.zeros((1,), jnp.float32)
    if axes:
        x = x + 0 * jax.lax.psum(jnp.ones((), x.dtype), axes)
    return {"Out": x}


@register_op("c_embedding", inputs=["W", "Ids!"], outputs=["Out"],
             side_effect=True)
def c_embedding(ins, attrs, ctx):
    # model-parallel embedding shard: rows [start, start+n) live here
    w, ids = ins["W"], ins["Ids"].astype(jnp.int32)
    start = attrs.get("start_index", 0)
    local = ids - start
    valid = (local >= 0) & (local < w.shape[0])
    out = jnp.take(w, jnp.clip(local, 0, w.shape[0] - 1), axis=0)
    out = jnp.where(valid[..., None], out, jnp.zeros_like(out))
    axes = _axes(ctx, attrs)
    if axes:
        out = jax.lax.psum(out, axes)
    return {"Out": out}


@register_op("c_concat", inputs=["X"], outputs=["Out"], side_effect=True)
def c_concat(ins, attrs, ctx):
    # tensor-parallel allgather along last dim
    x = ins["X"]
    axes = _axes(ctx, attrs)
    if not axes:
        return {"Out": x}
    ax = axes if isinstance(axes, str) else axes[0]
    return {"Out": jax.lax.all_gather(x, ax, axis=x.ndim - 1, tiled=True)}


@register_op("c_split", inputs=["X"], outputs=["Out"], side_effect=True)
def c_split(ins, attrs, ctx):
    x = ins["X"]
    axes = _axes(ctx, attrs)
    if not axes:
        return {"Out": x}
    ax = axes if isinstance(axes, str) else axes[0]
    n = _axis_size(ax)
    idx = jax.lax.axis_index(ax)
    shard = x.shape[-1] // n
    return {"Out": jax.lax.dynamic_slice_in_dim(x, idx * shard, shard,
                                                x.ndim - 1)}


def _mp_allreduce_grad(ins, attrs, ctx):
    """Megatron g-operator backward: the forward psum's cotangent is
    replicated, and each shard's input contributed once — identity (NOT
    another psum, which would scale grads by the tp degree)."""
    return {"X@GRAD": ins["Out@GRAD"]}


@register_op("mp_allreduce_sum", inputs=["X"], outputs=["Out"],
             grad=_mp_allreduce_grad, side_effect=True)
def mp_allreduce_sum(ins, attrs, ctx):
    """Model-parallel partial-sum reduction (paddle mp_allreduce_sum):
    same forward as c_allreduce_sum, differentiable with identity
    backward."""
    x = ins["X"]
    axes = _axes(ctx, attrs)
    if not axes:
        return {"Out": x}
    return {"Out": jax.lax.psum(x, axes)}


def _c_identity_grad(ins, attrs, ctx):
    """Reference model-parallel semantics (_c_identity in paddle's mp
    helpers): identity forward, allreduce backward over the bound ring —
    the Megatron f-operator guarding a column-parallel layer's input."""
    g = ins["Out@GRAD"]
    axes = _axes(ctx, attrs)
    if not axes:
        return {"X@GRAD": g}
    return {"X@GRAD": jax.lax.psum(g, axes)}


@register_op("c_identity", inputs=["X"], outputs=["Out"],
             grad=_c_identity_grad, side_effect=True)
def c_identity(ins, attrs, ctx):
    return {"Out": ins["X"]}


@register_op("c_sync_calc_stream", inputs=["X"], outputs=["Out"], grad=None,
             side_effect=True)
def c_sync_calc_stream(ins, attrs, ctx):
    return {"Out": ins["X"]}  # no user streams under XLA


@register_op("c_sync_comm_stream", inputs=["X"], outputs=["Out"], grad=None,
             side_effect=True)
def c_sync_comm_stream(ins, attrs, ctx):
    return {"Out": ins["X"]}


@register_op("c_comm_init", inputs=["X?"], outputs=[], grad=None,
             side_effect=True)
def c_comm_init(ins, attrs, ctx):
    return {}  # mesh formation happens in jax.distributed / Mesh creation


@register_op("c_comm_init_all", inputs=[], outputs=[], grad=None,
             side_effect=True)
def c_comm_init_all(ins, attrs, ctx):
    return {}


@register_op("c_gen_nccl_id", inputs=[], outputs=["Out?"], grad=None,
             side_effect=True)
def c_gen_nccl_id(ins, attrs, ctx):
    return {}  # no NCCL id on TPU; kept for program compatibility


@register_op("c_wait_comm", inputs=["X"], outputs=["Out"], grad=None,
             side_effect=True)
def c_wait_comm(ins, attrs, ctx):
    return {"Out": ins["X"]}


@register_op("c_wait_compute", inputs=["X"], outputs=["Out"], grad=None,
             side_effect=True)
def c_wait_compute(ins, attrs, ctx):
    return {"Out": ins["X"]}


@register_op("partial_allgather", inputs=["X"], outputs=["Out"],
             side_effect=True)
def partial_allgather(ins, attrs, ctx):
    return c_allgather(ins, attrs, ctx)


@register_op("alltoall", inputs=["X"], outputs=["Out"], side_effect=True)
def alltoall(ins, attrs, ctx):
    x = ins["X"]
    axes = _axes(ctx, attrs)
    if not axes:
        return {"Out": x}
    ax = axes if isinstance(axes, str) else axes[0]
    n = _axis_size(ax)
    xs = x.reshape((n, x.shape[0] // n) + x.shape[1:])
    out = jax.lax.all_to_all(xs, ax, split_axis=0, concat_axis=0, tiled=False)
    return {"Out": out.reshape(x.shape)}


@register_op("p_send", inputs=["X"], outputs=["Out?"], grad=None,
             side_effect=True)
def p_send(ins, attrs, ctx):
    """Point-to-point send half.  Under SPMD tracing the send/recv pair is a
    single collective_permute, realised on the recv side; the send is an
    identity marker (reference: operators/collective send_v2 over NCCL)."""
    return {"Out": ins["X"]}


@register_op("p_recv", inputs=["X"], outputs=["Out"], grad=None,
             side_effect=True)
def p_recv(ins, attrs, ctx):
    """Point-to-point recv: lax.ppermute from `peer` along the ring axis.
    Degenerates to identity outside a mesh (world of 1)."""
    x = ins["X"]
    axes = _axes(ctx, attrs)
    if not axes:
        return {"Out": x}
    ax = axes if isinstance(axes, str) else axes[0]
    n = _axis_size(ax)
    peer = attrs.get("peer", 0)
    me = attrs.get("me", None)
    # permutation sending peer -> this rank; built statically over the ring
    perm = [(peer, i) for i in range(n)] if me is None else [(peer, me)]
    return {"Out": jax.lax.ppermute(x, ax, [(s % n, d % n)
                                            for s, d in perm])}


@register_op("elastic_commit_mask", inputs=["X"], outputs=["Out"],
             grad=None, side_effect=True)
def elastic_commit_mask(ins, attrs, ctx):
    """Commit mask for the elastic schedule (distributed/elastic.py):
    True when the post-increment micro-step counter completes a window of
    K = logical_dp / mesh-world micro-steps.  K is resolved HERE at trace
    time, so the same program serves every world size; off-mesh the world
    is 1 and a single process walks all N logical micro-steps."""
    cnt = ins["X"]
    n = int(attrs["logical_dp"])
    axes = _axes(ctx, attrs)
    m = 1
    if axes:
        ax = axes if isinstance(axes, str) else axes[0]
        m = _axis_size(ax)
    if m < 1 or n % m != 0:
        raise ValueError(
            f"elastic logical_dp={n} is not divisible by the mesh dp "
            f"degree {m}; an elastic mesh must be a divisor of the "
            "logical world")
    k = n // m
    return {"Out": jnp.mod(cnt, k) == 0}


@register_op("c_elastic_fold", inputs=["X", "Acc"], outputs=["Out"],
             grad=None, side_effect=True)
def c_elastic_fold(ins, attrs, ctx):
    """World-size-invariant ordered reduction (distributed/elastic.py):
    all_gather the per-rank values, then continue an EXPLICIT unrolled
    left-fold from the accumulator — micro-step j of an M-device mesh
    adds logical ranks jM..jM+M-1 in rank order, so after a full window
    the result is (((v0+v1)+v2)+...)+v_{N-1} for every factorization of
    the logical world.  psum must not be used here: its reduction order
    is implementation-defined and XLA may reassociate psum(a+b) into
    psum(a)+psum(b), both of which break bitwise topology invariance.
    Off-mesh this degrades to acc + x (a world of one logical rank per
    micro-step).

    ``pre_reduced=True`` (the elastic × ZeRO-1 composition,
    distributed/elastic.py): X is ALREADY a cross-rank reduction — the
    1/N reduce-scattered gradient shard — so the gather half is skipped
    and the op is the accumulator continuation ``acc + x`` on every
    mesh.  The explicit fold order (hence bitwise topology invariance)
    is traded away there; the composition's contract is allclose, not
    bitwise (docs/elastic.md)."""
    x, acc = ins["X"], ins["Acc"]
    if attrs.get("pre_reduced"):
        return {"Out": acc + x}
    axes = _axes(ctx, attrs)
    if not axes:
        return {"Out": acc + x}
    ax = axes if isinstance(axes, str) else axes[0]
    gathered = jax.lax.all_gather(x, ax, axis=0, tiled=False)
    out = acc
    for i in range(gathered.shape[0]):
        out = out + gathered[i]
    return {"Out": out}


@register_op("scale_by_world_size", inputs=["X"], outputs=["Out"], grad=None,
             side_effect=True)
def scale_by_world_size(ins, attrs, ctx):
    """Divide by the collective world size (used after c_allreduce_sum for
    gradient averaging — the reference's ScaleLossGradOpHandle /
    GradientScaleStrategy.CoeffNumDevice, details/scale_loss_grad_op_handle)."""
    axes = _axes(ctx, attrs)
    if not axes:
        return {"Out": ins["X"]}
    from ...core.selected_rows import SelectedRows
    n = jax.lax.psum(1, axes)
    x = ins["X"]
    if isinstance(x, SelectedRows):
        return {"Out": SelectedRows(
            x.rows, x.values / jnp.asarray(n, x.values.dtype), x.height)}
    return {"Out": (x / jnp.asarray(n, x.dtype))}
