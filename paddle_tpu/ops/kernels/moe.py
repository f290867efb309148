"""Routed-expert ops for a chip that holds a SHARE of each layer's experts
(expert parallelism, this chip's part): a sigmoid router over ALL the
published experts with a selection bias and top-k, and the dropless
grouped expert computation over the experts held here.

Both are FORWARD ONLY (``grad=None``): the serving path is their one
caller (gradients of the forward-only ops: ROADMAP R-a).

`moe_router_topk` scores every published expert, held or not: which
experts a token picks and how its weights are normalised do not depend on
where the experts live.  `moe_grouped_experts` is told which experts this
chip holds (attrs ``first_held``, ``held`` of ``n_experts``) and computes
their part of each token's weighted sum; what absent experts would add is
left out — no capacity, no token dropped, no stand-in for the other chips
or the exchange with them.  Shapes are static: the ``N * k`` token-expert
pairs are sorted by expert and the held experts' two matmuls run as a
grouped product over the sorted rows (a Pallas grouped matmul, the
`megablox` kernel that ships with JAX: it visits only the row tiles of
held, non-empty groups, so a pair of an absent expert costs no FLOPs and
an untouched expert's weights are never read).

Numerics: router logits, sigmoid, top-k and the weights are float32 at
``Precision.HIGHEST`` (the published code computes its gate in float32);
the expert matmuls take their operands as given (bfloat16 when served)
and accumulate in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import gmm

from ..attention import _interpret
from .ssm import _valid
from ..registry import register_op

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
# rows of the sorted pairs a grid step takes (a group's rows are masked
# inside a tile that straddles two groups), and the widest k / n tile:
# [1024, 896] bfloat16 is 1.8 MB, twice buffered well inside VMEM
_TILE_M, _TILE_KN = 128, 1024
STATS = ("routed", "pairs", "touched", "max_load")


@register_op("moe_router_topk", inputs=["X", "Weight", "Bias?"],
             outputs=["Experts", "Weights"], grad=None)
def moe_router_topk(ins, attrs, ctx):
    """``s = sigmoid(float32(x) @ float32(W))``; ``pick = top_k(s + b)``
    (the bias SELECTS only); ``w = s[pick]``, divided by ``sum(w) + 1e-20``
    where attr ``norm_topk_prob`` (default True), times attr
    ``routed_scaling_factor``.

    X [..., hidden]; Weight [hidden, E] over ALL published experts; Bias
    [E].  Experts [..., k] int32 (descending ``s + b``, the lower index
    first on a tie), Weights [..., k] float32 — normalised over all k
    picks wherever the picked experts live.  Forward only."""
    x = ins["X"].astype(_F32)
    s = jax.nn.sigmoid(jnp.matmul(x, ins["Weight"].astype(_F32),
                                  precision=_HI))
    choose = s if ins.get("Bias") is None else s + ins["Bias"].astype(_F32)
    _, pick = lax.top_k(choose, int(attrs["top_k"]))
    w = jnp.take_along_axis(s, pick, axis=-1)
    if attrs.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * float(attrs.get("routed_scaling_factor", 1.0))
    return {"Experts": pick.astype(jnp.int32), "Weights": w}


def _tile(size, cap=_TILE_KN):
    """The widest tile <= cap that divides `size` into whole lanes (a
    multiple of 128), else the whole axis."""
    if size <= cap:
        return size
    for t in range(cap - cap % 128, 0, -128):
        if size % t == 0:
            return t
    return size


def _grouped(rows, w, sizes, first):
    """``rows[group g's rows] @ w[g - first]`` for the held groups, zeros
    elsewhere, float32.  rows [M, K] sorted by group; w [held, K, N];
    sizes [E] rows a group."""
    k, n = w.shape[1], w.shape[2]
    return gmm(rows, w, sizes, preferred_element_type=_F32,
               tiling=(min(_TILE_M, rows.shape[0]), _tile(k), _tile(n)),
               group_offset=jnp.int32(first), interpret=_interpret())


@register_op("moe_grouped_experts",
             inputs=["X", "Experts!", "Weights", "W1", "W2", "Lengths?!"],
             outputs=["Out", "Stats"], grad=None)
def moe_grouped_experts(ins, attrs, ctx):
    """This chip's part of ``sum_{e in pick} w_e * (relu(x A_e)^2 B_e)``:
    the sum over the picked experts that are HELD here.

    X [..., D]; Experts [..., k] int32 in [0, n_experts); Weights [..., k]
    float32; W1 [held, D, F], W2 [held, F, D] the held experts' matrices,
    expert ``first_held + i`` at index i; Lengths [B] (optional, X then
    [B, T, D]): how many of a row's T positions are valid — a prompt's
    pads and an idle decode row (length 0) route nothing, their pairs join
    no group.  attrs ``n_experts`` (the published count the router
    scores), ``first_held``, ``held``.  Dropless: every pair of a held
    expert is computed, whatever the experts' loads (no capacity).

    Out [..., D] float32.  Stats [4] int32 (`STATS`): the pairs routed
    (valid positions x k, held or not), the pairs that landed on held
    experts, the held experts with at least one pair, and the largest
    held expert's load.  Forward only."""
    # a kernel of its own on the device (see `mamba2_state_update`): the
    # sort, the gathers and the un-sort keep this op's scope
    x, experts, weights = lax.optimization_barrier(
        (ins["X"], ins["Experts"], ins["Weights"]))
    lead, k = experts.shape[:-1], experts.shape[-1]
    total, first, held = (int(attrs[a]) for a in
                          ("n_experts", "first_held", "held"))
    w1, w2 = ins["W1"], ins["W2"]
    if w1.shape[0] != held or first < 0 or first + held > total:
        raise ValueError(
            f"moe_grouped_experts: W1 holds {w1.shape[0]} experts, attrs "
            f"say {held} from {first} of {total}")
    ids = experts.reshape(-1).astype(jnp.int32)
    m = ids.shape[0]
    if ins.get("Lengths") is not None:  # sentinel: sorts last, no group
        valid = _valid(ins["Lengths"], lead[0], lead[1]).reshape(-1)
        ids = jnp.where(jnp.repeat(valid, k), ids, total)
    order = jnp.argsort(ids, stable=True).astype(jnp.int32)
    sorted_ids = ids[order]
    sizes = jnp.zeros(total + 1, jnp.int32).at[ids].add(1)[:total]
    loads = lax.dynamic_slice(sizes, (first,), (held,))
    stats = jnp.stack([jnp.sum(sizes), jnp.sum(loads), jnp.sum(loads > 0),
                       jnp.max(loads)]).astype(jnp.int32)
    # whole row tiles for the kernel: pad rows join no group either
    pad = -m % min(_TILE_M, m + -m % 8)
    rows = jnp.pad(x.reshape(-1, x.shape[-1])[order // k],
                   ((0, pad), (0, 0)))
    hidden = _grouped(rows, w1, sizes, first)
    hidden = jnp.square(jax.nn.relu(hidden)).astype(x.dtype)
    out = _grouped(hidden, w2, sizes, first)[:m]
    # rows of no held group are not the kernel's to write (it zeroes them
    # only when it holds a part of the groups)
    mine = (sorted_ids >= first) & (sorted_ids < first + held)
    out = jnp.where(mine[:, None], out, 0.0) \
        * weights.reshape(-1).astype(_F32)[order][:, None]
    # un-sort: pair j of token t sits at sorted position inverse[t * k + j]
    inverse = jnp.zeros(m, jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32))
    out = jnp.sum(out[inverse].reshape(m // k, k, -1), axis=1)
    out, stats = lax.optimization_barrier(
        (out.reshape(lead + out.shape[-1:]), stats))
    return {"Out": out, "Stats": stats}
