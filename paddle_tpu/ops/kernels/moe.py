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

Tiling (`grouped_tiling`, from the operand shapes alone): megablox walks
a grid of (n tiles, active row tiles, k tiles), k innermost, and fetches
the weight block ``(expert, k_i, n_i)`` again whenever its index changes.
With two or more k tiles it changes on every grid step, and an expert
that holds hundreds of a prompt's rows has its weights read again for
every 128 of them.  So the k tile is the WHOLE contraction wherever the blocks fit
`_VMEM_BYTES` with an n tile of at least 512 lanes (the widest that fits,
narrowed before the whole contraction is given up): the weight block's
index then stays put across one expert's consecutive row tiles and Pallas
skips the copy, each expert's ``[K, tn]`` read once per n tile.  Where
nothing fits (openPangu's W1, K 7,680) the tiles are `_tile(K)` and
`_tile(N)`.  Where an expert's rows fit one tile (every decode step) the
bytes read are the same and the grid is shorter.  The row tile stays 128:
a row tile that two groups share computes both groups' rows for each, so
a taller one wastes MXU rows at every group boundary.  A trace counts
``ops.moe.gmm_whole_k`` (matmuls traced with the whole contraction).

Numerics: router logits, sigmoid, top-k and the weights are float32 at
``Precision.HIGHEST`` (the published code computes its gate in float32);
the expert matmuls take their operands as given (bfloat16 when served)
and accumulate in float32 (a whole-contraction tile adds the same float32
partial products in another order).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import gmm

from ...core.monitor import stat_add
from ..attention import _interpret
from .ssm import _valid
from ..registry import register_op

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
# rows of the sorted pairs a grid step takes (a group's rows are masked
# inside a tile that straddles two groups), and the widest n tile (and k
# tile, where the whole contraction does not fit)
_TILE_M, _TILE_KN = 128, 1024
# the narrowest n tile a whole-contraction k tile is taken with
_TILE_N_MIN = 512
# VMEM the blocks of a whole-contraction tiling may take (`_vmem_bytes`):
# megablox asks for the default scoped VMEM, 16 MiB on the v5e, and the
# chip-free compile for the v5e takes 15.9 MB by this count (K 4,096, tn
# 768) and refuses 16.3 MB (K 7,680, tn 384) and 20.4 MB (K 4,096, tn 1,024)
_VMEM_BYTES = 14 << 20
# a call with more token-expert pairs than this (a prompt of thousands of
# tokens) sorts the HELD pairs to the front and computes them in blocks of
# this many rows, so the pairs of absent experts are never gathered to full
# width: [N * k, F] float32 for 8,192 tokens x 8 picks would be 2.1 GB.
# (A smaller call takes the path it had before; held-first in one block
# runs it as fast on the v5e, 2.55 against 2.59 ms at 64 rows x 22 picks:
# the fork is ROADMAP D10's to delete, with the cells' pairs measured.)
_BLOCK_ROWS = 16384
STATS = ("routed", "pairs", "touched", "max_load")
ACTIVATIONS = ("relu2", "silu_gated")


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


def _tiles(size, cap=_TILE_KN):
    """The tiles of `size` no wider than `cap`, widest first: the whole
    axis where it is no wider, then the whole-lane (multiple of 128)
    divisors."""
    whole = [size] if size <= cap else []
    return whole + [t for t in range(cap - cap % 128, 0, -128)
                    if t < size and size % t == 0]


def _tile(size, cap=_TILE_KN):
    """The widest tile <= cap that divides `size` into whole lanes (a
    multiple of 128), else the whole axis."""
    return next(iter(_tiles(size, cap)), size)


def _vmem_bytes(tm, tk, tn, itemsize):
    """VMEM of a grouped matmul's blocks: the weight [tk, tn] and the rows
    [tm, tk], each twice buffered, the float32 output [tm, tn] twice and
    its accumulator once."""
    return 2 * (tk * tn + tm * tk) * itemsize + 3 * tm * tn * 4


def grouped_tiling(m, k, n, itemsize=2):
    """``(tm, tk, tn)`` of `_grouped` over [m, k] rows and [held, k, n]
    weights of `itemsize` bytes: tk the whole contraction with the widest
    n tile of at least `_TILE_N_MIN` lanes (or the whole of a narrower n)
    whose blocks fit `_VMEM_BYTES`; where none does, `_tile(k)` and
    `_tile(n)`."""
    tm = min(_TILE_M, m)
    for tn in _tiles(n):
        if tn >= min(_TILE_N_MIN, n) and \
                _vmem_bytes(tm, k, tn, itemsize) <= _VMEM_BYTES:
            return tm, k, tn
    return tm, _tile(k), _tile(n)


def _grouped(rows, w, sizes, first):
    """``rows[group g's rows] @ w[g - first]`` for the held groups, zeros
    elsewhere, float32.  rows [M, K] sorted by group; w [held, K, N];
    sizes [E] rows a group."""
    k, n = w.shape[1], w.shape[2]
    tiling = grouped_tiling(rows.shape[0], k, n,
                            max(rows.dtype.itemsize, w.dtype.itemsize))
    if tiling[1] == k:
        stat_add("ops.moe.gmm_whole_k")
    return gmm(rows, w, sizes, preferred_element_type=_F32, tiling=tiling,
               group_offset=jnp.int32(first), interpret=_interpret())


def _expert_pair(rows, w1, w2, sizes, first, activation, dtype):
    """The held experts' two grouped matmuls over sorted `rows` with the
    activation between them (rounded to `dtype`): float32 [M, D]."""
    hidden = _grouped(rows, w1, sizes, first)
    if activation == "silu_gated":      # W1 = [gate | up], each F wide
        gate, up = jnp.split(hidden, 2, axis=-1)
        hidden = jax.nn.silu(gate) * up
    else:
        hidden = jnp.square(jax.nn.relu(hidden))
    return _grouped(hidden.astype(dtype), w2, sizes, first)


def _held_first(x, ids, weights, w1, w2, first, held, k, activation):
    """The weighted sum over the held pairs for a call of many tokens:
    [N, D] float32.  The pairs are sorted HELD FIRST (by expert among
    them; absent and masked pairs last, in no group), and blocks of
    `_BLOCK_ROWS` sorted rows are computed one after another until the
    held pairs are through, each block's rows added to their tokens: what
    is gathered, multiplied and kept at full width is a block, whatever
    share of the pairs is held (dropless: every held pair is in a block).
    ids [M] int32 (a masked pair's is outside the held range)."""
    m, n = ids.shape[0], x.shape[0]
    local = jnp.where((ids >= first) & (ids < first + held), ids - first,
                      held)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    loads = jnp.zeros(held + 1, jnp.int32).at[local].add(1)[:held]
    ends = jnp.cumsum(loads)
    weight = weights.astype(_F32)[order]
    block = _BLOCK_ROWS

    def body(carry):
        start, out = carry
        tokens = lax.dynamic_slice(order, (start,), (block,)) // k
        # a group's rows inside [start, start + block)
        sizes = jnp.clip(ends - start, 0, block) \
            - jnp.clip(ends - loads - start, 0, block)
        y = _expert_pair(x[tokens], w1, w2, sizes, 0, activation, x.dtype)
        row = jnp.arange(block, dtype=jnp.int32)
        mine = row < jnp.sum(sizes)     # rows past the held pairs: not the
        y = jnp.where(mine[:, None], y, 0.0) \
            * lax.dynamic_slice(weight, (start,), (block,))[:, None]
        return start + block, out.at[tokens].add(y)

    # the last block may reach past M: pad the sorted order (its rows lie
    # past the held pairs, `mine` drops them)
    order = jnp.pad(order, (0, -m % block))
    weight = jnp.pad(weight, (0, -m % block))
    _, out = lax.while_loop(lambda c: c[0] < ends[-1], body,
                            (jnp.int32(0), jnp.zeros((n, w2.shape[2]), _F32)))
    return out


@register_op("moe_grouped_experts",
             inputs=["X", "Experts!", "Weights", "W1", "W2", "Lengths?!"],
             outputs=["Out", "Stats"], grad=None)
def moe_grouped_experts(ins, attrs, ctx):
    """This chip's part of ``sum_{e in pick} w_e * (act(x A_e) B_e)``:
    the sum over the picked experts that are HELD here.  attr
    ``activation``: ``relu2`` (default) ``act(h) = relu(h)^2``, W1 [held,
    D, F]; ``silu_gated`` ``act([g | u]) = silu(g) * u``, W1 [held, D, 2F]
    the gate and the up projection side by side.

    X [..., D]; Experts [..., k] int32 in [0, n_experts); Weights [..., k]
    float32; W1, W2 [held, F, D] the held experts' matrices,
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
    activation = attrs.get("activation", "relu2")
    if activation not in ACTIVATIONS:
        raise ValueError(f"moe_grouped_experts: activation {activation!r} "
                         f"is not one of {ACTIVATIONS}")
    if w1.shape[0] != held or first < 0 or first + held > total:
        raise ValueError(
            f"moe_grouped_experts: W1 holds {w1.shape[0]} experts, attrs "
            f"say {held} from {first} of {total}")
    ids = experts.reshape(-1).astype(jnp.int32)
    m = ids.shape[0]
    if ins.get("Lengths") is not None:  # sentinel: sorts last, no group
        valid = _valid(ins["Lengths"], lead[0], lead[1]).reshape(-1)
        ids = jnp.where(jnp.repeat(valid, k), ids, total)
    sizes = jnp.zeros(total + 1, jnp.int32).at[ids].add(1)[:total]
    loads = lax.dynamic_slice(sizes, (first,), (held,))
    stats = jnp.stack([jnp.sum(sizes), jnp.sum(loads), jnp.sum(loads > 0),
                       jnp.max(loads)]).astype(jnp.int32)
    if m > _BLOCK_ROWS:
        out = _held_first(x.reshape(-1, x.shape[-1]), ids,
                          weights.reshape(-1), w1, w2, first, held, k,
                          activation)
        out, stats = lax.optimization_barrier(
            (out.reshape(lead + out.shape[-1:]), stats))
        return {"Out": out, "Stats": stats}
    order = jnp.argsort(ids, stable=True).astype(jnp.int32)
    sorted_ids = ids[order]
    # whole row tiles for the kernel: pad rows join no group either
    pad = -m % min(_TILE_M, m + -m % 8)
    rows = jnp.pad(x.reshape(-1, x.shape[-1])[order // k],
                   ((0, pad), (0, 0)))
    out = _expert_pair(rows, w1, w2, sizes, first, activation, x.dtype)[:m]
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
