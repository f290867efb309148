"""Attention for a decoder whose KV cache lives on the device per slot, a
layer keeping either every column or a window's worth in a RING: rotary
positions, a prompt's blocked causal attention (window or none), the ring a
window layer keeps of a prompt, and the one-token step over the cache with
the new column written in place (to a trip count read from the rows, or to
a bound on their columns fixed at trace time).

All are FORWARD ONLY (``grad=None``): the serving path is their one caller
(the rotary op's gradient is what is left of ROADMAP R-a).

A window layer with window ``W`` lets query ``i`` see key ``j`` iff ``j <=
i`` and ``i - j < W``.  Its cache is a ring of ``W`` columns: the key of
position ``p`` lies at column ``p mod W``.  Positions are applied (rotary)
BEFORE a key is stored, and a softmax does not care in which order it
meets its keys, so the ring is never unrolled: a row of cache length ``n``
reads its first ``min(n, W)`` columns.  The new column of a decode step
overwrites the one that has just left the window.

Numerics: angles, scores' accumulation, the softmax and its statistics are
float32 whatever the activations' dtype; the matmuls take their operands as
given (bfloat16 when served).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import _fit_block, _interpret
from ..registry import register_op

_F32 = jnp.float32
# rows of queries a grid step takes per kv head (times the heads that share
# it), the columns of keys a turn of its loop reads, and the rows of the
# merged [heads x queries] axis a turn folds at a time (timed on the v5e at
# [1, 8, 16, T, 128], PERF.md PR 36: 128 x 512 x 256)
_BLOCK_Q, _BLOCK_K, _FOLD_ROWS = 128, 512, 256
# columns of the cache a turn of the decode step's loop reads, and the
# fewest a bounded read takes: a lane tile (an array of 64-wide heads lies
# with its columns along the lanes; for a narrower slice XLA:TPU turns the
# whole array into another layout and back, two copies of it a layer)
_DECODE_BLOCK, _DECODE_LANES = 512, 128
# positions of one head a grid step of the rotary kernel turns
_ROTARY_BLOCK = 2048
_VMEM_LIMIT = 64 << 20


def _pairs(x32, lane_even, nxt, prv):
    """The partner of lane 2i is -x[2i+1], of lane 2i+1 it is x[2i]: two
    lane rotations (`nxt`, `prv`: x turned one lane down / up), no
    [D/2, 2] reshape of the minor axis."""
    return jnp.where(lane_even, -nxt, prv)


def _rotary_kernel(x_ref, cos_ref, sin_ref, o_ref):
    x32 = x_ref[0, 0].astype(_F32)                              # [bt, D]
    d = x32.shape[-1]
    even = lax.rem(lax.broadcasted_iota(jnp.int32, x32.shape, 1),
                   jnp.int32(2)) == 0
    partner = _pairs(x32, even, pltpu.roll(x32, d - 1, 1),
                     pltpu.roll(x32, 1, 1))
    o_ref[0, 0] = (x32 * cos_ref[0] + partner * sin_ref[0]).astype(
        o_ref.dtype)


@register_op("rotary_embedding", inputs=["X", "Positions?!"],
             outputs=["Out"], grad=None)
def rotary_embedding(ins, attrs, ctx):
    """Rotary positions, interleaved pairs (the GPT-J form): dims ``(2i,
    2i + 1)`` of every head turn by ``position * theta^(-2i / D)``, all
    ``D`` dims.

    X [B, H, T, D]; Positions [B, T] int32 (default ``0..T-1``: a prompt;
    a decode row's position is its cache length).  Angles, cos and sin are
    float32 from the int32 positions; Out in X's dtype.  attr ``theta``.
    A prompt goes through one pass of a kernel (a block of positions of
    one head a grid step); a single position a row is a few elementwise
    ops.  Forward only."""
    x, pos = lax.optimization_barrier((ins["X"], ins.get("Positions")))
    b, h, t, d = x.shape
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    theta = float(attrs["theta"])
    inv = theta ** (-jnp.arange(0, d, 2, dtype=_F32) / d)       # [D/2]
    angle = pos.astype(_F32)[:, :, None] * inv                 # [B,T,D/2]
    cos = jnp.repeat(jnp.cos(angle), 2, axis=-1)                # [B,T,D]
    sin = jnp.repeat(jnp.sin(angle), 2, axis=-1)
    block = _fit_block(t, _ROTARY_BLOCK)
    if block is None:           # a decode step's one position a row
        x32 = x.astype(_F32)
        partner = _pairs(x32, jnp.arange(d) % 2 == 0,
                         jnp.roll(x32, -1, axis=-1), jnp.roll(x32, 1, axis=-1))
        out = (x32 * cos[:, None] + partner * sin[:, None]).astype(x.dtype)
        return {"Out": lax.optimization_barrier(out)}
    tile = pl.BlockSpec((1, 1, block, d), lambda bi, hi, ti: (bi, hi, ti, 0))
    angles = pl.BlockSpec((1, block, d), lambda bi, hi, ti: (bi, ti, 0))
    out = pl.pallas_call(
        _rotary_kernel, grid=(b, h, t // block),
        in_specs=[tile, angles, angles], out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=_interpret(), name="rotary_embedding")(x, cos, sin)
    return {"Out": lax.optimization_barrier(out)}


def _prefill_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                    acc_ref, *, block_q, block_k, heads, window, scale):
    """One block of `block_q` queries of the `G` heads that share a kv head
    against the key blocks it can see: online softmax over them.

    A block of queries that begins at or past the row's length (`len_ref`,
    prefetched) is not computed: zeros.

    Only the key blocks that can hide a key are masked, and they come
    first: the DIAGONAL blocks (those that hold a key some query of the
    block may not see yet), after which every row has met its own key, so
    that its running maximum is finite from there on, then the window's far
    EDGE blocks.  The INTERIOR blocks, every key of which every query sees,
    take no mask at all.

    A turn of a loop folds the block's `G * block_q` rows `heads` heads at
    a time, the statistics (`m_ref` the running maximum, `l_ref` the
    running sum kept as one partial sum a lane, `acc_ref`) in scratch
    between turns: the scores of all the rows at once ([2,048, 512] float32
    at the served shapes) are many times the vector registers, and a loop
    over them is bound by their spills on the one store slot a bundle has,
    not by the matmuls or the masks (PERF.md, PR 36)."""
    groups, d = q_ref.shape[2], q_ref.shape[4]
    lanes = m_ref.shape[1]
    rows = heads * block_q
    first = pl.program_id(2) * block_q
    last = first + (block_q - 1)
    valid = first < len_ref[pl.program_id(0)]

    def folds(fold):
        """`fold(c)` for each of the block's folds.  Written out, so that
        one fold's matmuls overlap the next one's softmax (rolled, a turn
        has 40% more bundles), but traced once."""
        lax.fori_loop(0, groups // heads, lambda c, _: fold(c), None,
                      unroll=True)

    @pl.when(jnp.logical_not(valid))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(valid)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, _F32)
        l_ref[...] = jnp.zeros(l_ref.shape, _F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)
        # a row of the merged [heads * block_q] axis is query first + row %
        # block_q; a mask compares a column of rows with a row of columns
        qpos = first + lax.rem(
            lax.broadcasted_iota(jnp.int32, (rows, 1), 0),
            jnp.int32(block_q))
        col = lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

        def block(j, masked):
            at = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
            ks, vs = k_ref[0, 0, at, :], v_ref[0, 0, at, :]

            def fold(c):
                part = pl.ds(pl.multiple_of(c * rows, rows), rows)
                q = q_ref[0, 0, pl.ds(c * heads, heads)].reshape(rows, d)
                s = lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                                    preferred_element_type=_F32) * scale
                if masked:
                    rel = qpos - j * block_k        # the row's own column
                    seen = col <= rel
                    if window:
                        seen &= col > rel - window
                    s = jnp.where(seen, s, -jnp.inf)
                m = m_ref[part, :]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                # a row none of whose keys lay in the blocks so far:
                # exp(-inf + inf); only before its own key's block
                safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0) \
                    if masked else m_new
                p = jnp.exp(s - jnp.tile(safe, (1, block_k // lanes)))
                alpha = jnp.exp(m - safe)
                partial = p[:, :lanes]
                for i in range(1, block_k // lanes):
                    partial = partial + p[:, i * lanes:(i + 1) * lanes]
                m_ref[part, :] = m_new
                l_ref[part, :] = alpha * l_ref[part, :] + partial
                acc_ref[part, :] = (
                    jnp.tile(alpha, (1, d // lanes)) * acc_ref[part, :]
                    + lax.dot_general(
                        p.astype(vs.dtype), vs, (((1,), (0,)), ((), ())),
                        preferred_element_type=_F32))
            folds(fold)

        # whole blocks: [lo, edge) hold a key outside SOME query's window
        # (from the first with a key inside the first query's), [edge,
        # diag) neither edge, [diag, hi) a key after the first query
        diag, hi = (first + 1) // block_k, last // block_k + 1
        if window:
            lo = jnp.maximum(first - window + 1, 0) // block_k
            edge = jnp.clip(jnp.maximum(last - window + block_k, 0)
                            // block_k, lo, diag)
        else:
            lo = edge = 0
        # ONE loop over the masked blocks, diagonal then edge (a loop is a
        # trace of the folds, and a program holds a kernel a layer)
        lax.fori_loop(
            0, hi - diag + edge - lo,
            lambda i, _: block(jnp.where(i < hi - diag, diag + i,
                                         lo + i - (hi - diag)), True), None)
        lax.fori_loop(edge, diag, lambda j, _: block(j, False), None)

        def normalize(c):
            part = pl.ds(pl.multiple_of(c * rows, rows), rows)
            l = jnp.sum(l_ref[part, :], axis=-1, keepdims=True)
            o_ref[0, 0, pl.ds(c * heads, heads)] = (
                acc_ref[part, :] / l).reshape(heads, block_q, d).astype(
                    o_ref.dtype)
        folds(normalize)


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "block_q", "block_k", "fold_rows", "interpret"))
def _prefill_attention(q, k, v, lengths, *, window, scale, block_q, block_k,
                       fold_rows, interpret):
    """Q [B, Hkv, G, T, D] against K, V [B, Hkv, T, D], T whole blocks.  A
    function of its own under the program's trace (as `_write_columns`):
    the layers of one kind, and every pass that traces a program, share
    ONE trace and ONE lowering of the kernel a bucket."""
    b, hkv, groups, t, d = q.shape
    # the heads of a block a turn of the key loop folds at a time
    heads = max(h for h in range(1, groups + 1)
                if groups % h == 0 and (h == 1 or h * block_q <= fold_rows))
    kernel = functools.partial(_prefill_kernel, block_q=block_q,
                               block_k=block_k, heads=heads, window=window,
                               scale=scale)
    whole = pl.BlockSpec((1, 1, t, d), lambda bi, hi, qi, _: (bi, hi, 0, 0))
    # a skipped block's queries are not fetched: the last valid block's
    # tile stays where it is
    queries = pl.BlockSpec(
        (1, 1, groups, block_q, d), lambda bi, hi, qi, lens: (
            bi, hi, 0, jnp.minimum(qi, jnp.maximum(lens[bi] - 1, 0)
                                   // block_q), 0))
    out = pl.BlockSpec((1, 1, groups, block_q, d),
                       lambda bi, hi, qi, _: (bi, hi, 0, qi, 0))
    # one value a row is kept across `lanes` lanes: a whole register's at
    # the served shapes, so that no turn broadcasts or reduces across them
    lanes = math.gcd(128, block_k, d)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, hkv, t // block_q),
            in_specs=[queries, whole, whole], out_specs=out,
            scratch_shapes=[pltpu.VMEM((groups * block_q, lanes), _F32),
                            pltpu.VMEM((groups * block_q, lanes), _F32),
                            pltpu.VMEM((groups * block_q, d), _F32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="windowed_prefill_attention",
    )(lengths, q, k, v)


@register_op("windowed_prefill_attention",
             inputs=["Q", "K", "V", "Lengths?!"], outputs=["Out"], grad=None)
def windowed_prefill_attention(ins, attrs, ctx):
    """Causal grouped-query attention of a whole prompt among its own
    tokens, over blocks of queries: `[T, T]` scores never exist for all
    heads, and a window layer reads only the key blocks inside the window,
    so its work is ``sum_i min(i, W)``, not ``T^2 / 2``.

    Q [B, Hq, T, D]; K, V [B, Hkv, T, D] (positions already applied);
    Lengths [B] (optional) the valid positions a row, the pads after them.
    attrs ``scale`` (default ``D^-0.5``), ``window`` (0: every earlier key
    is seen).  A pad's KEY needs no lengths: it lies after every valid
    token and causality hides it.  A pad's own ROW is nobody's to read —
    the ring a window layer keeps (`kv_ring_pack`) and the decode step
    read by length, the head takes the last valid row, the experts route
    valid positions only, and no valid query sees a pad — so with Lengths
    a block of queries that lies wholly past its row's length is not
    computed and comes back as zeros (the block the length falls in is
    computed whole).  Without Lengths every position is valid and every
    row is computed (the op itself pads T to whole sublanes with rows it
    then drops).  Forward only."""
    q, k, v = lax.optimization_barrier((ins["Q"], ins["K"], ins["V"]))
    b, hq, t, d = q.shape
    hkv = k.shape[1]
    groups = hq // hkv
    window = int(attrs.get("window", 0) or 0)
    scale = float(attrs.get("scale", d ** -0.5))
    lengths = ins.get("Lengths")
    lengths = jnp.full((b,), t, jnp.int32) if lengths is None \
        else lengths.astype(jnp.int32).reshape(b)
    real, pad = t, -t % 8
    if pad:     # whole sublanes: trailing rows, which causality hides
        q, k, v = (jnp.pad(y, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for y in (q, k, v))
        t += pad
    block_q, block_k = _fit_block(t, _BLOCK_Q), _fit_block(t, _BLOCK_K)
    out = _prefill_attention(
        q.reshape(b, hkv, groups, t, d), k, v, lengths, window=window,
        scale=scale, block_q=block_q, block_k=block_k, fold_rows=_FOLD_ROWS,
        interpret=_interpret())
    out = out.reshape(b, hq, t, d)[:, :, :real]
    return {"Out": lax.optimization_barrier(out)}


@register_op("kv_ring_pack", inputs=["X", "Lengths!"], outputs=["Out"],
             grad=None)
def kv_ring_pack(ins, attrs, ctx):
    """The ring a window layer keeps of a prompt: column ``c`` of Out holds
    the key (or value) of the LAST valid position ``p < Lengths[b]`` with
    ``p mod window == c`` — the last ``min(length, window)`` positions,
    each at its own column; a column no valid position maps to holds
    whatever lies at position ``c`` (a pad's, never read).

    X [B, Hkv, T, D]; Lengths [B]; attr ``window``.  Out [B, Hkv, window,
    D].  Forward only."""
    x = ins["X"]
    t, w = x.shape[2], int(attrs["window"])
    if t <= w:
        return {"Out": jnp.pad(x, ((0, 0), (0, 0), (0, w - t), (0, 0)))}
    col = jnp.arange(w, dtype=jnp.int32)[None, :]
    last = ins["Lengths"].astype(jnp.int32).reshape(-1, 1) - 1
    pos = col + w * jnp.maximum((last - col) // w, 0)           # [B, W]
    pos = jnp.minimum(pos, t - 1)
    return {"Out": jnp.take_along_axis(x, pos[:, None, :, None], axis=2)}


def _at(*index):
    """Indices of one dtype (int32), whatever x64 makes of a Python int."""
    return tuple(jnp.asarray(i, jnp.int32) for i in index)


@jax.jit
def _write_columns(cache, cols, index, pos):
    """`cols` [S, Hkv, 1, D] into `cache` [La, S, Hkv, L, D], row `s` at
    `(index, s, :, pos[s])`: a chain of one-column updates, each of which
    XLA makes where the array lies, so a donated cache is never copied (a
    loop over the rows, a scatter, or reading the old column first each
    cost one copy of the whole array on the v5e's compiler).  A function
    of its own under the step's trace (`index` a traced scalar): every
    layer's K and V call the ONE chain the module holds, which XLA inlines
    — written out per call it was a third of a decode program's text and
    doubled the time to lower it."""
    cols = cols[None].astype(cache.dtype)               # [1, S, Hkv, 1, D]
    zero = jnp.int32(0)
    for s in range(cols.shape[1]):
        cache = lax.dynamic_update_slice(
            cache, lax.slice_in_dim(cols, s, s + 1, axis=1),
            (index, jnp.int32(s), zero, pos[s], zero))
    return cache


@register_op("cached_decode_attention",
             inputs=["Q", "K", "V", "KCache", "VCache", "CacheLengths!",
                     "Active!"],
             outputs=["Out", "NewKCache", "NewVCache"], grad=None)
def cached_decode_attention(ins, attrs, ctx):
    """One token a row over its cached keys and values, the new column
    written first, in place.

    Q [S, Hq, 1, D]; K, V [S, Hkv, 1, D] the new token's (positions
    applied); KCache, VCache [La, S, Hkv, L, D] the whole arrays of a
    cache group, this layer's entry at attr ``slab_index``; CacheLengths
    [S] the tokens a row holds BEFORE this one; Active [S] 1 for a row
    that takes its token (an idle row reads nothing, its Out row is
    nobody's to read, and its new column lands at the column its
    CacheLengths names: where its own next token will be written, a
    column no query of its sequence can see before that; for a slot
    without a sequence the engine passes 0, which the slot's next prompt
    overwrites).  attr ``window``: 0, the group keeps
    every column and the new one goes to column ``length``; W = L, the
    group is a ring and it goes to ``length mod W``.  attr ``scale``.

    HOW MUCH OF THE ARRAY A STEP READS.  attr ``columns`` 0 (a ring's
    description, ONE program whatever its rows hold): the row reads its
    first ``min(length + 1, L)`` columns, block by block up to the longest
    row's — a trip count read from the data.  ``columns`` = n > 0 (no ring;
    the engine's power-of-two bucket over the longest live row, so a
    program a bucket): every row's EARLIER tokens lie in the first n
    columns, which are read in ``n / block`` turns fixed at trace time (one
    up to 512 columns; never under a lane tile of 128, masked), and the new
    token is attended from K, V as given, not read back.  NewKCache,
    NewVCache: the whole arrays, updated where the caller donated them.
    Forward only."""
    q, k, v, kc, vc = lax.optimization_barrier(
        (ins["Q"], ins["K"], ins["V"], ins["KCache"], ins["VCache"]))
    s, hq, _, d = q.shape
    hkv, columns = kc.shape[2], kc.shape[3]
    index = int(attrs["slab_index"])
    window = int(attrs.get("window", 0) or 0)
    bound = int(attrs.get("columns", 0) or 0)
    scale = float(attrs.get("scale", d ** -0.5))
    if window and (window != columns or bound):
        raise ValueError(
            f"cached_decode_attention: a ring of {columns} columns for a "
            f"window of {window}, read to a bound of {bound}")
    if bound > columns:
        raise ValueError(f"cached_decode_attention: {bound} columns of an "
                         f"array that holds {columns}")
    lengths = ins["CacheLengths"].astype(jnp.int32)
    active = ins["Active"].astype(jnp.int32)
    pos = lengths % columns if window else jnp.minimum(lengths, columns - 1)
    entry = jnp.int32(index)
    kc = _write_columns(kc, k, entry, pos)
    vc = _write_columns(vc, v, entry, pos)
    # the columns of the array a row sees: with a bound its earlier tokens
    # (the new one is folded in from K, V below), else the new one too
    read = min(columns, max(bound, _DECODE_LANES)) if bound else columns
    valid = jnp.where(active > 0,
                      jnp.minimum(lengths + (0 if bound else 1), read), 0)
    block = _fit_block(read, _DECODE_BLOCK) or read
    qg = q.reshape(s, hkv, hq // hkv, d)
    col = jnp.arange(block, dtype=jnp.int32)

    def fold(carry, kb, vb, seen):
        """Online softmax over one more block of keys `kb`, values `vb`
        [S, Hkv, C, D], `seen` [S, C]."""
        m, l, acc = carry
        sc = jnp.einsum("shgd,shcd->shgc", qg, kb,
                        preferred_element_type=_F32) * scale
        sc = jnp.where(seen[:, None, None, :], sc, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(sc - safe)
        alpha = jnp.exp(m - safe)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "shgc,shcd->shgd", p.astype(q.dtype), vb,
            preferred_element_type=_F32)
        return m_new, l, acc

    def body(j, carry):
        at, size = _at(index, 0, 0, j * block, 0), (1, s, hkv, block, d)
        kb = lax.dynamic_slice(kc, at, size)[0].astype(q.dtype)
        vb = lax.dynamic_slice(vc, at, size)[0].astype(q.dtype)
        return fold(carry, kb, vb,
                    (j * block + col)[None, :] < valid[:, None])

    shape = (s, hkv, hq // hkv)
    carry = (jnp.full(shape + (1,), -jnp.inf, _F32),
             jnp.zeros(shape + (1,), _F32), jnp.zeros(shape + (d,), _F32))
    if bound:
        for j in range(read // block):
            carry = body(j, carry)
        carry = fold(carry, k.astype(q.dtype), v.astype(q.dtype),
                     active[:, None] > 0)
    else:
        carry = lax.fori_loop(0, (jnp.max(valid) + block - 1) // block,
                              body, carry)
    _, l, acc = carry
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype).reshape(s, hq, 1, d)
    out, kc, vc = lax.optimization_barrier((out, kc, vc))
    return {"Out": out, "NewKCache": kc, "NewVCache": vc}
