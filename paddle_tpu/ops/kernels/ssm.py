"""State-space (Mamba-2) and hybrid-decoder ops: RMS norms, a causal
depthwise conv with a carried tail, the chunked state-space scan (SSD,
Dao & Gu 2024) and its one-token update, grouped-query attention over a
dense cache, and the gated short-convolution mixer (last in this file).

All are FORWARD ONLY (``grad=None``): the serving path is their one
caller; gradients and the chunked scan's backward are what is left of
ROADMAP R-h.

Lengths are an input wherever a recurrence or a cache is carried.  A
prompt padded to its compile bucket must leave exactly the state of the
unpadded prompt, and an idle decode row must leave its state as it was:
at positions >= a row's length ``dt`` is forced to 0 (decay ``exp(0) = 1``
and nothing added: the state passes through bit for bit) and the conv's
new tail is taken from the last valid positions.

Numerics: the state ``S``, ``dt``, ``exp(dt A)``, softmax and the norms'
statistics are float32 whatever the activations' dtype; the contractions
that read or write ``S`` run at ``Precision.HIGHEST`` (on a TPU a float32
matmul is otherwise truncated to bfloat16 on its way into the MXU, and
what a prefill rounds away every later decode step inherits).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import _interpret
from ..registry import register_op

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_NEG = -1e30


def _valid(lengths, batch, steps):
    """[batch, steps] bool: position < the row's length (all True
    without lengths)."""
    if lengths is None:
        return jnp.ones((batch, steps), bool)
    return jnp.arange(steps)[None, :] < lengths.reshape(-1, 1)


def _rms(x32, eps):
    return x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)


@register_op("rms_norm", inputs=["X", "Scale"], outputs=["Out"], grad=None)
def rms_norm(ins, attrs, ctx):
    """``x / sqrt(mean(x^2) + eps) * w`` over the last axis, statistics
    in float32, result in X's dtype.  Forward only."""
    x = ins["X"]
    y = _rms(x.astype(_F32), attrs.get("epsilon", 1e-5))
    return {"Out": (y * ins["Scale"].astype(_F32)).astype(x.dtype)}


@register_op("gated_rms_norm", inputs=["X", "Gate", "Scale"],
             outputs=["Out"], grad=None)
def gated_rms_norm(ins, attrs, ctx):
    """``RMSNorm(x * silu(gate)) * w``: the gate is applied BEFORE the norm
    (Mamba-2's output norm).  attr ``groups`` (default 1): the statistics
    are taken over each of ``groups`` equal parts of the last axis on its
    own (``mean(y^2)`` over ``C / groups`` channels; Nemotron-H: 8 groups
    of 1,024), one group being the whole axis; the weight is per channel
    either way.  Forward only."""
    x = ins["X"]
    groups = int(attrs.get("groups", 1))
    y = x.astype(_F32) * jax.nn.silu(ins["Gate"].astype(_F32))
    if groups > 1:
        y = y.reshape(y.shape[:-1] + (groups, y.shape[-1] // groups))
    y = _rms(y, attrs.get("epsilon", 1e-5)).reshape(x.shape)
    return {"Out": (y * ins["Scale"].astype(_F32)).astype(x.dtype)}


@register_op("causal_conv1d",
             inputs=["X", "Weight", "Bias?", "Tail?", "Lengths?!"],
             outputs=["Out", "NewTail"], grad=None)
def causal_conv1d(ins, attrs, ctx):
    """Depthwise causal conv over a sequence with a carried tail.

    X [B, T, C]; Weight [C, K]; Tail [B, K-1, C] the K-1 inputs before
    X[:, 0] (zeros when absent: the start of a sequence); Lengths [B] how
    many of the T positions are valid.  ``Out[t] = act(sum_j W[:, j] *
    in[t - (K-1) + j] + b)``; NewTail is the last K-1 inputs up to each
    row's length, so a row of length 0 keeps its tail.  attr
    ``activation``: "silu" (default) or "".  attr ``slab_index`` >= 0:
    Tail is the whole ``[layers, B, K-1, C]`` array of a state pool, this
    layer's tail is ``Tail[slab_index]`` and NewTail is the whole array
    with that entry replaced (updated in place by XLA, no stack of the
    layers' tails afterwards).  Forward only."""
    x, w = ins["X"], ins["Weight"]
    b, t, c = x.shape
    k = w.shape[1]
    tail = slab = ins.get("Tail")
    index = int(attrs.get("slab_index", -1))
    if index >= 0:
        slab = jnp.asarray(slab)
        tail = slab[index]
    if tail is None:
        tail = jnp.zeros((b, k - 1, c), x.dtype)
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B,K-1+T,C]
    acc = jnp.zeros((b, t, c), _F32)
    w32 = w.astype(_F32)
    for j in range(k):
        acc = acc + full[:, j:j + t].astype(_F32) * w32[:, j]
    if ins.get("Bias") is not None:
        acc = acc + ins["Bias"].astype(_F32)
    if attrs.get("activation", "silu") == "silu":
        acc = jax.nn.silu(acc)
    lengths = ins.get("Lengths")
    if lengths is None:
        new_tail = full[:, t:]
    else:
        idx = lengths.reshape(-1, 1).astype(jnp.int32) + jnp.arange(k - 1)
        new_tail = jnp.take_along_axis(full, idx[:, :, None], axis=1)
    if index >= 0:
        new_tail = slab.at[index].set(new_tail.astype(slab.dtype))
    return {"Out": acc.astype(x.dtype), "NewTail": new_tail}


def _dt_and_decay(dt, dt_bias, a, valid):
    """float32 (dt, dt * A) with dt = softplus(dt + bias) where a bias is
    given, forced to 0 where `valid` is False."""
    dt = dt.astype(_F32)
    if dt_bias is not None:
        dt = jax.nn.softplus(dt + dt_bias.astype(_F32))
    dt = jnp.where(valid[..., None], dt, 0.0)
    return dt, dt * a.astype(_F32)


@register_op("mamba2_chunk_scan",
             inputs=["X", "Dt", "A", "B", "C", "D", "DtBias?",
                     "InitialState?", "Lengths?!"],
             outputs=["Y", "FinalState"], grad=None)
def mamba2_chunk_scan(ins, attrs, ctx):
    """The Mamba-2 recurrence over a whole sequence in chunks (SSD):

        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,   y_t = S_t C_t + D x_t

    X [B, T, H, P]; Dt [B, T, H] (softplus(Dt + DtBias) where DtBias [H]
    is given); A [H] (negative); B, C [B, T, G, N] with H % G == 0; D [H];
    InitialState [B, H, P, N] float32 (zeros when absent); Lengths [B].
    Quadratic inside a chunk of attr ``chunk_size`` positions, the state
    carried between chunks.  Y in X's dtype, FinalState float32 — the
    state after each row's last VALID position.  Forward only."""
    # a kernel of its own on the device (see `mamba2_state_update`)
    x, bm, cm, dt_in = lax.optimization_barrier(
        (ins["X"], ins["B"], ins["C"], ins["Dt"]))
    b, t, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    q = int(attrs.get("chunk_size", 256))
    q = t if q <= 0 or q > t else q
    pad = -t % q
    valid = _valid(ins.get("Lengths"), b, t)
    dt, da = _dt_and_decay(dt_in, ins.get("DtBias"), ins["A"], valid)
    x32 = x.astype(_F32)
    xdt = x32 * dt[..., None]                               # [B,T,H,P]
    bm32, cm32 = bm.astype(_F32), cm.astype(_F32)           # [B,T,G,N]
    if pad:     # trailing zeros: dt = 0 there, the state passes through
        da, xdt, bm32, cm32 = (
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (da, xdt, bm32, cm32))
    nc, r = (t + pad) // q, h // g

    def chunks(v):
        return v.reshape((b, nc, q) + v.shape[2:])

    da, xdt, bm32, cm32 = chunks(da), chunks(xdt), chunks(bm32), chunks(cm32)
    cs = jnp.cumsum(da, axis=2)                             # [B,nc,Q,H]
    # inside a chunk: y_i += sum_{j<=i} exp(cs_i - cs_j) (C_i . B_j) xdt_j
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]       # [B,nc,Q,Q,H]
    tri = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    cb = jnp.einsum("bcign,bcjgn->bcijg", cm32, bm32, precision=_HI)
    y = jnp.einsum("bcijh,bcjhp->bcihp", jnp.repeat(cb, r, -1) * decay, xdt,
                   precision=_HI)
    # what each chunk adds to the state by its end, and its total decay
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)                 # [B,nc,Q,H]
    add = jnp.einsum(
        "bcjgn,bcjgrp->bcgrpn", bm32,
        (xdt * to_end[..., None]).reshape(b, nc, q, g, r, p),
        precision=_HI).reshape(b, nc, h, p, n)
    total = jnp.exp(cs[:, :, -1, :])                        # [B,nc,H]
    s0 = ins.get("InitialState")
    s0 = jnp.zeros((b, h, p, n), _F32) if s0 is None else s0.astype(_F32)

    def carry(s, inp):
        add_c, total_c = inp
        return total_c[:, :, None, None] * s + add_c, s     # emit s ENTERING

    final, entering = lax.scan(
        carry, s0, (jnp.moveaxis(add, 1, 0), jnp.moveaxis(total, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                 # [B,nc,H,P,N]
    y = y + jnp.einsum(
        "bcign,bcgrpn->bcigrp", cm32,
        entering.reshape(b, nc, g, r, p, n),
        precision=_HI).reshape(b, nc, q, h, p) * jnp.exp(cs)[..., None]
    y = y.reshape(b, t + pad, h, p)[:, :t]
    y = y + x32 * ins["D"].astype(_F32)[None, None, :, None]
    y, final = lax.optimization_barrier((y.astype(x.dtype), final))
    return {"Y": y, "FinalState": final}


# a part of a row's entry [H * P, N] goes through VMEM, in and out, each
# double-buffered: four of them must fit beside the small operands
_SLAB_VMEM_BYTES = 12 << 20
_TILE = 128     # rows and lanes of the square blocks the kernel transposes


def _slab_parts(h, p, n):
    """Into how many equal parts of whole heads' [128, 128] blocks
    `_slab_update` cuts a row's entry [H * P, N] so that four parts fit
    the VMEM budget (1: the entry whole; 2 at 128 heads x 64 x 128, whose
    entry is 4.2 MB); a part's lane-dense `xdt` / `y` rows are whole
    (8, 128) registers.  0: no such cut."""
    blocks = h * p // _TILE
    for parts in range(1, blocks + 1):
        per = blocks // parts
        if blocks % parts or (parts > 1 and per % 8):
            continue
        if 4 * per * _TILE * n * 4 <= _SLAB_VMEM_BYTES:
            return parts
    return 0


def _slab_update_fits(slab, h, p, n):
    """Whether `_slab_update` takes this state array: float32, a row's
    entry made of whole [128, 128] blocks, each block whole heads or a
    part of one head on whole (8, 128) registers, and a cut of the entry
    (`_slab_parts`) within the VMEM budget.  Read from shapes alone: the
    same answer on every backend."""
    return (slab.dtype == _F32 and n % _TILE == 0 and p % 8 == 0
            and (h * p) % _TILE == 0
            and (_TILE % p == 0 or p % _TILE == 0)
            and _slab_parts(h, p, n) > 0)


@functools.partial(jax.jit, static_argnames="interpret")
def _slab_update(slab, index, decay, xdt, bm, cm, interpret):
    """``slab[index] <- decay * slab[index] + xdt (x) bm`` where it lies,
    and the read-out ``sum_n(new * cm)`` from the values being written:
    ONE read and ONE write of the layer's entry (a Pallas kernel; XLA
    keeps the in-place write and the reduction as two fusions that each
    read the entry).  slab [L, B, H, P, N] float32, aliased to the first
    result; index an int32 scalar; decay [B, H]; xdt [B, H, P]; bm, cm
    [B, G, N].  Returns (the slab, y [B, H, P]).

    `index` is data (a prefetched scalar the block indices read) and the
    function a `jax.jit` of its own, so every layer of every decode
    program shares ONE trace and ONE lowering of the kernel: with the
    index baked in, 36 kernels a program were traced and lowered apiece
    and the cell's warm set-up went from 105 to 157 s (644 s with the
    kernel unrolled; 110-117 s now; PERF.md section 6, PR 28).

    One grid step a batch row and part of its entry (`_slab_parts`: the
    VMEM budget holds per part, not per entry, so 128 heads go in two
    halves); a part is walked as [rows, N] in
    [128, 128] blocks, a few to a loop iteration (one block an iteration
    leaves the units idle between blocks: 169 us a layer on the v5e
    against 106-109 for two to eight, where a bare copy of the entry
    through VMEM takes 104).  `xdt` and `y` stay lane-dense ([B, H * P /
    128, 128]): a block's 128 values of `xdt` become per-row factors, and
    its 128 row sums a lane-dense row of `y`, through one 128 x 128
    transpose each — cheaper than a lane broadcast and a lane reduction a
    register (118 us)."""
    layers, b, h, p, n = slab.shape
    r, rows = h // bm.shape[1], h * p
    parts = _slab_parts(h, p, n)
    blocks = rows // _TILE // parts                 # of one part
    size = min(p, _TILE)            # rows of one head inside a block
    unroll = next(u for u in (8, 4, 2, 1) if blocks % u == 0)

    def kernel(index_ref, decay_ref, x_ref, b_ref, c_ref, s_ref, new_ref,
               y_ref):
        del index_ref               # read by the block indices
        row = pl.program_id(0)
        k0 = pl.program_id(1) * blocks      # this part's first block

        def block(k):
            x_rows = jnp.broadcast_to(x_ref[0, pl.ds(k, 1), :],
                                      (_TILE, _TILE)).T   # [i, :] = xdt[i]
            y = jnp.zeros((1, _TILE), _F32)
            for n0 in range(0, n, _TILE):
                lanes = slice(n0, n0 + _TILE)
                weighted = []
                for j in range(_TILE // size):
                    head = (k0 + k) * (_TILE // p) + j if p <= _TILE \
                        else (k0 + k) * _TILE // p
                    at = pl.ds(pl.multiple_of(k * _TILE + j * size, 8), size)
                    group = pl.ds(head // r, 1)
                    new = decay_ref[row, head] * s_ref[0, 0, at, lanes] \
                        + x_rows[j * size:(j + 1) * size] \
                        * b_ref[0, group, lanes]
                    new_ref[0, 0, at, lanes] = new
                    weighted.append(new * c_ref[0, group, lanes])
                y = y + jnp.sum(jnp.concatenate(weighted, axis=0).T, axis=0,
                                keepdims=True)
            y_ref[0, pl.ds(k, 1), :] = y

        def several(i, carry):
            for u in range(unroll):
                block(i * unroll + u)
            return carry

        lax.fori_loop(0, blocks // unroll, several, 0)

    entry = pl.BlockSpec((1, 1, rows // parts, n),
                         lambda i, j, index_ref: (index_ref[0], i, j, 0))
    per_row = [pl.BlockSpec((1,) + v.shape[1:], lambda i, j, _: (i, 0, 0))
               for v in (bm, cm)]
    dense = pl.BlockSpec((1, blocks, _TILE), lambda i, j, _: (i, j, 0))
    new, y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, parts),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), dense,
                      *per_row, entry],
            out_specs=[entry, dense]),
        out_shape=[jax.ShapeDtypeStruct((layers, b, rows, n), _F32),
                   jax.ShapeDtypeStruct((b, blocks * parts, _TILE), _F32)],
        input_output_aliases={5: 0},
        interpret=interpret,
        name="mamba2_state_update",
    )(index.reshape(1), decay, xdt.reshape(b, blocks * parts, _TILE), bm, cm,
      slab.reshape(layers, b, rows, n))      # the reshapes are bitcasts
    return new.reshape(slab.shape), y.reshape(b, h, p)


@register_op("mamba2_state_update",
             inputs=["X", "Dt", "A", "B", "C", "D", "State", "DtBias?",
                     "Lengths?!"],
             outputs=["Y", "NewState"], grad=None)
def mamba2_state_update(ins, attrs, ctx):
    """One token of the Mamba-2 recurrence per row (the decode step).

    X [B, H, P]; Dt [B, H]; A, D, DtBias [H]; B, C [B, G, N]; State
    [B, H, P, N] float32; Lengths [B] 1 for a row that takes the token, 0
    for an idle row, whose state comes back bit for bit.  No matmul: the
    update and the read-out are elementwise passes over the state, which
    is what bounds them.  attr ``slab_index`` >= 0: State is the whole
    ``[layers, B, H, P, N]`` array of a state pool, this layer's state is
    ``State[slab_index]`` and NewState the whole array with that entry
    replaced — in place where the caller has donated the array, and, for
    shapes `_slab_update` takes, in one pass: the entry read once and
    written once, the read-out taken from what is written.  Forward only."""
    # a kernel of its own on the device: without the barriers XLA fuses
    # the state's read or its write into a neighbour's fusion, which then
    # carries the neighbour's scope (the first chip trace read 158% of
    # this op's roofline: part of its traffic sat under `forward/stack`)
    x, slab, dt_in, b_in, c_in = lax.optimization_barrier(
        (ins["X"], ins["State"], ins["Dt"], ins["B"], ins["C"]))
    index = int(attrs.get("slab_index", -1))
    b, h, p = x.shape
    g = b_in.shape[1]
    valid = _valid(ins.get("Lengths"), b, 1)[:, 0]
    dt, da = _dt_and_decay(dt_in, ins.get("DtBias"), ins["A"], valid)
    x32 = x.astype(_F32)
    b32, c32 = b_in.astype(_F32), c_in.astype(_F32)         # [B,G,N]
    if index >= 0 and _slab_update_fits(slab, h, p, b32.shape[-1]):
        new, y = _slab_update(slab, jnp.int32(index), jnp.exp(da),
                              x32 * dt[..., None], b32, c32,
                              interpret=_interpret())
    else:
        s = (slab[index] if index >= 0 else slab).astype(_F32)
        bm = jnp.repeat(b32, h // g, axis=1)                # [B,H,N]
        cm = jnp.repeat(c32, h // g, axis=1)
        new = jnp.exp(da)[:, :, None, None] * s \
            + (x32 * dt[..., None])[..., None] * bm[:, :, None, :]
        y = jnp.sum(new * cm[:, :, None, :], axis=-1)
        if index >= 0:
            new = slab.at[index].set(new.astype(slab.dtype))
    y = y + x32 * ins["D"].astype(_F32)[None, :, None]
    y, new = lax.optimization_barrier((y.astype(x.dtype), new))
    return {"Y": y, "NewState": new}


@register_op("gqa_attention",
             inputs=["Q", "K", "V", "KCache?", "VCache?", "CacheLengths?!"],
             outputs=["Out"], grad=None)
def gqa_attention(ins, attrs, ctx):
    """Grouped-query attention without positions.

    Q [B, Hq, T, D]; K, V [B, Hkv, T, D] the same T new tokens, causal
    among themselves (Hq % Hkv == 0: each kv head serves Hq / Hkv query
    heads); KCache, VCache [B, Hkv, L, D] earlier tokens of which the
    first CacheLengths[b] columns are valid (every new token sees them).
    Scores are ``q . k * scale`` (attr ``scale``: the model's own
    multiplier, not 1/sqrt(D) by default), softmax in float32.
    Forward only."""
    q, k, v = ins["Q"], ins["K"], ins["V"]
    b, hq, t, d = q.shape
    hkv = k.shape[1]
    scale = float(attrs.get("scale", d ** -0.5))
    qg = q.reshape(b, hkv, hq // hkv, t, d)
    new = jnp.einsum("bhgtd,bhsd->bhgts", qg, k,
                     preferred_element_type=_F32) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = [jnp.where(causal, new, _NEG)]
    values = [v]
    kc = ins.get("KCache")
    if kc is not None and kc.shape[2]:
        old = jnp.einsum("bhgtd,bhsd->bhgts", qg, kc.astype(q.dtype),
                         preferred_element_type=_F32) * scale
        seen = _valid(ins.get("CacheLengths"), b, kc.shape[2])
        scores.insert(0, jnp.where(seen[:, None, None, None, :], old, _NEG))
        values.insert(0, ins["VCache"].astype(q.dtype))
    probs = jax.nn.softmax(jnp.concatenate(scores, -1), axis=-1)
    out = jnp.einsum("bhgts,bhsd->bhgtd", probs.astype(q.dtype),
                     jnp.concatenate(values, 2),
                     preferred_element_type=_F32)
    return {"Out": out.reshape(b, hq, t, d).astype(q.dtype)}


@register_op("short_conv",
             inputs=["X", "WIn", "Weight", "WOut", "Tail?", "Lengths?!"],
             outputs=["Out", "NewTail"], grad=None)
def short_conv(ins, attrs, ctx):
    """The gated short-convolution mixer whole (LFM2's), one op so that
    its device time has one scope: ``[B | C | x] = X WIn`` (three equal
    parts), ``u = B * x``, ``c`` = `causal_conv1d` of ``u`` over its
    carried tail (no bias, no activation), ``Out = (C * c) WOut``.

    X [B, T, C]; WIn [C, 3C]; Weight [C, K]; WOut [C, C]; Tail, Lengths
    and attr ``slab_index`` as `causal_conv1d`'s: NewTail is the last K-1
    ``u`` rows up to each row's length (a row of length 0 keeps its tail),
    written into the state array's entry where it lies.  The matmuls take
    their operands as given and accumulate in float32; ``u`` and the gate
    are products in X's dtype.  Forward only."""
    x, w_in, w_out, tail = lax.optimization_barrier(
        (ins["X"], ins["WIn"], ins["WOut"], ins.get("Tail")))
    bcx = jnp.matmul(x, w_in, preferred_element_type=_F32).astype(x.dtype)
    gate_b, gate_c, xs = jnp.split(bcx, 3, axis=-1)
    conv = causal_conv1d(
        {"X": gate_b * xs, "Weight": ins["Weight"], "Tail": tail,
         "Lengths": ins.get("Lengths")},
        {"activation": "", "slab_index": attrs.get("slab_index", -1)}, ctx)
    out = jnp.matmul(gate_c * conv["Out"], w_out,
                     preferred_element_type=_F32).astype(x.dtype)
    out, new_tail = lax.optimization_barrier((out, conv["NewTail"]))
    return {"Out": out, "NewTail": new_tail}
