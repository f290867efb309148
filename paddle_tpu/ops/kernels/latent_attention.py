"""Multi-head latent attention (MLA, the DeepSeek-V2/V3 form) for a decoder
whose cache keeps ONE row a token and layer, shared by all heads: ``[c_kv |
k_pe]``, the normed latent (``kv_rank`` wide) and the rotated key
positions (``rope`` wide) — 576 values where an expanded cache of 128 heads
would keep 40,960.

Three ops, forward only (``grad=None``: the serving path is their one
caller):

- `mla`, the block's projections and norms (its device scope is
  ``forward/mla``), in two parts: ``in`` — from the layer's input to the
  queries and to the new cache rows — and ``out`` — from the attention's
  context to the layer's output — each in one of two forms.  ``expanded``
  (a prompt): per-head keys ``[k_nope_h | k_pe]`` and values ``v_h`` from
  ``c_kv W_kvb``.  ``absorbed`` (a decode step): the query is folded into
  the latent, ``q~_h = W_UK,h q_nope_h``, so that one cache row is read
  for all heads; the context comes back in the latent and leaves through
  ``W_UV,h``.  Both are the same mathematics (matmuls regrouped).
- `latent_prefill_attention`: a prompt's causal attention in the expanded
  form, queries and keys ``nope + rope`` wide, values ``v`` wide: the
  blocked Pallas kernel of `window_attention` (``window`` 0), its V width
  its own, blocks of queries past the prompt's length not computed, at the
  block shape `prompt_blocks` gives one query head a kv head.
- `latent_decode_attention`: one token a row in the absorbed form over the
  cache group's whole array: the new row written where the array lies,
  then ONE Pallas kernel a layer, grid (rows, column blocks), in which the
  128 heads of a row are the rows of the MXU tile against a block of the
  row's own cache rows; a block past the row's length is neither fetched
  nor computed.

Rotary positions turn interleaved pairs ``(2i, 2i + 1)`` of the ``rope``
dims by ``position * theta^(-2i / rope)`` (DeepSeek-V3's layout, whose
checkpoints de-interleave the same pairs).  Numerics: norms' statistics,
angles, scores, the softmax and its statistics are float32; matmuls take
their operands as given (bfloat16 when served) and accumulate in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import _fit_block, _interpret
from ..registry import register_op
from .window_attention import _VMEM_LIMIT, _prefill_attention, prompt_blocks

__all__ = ["mla", "latent_prefill_attention", "latent_decode_attention",
           "decode_block", "mla_attrs", "DECODE_BLOCK"]

_F32 = jnp.float32
# cache rows a grid step of the decode kernel reads: [1,024, 576] bfloat16
# is 1.2 MB, twice buffered well inside VMEM
DECODE_BLOCK = 1024
PARTS, FORMS = ("in", "out"), ("expanded", "absorbed")


def _mm(a, b):
    return jnp.matmul(a, b, preferred_element_type=_F32)


def _rms(x, weight, eps):
    x32 = x.astype(_F32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * weight.astype(_F32)).astype(x.dtype)


def _rope_pairs(x, pos, theta):
    """x [..., T, D] turned to positions `pos` [..., T] (broadcast over
    the axes between), interleaved pairs, float32 arithmetic."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=_F32) / d)
    angle = pos.astype(_F32)[..., None] * inv                 # [..., T, D/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pair = x.astype(_F32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pair[..., 0], pair[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


@register_op("mla", inputs=["X?", "Positions?!", "WqA?", "QNorm?", "WqB?",
                            "WkvA?", "KvNorm?", "WkvB?", "Ctx?", "Wo?"],
             outputs=["Q", "K", "V", "Latent", "Out"], grad=None)
def mla(ins, attrs, ctx):
    """The latent attention block's projections and norms.

    attrs ``part`` (`PARTS`), ``form`` (`FORMS`), ``heads``, ``nope``,
    ``rope``, ``v_dim``, ``kv_rank``, ``theta``, ``eps``.

    part ``in``: X [B, T, hidden]; Positions [B, T] int32 (default
    ``0..T-1``); WqA [hidden, q_rank], QNorm [q_rank], WqB [q_rank, heads x
    (nope + rope)], WkvA [hidden, kv_rank + rope], KvNorm [kv_rank], WkvB
    [kv_rank, heads x (nope + v_dim)].  ``c_q = rms(X WqA)``, ``[q_nope |
    q_pe]_h = (c_q WqB)_h``; ``[c_kv | k_pe] = X WkvA``, ``c_kv`` normed;
    q_pe and k_pe turned to their positions.  Latent [B, T, kv_rank + rope]
    = ``[c_kv | k_pe]`` (what the cache keeps).  ``expanded``: Q, K [B,
    heads, T, nope + rope] (``k_h = [k_nope_h | k_pe]``), V [B, heads, T,
    v_dim].  ``absorbed`` (T = 1): Q [B, heads, kv_rank + rope] = ``[q~_h |
    q_pe_h]``, Latent [B, kv_rank + rope].

    part ``out``: ``expanded``: Ctx [B, heads, T, v_dim], Wo [heads x
    v_dim, hidden] -> Out [B, T, hidden].  ``absorbed``: Ctx [B, heads,
    kv_rank] (the context in the latent), WkvB, Wo -> Out [B, 1, hidden].
    Forward only."""
    part, form = attrs["part"], attrs["form"]
    if part not in PARTS or form not in FORMS:
        raise ValueError(f"mla: part {part!r}, form {form!r}")
    heads, nope, rope, dv, rank = (int(attrs[k]) for k in (
        "heads", "nope", "rope", "v_dim", "kv_rank"))
    if part == "out":
        c, wo = lax.optimization_barrier((ins["Ctx"], ins["Wo"]))
        if form == "absorbed":
            w_uv = ins["WkvB"].reshape(rank, heads, nope + dv)[..., nope:]
            c = jnp.einsum("shc,chv->shv", c, w_uv,
                           preferred_element_type=_F32).astype(c.dtype)
            c = c[:, None]                                 # [S, 1, heads, v]
        else:
            c = jnp.transpose(c, (0, 2, 1, 3))             # [B, T, heads, v]
        out = _mm(c.reshape(c.shape[:2] + (heads * dv,)), wo).astype(c.dtype)
        return {"Out": lax.optimization_barrier(out)}
    x = lax.optimization_barrier(ins["X"])
    b, t = x.shape[0], x.shape[1]
    eps, theta = float(attrs["eps"]), float(attrs["theta"])
    pos = ins.get("Positions")
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t)) \
        if pos is None else pos.astype(jnp.int32).reshape(b, t)
    cq = _rms(_mm(x, ins["WqA"]).astype(x.dtype), ins["QNorm"], eps)
    q = _mm(cq, ins["WqB"]).astype(x.dtype).reshape(b, t, heads, nope + rope)
    kv = _mm(x, ins["WkvA"]).astype(x.dtype)
    ckv = _rms(kv[..., :rank], ins["KvNorm"], eps)            # [B, T, rank]
    k_pe = _rope_pairs(kv[..., rank:], pos, theta)             # [B, T, rope]
    q_pe = _rope_pairs(jnp.transpose(q[..., nope:], (0, 2, 1, 3)),
                      pos[:, None], theta)                 # [B, heads, T, r]
    latent = jnp.concatenate([ckv, k_pe], -1)
    w_kvb = ins["WkvB"]
    if form == "absorbed":
        w_uk = w_kvb.reshape(rank, heads, nope + dv)[..., :nope]
        q_lat = jnp.einsum("shd,chd->shc", q[:, 0, :, :nope], w_uk,
                           preferred_element_type=_F32).astype(x.dtype)
        q = jnp.concatenate([q_lat, q_pe[:, :, 0]], -1)    # [S, heads, W]
        q, latent = lax.optimization_barrier((q, latent[:, 0]))
        return {"Q": q, "Latent": latent}
    kvb = _mm(ckv, w_kvb).astype(x.dtype).reshape(b, t, heads, nope + dv)
    kvb = jnp.transpose(kvb, (0, 2, 1, 3))                 # [B, heads, T, .]
    q = jnp.concatenate(
        [jnp.transpose(q[..., :nope], (0, 2, 1, 3)), q_pe], -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_pe[:, None], (b, heads, t, rope))], -1)
    q, k, v, latent = lax.optimization_barrier(
        (q, k, kvb[..., nope:], latent))
    return {"Q": q, "K": k, "V": v, "Latent": latent}


@register_op("latent_prefill_attention",
             inputs=["Q", "K", "V", "Lengths?!"], outputs=["Out"], grad=None)
def latent_prefill_attention(ins, attrs, ctx):
    """Causal attention of a whole prompt among its own tokens, a head a
    key: Q, K [B, H, T, D] (positions applied), V [B, H, T, Dv]; Lengths
    [B] (optional) the valid positions a row.  attr ``scale`` (default
    ``D^-0.5``).  Out [B, H, T, Dv]; a block of queries wholly past its
    row's length is not computed and comes back as zeros (nobody reads a
    pad's row: `windowed_prefill_attention` says why).  Forward only."""
    q, k, v = lax.optimization_barrier((ins["Q"], ins["K"], ins["V"]))
    b, h, t, d = q.shape
    dv = v.shape[-1]
    scale = float(attrs.get("scale", d ** -0.5))
    lengths = ins.get("Lengths")
    lengths = jnp.full((b,), t, jnp.int32) if lengths is None \
        else lengths.astype(jnp.int32).reshape(b)
    real, pad = t, -t % 8
    if pad:     # whole sublanes: trailing rows, which causality hides
        q, k, v = (jnp.pad(y, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for y in (q, k, v))
        t += pad
    block_q, block_k, fold_rows = prompt_blocks(t, 1)
    out = _prefill_attention(
        q.reshape(b, h, 1, t, d), k, v, lengths, window=0, scale=scale,
        block_q=block_q, block_k=block_k, fold_rows=fold_rows,
        interpret=_interpret(), name="latent_prefill_attention")
    out = out.reshape(b, h, t, dv)[:, :, :real]
    return {"Out": lax.optimization_barrier(out)}


@jax.jit
def _write_rows(cache, rows, index, pos):
    """`rows` [S, W] into `cache` [La, S, C, W], row `s` at `(index, s,
    pos[s])`: a chain of one-row updates, each made where the array lies
    (as `window_attention._write_columns`, and for the same reasons: a
    donated cache is never copied, and the layers share one trace)."""
    rows = rows[None, :, None, :].astype(cache.dtype)       # [1, S, 1, W]
    zero = jnp.int32(0)
    for s in range(rows.shape[1]):
        cache = lax.dynamic_update_slice(
            cache, lax.slice_in_dim(rows, s, s + 1, axis=1),
            (index, jnp.int32(s), pos[s], zero))
    return cache


def _decode_kernel(len_ref, layer_ref, q_ref, new_ref, c_ref, o_ref, m_ref,
                   l_ref, acc_ref, *, block, rank, scale):
    """Row `s`'s heads [H, W] against block `j` of its cache rows [block,
    W]: online softmax, the statistics lane-replicated in scratch.  The
    row's own new token starts the statistics (it is not read back); the
    blocks at or past the row's length (`len_ref`) are not computed."""
    s, j = pl.program_id(0), pl.program_id(1)
    n = len_ref[s]
    q = q_ref[0]                                            # [H, W]

    @pl.when(j == 0)
    def _():
        new = new_ref[0].astype(_F32)                       # [1, W]
        sc = jnp.sum(q.astype(_F32) * new, axis=-1, keepdims=True) * scale
        m_ref[...] = jnp.broadcast_to(sc, m_ref.shape)
        l_ref[...] = jnp.ones(l_ref.shape, _F32)
        acc_ref[...] = jnp.broadcast_to(new[:, :rank], acc_ref.shape)

    @pl.when(j * block < n)
    def _():
        kv = c_ref[0, 0]                                    # [block, W]
        sc = lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                             preferred_element_type=_F32) * scale
        col = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
        sc = jnp.where(col < n, sc, -jnp.inf)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new[:, :1])
        alpha = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + lax.dot_general(
            p.astype(kv.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "block",
                                             "interpret"))
def _latent_decode(q, new, cache, lengths, index, *, scale, rank, block,
                   interpret):
    """Q [S, H, W], new [S, W], cache [La, S, C, W] (entry `index`, a
    traced scalar: the layers share one trace), lengths [S] the earlier
    rows each reads -> [S, H, rank] in Q's dtype."""
    s, h, w = q.shape
    columns = cache.shape[2]
    lanes = 128

    def rows(si, j, lens, layer):
        # a block at or past the length is not fetched: the last one read
        # stays where it is
        return (layer[0], si, jnp.minimum(j, jnp.maximum(lens[si] - 1, 0)
                                          // block), 0)

    return pl.pallas_call(
        functools.partial(_decode_kernel, block=block, rank=rank,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(s, columns // block),
            in_specs=[pl.BlockSpec((1, h, w), lambda si, j, *_: (si, 0, 0)),
                      pl.BlockSpec((1, 1, w), lambda si, j, *_: (si, 0, 0)),
                      pl.BlockSpec((1, 1, block, w), rows)],
            out_specs=pl.BlockSpec((1, h, rank),
                                   lambda si, j, *_: (si, 0, 0)),
            scratch_shapes=[pltpu.VMEM((h, lanes), _F32),
                            pltpu.VMEM((h, lanes), _F32),
                            pltpu.VMEM((h, rank), _F32)]),
        out_shape=jax.ShapeDtypeStruct((s, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="latent_decode_attention",
    )(lengths, index.reshape(1), q, new[:, None], cache)


def decode_block(columns):
    """Cache rows a grid step of `latent_decode_attention` reads from an
    array of `columns` (a power of two)."""
    return _fit_block(columns, DECODE_BLOCK) or columns


@register_op("latent_decode_attention",
             inputs=["Q", "New", "Cache", "CacheLengths!", "Active!"],
             outputs=["Out", "NewCache"], grad=None)
def latent_decode_attention(ins, attrs, ctx):
    """One token a row in the absorbed form, the new cache row written
    first, in place.

    Q [S, H, W] (``[q~_h | q_pe_h]``, W = kv_rank + rope); New [S, W] the
    token's own ``[c_kv | k_pe]``; Cache [La, S, C, Wc] the whole array of
    the latent group (Wc >= W: a row in whole lanes, zeros past W), this
    layer's entry at attr ``slab_index``;
    CacheLengths [S] the rows a sequence holds BEFORE this token (its new
    row goes to that column); Active [S] 1 for a row that takes its token
    (an idle row reads nothing; its Out row is nobody's and its write lands
    where its own next token will be written, as `cached_decode_attention`
    says).  attrs ``scale``, ``kv_rank``.

    Out [S, H, kv_rank]: ``sum_j p_j c_kv_j`` over the row's earlier rows
    and its new one, ``p = softmax((q~ . c_kv + q_pe . k_pe) * scale)``.
    NewCache: the whole array, updated where the caller donated it.
    Forward only."""
    q, new, cache = lax.optimization_barrier(
        (ins["Q"], ins["New"], ins["Cache"]))
    columns, pad = cache.shape[2], cache.shape[3] - q.shape[-1]
    if pad:     # a row in whole lanes: zeros past the row's values
        q, new = (jnp.pad(y, [(0, 0)] * (y.ndim - 1) + [(0, pad)])
                  for y in (q, new))
    lengths = ins["CacheLengths"].astype(jnp.int32)
    active = ins["Active"].astype(jnp.int32)
    index = jnp.int32(int(attrs["slab_index"]))
    cache = _write_rows(cache, new, index,
                        jnp.minimum(lengths, columns - 1))
    read = jnp.where(active > 0, jnp.minimum(lengths, columns), 0)
    out = _latent_decode(
        q, new, cache, read, index,
        scale=float(attrs.get("scale", q.shape[-1] ** -0.5)),
        rank=int(attrs["kv_rank"]), block=decode_block(columns),
        interpret=_interpret())
    out, cache = lax.optimization_barrier((out, cache))
    return {"Out": out, "NewCache": cache}


def mla_attrs(spec, heads, eps, part, form):
    """`mla`'s attrs from a `LatentSpec`-shaped description."""
    return {"part": part, "form": form, "heads": int(heads),
            "nope": int(spec.qk_nope_head_dim),
            "rope": int(spec.qk_rope_head_dim),
            "v_dim": int(spec.v_head_dim), "kv_rank": int(spec.kv_lora_rank),
            "theta": float(spec.rope_theta), "eps": float(eps)}
