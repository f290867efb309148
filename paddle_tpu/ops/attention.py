"""Attention kernels: flash attention (Pallas/TPU) + ring attention
(sequence parallelism over a mesh axis).

Reference capability: the reference's attention exists only as fused
inference kernels (operators/fused/multihead_matmul_op.cu,
math/bert_encoder_functor.cu) and it has NO long-context story
(SURVEY.md §5.7).  This module is the TPU-native upgrade the north star
requires:

  * `flash_attention` — block-wise online-softmax attention as a Pallas TPU
    kernel (VMEM-tiled, MXU matmuls, O(S) memory instead of the O(S^2)
    scores matrix).  Forward is the Pallas kernel; backward recomputes
    blocks through the reference formulation (jax.vjp), i.e. activation
    memory stays O(S).
  * `ring_attention` — sequence-parallel attention: each device of a mesh
    axis holds a sequence shard; K/V shards rotate around the ring via
    lax.ppermute while online-softmax statistics accumulate (RingAttention
    / blockwise-parallel-transformer pattern).  Compute overlaps the ICI
    transfer of the next shard.

Pallas runs in interpreter mode when the backend is the CPU and compiles
everywhere else; ring attention is pure jax and runs under any shard_map
mesh.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["flash_attention", "ring_attention", "reference_attention",
           "enable_flash_attention", "flash_enabled", "use_flash_for",
           "set_flash_min_seq_len"]

# reserved ring id binding the sequence-parallel mesh axis (user groups from
# paddle.distributed.new_group start at 1 and must not collide)
SP_RING_ID = 101

# mode: "auto" dispatches per call on sequence length.  Measured on the
# real v5e chip (r5, BERT-base bench): XLA's fused attention beats this
# Pallas kernel at EVERY length where both fit — 0.66x at seq 512,
# 0.73x at 2048, 0.75x at 4096 — so auto mode keeps the XLA path
# through the measured range and selects flash only from 8192 up, where
# the materialized [B,H,S,S] scores stop fitting HBM and the
# memory-frugal kernel is the difference between running and OOM.
# Explicit control: enable_flash_attention / FLAGS_use_flash_attention
# / the flash_min_seq_len flag; tools/tune_flash.py re-evaluates the
# crossover from block-size sweeps on hardware.
_FLASH_STATE = {"mode": "auto", "min_seq_len": 8192}


def enable_flash_attention(on: bool = True):
    """Force MultiHeadAttention / scaled_dot_product_attention through
    (on=True) or away from (on=False) the Pallas flash kernel,
    overriding the seq-length auto-dispatch
    (FLAGS_use_flash_attention analog)."""
    _FLASH_STATE["mode"] = "on" if on else "off"


def set_flash_min_seq_len(n: int):
    """Auto-dispatch crossover: sequences >= n take the flash kernel."""
    _FLASH_STATE["min_seq_len"] = int(n)


def flash_enabled() -> bool:
    """True when flash is FORCED on (legacy probe; prefer
    use_flash_for(seq_len))."""
    if _FLASH_STATE["mode"] == "on":
        return True
    from ..core.flags import flag
    return bool(flag("use_flash_attention", False))


def use_flash_for(seq_len) -> bool:
    """Per-callsite dispatch decision: forced on/off wins; in auto mode a
    STATIC sequence length >= the crossover threshold selects flash."""
    if _FLASH_STATE["mode"] == "on":
        return True
    from ..core.flags import flag
    if bool(flag("use_flash_attention", False)):
        return True
    if _FLASH_STATE["mode"] == "off":
        return False
    if seq_len is None or not isinstance(seq_len, int) or seq_len <= 0:
        return False  # dynamic/unknown seq: keep the XLA path
    thr = int(flag("flash_min_seq_len", _FLASH_STATE["min_seq_len"]))
    return seq_len >= thr


# ---------------------------------------------------------------------------
# reference (what the kernels are tested against; the path for biased calls)
# ---------------------------------------------------------------------------
def reference_attention(q, k, v, bias=None, causal=False, scale=None):
    """Plain softmax(QK^T)V.  q,k,v: [B, H, S, D] (float)."""
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if bias is not None:
        logits = logits + bias
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)


# ---------------------------------------------------------------------------
# Pallas flash attention (forward kernel)
# ---------------------------------------------------------------------------
def _causal_mask_block(s, qi, kb, block_q, block_k, sk, sq):
    """Apply the bottom-right-aligned causal mask to one [block_q, block_k]
    logits tile: query i attends keys <= i + (sk - sq) — matches
    reference_attention's tril(k=sk-sq).  Shared by fwd and both bwd
    kernels so the alignment can never drift between them."""
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (sk - sq)
    kpos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(qpos >= kpos, s, -jnp.inf)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, sk,
                      sq, causal, scale, block_q):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)  # query-block index (grid: B, H, Sq/block_q)
    # operands stay in their storage dtype (bf16 under AMP) so the MXU runs
    # at low-precision rate; accumulation is fp32 via preferred_element_type
    # and the scale folds into the fp32 scores
    q = q_ref[0, 0, :, :]                              # [block_q, d]

    m = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    n_kb = sk // block_k

    def body(kb, carry):
        m, l, acc = carry
        ks = k_ref[0, 0, pl.ds(kb * block_k, block_k), :]
        vs = v_ref[0, 0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk] f32
        if causal:
            s = _causal_mask_block(s, qi, kb, block_q, block_k, sk, sq)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # guard fully-masked rows (m_new == -inf): exp(-inf - -inf) → nan
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m)
        p = jnp.where(jnp.isfinite(m_new), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + jax.lax.dot_general(
            p.astype(vs.dtype), vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    if causal:
        # skip key blocks entirely above the (bottom-right) diagonal
        n_needed = jnp.minimum(
            n_kb, ((qi + 1) * block_q + (sk - sq) + block_k - 1) // block_k)
        m, l, acc = jax.lax.fori_loop(0, n_needed, body, (m, l, acc))
    else:
        m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m, l, acc))

    o_ref[0, 0, :, :] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # logsumexp per query row, saved for the blockwise backward
    lse = jnp.where(jnp.isfinite(m), m + jnp.log(jnp.maximum(l, 1e-30)),
                    -jnp.inf)
    lse_ref[0, 0, :, :] = lse


def _fit_block(n, want):
    """Largest block size <= `want` that tiles `n` evenly and satisfies the
    Mosaic sublane constraint (multiple of 8); None if impossible (a bare
    min() would refuse e.g. sq=384 with want=256 although 128 tiles it)."""
    for b in range(min(want, n), 7, -1):
        if n % b == 0 and b % 8 == 0:
            return b
    return None


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    """Returns (out, lse); lse is [B, H, Sq, 1] float32."""
    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = _fit_block(sq, block_q)
    block_k = _fit_block(sk, block_k)

    kernel = functools.partial(_flash_fwd_kernel, block_k=block_k, sk=sk,
                               sq=sq, causal=causal, scale=scale,
                               block_q=block_q)
    grid = (b, h, sq // block_q)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Pallas flash attention (backward kernels)
#
# Standard flash-attention backward: probabilities are recomputed per
# (q-block, k-block) tile from q, k and the saved logsumexp, so nothing
# O(S^2) is ever materialized.  Two kernels because TPU has no atomics:
#   dq  — grid over q blocks, inner loop over k blocks
#   dkv — grid over k blocks, inner loop over q blocks
# Both need D = rowsum(dO * O) (the softmax-jacobian correction), computed
# once outside as an elementwise reduce that XLA fuses.
# ---------------------------------------------------------------------------
def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                         dq_ref, *, block_k, sk, sq, causal, scale,
                         block_q):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    q = q_ref[0, 0, :, :]                              # [bq, d] storage dtype
    do = do_ref[0, 0, :, :]                            # [bq, d]
    lse = lse_ref[0, 0, :, :]                          # [bq, 1] f32
    dd = dd_ref[0, 0, :, :]                            # [bq, 1] f32
    safe_lse = jnp.where(jnp.isfinite(lse), lse, 0.0)

    n_kb = sk // block_k

    def body(kb, dq):
        ks = k_ref[0, 0, pl.ds(kb * block_k, block_k), :]
        vs = v_ref[0, 0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk] f32
        if causal:
            s = _causal_mask_block(s, qi, kb, block_q, block_k, sk, sq)
        p = jnp.where(jnp.isfinite(lse), jnp.exp(s - safe_lse), 0.0)
        dp = jax.lax.dot_general(
            do, vs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk] f32
        ds = p * (dp - dd)                               # [bq, bk] f32
        return dq + jax.lax.dot_general(
            ds.astype(ks.dtype), ks, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    dq = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    if causal:
        n_needed = jnp.minimum(
            n_kb, ((qi + 1) * block_q + (sk - sq) + block_k - 1) // block_k)
        dq = jax.lax.fori_loop(0, n_needed, body, dq)
    else:
        dq = jax.lax.fori_loop(0, n_kb, body, dq)
    dq_ref[0, 0, :, :] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                          dk_ref, dv_ref, *, block_k, sk, sq, causal,
                          scale, block_q):
    from jax.experimental import pallas as pl

    kb = pl.program_id(2)
    ks = k_ref[0, 0, :, :]                              # [bk, d] storage dtype
    vs = v_ref[0, 0, :, :]                              # [bk, d]

    n_qb = sq // block_q

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(qi * block_q, block_q), :]   # [bq, d]
        do = do_ref[0, 0, pl.ds(qi * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q), :]
        dd = dd_ref[0, 0, pl.ds(qi * block_q, block_q), :]
        safe_lse = jnp.where(jnp.isfinite(lse), lse, 0.0)
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk] f32
        if causal:
            s = _causal_mask_block(s, qi, kb, block_q, block_k, sk, sq)
        p = jnp.where(jnp.isfinite(lse), jnp.exp(s - safe_lse), 0.0)
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, d]
        dp = jax.lax.dot_general(
            do, vs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk] f32
        ds = (p * (dp - dd)).astype(q.dtype)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bk, d]
        return dk, dv

    dk = jnp.zeros((block_k, ks.shape[-1]), jnp.float32)
    dv = jnp.zeros((block_k, vs.shape[-1]), jnp.float32)
    if causal:
        # first q block that can see this k block: q_pos >= k_pos-(sk-sq)
        start = jnp.maximum(0, (kb * block_k - (sk - sq)) // block_q)
        dk, dv = jax.lax.fori_loop(start, n_qb, body, (dk, dv))
    else:
        dk, dv = jax.lax.fori_loop(0, n_qb, body, (dk, dv))
    dk_ref[0, 0, :, :] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0, :, :] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k,
               interpret):
    # NOTE: like the forward, the non-gridded operands (full K/V here, full
    # Q/dO/lse in the dkv kernel) are staged whole in VMEM, which caps the
    # single-chip sequence length at roughly S*D*4B ≲ a few MB (S ≈ 8-16k
    # at D=64).  Longer sequences are the ring_attention path's job; if a
    # single-chip >16k case appears, move these operands to ANY memory
    # space with explicit DMA per block.
    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = _fit_block(sq, block_q)
    block_k = _fit_block(sk, block_k)

    # D = rowsum(dO * O): elementwise + reduce, XLA fuses; O(S) memory
    dd = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                 axis=-1, keepdims=True)                 # [b, h, sq, 1]

    qspec = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0))
    qrow = pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0))
    full_q = pl.BlockSpec((1, 1, sq, d), lambda bi, hi, i: (bi, hi, 0, 0))
    full_qrow = pl.BlockSpec((1, 1, sq, 1), lambda bi, hi, i: (bi, hi, 0, 0))
    full_k = pl.BlockSpec((1, 1, sk, d), lambda bi, hi, i: (bi, hi, 0, 0))
    kspec = pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0))

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, block_k=block_k, sk=sk, sq=sq, causal=causal,
        scale=scale, block_q=block_q)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, sq // block_q),
        in_specs=[qspec, full_k, full_k, qspec, qrow, qrow],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        interpret=interpret,
    )(q, k, v, g, lse, dd)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, block_k=block_k, sk=sk, sq=sq, causal=causal,
        scale=scale, block_q=block_q)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, sk // block_k),
        in_specs=[full_q, kspec, kspec, full_q, full_qrow, full_qrow],
        out_specs=[kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, g, lse, dd)
    return dq, dk, dv


def _interpret() -> bool:
    """Pallas interpret mode exactly when the backend is the CPU, by name;
    every other backend compiles the kernels."""
    return jax.default_backend() == "cpu"


def _require_tiles(sq, sk, block_q, block_k):
    """The kernels tile Sq/Sk exactly.  A shape that cannot be tiled is an
    error: whoever selected flash (forced on, or past the auto crossover
    where materialized scores stop fitting) must not silently get the
    O(S^2) reference instead."""
    if _fit_block(sq, block_q) is None or _fit_block(sk, block_k) is None:
        raise ValueError(
            f"flash_attention: sequence lengths (q={sq}, k={sk}) do not "
            f"tile into blocks that are multiples of 8 (block_q<="
            f"{block_q}, block_k<={block_k}); pad the sequence or call "
            "reference_attention")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        interpret=_interpret())
    return out


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                          interpret=_interpret())
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q,
                      block_k, interpret=_interpret())


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, bias=None, causal=False, scale=None,
                    block_q=256, block_k=512):
    """Flash attention over [B, H, S, D] tensors.  `bias` forces the
    reference path (arbitrary bias breaks the blockwise max-trick bound
    chosen here; padding masks should be folded into K by the caller).

    Fully-masked rows (causal with sq > sk leaves the first sq-sk queries
    without any visible key) output ZERO here, while the reference path's
    finfo.min masking degrades to a uniform average of V — both values
    are semantically undefined; don't consume those rows."""
    if bias is not None:
        return reference_attention(q, k, v, bias=bias, causal=causal,
                                   scale=scale)
    from ..core.flags import flag
    block_q = int(flag("flash_block_q", block_q))
    block_k = int(flag("flash_block_k", block_k))
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    # the Pallas kernels keep operands in storage dtype for MXU rate, so
    # mixed q/k/v dtypes (bf16 queries over an fp32 KV cache) promote to a
    # common dtype first — lax.dot_general requires identical operands
    cdt = jnp.promote_types(jnp.promote_types(q.dtype, k.dtype), v.dtype)
    q, k, v = q.astype(cdt), k.astype(cdt), v.astype(cdt)
    _require_tiles(q.shape[2], k.shape[2], block_q, block_k)
    return _flash(q, k, v, causal, scale, block_q, block_k)


# ---------------------------------------------------------------------------
# ring attention (sequence parallel)
# ---------------------------------------------------------------------------
def ring_attention(q, k, v, axis_name: str, causal=False, scale=None):
    """Sequence-parallel attention inside shard_map: every device holds
    [B, H, S/n, D] shards (sequence dim sharded over `axis_name`); K/V
    rotate around the ring while online-softmax stats accumulate.

    Causal masking uses GLOBAL positions: device r's queries are rows
    [r*S_loc, (r+1)*S_loc); the k-th rotation holds keys of device
    (r - step) % n.
    """
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    s_loc = q.shape[2]

    def step_fn(carry, step):
        m, l, acc, ks, vs = carry
        src = (me - step) % n  # whose keys we currently hold
        # operands stay in storage dtype (bf16 MXU rate); scores accumulate
        # fp32 and the scale folds in afterwards
        s = jnp.einsum("bhqd,bhkd->bhqk", q, ks,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = src * s_loc + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 3)
            qp = me * s_loc + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 2)
            s = jnp.where(qp >= kpos, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(m_new), jnp.exp(s - safe_m), 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vs.dtype), vs,
            preferred_element_type=jnp.float32)
        # rotate K/V to the next device (overlaps with next step's compute)
        perm = [(i, (i + 1) % n) for i in range(n)]
        ks = jax.lax.ppermute(ks, axis_name, perm)
        vs = jax.lax.ppermute(vs, axis_name, perm)
        return (m_new, l_new, acc_new, ks, vs), None

    b, h = q.shape[0], q.shape[1]
    m0 = jnp.full((b, h, s_loc, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    a0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    (m, l, acc, _, _), _ = jax.lax.scan(
        step_fn, (m0, l0, a0, k, v), jnp.arange(n))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
