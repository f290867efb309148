"""Loss ops (reference: softmax_with_cross_entropy_op.cc, cross_entropy_op.cc,
bce_loss_op.cc, nll_loss_op.cc, huber_loss, smooth_l1_loss, log_loss,
kldiv_loss, sigmoid_cross_entropy_with_logits, mse ...)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..registry import register_op


def _compute_dtype(x):
    """f32 accumulation for half types; preserve f32/f64."""
    return jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype


@register_op("softmax_with_cross_entropy", inputs=["Logits", "Label!"],
             outputs=["Softmax", "Loss"])
def softmax_with_cross_entropy(ins, attrs, ctx):
    logits, label = ins["Logits"], ins["Label"]
    axis = attrs.get("axis", -1) % logits.ndim
    soft_label = attrs.get("soft_label", False)
    ignore_index = attrs.get("ignore_index", -100)
    cdt = _compute_dtype(logits)
    lf = logits.astype(cdt)
    logp = jax.nn.log_softmax(lf, axis=axis)
    sm = jnp.exp(logp)
    if soft_label:
        loss = -jnp.sum(label.astype(cdt) * logp, axis=axis, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == logits.ndim and lbl.shape[axis] == 1:
            lbl = jnp.squeeze(lbl, axis)
        lbl = lbl.astype(jnp.int32)
        picked = jnp.take_along_axis(
            logp, jnp.expand_dims(jnp.clip(lbl, 0, logits.shape[axis] - 1),
                                  axis), axis=axis)
        loss = -picked
        # ignored rows zero out REGARDLESS of the index's sign (the
        # reference default is -100; softmax_with_cross_entropy_op.h
        # compares equality, not sign)
        loss = jnp.where(jnp.expand_dims(lbl, axis) == ignore_index,
                         jnp.zeros_like(loss), loss)
    return {"Softmax": sm.astype(logits.dtype), "Loss": loss}


# ---------------------------------------------------------------------------
# linear_softmax_xent: an LM head and its loss over blocks of tokens
# ---------------------------------------------------------------------------
# What `static/head_loss_rewrite.py` puts where a program had mul ->
# elementwise_add (bias) -> softmax_with_cross_entropy on [B, S, H]
# activations: loss[b, s] = logsumexp(x[b, s] @ W + bias) - picked logit.
# The [B, S, V] logits, their softmax and their gradient exist one block
# of positions at a time.  The backward recomputes a block's logits from
# the op's inputs; beside them it keeps only `Lse`, the [B, S, 1]
# log-sum-exp (saving it takes a third off the head's time on the v5e:
# the backward then reads each block once, not three times).

# One block's logits in fp32 may take this much.  Under it the whole
# head is one piece (n = 1: small programs compute exactly what the
# three ops computed).  512 MiB: at BERT-base b64 x s512 x V 30,522 the
# 4.0 GB of logits fall into 8 blocks of 64 positions, each a
# [4096, 768] x [768, 30522] matmul (far above an MXU tile), each 3% of
# a 16 GB chip; the weight gradient is carried through the n blocks,
# n x H x V x 4 bytes of traffic (1.5 GB, ~2 ms of a 330 ms step), so
# smaller blocks buy little memory for more traffic (v5e, that shape,
# forward + backward of this op's first version: n = 4 54.8 ms, 8
# 56.8 ms, 16 57.6 ms; PERF.md section 6, PR 26).  Derived
# from the shapes and not settable: it follows the device's memory, not
# the job.
HEAD_BLOCK_BYTES = 512 << 20


def head_token_blocks(batch, seq, vocab):
    """(positions per block, blocks) for a [batch, seq, vocab] head.
    Blocks cut the SEQUENCE axis, which data parallelism never shards,
    so a dp shard sees the same mathematics on its own rows.  An even
    split is preferred (one loop, no ragged tail) when a divisor of
    `seq` lies within twice the least block count."""
    least = min(seq, max(1, -(-batch * seq * vocab * 4 // HEAD_BLOCK_BYTES)))
    n = next((d for d in range(least, min(seq, 2 * least) + 1)
              if seq % d == 0), least)
    blk = seq // n
    return blk, -(-seq // blk)


def _cut(a, blk):
    """[B, S, ...] -> the full blocks stacked [S // blk, B, blk, ...]
    and the ragged tail [B, S % blk, ...]."""
    b, full = a.shape[0], a.shape[1] // blk
    head = a[:, :full * blk].reshape((b, full, blk) + a.shape[2:])
    return jnp.moveaxis(head, 1, 0), a[:, full * blk:]


def _over_blocks(body, carry, arrays, blk):
    """`body(carry, block of each array) -> (carry, [B, blk, ...] out)`
    over the blocks of S in order: a scan over the full blocks, so that
    one block is live at a time, then once over the tail.  Returns the
    carry and the outs joined back to [B, S, ...]."""
    if blk >= arrays[0].shape[1]:
        return body(carry, arrays)
    cut = [_cut(a, blk) for a in arrays]
    carry, ys = jax.lax.scan(body, carry, tuple(c[0] for c in cut))
    out = jnp.moveaxis(ys, 0, 1)
    out = out.reshape((out.shape[0], -1) + out.shape[3:])
    if cut[0][1].shape[1]:
        carry, y_tail = body(carry, tuple(c[1] for c in cut))
        out = jnp.concatenate([out, y_tail], axis=1)
    return carry, out


class _Head:
    """The operands of `linear_softmax_xent` and its grad, prepared once:
    labels as clipped int32 [B, S] with the ignored rows marked, the
    weight in the activations' dtype, the block size."""

    def __init__(self, ins, attrs, ctx):
        self.x, w, self.bias = ins["X"], ins["W"], ins["Bias"]
        lbl = ins["Label"]
        if lbl.ndim == self.x.ndim:
            lbl = lbl[..., 0]
        lbl = lbl.astype(jnp.int32)
        vocab = w.shape[-1]
        self.ignored = (lbl == attrs.get("ignore_index", -100))[..., None]
        self.label = jnp.clip(lbl, 0, vocab - 1)
        self.classes = jnp.arange(vocab, dtype=jnp.int32)
        self.cdt = _compute_dtype(self.x)
        self.w_lo = w.astype(self.x.dtype)
        self.blk, n = head_token_blocks(self.x.shape[0], self.x.shape[1],
                                        vocab)
        if getattr(ctx, "program", None) is not None:
            # traced into a Program's step (BlockTracer), not by shape
            # inference
            from ...core.monitor import gauge_set
            gauge_set("static.head_loss.token_blocks", n)

    def logits(self, xb):
        """One block's logits: operands in the activations' dtype (bf16
        under AMP), fp32 accumulation, the bias added in fp32; then ONE
        rounding to the activations' dtype, where mul and
        elementwise_add each made one — half the bytes wherever XLA
        leaves the block in HBM.  The softmax upcasts, as
        softmax_with_cross_entropy does."""
        z = jnp.einsum("bsh,hv->bsv", xb, self.w_lo,
                       preferred_element_type=self.cdt)
        z = z + self.bias.astype(self.cdt)
        return z.astype(self.x.dtype).astype(self.cdt)

    def onehot(self, lb):
        return self.classes == lb[..., None]


def _linear_softmax_xent_grad(ins, attrs, ctx):
    h = _Head(ins, attrs, ctx)
    x, w, cdt = h.x, ins["W"], h.cdt
    lse = ins.get("Lse")
    if lse is None:
        lse = linear_softmax_xent(ins, attrs, ctx)["Lse"]
    g = ins.get("Loss@GRAD")
    g = jnp.zeros(lse.shape, cdt) if g is None else g.astype(cdt)
    g = jnp.where(h.ignored, 0, g)

    def body(carry, block):
        dw, db = carry
        xb, lb, gb, lseb = block
        p = jnp.exp(h.logits(xb) - lseb)
        # d loss / d logits, rounded once to the matmuls' operand dtype
        d = ((p - h.onehot(lb)) * gb).astype(x.dtype)
        dx = jnp.einsum("bsv,hv->bsh", d, h.w_lo,
                        preferred_element_type=cdt)
        dw = dw + jnp.einsum("bsh,bsv->hv", xb, d,
                             preferred_element_type=cdt)
        db = db + jnp.sum(d, axis=(0, 1), dtype=cdt)
        return (dw, db), dx.astype(x.dtype)

    zero = (jnp.zeros(w.shape, cdt), jnp.zeros(h.bias.shape, cdt))
    (dw, db), dx = _over_blocks(body, zero,
                                (x, h.label, g, lse.astype(cdt)), h.blk)
    return {"X@GRAD": dx, "W@GRAD": dw.astype(w.dtype),
            "Bias@GRAD": db.astype(h.bias.dtype)}


@register_op("linear_softmax_xent", inputs=["X", "W", "Bias", "Label!"],
             outputs=["Loss", "Lse"], grad=_linear_softmax_xent_grad)
def linear_softmax_xent(ins, attrs, ctx):
    h = _Head(ins, attrs, ctx)

    def body(carry, block):
        xb, lb = block
        logits = h.logits(xb)
        lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        # the label's logit by a masked sum, not a gather: it rides the
        # pass over the block that the log-sum-exp makes anyway
        picked = jnp.sum(jnp.where(h.onehot(lb), logits, 0), axis=-1,
                         keepdims=True)
        return carry, jnp.concatenate([lse - picked, lse], axis=-1)

    _, out = _over_blocks(body, None, (h.x, h.label), h.blk)
    return {"Loss": jnp.where(h.ignored, 0, out[..., :1]),
            "Lse": out[..., 1:]}


@register_op("cross_entropy", inputs=["X", "Label!"], outputs=["Y"])
def cross_entropy(ins, attrs, ctx):
    x, label = ins["X"], ins["Label"]
    soft_label = attrs.get("soft_label", False)
    ignore_index = attrs.get("ignore_index", -100)
    eps = 1e-12
    if soft_label:
        y = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == x.ndim and lbl.shape[-1] == 1:
            lbl = jnp.squeeze(lbl, -1)
        lbl = lbl.astype(jnp.int32)
        p = jnp.take_along_axis(x, jnp.expand_dims(
            jnp.clip(lbl, 0, x.shape[-1] - 1), -1), axis=-1)
        y = -jnp.log(p + eps)
        y = jnp.where(jnp.expand_dims(lbl, -1) == ignore_index,
                      jnp.zeros_like(y), y)
    return {"Y": y}


@register_op("cross_entropy2", inputs=["X", "Label!"],
             outputs=["Y", "XShape", "MatchX"])
def cross_entropy2(ins, attrs, ctx):
    out = cross_entropy(ins, attrs, ctx)
    x = ins["X"]
    lbl = ins["Label"]
    if lbl.ndim == x.ndim and lbl.shape[-1] == 1:
        lbl = jnp.squeeze(lbl, -1)
    matchx = jnp.take_along_axis(x, jnp.expand_dims(
        jnp.clip(lbl.astype(jnp.int32), 0, x.shape[-1] - 1), -1), axis=-1)
    return {"Y": out["Y"], "XShape": jnp.zeros((0,) + x.shape, x.dtype),
            "MatchX": matchx}


@register_op("bce_loss", inputs=["X", "Label"], outputs=["Out"])
def bce_loss(ins, attrs, ctx):
    x, label = ins["X"], ins["Label"]
    eps = 1e-12
    out = -(label * jnp.log(x + eps) + (1 - label) * jnp.log(1 - x + eps))
    return {"Out": out}


@register_op("nll_loss", inputs=["X", "Label!", "Weight?"],
             outputs=["Out", "Total_weight"])
def nll_loss(ins, attrs, ctx):
    x, label = ins["X"], ins["Label"].astype(jnp.int32)
    weight = ins.get("Weight")
    reduction = attrs.get("reduction", "mean")
    ignore_index = attrs.get("ignore_index", -100)
    n, c = x.shape[0], x.shape[1]
    picked = -jnp.take_along_axis(
        x, jnp.expand_dims(jnp.clip(label, 0, c - 1), 1), axis=1).squeeze(1)
    w = jnp.ones_like(picked) if weight is None \
        else jnp.take(weight, jnp.clip(label, 0, c - 1))
    valid = label != ignore_index
    picked = jnp.where(valid, picked * w, 0.0)
    w = jnp.where(valid, w, 0.0)
    tw = jnp.sum(w)
    if reduction == "mean":
        return {"Out": jnp.sum(picked) / jnp.maximum(tw, 1e-12),
                "Total_weight": tw}
    if reduction == "sum":
        return {"Out": jnp.sum(picked), "Total_weight": tw}
    return {"Out": picked, "Total_weight": tw}


@register_op("hinge_loss", inputs=["Logits", "Labels!"], outputs=["Loss"])
def hinge_loss(ins, attrs, ctx):
    logits, labels = ins["Logits"], ins["Labels"]
    return {"Loss": jnp.maximum(0.0, 1.0 - (2 * labels - 1) * logits)}


@register_op("huber_loss", inputs=["X", "Y"], outputs=["Residual", "Out"])
def huber_loss(ins, attrs, ctx):
    delta = attrs.get("delta", 1.0)
    r = ins["Y"] - ins["X"]
    ab = jnp.abs(r)
    out = jnp.where(ab <= delta, 0.5 * r * r, delta * (ab - 0.5 * delta))
    return {"Residual": r, "Out": out}


@register_op("smooth_l1_loss", inputs=["X", "Y", "InsideWeight?",
                                       "OutsideWeight?"],
             outputs=["Diff", "Out"])
def smooth_l1_loss(ins, attrs, ctx):
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    d = ins["X"] - ins["Y"]
    if ins.get("InsideWeight") is not None:
        d = d * ins["InsideWeight"]
    ab = jnp.abs(d)
    loss = jnp.where(ab < 1.0 / s2, 0.5 * d * d * s2, ab - 0.5 / s2)
    if ins.get("OutsideWeight") is not None:
        loss = loss * ins["OutsideWeight"]
    out = jnp.sum(loss.reshape(loss.shape[0], -1), axis=1, keepdims=True)
    return {"Diff": d, "Out": out}


@register_op("log_loss", inputs=["Predicted", "Labels"], outputs=["Loss"])
def log_loss(ins, attrs, ctx):
    eps = attrs.get("epsilon", 1e-4)
    p, l = ins["Predicted"], ins["Labels"]
    return {"Loss": -l * jnp.log(p + eps) - (1 - l) * jnp.log(1 - p + eps)}


@register_op("kldiv_loss", inputs=["X", "Target"], outputs=["Loss"])
def kldiv_loss(ins, attrs, ctx):
    x, t = ins["X"], ins["Target"]
    reduction = attrs.get("reduction", "mean")
    loss = jnp.where(t > 0, t * (jnp.log(jnp.maximum(t, 1e-12)) - x), 0.0)
    if reduction == "mean":
        return {"Loss": jnp.mean(loss)}
    if reduction == "sum":
        return {"Loss": jnp.sum(loss)}
    if reduction == "batchmean":
        return {"Loss": jnp.sum(loss) / x.shape[0]}
    return {"Loss": loss}


@register_op("sigmoid_cross_entropy_with_logits", inputs=["X", "Label"],
             outputs=["Out"])
def sigmoid_cross_entropy_with_logits(ins, attrs, ctx):
    x, label = ins["X"], ins["Label"]
    ignore_index = attrs.get("ignore_index", -100)
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    mask = label != ignore_index
    loss = jnp.where(mask, loss, 0.0)
    if attrs.get("normalize", False):
        loss = loss / jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    return {"Out": loss}


@register_op("sigmoid_focal_loss", inputs=["X", "Label!", "FgNum!"],
             outputs=["Out"])
def sigmoid_focal_loss(ins, attrs, ctx):
    x, label, fg = ins["X"], ins["Label"].astype(jnp.int32), ins["FgNum"]
    gamma = attrs.get("gamma", 2.0)
    alpha = attrs.get("alpha", 0.25)
    n, c = x.shape
    # per-class binary target: label in [0, C]; 0 = background
    tgt = jax.nn.one_hot(label.ravel() - 1, c, dtype=x.dtype)
    p = jax.nn.sigmoid(x)
    ce = jnp.maximum(x, 0.0) - x * tgt + jnp.log1p(jnp.exp(-jnp.abs(x)))
    p_t = p * tgt + (1 - p) * (1 - tgt)
    a_t = alpha * tgt + (1 - alpha) * (1 - tgt)
    loss = a_t * jnp.power(1 - p_t, gamma) * ce / jnp.maximum(
        fg.astype(x.dtype), 1.0)
    return {"Out": loss}


@register_op("mse_loss", inputs=["X", "Y"], outputs=["Out"])
def mse_loss(ins, attrs, ctx):
    return {"Out": jnp.square(ins["X"] - ins["Y"])}


@register_op("rank_loss", inputs=["Label!", "Left", "Right"], outputs=["Out"])
def rank_loss(ins, attrs, ctx):
    label, left, right = ins["Label"], ins["Left"], ins["Right"]
    d = left - right
    return {"Out": jnp.log1p(jnp.exp(d)) - label * d}


@register_op("margin_rank_loss", inputs=["Label!", "X1", "X2"],
             outputs=["Out", "Activated"])
def margin_rank_loss(ins, attrs, ctx):
    margin = attrs.get("margin", 0.0)
    label, x1, x2 = ins["Label"], ins["X1"], ins["X2"]
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": out, "Activated": (out > 0).astype(x1.dtype)}


@register_op("bpr_loss", inputs=["X", "Label!"], outputs=["Y"])
def bpr_loss(ins, attrs, ctx):
    x, label = ins["X"], ins["Label"].astype(jnp.int32)
    n, c = x.shape
    if label.ndim == 2:
        label = label.squeeze(-1)
    pos = jnp.take_along_axis(x, label[:, None], axis=1)
    diff = x - pos
    # exclude the positive column itself
    mask = jax.nn.one_hot(label, c, dtype=x.dtype)
    loss = -jnp.sum(jnp.log(jax.nn.sigmoid(-diff) + 1e-12) * (1 - mask),
                    axis=1, keepdims=True) / (c - 1)
    return {"Y": loss}


@register_op("center_loss", inputs=["X", "Label!", "Centers", "CenterUpdateRate!"],
             outputs=["CentersOut", "SampleCenterDiff", "Loss"])
def center_loss(ins, attrs, ctx):
    x, label, centers = ins["X"], ins["Label"].astype(jnp.int32).ravel(), \
        ins["Centers"]
    alpha = ins["CenterUpdateRate"].reshape(())
    picked = jnp.take(centers, label, axis=0)
    diff = x - picked
    loss = 0.5 * jnp.sum(jnp.square(diff), axis=1, keepdims=True)
    if attrs.get("need_update", True):
        counts = jnp.zeros((centers.shape[0],), x.dtype).at[label].add(1.0)
        upd = jnp.zeros_like(centers).at[label].add(diff)
        centers = centers + alpha * upd / (counts[:, None] + 1.0)
    return {"CentersOut": centers, "SampleCenterDiff": diff, "Loss": loss}
