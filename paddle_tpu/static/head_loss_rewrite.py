"""Program rewrite: an LM head and its loss become one op over blocks of
tokens.

A language model's last three forward ops are `mul` (hidden states times
the [H, V] output projection), `elementwise_add` (the bias) and
`softmax_with_cross_entropy`.  Their tensors are the largest of the step:
at BERT-base b64 x s512 x V 30,522 the bf16 logits, the `Softmax` kept as
the loss's residual and the logits gradient are 2.0 GB each, live exactly
when every saved encoder activation is live too — XLA pays for them by
recomputing encoder work.  `fuse_head_loss` replaces the three by one
`linear_softmax_xent` op (ops/kernels/loss.py) whose kernel walks the
tokens block by block, so those tensors never exist; its grad op
recomputes one block's logits at a time from the op's inputs and the
[B, S, 1] log-sum-exp the forward op emits beside the loss (`Lse`).

It runs on the FORWARD program — `Optimizer.backward` calls it after
AMP's cast insertion and before `append_backward`, so the backward gets
one grad op — and fires only on what the IR shows:

  * `mul` contracts the last dim of a rank-3 X against a [H, V] weight,
    the add's Y is a [V] bias on the last axis, the loss has hard
    labels on the last axis;
  * the mul's output, the biased logits and `Softmax` have no other
    consumer, are not persistable, and are neither in the program's
    `_fetch_names` nor among the caller's `keep` names (the recompute
    checkpoints);
  * no op or operand of the head carries a tensor-parallel annotation
    (`mp_axis` / `tp_degree` stamps, a `dist_attr` on the weight or
    bias): a vocab-sharded head needs its unfused collectives.

Anything else keeps its three ops.  A var the rewrite removed cannot be
fetched afterwards (the Executor's error says so, from
`program._fused_away`): name it in `program._fetch_names` before
`minimize` (or give it a second consumer) and the head stays unfused.
There is no switch; how many blocks the kernel takes follows from the
shapes (`ops.kernels.loss.head_token_blocks`).

Counters (core/monitor): `static.head_loss.rewritten` counts programs
rewritten; the gauge `static.head_loss.token_blocks` holds the block
count of the head most recently traced into a step.
"""
from __future__ import annotations

from typing import Iterable, Optional

from ..core.program import OpDesc, OpRole, Program, unique_name

__all__ = ["fuse_head_loss"]

_TP_STAMPS = ("mp_axis", "tp_degree")

# what `Executor` says when a removed var is fetched (`_fused_away`)
_FUSED_AWAY = (
    "the head and its loss were fused into one linear_softmax_xent op "
    "over blocks of tokens and the logits no longer exist; list the var "
    "in program._fetch_names before minimize() to keep them "
    "(static/head_loss_rewrite.py)")


def _producer_and_consumers(block):
    producer, consumers = {}, {}
    for op in block.ops:
        for n in op.input_names():
            consumers.setdefault(n, []).append(op)
        for n in op.output_names():
            producer.setdefault(n, op)
    return producer, consumers


def _one(op, slot, outputs=False) -> Optional[str]:
    names = (op.outputs if outputs else op.inputs).get(slot, [])
    return names[0] if len(names) == 1 and names[0] else None


def _match(block, xent, producer, consumers, protected):
    """(mul, add, names of the vars that stop existing) when the IR
    shows the whole pattern feeding `xent`, else None."""
    logits = _one(xent, "Logits")
    add = producer.get(logits)
    if add is None or add.type != "elementwise_add":
        return None
    mul = producer.get(_one(add, "X"))
    if mul is None or mul.type != "mul":
        return None
    x, w, bias = _one(mul, "X"), _one(mul, "Y"), _one(add, "Y")
    label, loss = _one(xent, "Label"), _one(xent, "Loss", outputs=True)
    names = (x, w, bias, label, loss)
    if not all(names) or not all(block.has_var(n) for n in names):
        return None
    xs, ws, bs = (block.var(n).shape for n in (x, w, bias))
    if xs is None or ws is None or bs is None or len(xs) != 3 \
            or len(ws) != 2 or len(bs) != 1 or bs[0] != ws[1]:
        return None
    rank = len(xs)
    if int(mul.attrs.get("x_num_col_dims", 1)) != rank - 1 \
            or int(mul.attrs.get("y_num_col_dims", 1)) != 1 \
            or int(add.attrs.get("axis", -1)) not in (-1, rank - 1) \
            or int(xent.attrs.get("axis", -1)) not in (-1, rank - 1) \
            or xent.attrs.get("soft_label", False):
        return None
    # the tensors that stop existing: one consumer each, nobody's fetch
    softmax = _one(xent, "Softmax", outputs=True)
    chain = [(name, only) for name, only in (
        (_one(mul, "Out", outputs=True), [add]), (logits, [xent]),
        (softmax, [])) if name is not None]
    for name, only in chain:
        if consumers.get(name, []) != only or name in protected \
                or block.var(name).persistable:
            return None
    # a tensor-parallel head keeps its own ops (IR annotation, never a
    # model's name): stamps on the ops, dist_attr on the parameters —
    # under AMP the operands are casts of them
    if any(k in op.attrs for op in (mul, add) for k in _TP_STAMPS):
        return None
    for n in (w, bias):
        src = producer.get(n)
        roots = [n] + (src.input_names() if src is not None
                       and src.type == "cast" else [])
        if any(block.has_var(r) and "dist_attr" in block.var(r).attrs
               for r in roots):
            return None
    return mul, add, [name for name, _ in chain]


def fuse_head_loss(program: Program, keep: Iterable[str] = ()) -> int:
    """Rewrite every head `program`'s global block shows (module
    docstring); returns how many.  `keep`: var names that must survive
    (checkpoints a recompute pass will look for)."""
    block = program.global_block()
    producer, consumers = _producer_and_consumers(block)
    protected = set(keep) | set(getattr(program, "_fetch_names", ()) or ())
    drop, put = set(), {}
    for xent in block.ops:
        if xent.type != "softmax_with_cross_entropy" or \
                (int(xent.op_role) & ~OpRole.Loss) != OpRole.Forward:
            continue
        found = _match(block, xent, producer, consumers, protected)
        if found is None:
            continue
        mul, add, dead = found
        # the loss op's own annotations (role, device, ignore_index)
        # carry over; what described its softmax does not
        attrs = {k: v for k, v in xent.attrs.items()
                 if k not in ("soft_label", "axis", "numeric_stable_mode")}
        attrs["op_uid"] = program._next_uid()
        # the one residual beside the inputs: the [B, S, 1] log-sum-exp
        loss = block.var(_one(xent, "Loss", outputs=True))
        lse = block.create_var(name=unique_name(loss.name + ".lse"),
                               shape=loss.shape, dtype=loss.dtype,
                               stop_gradient=True)
        fused = OpDesc(
            "linear_softmax_xent",
            {"X": mul.inputs["X"], "W": mul.inputs["Y"],
             "Bias": add.inputs["Y"], "Label": xent.inputs["Label"]},
            {"Loss": [loss.name], "Lse": [lse.name]}, attrs)
        drop.update((id(mul), id(add)))
        put[id(xent)] = fused
        if not hasattr(program, "_fused_away"):
            program._fused_away = {}
        for name in dead:
            block.vars.pop(name, None)
            program._fused_away[name] = _FUSED_AWAY
    if not put:
        return 0
    block.ops = [put.get(id(op), op) for op in block.ops
                 if id(op) not in drop]
    program._fingerprint_cache = None
    from ..core.monitor import stat_add
    stat_add("static.head_loss.rewritten", 1)
    from ..core.pass_framework import finish_pass
    finish_pass(program, "head_loss", heads=len(put))
    return len(put)
