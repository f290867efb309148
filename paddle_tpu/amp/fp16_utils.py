"""Program rewriting for static-graph AMP: cast insertion.

Reference: /root/reference/python/paddle/fluid/contrib/mixed_precision/
fp16_utils.py — `rewrite_program` walks the block, classifying each op
white/black/gray and inserting `cast` ops so white ops consume fp16 and
black ops consume fp32.

TPU design notes: the casts are pure dataflow ops that XLA fuses into the
adjacent matmul/conv (free on the MXU path), so we insert per-use casts and
keep parameters fp32 (master weights) rather than maintaining fp16 parameter
copies like `cast_parameters_to_fp16`.
"""
from __future__ import annotations

from typing import Dict

from ..core.program import Program, Block, OpDesc, OpRole, unique_name
from .fp16_lists import AutoMixedPrecisionLists

__all__ = ["rewrite_program", "cast_model_to_fp16"]

_FLOAT = ("float32", "float64")

# Slot-level dtype semantics: these output slots stay fp32 regardless of
# the op's precision decision (their kernels always emit fp32 — statistics
# and loss values), so the rewrite must not declare them low-precision.
_FP32_OUT_SLOTS = {
    "softmax_with_cross_entropy": {"Loss"},
    "linear_softmax_xent": {"Loss", "Lse"},
    "layer_norm": {"Mean", "Variance"},
}

# Gray ops whose kernels upcast internally and accept fp32 parameters
# alongside low-precision activations (layer_norm casts Scale/Bias to the
# compute dtype itself) — persistable float inputs don't block the
# low-precision decision and are left as fp32 master weights.
_PARAM_TOLERANT = {"layer_norm"}

# Gray ops whose kernels FOLLOW one MAIN operand's dtype under mixed
# operands instead of promoting (softmax_with_cross_entropy returns
# softmax/loss in the Logits dtype and upcasts the label internally —
# ops/kernels/loss.py): a black fp32 SECONDARY operand (a label-smooth
# target) doesn't force the whole op — and its giant output — back to
# fp32.  When the MAIN operand (the value of this map) is already
# low-precision, the op is decided low, black operands stay protected
# (uncast), and the output declarations match what the kernel actually
# emits; when the main operand itself is black/fp32, black-wins
# applies as usual (the kernel follows it to fp32).  Promoting binaries
# (elementwise_add etc.) are deliberately NOT here: their kernel output
# under mixed operands IS fp32, so black-wins keeps declarations
# truthful for them.
_MIXED_FOLLOW = {"softmax_with_cross_entropy": "Logits"}


def _is_float_var(block, name):
    try:
        v = block.var(name)
    except KeyError:
        return False
    return v.dtype in _FLOAT or v.dtype in ("float16", "bfloat16")


def _insert_cast(block, name, src_dtype, dst_dtype, cache, new_ops, uid_fn):
    key = (name, dst_dtype)
    if key in cache:
        return cache[key]
    out = unique_name(f"{name}.cast_{dst_dtype}")
    block.create_var(name=out, shape=block.var(name).shape, dtype=dst_dtype,
                     stop_gradient=block.var(name).stop_gradient)
    op = OpDesc("cast", {"X": [name]}, {"Out": [out]},
                {"in_dtype": src_dtype, "out_dtype": dst_dtype,
                 OpRole.KEY: OpRole.Forward, "op_uid": uid_fn()})
    new_ops.append(op)
    cache[key] = out
    return out


def rewrite_program(main_program: Program, amp_lists=None,
                    dest_dtype: str = "bfloat16"):
    """fp16_utils.py rewrite_program parity (forward block only — call
    BEFORE append_backward, as decorate() does)."""
    amp_lists = amp_lists or AutoMixedPrecisionLists()
    block = main_program.global_block()
    var_dtype: Dict[str, str] = {}  # rewritten dtype of each var
    black_out = set()  # vars produced by black ops — fp32 for a REASON
    new_ops = []
    cache: Dict = {}
    uid_fn = main_program._next_uid

    for op in block.ops:
        if op.op_role != OpRole.Forward and op.op_role != OpRole.Loss:
            new_ops.append(op)
            continue
        t = op.type
        if t in amp_lists.white_list and not (
                amp_lists.black_varnames &
                set(op.input_names() + op.output_names())):
            want = dest_dtype
        elif t in amp_lists.gray_list:
            # reference gray semantics (fp16_utils.py _rewrite): a black
            # producer wins (its fp32 output is protected — don't cast it
            # back down); otherwise follow any low-precision producer,
            # casting the remaining float inputs (e.g. the fp32 bias param
            # of an fc's bias-add); with neither, stay fp32.  Exception:
            # a _MIXED_FOLLOW kernel fed by BOTH (bf16 logits + a black
            # fp32 label) runs mixed and follows the low operand, so it
            # is decided low with the black operand left uncast — the
            # verifier's V103 catches the stale-fp32 alternative.
            ins = [n for n in op.input_names() if _is_float_var(block, n)]
            low = any(var_dtype.get(n, block.var(n).dtype) == dest_dtype
                      for n in ins)
            # follower exception keys on the MAIN operand specifically:
            # a bf16 label with black fp32 logits must NOT flip the op
            # low (the kernel would follow the fp32 logits)
            follow_low = False
            if t in _MIXED_FOLLOW:
                follow_low = any(
                    var_dtype.get(n, block.var(n).dtype) == dest_dtype
                    for n in op.inputs.get(_MIXED_FOLLOW[t], [])
                    if n and _is_float_var(block, n))
            if any(n in black_out for n in ins) and not follow_low:
                want = None
                black_out.update(
                    n for n in op.output_names()
                    if _is_float_var(block, n))
            elif low:
                want = dest_dtype
            else:
                want = None
        else:
            want = "float32"
            black_out.update(n for n in op.output_names()
                             if _is_float_var(block, n))

        if want is not None:
            for slot, names in op.inputs.items():
                out_names = []
                for n in names:
                    if not _is_float_var(block, n) or (
                            t in _PARAM_TOLERANT and
                            block.var(n).persistable) or (
                            t in amp_lists.gray_list and n in black_out):
                        # on a low-decided GRAY op a black-produced fp32
                        # operand stays protected (the kernel upcasts it
                        # internally); white ops still cast everything
                        # down — running the matmul in bf16 is their job
                        out_names.append(n)
                        continue
                    cur = var_dtype.get(n, block.var(n).dtype)
                    if cur in _FLOAT + ("float16", "bfloat16") and cur != want:
                        out_names.append(_insert_cast(
                            block, n, cur, want, cache, new_ops, uid_fn))
                    else:
                        out_names.append(n)
                op.inputs[slot] = out_names
            fp32_slots = _FP32_OUT_SLOTS.get(t, ())
            for slot, names in op.outputs.items():
                for n in names:
                    if not _is_float_var(block, n):
                        continue
                    if slot in fp32_slots:
                        block.var(n).dtype = "float32"
                        var_dtype[n] = "float32"
                    else:
                        block.var(n).dtype = want
                        var_dtype[n] = want
        new_ops.append(op)
    block.ops = new_ops
    main_program._fingerprint_cache = None
    from ..core.pass_framework import finish_pass
    finish_pass(main_program, "amp", dest_dtype=dest_dtype)
    return main_program


def cast_model_to_fp16(program: Program, amp_lists=None,
                       dest_dtype: str = "bfloat16"):
    """fp16_utils.py cast_model_to_fp16 (pure-fp16 mode O2): every float var
    and op flipped to the low dtype except the black list."""
    amp_lists = amp_lists or AutoMixedPrecisionLists()
    lists = AutoMixedPrecisionLists(
        custom_white_list=amp_lists.gray_list | amp_lists.white_list,
        custom_black_list=amp_lists.black_list)
    return rewrite_program(program, lists, dest_dtype)
