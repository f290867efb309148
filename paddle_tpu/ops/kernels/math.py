"""Core math ops (reference: /root/reference/paddle/fluid/operators/
matmul_op.cc, mul_op.cc, bmm_op.cc, dot_op.cc, sum_op.cc, scale_op.cc,
mean_op.cc, clip_op.cc, cumsum_op.cc, ...).  All kernels are pure jnp —
matmuls land on the MXU; `preferred_element_type` keeps bf16 inputs
accumulating in f32."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..registry import register_op


def _matmul(x, y):
    # Low-precision dots run PLAIN (bf16 x bf16 -> bf16): the TPU MXU
    # accumulates bf16 dots in f32 internally and rounds the output, so an
    # explicit preferred_element_type=f32 + astype round-trip produces
    # IDENTICAL forward numerics — but its vjp routes the cotangent
    # through the f32 convert, silently turning every backward matmul
    # into f32 (measured: 34/51 bench dots f32 = the whole backward,
    # ~4x off bf16 MXU peak on v5e).
    return jnp.matmul(x, y)


@register_op("matmul", inputs=["X", "Y"], outputs=["Out"])
def matmul(ins, attrs, ctx):
    x, y = ins["X"], ins["Y"]
    if attrs.get("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if attrs.get("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    out = _matmul(x, y)
    alpha = attrs.get("alpha", 1.0)
    if alpha != 1.0:
        out = out * jnp.asarray(alpha, out.dtype)
    return {"Out": out}


@register_op("matmul_v2", inputs=["X", "Y"], outputs=["Out"])
def matmul_v2(ins, attrs, ctx):
    x, y = ins["X"], ins["Y"]
    if attrs.get("trans_x", False):
        x = jnp.swapaxes(x, -1, -2)
    if attrs.get("trans_y", False):
        y = jnp.swapaxes(y, -1, -2)
    if attrs.get("out_dtype"):
        # the accumulator itself, not a rounded low-precision result (a
        # served logits row: bf16 operands, float32 out)
        from ...core.dtype import np_dtype
        return {"Out": jnp.matmul(
            x, y, preferred_element_type=np_dtype(attrs["out_dtype"]))}
    return {"Out": _matmul(x, y)}


@register_op("mul", inputs=["X", "Y"], outputs=["Out"])
def mul(ins, attrs, ctx):
    # flatten X to 2-D at x_num_col_dims, Y at y_num_col_dims (mul_op.cc)
    x, y = ins["X"], ins["Y"]
    xnc = attrs.get("x_num_col_dims", 1)
    ync = attrs.get("y_num_col_dims", 1)
    xs, ys = x.shape, y.shape
    x2 = x.reshape((int(np.prod(xs[:xnc])), -1))
    y2 = y.reshape((int(np.prod(ys[:ync])), -1))
    out = _matmul(x2, y2)
    return {"Out": out.reshape(xs[:xnc] + ys[ync:])}


@register_op("bmm", inputs=["X", "Y"], outputs=["Out"])
def bmm(ins, attrs, ctx):
    return {"Out": _matmul(ins["X"], ins["Y"])}


@register_op("mv", inputs=["X", "Vec"], outputs=["Out"])
def mv(ins, attrs, ctx):
    return {"Out": _matmul(ins["X"], ins["Vec"])}


@register_op("dot", inputs=["X", "Y"], outputs=["Out"])
def dot(ins, attrs, ctx):
    # dot_op.cc InferShape: out dims = x dims with last dim -> 1
    x, y = ins["X"], ins["Y"]
    return {"Out": jnp.sum(x * y, axis=-1, keepdims=True)}


@register_op("addmm", inputs=["Input", "X", "Y"], outputs=["Out"])
def addmm(ins, attrs, ctx):
    alpha = attrs.get("Alpha", 1.0)
    beta = attrs.get("Beta", 1.0)
    out = alpha * _matmul(ins["X"], ins["Y"]) + beta * ins["Input"]
    return {"Out": out.astype(ins["X"].dtype)}


@register_op("kron", inputs=["X", "Y"], outputs=["Out"])
def kron(ins, attrs, ctx):
    return {"Out": jnp.kron(ins["X"], ins["Y"])}


@register_op("scale", inputs=["X"], outputs=["Out"])
def scale(ins, attrs, ctx):
    from ...core.selected_rows import SelectedRows
    x = ins["X"]
    if isinstance(x, SelectedRows):
        # scale a sparse gradient in place (bias would densify; the only
        # framework use on grads is pure scaling)
        if attrs.get("bias", 0.0) != 0.0:
            raise ValueError("scale(bias!=0) on SelectedRows would densify")
        s = jnp.asarray(attrs.get("scale", 1.0), x.values.dtype)
        return {"Out": SelectedRows(x.rows, x.values * s, x.height)}
    s = jnp.asarray(attrs.get("scale", 1.0), x.dtype)
    b = jnp.asarray(attrs.get("bias", 0.0), x.dtype)
    if attrs.get("bias_after_scale", True):
        return {"Out": x * s + b}
    return {"Out": (x + b) * s}


@register_op("sum", inputs=["X*"], outputs=["Out"])
def sum_op(ins, attrs, ctx):
    from ...core.selected_rows import SelectedRows
    xs = ins["X"]
    if any(isinstance(x, SelectedRows) for x in xs):
        if all(isinstance(x, SelectedRows) for x in xs):
            # gradient aggregation of two sparse lookups on the same table
            # (selected_rows_functor.cc MergeAdd): concatenation IS the sum
            # under scatter-add semantics
            return {"Out": SelectedRows(
                jnp.concatenate([x.rows for x in xs]),
                jnp.concatenate([x.values for x in xs]),
                xs[0].height)}
        xs = [x.to_dense() if isinstance(x, SelectedRows) else x for x in xs]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": out}


@register_op("mean", inputs=["X"], outputs=["Out"])
def mean(ins, attrs, ctx):
    return {"Out": jnp.mean(ins["X"])}


@register_op("minus", inputs=["X", "Y"], outputs=["Out"])
def minus(ins, attrs, ctx):
    return {"Out": ins["X"] - ins["Y"]}


@register_op("clip", inputs=["X", "Min?!", "Max?!"], outputs=["Out"])
def clip(ins, attrs, ctx):
    lo = ins.get("Min")
    hi = ins.get("Max")
    lo = attrs.get("min", -np.inf) if lo is None else lo
    hi = attrs.get("max", np.inf) if hi is None else hi
    return {"Out": jnp.clip(ins["X"], lo, hi)}


@register_op("clip_by_norm", inputs=["X"], outputs=["Out"])
def clip_by_norm(ins, attrs, ctx):
    x = ins["X"]
    max_norm = attrs["max_norm"]
    norm = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    factor = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    return {"Out": (x.astype(jnp.float32) * factor).astype(x.dtype)}


@register_op("sign", inputs=["X"], outputs=["Out"], grad=None)
def sign(ins, attrs, ctx):
    return {"Out": jnp.sign(ins["X"])}


@register_op("cumsum", inputs=["X"], outputs=["Out"])
def cumsum(ins, attrs, ctx):
    x = ins["X"]
    axis = attrs.get("axis", -1)
    if attrs.get("flatten", False):
        x = x.reshape(-1)
        axis = 0
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("exclusive", False):
        out = out - x
    if attrs.get("reverse", False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
        if attrs.get("exclusive", False):
            out = out - x
    return {"Out": out}


@register_op("logsumexp", inputs=["X"], outputs=["Out"])
def logsumexp(ins, attrs, ctx):
    axis = attrs.get("axis", None) or attrs.get("dim", None)
    keepdim = attrs.get("keepdim", False)
    if attrs.get("reduce_all", False):
        axis = None
    elif isinstance(axis, (list, tuple)):
        axis = tuple(axis)
    return {"Out": jax.scipy.special.logsumexp(ins["X"], axis=axis,
                                               keepdims=keepdim)}


@register_op("trace", inputs=["Input"], outputs=["Out"])
def trace(ins, attrs, ctx):
    return {"Out": jnp.trace(ins["Input"], offset=attrs.get("offset", 0),
                             axis1=attrs.get("axis1", 0),
                             axis2=attrs.get("axis2", 1))}


@register_op("tril_triu", inputs=["X"], outputs=["Out"])
def tril_triu(ins, attrs, ctx):
    x = ins["X"]
    diag = attrs.get("diagonal", 0)
    if attrs.get("lower", True):
        return {"Out": jnp.tril(x, diag)}
    return {"Out": jnp.triu(x, diag)}


@register_op("cholesky", inputs=["X"], outputs=["Out"])
def cholesky(ins, attrs, ctx):
    x = ins["X"]
    out = jnp.linalg.cholesky(x)
    if not attrs.get("upper", False):
        return {"Out": out}
    return {"Out": jnp.swapaxes(out, -1, -2)}


@register_op("inverse", inputs=["Input"], outputs=["Output"])
def inverse(ins, attrs, ctx):
    return {"Output": jnp.linalg.inv(ins["Input"])}


@register_op("cross", inputs=["X", "Y"], outputs=["Out"])
def cross(ins, attrs, ctx):
    dim = attrs.get("dim", None)
    if dim is None or dim == -100:  # DefaultDim sentinel in reference
        # first axis of size 3
        dim = next(i for i, s in enumerate(ins["X"].shape) if s == 3)
    return {"Out": jnp.cross(ins["X"], ins["Y"], axis=dim)}


@register_op("dist", inputs=["X", "Y"], outputs=["Out"])
def dist(ins, attrs, ctx):
    p = attrs.get("p", 2.0)
    d = (ins["X"] - ins["Y"]).ravel()
    if p == np.inf:
        return {"Out": jnp.max(jnp.abs(d))}
    if p == -np.inf:
        return {"Out": jnp.min(jnp.abs(d))}
    if p == 0:
        return {"Out": jnp.sum(d != 0).astype(d.dtype)}
    return {"Out": jnp.power(jnp.sum(jnp.power(jnp.abs(d), p)), 1.0 / p)}


@register_op("cos_sim", inputs=["X", "Y"], outputs=["Out", "XNorm", "YNorm"])
def cos_sim(ins, attrs, ctx):
    x, y = ins["X"], ins["Y"]
    xn = jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(jnp.square(y), -1, keepdims=True))
    out = jnp.sum(x * y, -1, keepdims=True) / (xn * yn)
    return {"Out": out, "XNorm": xn, "YNorm": yn}


@register_op("p_norm", inputs=["X"], outputs=["Out"])
def p_norm(ins, attrs, ctx):
    x = ins["X"]
    p = attrs.get("porder", 2.0)
    axis = attrs.get("axis", -1)
    keepdim = attrs.get("keepdim", False)
    if attrs.get("asvector", False):
        x, axis = x.ravel(), 0
    out = jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis,
                            keepdims=keepdim), 1.0 / p)
    return {"Out": out}


@register_op("norm", inputs=["X"], outputs=["Out", "Norm"])
def norm(ins, attrs, ctx):
    x = ins["X"]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": x / n, "Norm": n}


@register_op("frobenius_norm", inputs=["X"], outputs=["Out"])
def frobenius_norm(ins, attrs, ctx):
    axis = attrs.get("dim", None)
    keepdim = attrs.get("keep_dim", False)
    if attrs.get("reduce_all", False) or axis is None:
        axis = None
    else:
        axis = tuple(axis)
    return {"Out": jnp.sqrt(jnp.sum(jnp.square(ins["X"]), axis=axis,
                                    keepdims=keepdim))}


@register_op("squared_l2_norm", inputs=["X"], outputs=["Out"])
def squared_l2_norm(ins, attrs, ctx):
    return {"Out": jnp.sum(jnp.square(ins["X"])).reshape(1)}


@register_op("squared_l2_distance", inputs=["X", "Y"],
             outputs=["sub_result", "Out"])
def squared_l2_distance(ins, attrs, ctx):
    sub = ins["X"] - ins["Y"]
    out = jnp.sum(jnp.square(sub), axis=tuple(range(1, sub.ndim)),
                  keepdims=False).reshape(-1, 1)
    return {"sub_result": sub, "Out": out}


@register_op("l1_norm", inputs=["X"], outputs=["Out"])
def l1_norm(ins, attrs, ctx):
    return {"Out": jnp.sum(jnp.abs(ins["X"]))}


@register_op("increment", inputs=["X"], outputs=["Out"], grad=None)
def increment(ins, attrs, ctx):
    return {"Out": ins["X"] + jnp.asarray(attrs.get("step", 1.0),
                                          ins["X"].dtype)}


@register_op("bilinear_tensor_product", inputs=["X", "Y", "Weight", "Bias?"],
             outputs=["Out"])
def bilinear_tensor_product(ins, attrs, ctx):
    x, y, w = ins["X"], ins["Y"], ins["Weight"]
    # w: [out, dx, dy]; out[b,o] = x[b]^T w[o] y[b]
    out = jnp.einsum("bi,oij,bj->bo", x, w, y)
    if ins.get("Bias") is not None:
        out = out + ins["Bias"]
    return {"Out": out}


@register_op("histogram", inputs=["X!"], outputs=["Out"], grad=None)
def histogram(ins, attrs, ctx):
    x = ins["X"].ravel()
    bins = attrs.get("bins", 100)
    lo, hi = attrs.get("min", 0), attrs.get("max", 0)
    out, _ = jnp.histogram(x, bins=bins,
                           range=None if lo == hi == 0 else (lo, hi))
    return {"Out": out.astype(jnp.int64)}


@register_op("allclose", inputs=["Input!", "Other!"], outputs=["Out"],
             grad=None)
def allclose(ins, attrs, ctx):
    rtol = float(attrs.get("rtol", 1e-5))
    atol = float(attrs.get("atol", 1e-8))
    return {"Out": jnp.allclose(ins["Input"], ins["Other"], rtol=rtol,
                                atol=atol,
                                equal_nan=attrs.get("equal_nan", False))}


@register_op("isfinite", inputs=["X!"], outputs=["Out"], grad=None)
def isfinite(ins, attrs, ctx):
    return {"Out": jnp.all(jnp.isfinite(ins["X"])).reshape(1)}


@register_op("isfinite_v2", inputs=["X!"], outputs=["Out"], grad=None)
def isfinite_v2(ins, attrs, ctx):
    return {"Out": jnp.isfinite(ins["X"])}


@register_op("isinf_v2", inputs=["X!"], outputs=["Out"], grad=None)
def isinf_v2(ins, attrs, ctx):
    return {"Out": jnp.isinf(ins["X"])}


@register_op("isnan_v2", inputs=["X!"], outputs=["Out"], grad=None)
def isnan_v2(ins, attrs, ctx):
    return {"Out": jnp.isnan(ins["X"])}


@register_op("einsum", inputs=["Operands*"], outputs=["Out"])
def einsum_op(ins, attrs, ctx):
    """paddle.einsum lowering: one jnp.einsum per equation (XLA emits
    the optimal contraction on the MXU)."""
    return {"Out": jnp.einsum(attrs["equation"], *ins["Operands"])}
