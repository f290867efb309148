"""Plain reference for the `granitemoehybrid` configurations without
experts (`granite-4.0-h-micro`): the full forward pass of the hybrid
decoder in straightforward `jax.numpy`, float32, matmuls at `highest`
precision — the recurrence token by token (`lax.scan`), no chunks, no
cache, no batching, no kernels.  Written from the published configuration
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json)
and the Mamba-2 paper's recurrence (Dao & Gu 2024, eq. 1-2 with scalar A
per head); the equations, with `h` [T, hidden]:

    h = E[ids] * embedding_multiplier                      (no positions)
    block:  h = h + residual_multiplier * mixer(RMSNorm(h))
            h = h + residual_multiplier * MLP(RMSNorm(h))
    MLP:    [g, u] = split(x W_in);  out = (silu(g) * u) W_out
    attention (grouped-query, causal, no rotary):
            softmax(q k^T * attention_multiplier) v, each kv head shared
            by heads / kv_heads query heads
    Mamba-2: [z, xBC, dt] = split(x W_in)
            xBC_t = silu(sum_j w_conv[:, j] * xBC_{t-3+j} + b_conv)
            [x, B, C] = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
            S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
            out = RMSNorm(y * silu(z)) W_out     (gate before the norm)
    logits = RMSNorm(h) E^T / logits_scaling               (tied table)

It takes the served model's own weights (whatever their dtype) and casts
ONE LAYER AT A TIME to float32, so that it fits beside a served 3 B model
on one chip.  `weights_as` rounds the matrices through a lower precision
first ("int8": symmetric per-output-channel) — the reading that has to come
out as not correct.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mlp(x, w_in, w_out):
    g, u = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_out


def _attention(x, wq, wk, wv, wo, heads, kv_heads, multiplier):
    t, hidden = x.shape
    d = hidden // heads

    def split_heads(y, n):
        return y.reshape(t, n, d).transpose(1, 0, 2)

    q = split_heads(x @ wq, heads)
    k = jnp.repeat(split_heads(x @ wk, kv_heads), heads // kv_heads, axis=0)
    v = jnp.repeat(split_heads(x @ wv, kv_heads), heads // kv_heads, axis=0)
    scores = (q @ k.transpose(0, 2, 1)) * multiplier
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = (jax.nn.softmax(scores, axis=-1) @ v).transpose(1, 0, 2)
    return ctx.reshape(t, hidden) @ wo


def _mamba(x, w_in, w_out, conv_w, conv_b, norm_w, a_log, dt_bias, d_skip,
           n_heads, d_head, d_state, n_groups, eps):
    t = x.shape[0]
    inner, k = n_heads * d_head, conv_w.shape[1]
    gn = n_groups * d_state
    z, xbc, dt = jnp.split(x @ w_in, [inner, inner + inner + 2 * gn], axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    xbc = jax.nn.silu(sum(padded[j:j + t] * conv_w[:, j] for j in range(k))
                      + conv_b)
    xs, bm, cm = jnp.split(xbc, [inner, inner + gn], axis=-1)
    xs = xs.reshape(t, n_heads, d_head)
    rep = n_heads // n_groups
    bm = jnp.repeat(bm.reshape(t, n_groups, d_state), rep, axis=1)
    cm = jnp.repeat(cm.reshape(t, n_groups, d_state), rep, axis=1)
    dt = jax.nn.softplus(dt + dt_bias)                     # [T, H]
    a = -jnp.exp(a_log)

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], -1) + d_skip[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((n_heads, d_head, d_state), F32),
                        (xs, bm, cm, dt))
    y = _rms_norm(y.reshape(t, inner) * jax.nn.silu(z), norm_w, eps)
    return y @ w_out


def _block(h, p, kind, cfg):
    """One block over a whole sequence h [T, hidden]; `p` the layer's
    arrays by their short names, `cfg` the hashable sizes."""
    with jax.default_matmul_precision("highest"):
        (heads, kv_heads, n_heads, d_head, d_state, n_groups, att_mult,
         res_mult, eps) = cfg
        x = _rms_norm(h, p["norm1"], eps)
        if kind == "attention":
            out = _attention(x, p["wq"], p["wk"], p["wv"], p["wo"], heads,
                             kv_heads, att_mult)
        else:
            out = _mamba(x, p["w_in"], p["w_out"], p["conv_w"], p["conv_b"],
                         p["norm_w"], p["a_log"], p["dt_bias"], p["d"],
                         n_heads, d_head, d_state, n_groups, eps)
        h = h + res_mult * out
        out = _mlp(_rms_norm(h, p["norm2"], eps), p["mlp_w_in"],
                   p["mlp_w_out"])
        return h + res_mult * out


def _head(h, norm_f, table, eps, logits_scaling):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(h, norm_f, eps) @ table.T / logits_scaling


_block_jit = jax.jit(_block, static_argnums=(2, 3))
_head_jit = jax.jit(_head, static_argnums=(3, 4))

MATRICES = ("wq", "wk", "wv", "wo", "w_in", "w_out", "mlp_w_in",
            "mlp_w_out")


def _through_int8(w):
    """Symmetric per-output-channel int8 and back (a weight-only
    quantization of the matrix, as PR 19's serving stamp does it)."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    return jnp.round(w / jnp.where(s > 0, s, 1.0)).clip(-127, 127) * s


def _f32(name, array, weights_as):
    w = jnp.asarray(array, F32)
    if weights_as == "int8" and name in MATRICES:
        return _through_int8(w)
    if weights_as not in (None, "int8"):
        raise ValueError(f"weights_as {weights_as!r}")
    return w


def logits(params, ids, cfg, weights_as=None):
    """Logits [T, vocab] float32 of one token sequence `ids` [T].

    `params`: {"embed", "norm_f", "layers": [{short name: array}]} — the
    served model's arrays as they are (`Served.reference_params`); `cfg`:
    the published keys.  Blocks run one jitted call each (two compiled
    programs for a 40-layer model) and each layer is cast to float32 only
    for its own call."""
    sizes = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
             cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
             cfg["mamba_n_groups"], float(cfg["attention_multiplier"]),
             float(cfg["residual_multiplier"]), float(cfg["rms_norm_eps"]))
    table = jnp.asarray(params["embed"], F32)
    h = table[jnp.asarray(ids)] * float(cfg["embedding_multiplier"])
    for kind, layer in zip(cfg["layer_types"], params["layers"]):
        h = _block_jit(h, {k: _f32(k, v, weights_as)
                           for k, v in layer.items()}, kind, sizes)
    return _head_jit(h, jnp.asarray(params["norm_f"], F32), table,
                     float(cfg["rms_norm_eps"]), float(cfg["logits_scaling"]))


def params_of(model):
    """`logits`' `params` from a `paddle_tpu.models.GraniteHybridModel`:
    its own device arrays, nothing copied."""
    layers = []
    for blk in model.layers:
        m = blk.mixer
        p = {"norm1": blk.norm1._value, "norm2": blk.norm2._value,
             "mlp_w_in": blk.mlp.w_in._value,
             "mlp_w_out": blk.mlp.w_out._value}
        names = ("wq", "wk", "wv", "wo") if blk.kind == "attention" else (
            "w_in", "w_out", "conv_w", "conv_b", "norm_w", "a_log",
            "dt_bias", "d")
        p.update({n: getattr(m, n)._value for n in names})
        layers.append(p)
    return {"embed": model.embed._value, "norm_f": model.norm_f._value,
            "layers": layers}
