"""Plain reference for the `lfm2_moe` configurations (`lfm2-8b-a1b`): the
full forward pass in straightforward `jax.numpy`, float32, matmuls at
`highest` precision — no cache, no carried tail, no sort, no kernels; the
convolution is a sum over shifted rows and the mask is built token by
token from ``i - j``.  Written from the published configuration
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json) and
the family's dense modelling code (transformers' `lfm2`); the equations,
with `h` [T, hidden], `x` a row, positions from 0, ``N(.)`` an RMSNorm
``w * x * rsqrt(mean(x^2) + eps)``:

    h            = E[ids]
    mixer, conv  = [B | C | x] = y W_in;  u = B * x
                   c_t = sum_{j=0..K-1} w[:, j] * u_{t-(K-1)+j}  (u_{<0} = 0)
                   (C * c) W_out
    mixer, attn  = q_h = N_q((y W_q)_h), k_g = N_k((y W_k)_g), v_g = (y W_v)_g
                   q, k = q cos + rotate_half(q) sin,  rotate_half = [-x2|x1]
                   angle(t, i) = t * theta^(-2i / D), i < D/2, both halves
                   softmax_j(q_h,i . k_g(h),j / sqrt(D), j <= i) v  ->  W_o
    h            = h + mixer(N_op(h));  h = h + ffn(N_ffn(h))
    ffn, dense   = W_2(silu(W_1 y) * W_3 y)
    ffn, experts = sum_{e in pick} g_e E_e(y),  s = sigmoid(y W_r) (float32),
                   pick = top_k(s + b),  g = s_pick / (sum s_pick + 1e-6) x
                   routed_scaling_factor;  no shared expert
    logits       = N_f(h) E^T                       (tied head)

Departures from a plain whole-sequence pass, all for memory, none in the
mathematics: the served model's own weights are cast to float32 a
SUBLAYER at a time inside that sublayer's jitted call, the experts'
matrices ONE EXPERT at a time inside the loop over them; the queries and
their scores go a block of `QUERY_BLOCK` tokens at a time and the dense
feed-forward `TOKEN_BLOCK` tokens at a time, so that 8,192 tokens at the
published widths fit beside the served model and its cache on one chip.
The head is computed for the rows asked for (`rows`).

Controls (`weights_as`), on the matrices only (the table too, where it is
the head; the router's matrix and bias, the conv's taps and the norms are
left as they are): "int8" rounds through symmetric per-output-channel int8
— the nearest precision below the bfloat16 the configuration states, which
has to come out as not correct; "float8_e4m3fn" each output channel, scaled
to that format's largest finite value, through float8 e4m3, a reading
beside it.

JUDGING SERVED TOKENS (`forced`): as `reference/nemotron_h.py` — the gate
is a discontinuity, so given the served gate's picks the reference weights
THOSE experts with its own float32 `s` and reports how far each served
pick lies under its own k-th best (biased) score (`shortfall`).
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 64
TOKEN_BLOCK = 2048
NORM_EPS_PICKS = 1e-6
MATRICES = ("in_proj", "out_proj", "wq", "wk", "wv", "wo", "w_in", "w_out",
            "w1", "w2", "head")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def rope(x, pos, theta):
    """x [T, ..., d] turned to positions pos [T], the half-split form."""
    d = x.shape[-1]
    angle = pos.astype(F32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)       # [T, d/2]
    angle = jnp.concatenate([angle, angle], -1)
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d,))
    return x * jnp.cos(angle) + rotate_half(x) * jnp.sin(angle)


def _through_int8(w):
    """Symmetric per-output-channel int8 and back (a weight-only
    quantization; of a stack of experts' matrices, each on its own)."""
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / jnp.where(s > 0, s, 1.0)).clip(-127, 127) * s


def _through_float8(w):
    """Each output channel scaled to float8 e4m3's largest finite value
    (448), rounded through that format and back."""
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


WEIGHT_FORMS = {"int8": _through_int8, "float8_e4m3fn": _through_float8}


def _f32(name, array, weights_as):
    w = jnp.asarray(array, F32)
    if weights_as is not None and name in MATRICES:
        return WEIGHT_FORMS[weights_as](w)
    return w


def short_conv(y, p, weights_as=None):
    """The gated short-convolution mixer over a whole sequence y [T, C]."""
    w_in, w_out = (_f32(k, p[k], weights_as) for k in ("in_proj", "out_proj"))
    taps = jnp.asarray(p["conv_w"], F32)                       # [C, K]
    gate_b, gate_c, xs = jnp.split(y @ w_in, 3, axis=-1)
    u = gate_b * xs
    t, k = u.shape[0], taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), F32), u], 0)
    conv = sum(padded[j:j + t] * taps[:, j] for j in range(k))
    return (gate_c * conv) @ w_out


def attention(y, p, sizes, weights_as=None):
    heads, kv_heads, theta, eps = sizes[:4]
    t = y.shape[0]
    w = {k: _f32(k, p[k], weights_as) for k in ("wq", "wk", "wv", "wo")}
    d = w["wq"].shape[1] // heads
    pos = jnp.arange(t)
    q = _rms((y @ w["wq"]).reshape(t, heads, d), jnp.asarray(p["q_norm"], F32),
             eps)
    k = _rms((y @ w["wk"]).reshape(t, kv_heads, d),
             jnp.asarray(p["k_norm"], F32), eps)
    v = (y @ w["wv"]).reshape(t, kv_heads, d)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens are not whole blocks of {block}")
    j = jnp.arange(t)[None, :]

    def some(first):
        i = first + jnp.arange(block)[:, None]
        qs = jax.lax.dynamic_slice_in_dim(q, first, block)
        scores = jnp.einsum("ihd,jhd->hij", qs, k) * d ** -0.5
        scores = jnp.where((j <= i)[None], scores, -jnp.inf)
        return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(scores, -1), v)

    ctx = jax.lax.map(some, jnp.arange(0, t, block))      # [n, block, H, d]
    return ctx.reshape(t, heads * d) @ w["wo"]


def _gated(x, w_in, w_out):
    """``D(silu(G x) * U x)`` with `w_in` = [G | U]."""
    g, u = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_out


def route(x, router_w, router_b, top_k, scaling, forced=None):
    """(own [T, k] int32, used [T, k], g [T, k], shortfall [T] or None):
    `own` the router's picks by ``s + b``, `g` the weights of the experts
    `used` — its own, or `forced` [T, k] INSTEAD: the router's own `s` at
    those experts, normalised over them, times `scaling`.  `shortfall`:
    the k-th best ``s + b`` less the smallest of a forced expert's."""
    s = jax.nn.sigmoid(x @ router_w)
    choose = s + router_b
    best, own = jax.lax.top_k(choose, top_k)
    used, short = own, None
    if forced is not None:
        used = forced
        short = best[:, -1] - jnp.min(
            jnp.take_along_axis(choose, forced, axis=-1), -1)
    g = jnp.take_along_axis(s, used, axis=-1)
    g = g / (jnp.sum(g, -1, keepdims=True) + NORM_EPS_PICKS) * scaling
    return own, used, g, short


def experts(x, p, sizes, weights_as=None, forced=None):
    """(the held experts' weighted sum [T, C], own picks, shortfall)."""
    top_k, scaling, first_held = sizes[4:7]
    own, pick, g, short = route(
        x, jnp.asarray(p["router_w"], F32), jnp.asarray(p["router_b"], F32),
        top_k, scaling, forced)

    def one(acc, inp):
        e, a_e, b_e = inp
        gate = jnp.sum(jnp.where(pick == first_held + e, g, 0.0), -1)
        return acc + gate[:, None] * _gated(
            x, _f32("w1", a_e, weights_as), _f32("w2", b_e, weights_as)), None

    held = p["w1"].shape[0]
    r, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(held), p["w1"], p["w2"]))
    return r, own, short


def _dense(x, p, weights_as):
    w_in, w_out = (_f32(k, p[k], weights_as) for k in ("w_in", "w_out"))
    t = x.shape[0]
    block = min(TOKEN_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens are not whole blocks of {block}")
    out = jax.lax.map(lambda xs: _gated(xs, w_in, w_out),
                      x.reshape(t // block, block, -1))
    return out.reshape(t, -1)


def _mixer_half(h, p, conv, sizes, weights_as=None):
    """``h + mixer(N_op(h))`` over a whole sequence h [T, hidden]."""
    with jax.default_matmul_precision("highest"):
        y = _rms(h, jnp.asarray(p["norm1"], F32), sizes[3])
        return h + (short_conv(y, p, weights_as) if conv
                    else attention(y, p, sizes, weights_as))


def _ffn_half(h, p, routed, sizes, weights_as=None, forced=None):
    """``h + ffn(N_ffn(h))``: (h, the router's own picks or None, its
    shortfall under `forced` or None)."""
    with jax.default_matmul_precision("highest"):
        y = _rms(h, jnp.asarray(p["norm2"], F32), sizes[3])
        pick = short = None
        if routed:
            f, pick, short = experts(y, p, sizes, weights_as, forced)
        else:
            f = _dense(y, p, weights_as)
        return h + f, pick, short


def _head(h, norm_f, table, eps, weights_as=None):
    with jax.default_matmul_precision("highest"):
        return _rms(h, jnp.asarray(norm_f, F32), eps) \
            @ _f32("head", table, weights_as).T


# two calls a layer, so that the mixer's weights and the feed-forward's
# are never cast to float32 side by side
_mixer_jit = jax.jit(_mixer_half, static_argnums=(2, 3, 4))
_ffn_jit = jax.jit(_ffn_half, static_argnums=(2, 3, 4))
_head_jit = jax.jit(_head, static_argnums=(3, 4))


def sizes_of(cfg):
    """The hashable sizes the layers take, from the configuration's keys
    (`first_held_expert`: the first expert this chip holds, 0 if absent)."""
    rope = cfg.get("rope_parameters") or {}
    return (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            float(cfg.get("rope_theta", rope.get("rope_theta", 1e6))),
            float(cfg["norm_eps"]), int(cfg["num_experts_per_tok"]),
            float(cfg["routed_scaling_factor"]),
            int(cfg.get("first_held_expert", 0)))


def logits(params, ids, cfg, weights_as=None, picks=None, forced=None,
           shortfall=None, rows=None, hidden=None):
    """Logits [T (or the `rows` asked for), vocabulary] float32 of one
    token sequence `ids` [T] (whole blocks of `QUERY_BLOCK` and of
    `TOKEN_BLOCK`, or fewer tokens than one).

    `params`: {"embed", "norm_f", "layers": [{short name: array}]} — the
    served model's arrays as they are (`params_of`), one entry a layer it
    runs; `cfg`: the published keys as the configuration file has them
    (`layer_types`, of which the first len(layers) run; `num_dense_layers`;
    `first_held_expert`).  A layer runs as two jitted calls.  `picks`: a
    list that receives each expert layer's own picks [T, k].  `forced`
    [expert layers, T, k]: the experts each expert layer weights instead
    of its own picks (`route`); `shortfall` then receives each such
    layer's [T].  `rows`: the positions whose logits are wanted (default
    all).  `hidden`: a list that receives `h` after each layer.
    `weights_as`: the control."""
    if weights_as is not None and weights_as not in WEIGHT_FORMS:
        raise ValueError(f"weights_as {weights_as!r}")
    sizes = sizes_of(cfg)
    dense = int(cfg["num_dense_layers"])
    h = jnp.asarray(params["embed"][jnp.asarray(ids)], F32)
    for n, layer in enumerate(params["layers"]):
        routed = n >= dense
        given = None if forced is None or not routed \
            else jnp.asarray(forced[n - dense], jnp.int32)
        h = _mixer_jit(h, layer, cfg["layer_types"][n] == "conv", sizes,
                       weights_as)
        h, pick, short = _ffn_jit(h, layer, routed, sizes, weights_as, given)
        if picks is not None and pick is not None:
            picks.append(pick)
        if shortfall is not None and short is not None:
            shortfall.append(short)
        if hidden is not None:
            hidden.append(h)
    if rows is not None:
        h = h[rows]
    return _head_jit(h, params["norm_f"], params["embed"], sizes[3],
                     weights_as)


def params_of(model):
    """`logits`' `params` from a `paddle_tpu.models.Lfm2MoeModel`: its own
    device arrays, nothing copied."""
    layers = []
    for blk in model.layers:
        p = {"norm1": blk.norm1._value, "norm2": blk.norm2._value}
        names = ("in_proj", "conv_w", "out_proj") if blk.kind == "short_conv" \
            else ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
        p.update({n: getattr(blk.mixer, n)._value for n in names})
        if blk.ffn == "mlp":
            p.update(w_in=blk.mlp.w_in._value, w_out=blk.mlp.w_out._value)
        else:
            p.update({n: getattr(blk.experts, n)._value
                      for n in ("router_w", "router_b", "w1", "w2")})
        layers.append(p)
    return {"embed": model.embed._value, "norm_f": model.norm_f._value,
            "layers": layers}
