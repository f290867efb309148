"""Plain reference for the `nemotron_h` configurations with latent routed
experts (`nemotron-3-super-120b-a12b`), for ONE CHIP'S SHARE of an
expert-parallel deployment: the full forward pass in straightforward
`jax.numpy`, float32, matmuls at `highest` precision — the recurrence
token by token (`lax.scan`), the experts as a plain loop over the ones
held, no sort, no chunks, no cache, no batching, no kernels.  Written from
the published configuration
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json),
the Mamba-2 paper's recurrence (Dao & Gu 2024) and the latent
mixture-of-experts layer the configuration's keys describe; the
equations, with `h` [T, hidden] and `x = RMSNorm(h)`:

    h = E[ids]                                   (no multiplier, no positions)
    layer of kind M | * | E:   h = h + f(RMSNorm(h))
    M  Mamba-2: [z, xBC, dt] = split(x W_in)
       xBC_t = silu(sum_j w_conv[:, j] * xBC_{t-3+j} + b_conv)
       [x, B, C] = split(xBC), B and C in n_groups groups, each shared by
       heads / n_groups heads;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
       S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
       y = y * silu(z), normalised IN n_groups GROUPS of the channels:
       y / sqrt(mean(y^2 over a group) + eps) * w;  out = y W_out
    *  grouped-query attention, causal, scores * head_dim^-0.5, no rotary
    E  s = sigmoid(x W_r)                         [T, n_routed_experts]
       pick = top_k(s + b_r)                      (the bias selects only)
       w = s[pick];  w = w / (sum(w) + 1e-20);  w = routed_scaling_factor * w
       u = x W_down                                          [T, latent]
       r = sum_{e in pick, e HELD HERE} w_e * (relu(u A_e)^2 B_e)
       f = r W_up + relu(x S_1)^2 S_2                      (shared expert)
    logits = RMSNorm(h) W_head                   (untied, the rows held here)

THE SHARE: `first_held`, `held` say which of the routed experts this chip
holds; `w` is normalised over all the picks, held or not, and what the
experts held elsewhere would add is LEFT OUT (the partial sum goes on to
the next layer, as in the system).  The embedding and the head are the
rows of the vocabulary held here as the model has them.

Departures, each on the reference's side of a comparison: none in the
mathematics; no rotary positions although the row carries `rope_theta`
(the `nemotron_h` modelling code applies none); the multi-token-prediction
module is not part of the main model's logits and is left out.

It takes the served model's own weights (whatever their dtype) and casts
ONE LAYER AT A TIME to float32 — inside that layer's own jitted call, and
the held experts' matrices ONE EXPERT AT A TIME inside the loop over them
— so that it fits beside the served model on one chip (an expert layer's
held experts would be 2.8 GB in float32 at the published widths; cast
whole, beside the 9.3 GB served model, its state and the step programs'
buffers, the first chip run peaked at 16.48 of the chip's 16.9 GB).
`weights_as` rounds the matrices through a lower precision first ("int8":
symmetric per-output-channel, an expert's matrices each on their own) —
the reading that has to come out as not correct.  The router's matrix is
left as it is: a weight-only quantization keeps the gate in float32, as the
published code computes it.

JUDGING SERVED TOKENS (`forced`): the gate is a discontinuity — a 22nd and
a 23rd score a rounding apart pick different experts, and a different
expert is a whole expert's contribution in the residual — so a reference
left to its own picks measures the served path's ties, not its arithmetic.
Given the served gate's picks it weights THOSE experts (with its own
float32 `s`), and reports beside the logits how far each served pick lies
under its own k-th best score (`shortfall`): the picks are held to their
own limit and the logits to theirs.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _attention(x, wq, wk, wv, wo, heads, kv_heads, d):
    t = x.shape[0]

    def split_heads(y, n):
        return y.reshape(t, n, d).transpose(1, 0, 2)

    q = split_heads(x @ wq, heads)
    k = jnp.repeat(split_heads(x @ wk, kv_heads), heads // kv_heads, axis=0)
    v = jnp.repeat(split_heads(x @ wv, kv_heads), heads // kv_heads, axis=0)
    scores = (q @ k.transpose(0, 2, 1)) * d ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = (jax.nn.softmax(scores, axis=-1) @ v).transpose(1, 0, 2)
    return ctx.reshape(t, heads * d) @ wo


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
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(
        t, n_groups, inner // n_groups)                    # norm in groups
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return (y.reshape(t, inner) * norm_w) @ w_out


def route(x, router_w, router_b, top_k, scaling, forced=None):
    """(own [T, k] int32, used [T, k], w [T, k], shortfall [T] or None) of
    the router's three lines: `own` its picks, `w` the weights of the
    experts `used` — its own, or `forced` [T, k] INSTEAD (the served gate's
    picks, when served tokens are judged: `w` is then the router's own `s`
    at those experts, normalised over them).  `shortfall` says how far the
    router's own selection disagrees with `forced`: its k-th best `s + b`
    less the smallest `s + b` of a forced expert, 0 where the sets are
    equal."""
    s = jax.nn.sigmoid(x @ router_w)
    best, own = jax.lax.top_k(s + router_b, top_k)
    used, short = own, None
    if forced is not None:
        used = forced
        short = best[:, -1] - jnp.min(
            jnp.take_along_axis(s + router_b, forced, axis=-1), -1)
    w = jnp.take_along_axis(s, used, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return own, used, scaling * w, short


def _experts(x, p, top_k, scaling, first_held, weights_as=None, forced=None):
    """The expert layer for the experts held here, a plain loop over
    them: expert `first_held + i` is `w1[i]`, `w2[i]` (as the model has
    them: each is cast, and rounded where `weights_as` says so, inside the
    loop).  Returns (f, the router's own picks, `route`'s shortfall)."""
    own, pick, w, short = route(x, p["router_w"], p["router_b"], top_k,
                                scaling, forced)
    u = x @ p["w_down"]
    held = p["w1"].shape[0]

    def one(acc, inp):
        e, a_e, b_e = inp
        a_e, b_e = _f32("w1", a_e, weights_as), _f32("w2", b_e, weights_as)
        gate = jnp.sum(jnp.where(pick == first_held + e, w, 0.0), -1)
        return acc + gate[:, None] * (_relu2(u @ a_e) @ b_e), None

    r, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (jnp.arange(held), p["w1"], p["w2"]))
    return (r @ p["w_up"] + _relu2(x @ p["shared_in"]) @ p["shared_out"],
            own, short)


def _layer(h, p, kind, cfg, weights_as=None, forced=None):
    """One layer over a whole sequence h [T, hidden]; `p` the layer's
    arrays by their short names as the model has them (cast here), `cfg`
    the hashable sizes.  Returns (h, the expert layer's own picks or None,
    its shortfall under `forced` or None)."""
    with jax.default_matmul_precision("highest"):
        p = {k: v if k in ("w1", "w2") else _f32(k, v, weights_as)
             for k, v in p.items()}
        (heads, kv_heads, head_dim, n_heads, d_head, d_state, n_groups,
         top_k, scaling, first_held, eps) = cfg
        pick = short = None
        if kind == "attention":
            out = _attention(_rms_norm(h, p["norm1"], eps), p["wq"], p["wk"],
                             p["wv"], p["wo"], heads, kv_heads, head_dim)
        elif kind == "mamba":
            out = _mamba(_rms_norm(h, p["norm1"], eps), p["w_in"],
                         p["w_out"], p["conv_w"], p["conv_b"], p["norm_w"],
                         p["a_log"], p["dt_bias"], p["d"], n_heads, d_head,
                         d_state, n_groups, eps)
        else:
            out, pick, short = _experts(
                _rms_norm(h, p["norm2"], eps), p, top_k, scaling, first_held,
                weights_as, forced)
        return h + out, pick, short


def _head(h, norm_f, head, eps, weights_as=None):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(h, jnp.asarray(norm_f, F32), eps) \
            @ _f32("head", head, weights_as)

MATRICES = ("wq", "wk", "wv", "wo", "w_in", "w_out", "w_down", "w_up", "w1",
            "w2", "shared_in", "shared_out", "head")


def _through_int8(w):
    """Symmetric per-output-channel int8 and back (a weight-only
    quantization; of a stack of experts' matrices, each on its own)."""
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / jnp.where(s > 0, s, 1.0)).clip(-127, 127) * s


def _f32(name, array, weights_as):
    w = jnp.asarray(array, F32)
    if weights_as == "int8" and name in MATRICES:
        return _through_int8(w)
    if weights_as not in (None, "int8"):
        raise ValueError(f"weights_as {weights_as!r}")
    return w


_layer_jit = jax.jit(_layer, static_argnums=(2, 3, 4))
_head_jit = jax.jit(_head, static_argnums=(3, 4))


def sizes_of(cfg):
    """The hashable sizes `_layer` takes, from the configuration's keys
    (`first_held_expert`: the first expert this chip holds, 0 if absent)."""
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"],
            cfg["num_experts_per_tok"], float(cfg["routed_scaling_factor"]),
            int(cfg.get("first_held_expert", 0)),
            float(cfg["layer_norm_epsilon"]))


KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def logits(params, ids, cfg, weights_as=None, picks=None, forced=None,
           shortfall=None):
    """Logits [T, vocabulary rows held] float32 of one token sequence
    `ids` [T].

    `params`: {"embed", "norm_f", "head", "layers": [{short name:
    array}]} — the served model's arrays as they are (`params_of`); `cfg`:
    the published keys as the configuration file has them (the pattern it
    runs, `first_held_expert`).  Layers run one jitted call each and each
    layer is cast to float32 only inside its own call.  `picks`: a list that
    receives each expert layer's own picked experts [T, k].  `forced`
    [expert layers, T, k]: the experts each expert layer weights instead of
    its own picks (`route`); `shortfall` then receives each expert layer's
    [T] (how far its own selection disagrees)."""
    if weights_as not in (None, "int8"):
        raise ValueError(f"weights_as {weights_as!r}")
    sizes = sizes_of(cfg)
    h = jnp.asarray(params["embed"][jnp.asarray(ids)], F32)
    n_experts = 0
    for c, layer in zip(cfg["hybrid_override_pattern"], params["layers"]):
        given = None
        if c == "E" and forced is not None:
            given = jnp.asarray(forced[n_experts], jnp.int32)
        h, pick, short = _layer_jit(h, layer, KINDS[c], sizes, weights_as,
                                    given)
        if c == "E":
            n_experts += 1
            if picks is not None:
                picks.append(pick)
            if shortfall is not None and short is not None:
                shortfall.append(short)
    return _head_jit(h, params["norm_f"], params["head"], sizes[-1],
                     weights_as)


def params_of(model):
    """`logits`' `params` from a `paddle_tpu.models.NemotronHModel`: its
    own device arrays, nothing copied."""
    names = {
        "attention": ("wq", "wk", "wv", "wo"),
        "mamba": ("w_in", "w_out", "conv_w", "conv_b", "norm_w", "a_log",
                  "dt_bias", "d"),
        "experts": ("router_w", "router_b", "w_down", "w_up", "w1", "w2",
                    "shared_in", "shared_out")}
    layers = []
    for blk in model.layers:
        if blk.kind:
            p = {"norm1": blk.norm1._value}
            p.update({n: getattr(blk.mixer, n)._value
                      for n in names[blk.kind]})
        else:
            p = {"norm2": blk.norm2._value}
            p.update({n: getattr(blk.experts, n)._value
                      for n in names["experts"]})
        layers.append(p)
    return {"embed": model.embed._value, "norm_f": model.norm_f._value,
            "head": model.head._value, "layers": layers}
