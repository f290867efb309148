"""Plain reference for the `cohere2_moe` configurations
(`command-a-plus-05-2026`), for ONE CHIP'S SHARE of an expert-parallel
deployment: the full forward pass in straightforward `jax.numpy`, float32,
matmuls at `highest` precision — no cache, no ring, no blocks of experts,
no sort, no batching, no kernels; the mask is built token by token from
``i - j``.  Written from the published configuration
(https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json);
the equations, with `h` [T, hidden], `l` a layer and `t_l` its
`layer_types` entry:

    h      = E[ids]
    x      = LayerNorm(h; g_l, eps)           mean-subtracting, scale only
    q,k,v  = x Wq [heads x d], x Wk [kv x d], x Wv [kv x d]     (no bias)
    sliding_attention: q,k <- RoPE(q,k; position, theta, interleaved pairs
                       (2i, 2i+1), all d dims)
                       key j visible to query i iff j <= i and i - j < W
    full_attention:    no positions; key j visible iff j <= i
    A      = softmax(q k^T / sqrt(d) + mask) v -> concat -> Wo
    s      = sigmoid(x W_r)                              [T, num_experts]
    E      = top_k(s);  w_e = s_e / sum_{e' in E} s_e'   (no bias, scale 1)
    R      = sum_{e in E, e HELD HERE} w_e D_e(silu(G_e x) * U_e x)
    S      = 1/n sum_{j=1..n} D'_j(silu(G'_j x) * U'_j x)  (shared, averaged)
    h      = h + A + R + S                    parallel block: one x for all
    logits = LayerNorm(h; g_f) E^T logit_scale            (tied table)

THE SHARE: `first_held_expert` and the experts' leading axis say which of
the routed experts this chip holds; `w` is normalised over all the picks,
held or not, and what the experts held elsewhere would add is LEFT OUT (the
partial sum goes on to the next layer, as in the system).  The table is
the rows of the vocabulary held here as the model has them.

Two readings the configuration alone does not settle (the file lists them
under `assumed`): the `average` of the shared experts is the mean of their
outputs; `rope_gptj` positions go on the sliding layers only (the family's
convention: global layers without positions).  Departure: the vision tower
is not part of the language model's configuration and is left out.

It takes the served model's own weights (whatever their dtype) and casts
them to float32 a LAYER at a time inside that layer's own jitted call, the
experts' matrices ONE EXPERT at a time inside the loops over them, and it
takes the queries a BLOCK at a time (`QUERY_BLOCK` rows of scores for all
heads at once), so that 8,192 tokens at the published widths fit beside
the served model on one chip.  The head is computed for the rows asked for
(`rows`).  `weights_as` rounds the matrices through a lower precision first
("int8": symmetric per-output-channel, an expert's matrices each on their
own) — a reading that has to come out as not correct; so have `window=False`
(the window mask taken off the sliding layers), `rotary=False` (no
positions anywhere) and `ring=` (`RING_FAULTS`: what the rows a served
sequence DECODES would see on the sliding layers if the ring cache were
kept wrongly, written here as masks, the prompt's rows left right).  The
router's matrix is left as it is.

JUDGING SERVED TOKENS (`forced`): as `reference/nemotron_h.py` — the gate
is a discontinuity, so given the served gate's picks the reference weights
THOSE experts with its own float32 `s` and reports how far each served
pick lies under its own k-th best score (`shortfall`).
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 128
# faults of a ring of W columns written at ``position mod W``, as the keys
# a query i >= `decode_from` (a row the served system decodes) then sees on
# a sliding layer; p = `decode_from`, the prompt's length:
# - "stale": the decode step's new column never reaches the ring — every
#   decoded row sees the ring as the prompt left it, keys p - W .. p - 1;
# - "unwrapped": the ring's valid columns taken as ``length mod W``, not
#   ``min(length, W)`` — past a wrap a row sees only the columns written
#   since, keys (i // W) W .. i.
RING_FAULTS = ("stale", "unwrapped")


def _layer_norm(x, w, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, heads, d], position = row: pairs (2i, 2i+1) turned by
    position * theta^(-2i/d)."""
    t, _, d = x.shape
    angle = jnp.arange(t, dtype=F32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)       # [T, d/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _attention(x, wq, wk, wv, wo, heads, kv_heads, d, window, theta,
               ring=None, decode_from=0):
    """`window` 0: every earlier key; `theta` 0: no positions; `ring`: one
    of `RING_FAULTS` for the rows from `decode_from` on."""
    t = x.shape[0]
    q = (x @ wq).reshape(t, heads, d)
    k = (x @ wk).reshape(t, kv_heads, d)
    v = (x @ wv).reshape(t, kv_heads, d)
    if theta:
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens are not whole blocks of {block}")
    j = jnp.arange(t)[None, :]

    def some(first):
        i = first + jnp.arange(block)[:, None]
        seen = j <= i
        if window:
            seen &= i - j < window
            if ring == "stale":
                seen = jnp.where(i < decode_from, seen, (j < decode_from)
                                 & (decode_from - j <= window))
            elif ring == "unwrapped":
                seen = jnp.where(i < decode_from, seen,
                                 (j <= i) & (j >= i // window * window))
        qs = jax.lax.dynamic_slice_in_dim(q, first, block)     # [b, H, d]
        scores = jnp.einsum("ihd,jhd->hij", qs, k) * d ** -0.5
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(scores, -1), v)

    ctx = jax.lax.map(some, jnp.arange(0, t, block))            # [n,b,H,d]
    return ctx.reshape(t, heads * d) @ wo


def route(x, router_w, top_k, forced=None):
    """(own [T, k] int32, used [T, k], w [T, k], shortfall [T] or None):
    `own` the router's picks, `w` the weights of the experts `used` — its
    own, or `forced` [T, k] INSTEAD (the served gate's picks: `w` is then
    the router's own `s` at those experts, normalised over them).
    `shortfall`: the router's k-th best `s` less the smallest `s` of a
    forced expert, 0 where the sets are equal."""
    s = jax.nn.sigmoid(x @ router_w)
    best, own = jax.lax.top_k(s, top_k)
    used, short = own, None
    if forced is not None:
        used = forced
        short = best[:, -1] - jnp.min(
            jnp.take_along_axis(s, forced, axis=-1), -1)
    w = jnp.take_along_axis(s, used, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return own, used, w, short


def _gated(x, w_in, w_out):
    """``D(silu(G x) * U x)`` with `w_in` = [G | U]."""
    g, u = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_out


def _experts(x, p, top_k, n_shared, first_held, weights_as=None,
             forced=None):
    """R + S for the experts held here, a plain loop over them (expert
    `first_held + i` is `w1[i]`, `w2[i]`), and a loop over the shared ones
    (`shared_in` = [G_1 .. G_n | U_1 .. U_n], `shared_out` the D_j
    stacked); each matrix is cast, and rounded where `weights_as` says so,
    inside its loop.  Returns (R + S, the router's own picks, `route`'s
    shortfall)."""
    own, pick, w, short = route(x, p["router_w"], top_k, forced)

    def one(acc, inp):
        e, a_e, b_e = inp
        gate = jnp.sum(jnp.where(pick == first_held + e, w, 0.0), -1)
        return acc + gate[:, None] * _gated(
            x, _f32("w1", a_e, weights_as), _f32("w2", b_e, weights_as)), None

    held = p["w1"].shape[0]
    r, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(held), p["w1"], p["w2"]))
    f = p["shared_out"].shape[0] // n_shared
    for j in range(n_shared):
        cols = p["shared_in"].shape[1] // 2
        w_in = jnp.concatenate(
            [p["shared_in"][:, j * f:(j + 1) * f],
             p["shared_in"][:, cols + j * f:cols + (j + 1) * f]], axis=1)
        r = r + _gated(x, _f32("shared_in", w_in, weights_as),
                       _f32("shared_out", p["shared_out"][j * f:(j + 1) * f],
                            weights_as)) / n_shared
    return r, own, short


def _layer(h, p, sliding, cfg, weights_as=None, forced=None, window=True,
           rotary=True, ring=None, decode_from=0):
    """One layer over a whole sequence h [T, hidden]; `p` the layer's
    arrays by their short names as the model has them (cast here), `cfg`
    the hashable sizes.  Returns (h, the router's own picks, its shortfall
    under `forced` or None)."""
    with jax.default_matmul_precision("highest"):
        (heads, kv_heads, head_dim, w, theta, top_k, n_shared, first_held,
         eps) = cfg
        x = _layer_norm(h, jnp.asarray(p["norm1"], F32), eps)
        wq, wk, wv, wo, router_w = (
            _f32(k, p[k], weights_as)
            for k in ("wq", "wk", "wv", "wo", "router_w"))
        a = _attention(x, wq, wk, wv, wo, heads, kv_heads, head_dim,
                       w if sliding and window else 0,
                       theta if sliding and rotary else 0.0,
                       ring if sliding else None, decode_from)
        f, pick, short = _experts(
            x, dict(p, router_w=router_w), top_k, n_shared, first_held,
            weights_as, forced)
        return h + a + f, pick, short


def _head(h, norm_f, embed, eps, scale, weights_as=None):
    with jax.default_matmul_precision("highest"):
        return _layer_norm(h, jnp.asarray(norm_f, F32), eps) \
            @ _f32("embed", embed, weights_as).T * scale


MATRICES = ("wq", "wk", "wv", "wo", "w1", "w2", "shared_in", "shared_out",
            "embed")


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


_layer_jit = jax.jit(_layer, static_argnums=(2, 3, 4, 6, 7, 8))
_head_jit = jax.jit(_head, static_argnums=(3, 4, 5))


def sizes_of(cfg):
    """The hashable sizes `_layer` takes, from the configuration's keys
    (`first_held_expert`: the first expert this chip holds, 0 if absent)."""
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], int(cfg["sliding_window"]),
            float(cfg["rope_theta"]), cfg["num_experts_per_tok"],
            cfg["num_shared_experts"], int(cfg.get("first_held_expert", 0)),
            float(cfg["layer_norm_eps"]))


def logits(params, ids, cfg, weights_as=None, picks=None, forced=None,
           shortfall=None, rows=None, window=True, rotary=True,
           hidden=None, ring=None, decode_from=0):
    """Logits [T (or the `rows` asked for), vocabulary rows held] float32
    of one token sequence `ids` [T] (whole blocks of `QUERY_BLOCK`, or
    fewer tokens than one).

    `params`: {"embed", "norm_f", "layers": [{short name: array}]} — the
    served model's arrays as they are (`params_of`); `cfg`: the published
    keys as the configuration file has them (the `layer_types` it runs,
    `first_held_expert`).  Layers run one jitted call each.  `picks`: a
    list that receives each layer's own picked experts [T, k].  `forced`
    [layers, T, k]: the experts each layer weights instead of its own
    picks (`route`); `shortfall` then receives each layer's [T].  `rows`:
    the positions whose logits are wanted (an index array or a slice;
    default all).  `window` / `rotary` False, `ring` one of `RING_FAULTS`
    for the rows from `decode_from` on: the controls.  `hidden`: a list
    that receives `h` after each layer."""
    if weights_as not in (None, "int8"):
        raise ValueError(f"weights_as {weights_as!r}")
    if ring is not None and ring not in RING_FAULTS:
        raise ValueError(f"ring {ring!r} is not one of {RING_FAULTS}")
    sizes = sizes_of(cfg)
    h = jnp.asarray(params["embed"][jnp.asarray(ids)], F32)
    for n, (kind, layer) in enumerate(zip(cfg["layer_types"],
                                          params["layers"])):
        given = None if forced is None \
            else jnp.asarray(forced[n], jnp.int32)
        h, pick, short = _layer_jit(
            h, layer, kind == "sliding_attention", sizes, weights_as, given,
            bool(window), bool(rotary), ring, jnp.int32(decode_from))
        if picks is not None:
            picks.append(pick)
        if shortfall is not None and short is not None:
            shortfall.append(short)
        if hidden is not None:
            hidden.append(h)
    if rows is not None:
        h = h[rows]
    return _head_jit(h, params["norm_f"], params["embed"], sizes[-1],
                     float(cfg.get("logit_scale", 1.0)), weights_as)


def params_of(model):
    """`logits`' `params` from a `paddle_tpu.models.Cohere2MoeModel`: its
    own device arrays, nothing copied."""
    layers = []
    for blk in model.layers:
        p = {"norm1": blk.norm1._value}
        p.update({n: getattr(blk.mixer, n)._value
                  for n in ("wq", "wk", "wv", "wo")})
        p.update({n: getattr(blk.experts, n)._value
                  for n in ("router_w", "w1", "w2", "shared_in",
                            "shared_out")})
        layers.append(p)
    return {"embed": model.embed._value, "norm_f": model.norm_f._value,
            "layers": layers}
