"""Plain reference for the `bert-base` configuration: the masked-LM loss
of a post-norm BERT encoder in straightforward `jax.numpy`, float32,
matmuls at `highest` precision — no kernels, no mixed precision, no
program IR.  Independent of the code under test: it is written from the
published architecture (Devlin et al. 2018; `google-bert/bert-base-uncased`)
with the departures the configuration file lists (no token-type table, no
pooler/NSP, no MLM transform, an untied output projection, loss on every
position, layer-norm epsilon 1e-5).
"""
import jax
import jax.numpy as jnp

LAYER_NORM_EPS = 1e-5           # the program's; the published model has 1e-12
PARAMS_PER_LAYER = 16           # q k v o (w, b), ln, ffn in/out (w, b), ln


def _layer_norm(x, scale, bias):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LAYER_NORM_EPS) * scale + bias


def _split_heads(x, heads):
    b, s, h = x.shape
    return x.reshape(b, s, heads, h // heads).transpose(0, 2, 1, 3)


def mlm_loss(params, ids, labels, num_layers, num_heads):
    """Mean cross-entropy over every position.

    `params`: the flat list of arrays in the order the architecture
    creates them — token table, position table, embedding layer norm
    (scale, bias); per layer Q, K, V, output projection (weight [in, out],
    bias), layer norm, FFN in, FFN out, layer norm; output projection
    (weight, bias).  `ids`, `labels`: int [batch, seq]."""
    with jax.default_matmul_precision("highest"):
        p = [jnp.asarray(a, jnp.float32) for a in params]
        tok, pos, ln_s, ln_b = p[:4]
        seq = ids.shape[1]
        x = _layer_norm(tok[ids] + pos[jnp.arange(seq)][None], ln_s, ln_b)
        for layer in range(num_layers):
            (wq, bq, wk, bk, wv, bv, wo, bo, l1s, l1b,
             w1, b1, w2, b2, l2s, l2b) = p[4 + layer * PARAMS_PER_LAYER:
                                           4 + (layer + 1) * PARAMS_PER_LAYER]
            q = _split_heads(x @ wq + bq, num_heads)
            k = _split_heads(x @ wk + bk, num_heads)
            v = _split_heads(x @ wv + bv, num_heads)
            scores = (q @ k.transpose(0, 1, 3, 2)) * (q.shape[-1] ** -0.5)
            ctx = jax.nn.softmax(scores, axis=-1) @ v
            ctx = ctx.transpose(0, 2, 1, 3).reshape(x.shape)
            x = _layer_norm(x + ctx @ wo + bo, l1s, l1b)
            ffn = jax.nn.gelu(x @ w1 + b1, approximate=False) @ w2 + b2
            x = _layer_norm(x + ffn, l2s, l2b)
        w_out, b_out = p[4 + num_layers * PARAMS_PER_LAYER:]
        logp = jax.nn.log_softmax(x @ w_out + b_out, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -picked.mean()


def mlm_loss_chunked(params, ids, labels, num_layers, num_heads, chunk=8):
    """The same mean over a batch too large to hold its logits at once:
    equal-sized chunks of sequences, averaged."""
    n = ids.shape[0]
    if n % chunk:
        raise ValueError(f"batch {n} is not a multiple of chunk {chunk}")
    fn = jax.jit(mlm_loss, static_argnums=(3, 4))
    parts = [fn(params, ids[i:i + chunk], labels[i:i + chunk], num_layers,
                num_heads) for i in range(0, n, chunk)]
    return float(jnp.mean(jnp.stack(parts)))
