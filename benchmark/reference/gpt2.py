"""Plain reference for the GPT-2 configurations: the full forward pass of
a pre-norm decoder in straightforward `jax.numpy`, float32, matmuls at
`highest` precision — no KV cache, no paging, no batching, no kernels.
Written from the published architecture (Radford et al. 2019;
`openai-community/gpt2*`): learned positions, pre-norm blocks, tied
output head.  Departure the configuration file lists: the program's MLP
uses the exact (erf) GELU where the published model uses the tanh
approximation (`gelu_new`); the reference follows the program so that the
comparison tests the serving path, and says so here.
"""
import jax
import jax.numpy as jnp

LAYER_NORM_EPS = 1e-5
PARAMS_PER_LAYER = 16


def _layer_norm(x, scale, bias):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LAYER_NORM_EPS) * scale + bias


def embed(tok, pos, ids):
    return tok[ids] + pos[jnp.arange(ids.shape[0])]


def block(x, layer_params, num_heads):
    """One pre-norm decoder block over a whole sequence x [seq, hidden],
    causal.  `layer_params`: ln1 (scale, bias), Q, K, V, output projection
    (weight [in, out], bias), ln2, MLP in, MLP out."""
    with jax.default_matmul_precision("highest"):
        (l1s, l1b, wq, bq, wk, bk, wv, bv, wo, bo,
         l2s, l2b, w1, b1, w2, b2) = layer_params
        seq, hidden = x.shape
        dh = hidden // num_heads
        h = _layer_norm(x, l1s, l1b)

        def heads(t):
            return t.reshape(seq, num_heads, dh).transpose(1, 0, 2)

        q, k, v = heads(h @ wq + bq), heads(h @ wk + bk), heads(h @ wv + bv)
        scores = (q @ k.transpose(0, 2, 1)) * (dh ** -0.5)
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        ctx = (jax.nn.softmax(scores, axis=-1) @ v).transpose(1, 0, 2)
        x = x + ctx.reshape(seq, hidden) @ wo + bo
        h = _layer_norm(x, l2s, l2b)
        return x + jax.nn.gelu(h @ w1 + b1, approximate=False) @ w2 + b2


def head(x, lnf_s, lnf_b, tok):
    with jax.default_matmul_precision("highest"):
        return _layer_norm(x, lnf_s, lnf_b) @ tok.T


_block_jit = jax.jit(block, static_argnums=2)
_head_jit = jax.jit(head)


def logits(params, ids, num_layers, num_heads):
    """Logits [seq, vocab] of one token sequence `ids` [seq].

    `params`: flat list in the order the architecture creates them —
    token table, position table; per layer the 16 arrays `block` names;
    final layer norm (scale, bias).  The blocks run one jitted call each,
    so a 48-layer model compiles one block, not 48."""
    p = [jnp.asarray(a, jnp.float32) for a in params]
    tok, pos = p[0], p[1]
    x = embed(tok, pos, jnp.asarray(ids))
    for layer in range(num_layers):
        lo = 2 + layer * PARAMS_PER_LAYER
        x = _block_jit(x, tuple(p[lo:lo + PARAMS_PER_LAYER]), num_heads)
    lnf_s, lnf_b = p[2 + num_layers * PARAMS_PER_LAYER:]
    return _head_jit(x, lnf_s, lnf_b, tok)
