"""Required work of the `lfm2_moe` decoder (gated short-convolution layers
beside grouped-query attention layers with QK-norm, two leading dense
layers, biased gated routed experts with no shared expert, a tied head),
from a configuration's published sizes and what a launch's own span says it
was asked for — the yardstick of the `lfm.*` roofline shares.  What the
*algorithm* needs: no padding to a bucket, idle rows do nothing, the
embedding lookup is not a matmul, an expert no token picked is not read, a
decode row attends its own columns (not the bucket's).

`cfg` is the configuration file: `num_hidden_layers` of `layer_types` run,
the first `num_dense_layers` of them with the dense MLP; `num_experts` the
experts held.
"""
BF16, F32 = 2, 4


def sizes(cfg):
    """The derived widths and counts, from the published keys."""
    types = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    heads, h = cfg["num_attention_heads"], cfg["hidden_size"]
    dense = int(cfg["num_dense_layers"])
    return {"hidden": h, "heads": heads,
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": h // heads, "taps": cfg["conv_L_cache"],
            "layers": len(types), "conv_layers": types.count("conv"),
            "attention_layers": types.count("full_attention"),
            "dense_layers": dense, "expert_layers": len(types) - dense,
            "experts": cfg["num_experts"]}


def matmul_params(cfg):
    """Weights that take part in a matmul: `conv` one short-convolution
    mixer's (in 3 x hidden, out hidden); `attention` one attention mixer's
    (q, k, v, o); `mlp` a dense layer's three; `expert` ONE routed
    expert's three; `head` the tied table read as the head; `dense` every
    always-read weight (the routed experts aside), the routers and the
    head among them."""
    s = sizes(cfg)
    h, d = s["hidden"], s["head_dim"]
    conv = 4 * h * h
    attention = 2 * h * s["heads"] * d + 2 * h * s["kv_heads"] * d
    mlp = 3 * h * cfg["intermediate_size"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    head = h * cfg["vocab_size"]
    return {"conv": conv, "attention": attention, "mlp": mlp,
            "expert": expert, "head": head,
            "dense": s["conv_layers"] * conv + s["attention_layers"]
            * attention + s["dense_layers"] * mlp
            + s["expert_layers"] * h * s["experts"] + head}


def all_params(cfg):
    """Every parameter this chip holds (3,928.7 M for the benchmark's cut:
    the configuration file's `bytes` sum with the routers, the taps and
    the norms): the always-read weights (the table once: tied), the held
    experts and the routers' biases, the conv taps, two norms a layer, two
    per-head norms an attention layer, the final norm."""
    s, m = sizes(cfg), matmul_params(cfg)
    norms = s["layers"] * 2 * s["hidden"] \
        + s["attention_layers"] * 2 * s["head_dim"] + s["hidden"]
    experts = s["expert_layers"] * s["experts"] * m["expert"]
    biases = s["expert_layers"] * s["experts"]
    return (m["dense"] + experts + biases
            + s["conv_layers"] * s["hidden"] * s["taps"] + norms)


def experts_work(cfg, pairs, touched):
    """(FLOPs, bytes) of the grouped expert computation of ONE launch's
    layers, given what its span carries: `pairs` token-expert pairs and
    `touched` experts with at least one pair (both summed over the
    launch's layers).  2 FLOPs a weight a pair; the touched experts'
    three matrices read once, a pair's input row read (bfloat16) and its
    result written (float32)."""
    m = matmul_params(cfg)
    return (2 * m["expert"] * pairs,
            BF16 * m["expert"] * touched
            + pairs * cfg["hidden_size"] * (BF16 + F32))


def prompt_attention_work(cfg, tokens):
    """(FLOPs, bytes) of the blocked prompt attention over ALL the
    attention layers of one prefill of `tokens` VALID tokens: 4 x head dim
    x query heads = 8,192 FLOPs a causal (query, key) pair a layer; q and
    the result (32 heads), k and v (8 heads) read or written once,
    bfloat16."""
    s = sizes(cfg)
    d, pairs = s["head_dim"], tokens * (tokens + 1) // 2
    return (s["attention_layers"] * 4 * d * s["heads"] * pairs,
            s["attention_layers"] * tokens * BF16 * d
            * 2 * (s["heads"] + s["kv_heads"]))


def decode_attention_work(cfg, kv_columns, active):
    """(FLOPs, bytes) of the cached attention of ONE decode step over all
    its attention layers: `kv_columns` = the columns its `active` rows
    attend, summed over rows and layers (the `engine/step` span's field:
    length + 1 a row a layer).  8,192 FLOPs and a K and a V column of 8
    heads, 2,048 B, a column; a row's q read and its context written (32
    heads), a layer."""
    s = sizes(cfg)
    d = s["head_dim"]
    return (4 * d * s["heads"] * kv_columns,
            BF16 * (2 * s["kv_heads"] * d * kv_columns
                    + active * s["attention_layers"] * 2 * s["heads"] * d))


def decode_step_work(cfg, active, kv_columns, pairs, touched):
    """(FLOPs, bytes) of one whole decode step over `active` rows: 2 FLOPs
    an always-read matmul weight (the head among them) a row; every such
    weight read once; the experts', the attention's and the conv tails'
    own."""
    m = matmul_params(cfg)
    e_flops, e_bytes = experts_work(cfg, pairs, touched)
    a_flops, a_bytes = decode_attention_work(cfg, kv_columns, active)
    s = sizes(cfg)
    tails = active * s["conv_layers"] * BF16 * 2 * (s["taps"] - 1) \
        * s["hidden"]
    return (2 * m["dense"] * active + e_flops + a_flops,
            BF16 * m["dense"] + e_bytes + a_bytes + tails)


def prefill_work(cfg, tokens, pairs, touched):
    """(FLOPs, bytes) of one prompt of `tokens` VALID tokens: 2 FLOPs an
    always-read matmul weight a token (the head for one row), the
    experts' pairs, the attention's causal pairs; the weights read once."""
    m = matmul_params(cfg)
    e_flops, e_bytes = experts_work(cfg, pairs, touched)
    a_flops, a_bytes = prompt_attention_work(cfg, tokens)
    return (2 * (m["dense"] - m["head"]) * tokens + 2 * m["head"]
            + e_flops + a_flops,
            BF16 * m["dense"] + e_bytes + a_bytes)
