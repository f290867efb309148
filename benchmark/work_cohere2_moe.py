"""Required work of one chip's share of the `cohere2_moe` decoder (window
and full attention layers, a parallel block, gated routed experts beside
averaged shared experts), from a configuration's published sizes and what
a launch's own span says it was asked for — the yardstick of the `cmd.*`
roofline shares.  What the *algorithm* needs: no padding to a bucket, idle
rows do nothing, the embedding lookup is not a matmul, an expert no token
picked is not read, a pair of an expert held elsewhere costs nothing, a
window layer's query meets the keys inside its window and no others.

`cfg` is the configuration file: `num_experts` there is the experts HELD
(the router's width is `published.num_experts`), `vocab_size` the rows of
the vocabulary held, `layer_types[:num_hidden_layers]` the layers run.
"""
BF16, F32 = 2, 4


def sizes(cfg):
    """The derived widths and counts, from the published keys."""
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    return {"hidden": cfg["hidden_size"],
            "q_dim": cfg["num_attention_heads"] * cfg["head_dim"],
            "kv_dim": cfg["num_key_value_heads"] * cfg["head_dim"],
            "router": cfg.get("published", {}).get(
                "num_experts", cfg["num_experts"]),
            "held": cfg["num_experts"],
            "window": int(cfg["sliding_window"]),
            "window_layers": kinds.count("sliding_attention"),
            "full_layers": kinds.count("full_attention"),
            "layers": len(kinds)}


def matmul_params(cfg):
    """Weights that take part in a matmul, a layer and in all: `expert` ONE
    routed expert's three matrices (gate, up, down); `layer_dense` what
    every token goes through in a layer (q, k, v, o, the router, the shared
    experts); `head` the tied table as the output projection; `dense` every
    layer's always-read weights and the head."""
    s = sizes(cfg)
    h, f = s["hidden"], cfg["intermediate_size"]
    attention = h * (s["q_dim"] + 2 * s["kv_dim"]) + s["q_dim"] * h
    expert = 3 * h * f
    layer_dense = attention + cfg["num_shared_experts"] * expert \
        + h * s["router"]
    head = h * cfg["vocab_size"]
    return {"attention": attention, "expert": expert,
            "layer_dense": layer_dense, "head": head,
            "dense": s["layers"] * layer_dense + head}


def all_params(cfg):
    """Every parameter this chip holds (4,733.3 M for the benchmark's cut:
    the issue's sum): the layers' always-read weights, their held experts
    and one norm each, the tied table once, the final norm."""
    s, m = sizes(cfg), matmul_params(cfg)
    return (s["layers"] * (m["layer_dense"] + s["held"] * m["expert"]
                           + s["hidden"])
            + m["head"] + s["hidden"])


def experts_work(cfg, pairs, touched):
    """(FLOPs, bytes) of the grouped expert computation of ONE launch's
    layers, given what its `engine/step` / `engine/prefill` span carries:
    `pairs` token-expert pairs that landed on held experts and `touched`
    held experts with at least one pair (both summed over the launch's
    layers).  2 FLOPs a weight a pair; the touched experts' matrices read
    once, a pair's input row read (bfloat16) and its result written
    (float32)."""
    m = matmul_params(cfg)
    return (2 * m["expert"] * pairs,
            BF16 * m["expert"] * touched
            + pairs * cfg["hidden_size"] * (BF16 + F32))


def visible_pairs(tokens, window=0):
    """(query, key) pairs a causal layer computes for a prompt of `tokens`:
    sum_i min(i + 1, window), all `i + 1` without a window."""
    if not window or tokens <= window:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def prefill_attention_work(cfg, tokens):
    """(FLOPs, bytes) of the prompt attention kernel over ALL the layers of
    one prefill of `tokens` VALID tokens: 4 FLOPs a head dim a visible pair
    (q.k and p.v); q read and the result written, k and v read, bfloat16."""
    s = sizes(cfg)
    pairs = s["window_layers"] * visible_pairs(tokens, s["window"]) \
        + s["full_layers"] * visible_pairs(tokens)
    return (4 * s["q_dim"] * pairs,
            s["layers"] * tokens * BF16 * 2 * (s["q_dim"] + s["kv_dim"]))


def decode_attention_work(cfg, kv_columns, active):
    """(FLOPs, bytes) of the cached attention of ONE decode step over all
    its layers: `kv_columns` = the valid columns its `active` rows read,
    summed over rows and layers (the `engine/step` span's field: 3 x
    min(length + 1, window) + length + 1 a row here).  4 FLOPs a head dim
    a column; each column's K and V read, a row's new K and V written, its
    q read and result written, a layer."""
    s = sizes(cfg)
    return (4 * s["q_dim"] * kv_columns,
            BF16 * (2 * s["kv_dim"] * kv_columns
                    + active * s["layers"] * 2 * (s["kv_dim"] + s["q_dim"])))


def decode_step_work(cfg, active, kv_columns, pairs, touched):
    """(FLOPs, bytes) of one whole decode step over `active` rows: 2 FLOPs
    an always-read matmul weight (the head among them) a row; every such
    weight read once; the experts' and the attention's own."""
    m = matmul_params(cfg)
    e_flops, e_bytes = experts_work(cfg, pairs, touched)
    a_flops, a_bytes = decode_attention_work(cfg, kv_columns, active)
    return (2 * m["dense"] * active + e_flops + a_flops,
            BF16 * m["dense"] + e_bytes + a_bytes)


def prefill_work(cfg, tokens, pairs, touched):
    """(FLOPs, bytes) of one prompt of `tokens` VALID tokens: 2 FLOPs an
    always-read matmul weight a token (the head for one row), the experts'
    pairs, the attention's visible pairs; the weights read once."""
    m = matmul_params(cfg)
    e_flops, e_bytes = experts_work(cfg, pairs, touched)
    a_flops, a_bytes = prefill_attention_work(cfg, tokens)
    return (2 * (m["dense"] - m["head"]) * tokens + 2 * m["head"]
            + e_flops + a_flops,
            BF16 * m["dense"] + e_bytes + a_bytes)
