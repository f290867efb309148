"""Required work of one chip's share of the `nemotron_h` hybrid decoder
with latent routed experts (Mamba-2, grouped-query attention, expert
layers; each layer ONE of them), from a configuration's published sizes
and what a step's experts were actually asked for — the yardstick of the
`nem.moe_*_roofline` shares.  What the *algorithm* needs: no padding to a
bucket, idle rows do nothing, the embedding lookup is not a matmul, an
expert no token picked is not read, a pair of an expert held elsewhere
costs nothing.

`cfg` is the configuration file: `n_routed_experts` there is the experts
HELD (the router's width is `published.n_routed_experts`), `vocab_size`
the rows of the vocabulary held.  The state-space layers' own kernels
(`ssm_update_work`, `ssm_scan_work`) are `work_hybrid`'s, read through
the `mamba_*` names the file repeats.
"""
from benchmark import work_hybrid

BF16, F32 = 2, 4


def sizes(cfg):
    """The derived widths, from the published keys."""
    h = cfg["hidden_size"]
    inner = cfg["expand"] * h
    pattern = cfg["hybrid_override_pattern"]
    return {"hidden": h, "inner": inner,
            "conv_dim": inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"],
            "q_dim": cfg["num_attention_heads"] * cfg["head_dim"],
            "kv_dim": cfg["num_key_value_heads"] * cfg["head_dim"],
            "router": cfg.get("published", {}).get(
                "n_routed_experts", cfg["n_routed_experts"]),
            "held": cfg["n_routed_experts"],
            "mamba_layers": pattern.count("M"),
            "attention_layers": pattern.count("*"),
            "expert_layers": pattern.count("E")}


def matmul_params(cfg):
    """Weights that take part in a matmul, a layer kind and in all.  Of an
    expert layer: `expert` ONE routed expert's two matrices, `experts_dense`
    what every token goes through (router, the latent pair, the shared
    expert).  `dense`: every layer's always-read weights and the head."""
    s = sizes(cfg)
    h, lat = s["hidden"], cfg["moe_latent_size"]
    mamba = h * (s["inner"] + s["conv_dim"] + cfg["mamba_num_heads"]) \
        + s["inner"] * h
    attention = 2 * h * s["q_dim"] + 2 * h * s["kv_dim"]
    expert = 2 * lat * cfg["moe_intermediate_size"]
    experts_dense = h * s["router"] + 2 * h * lat \
        + 2 * h * cfg["moe_shared_expert_intermediate_size"]
    head = h * cfg["vocab_size"]
    return {"mamba": mamba, "attention": attention, "expert": expert,
            "experts_dense": experts_dense, "head": head,
            "dense": s["mamba_layers"] * mamba
            + s["attention_layers"] * attention
            + s["expert_layers"] * experts_dense + head}


def all_params(cfg):
    """Every parameter this chip holds (4,648.2 M for the benchmark's
    cut: the issue's sum)."""
    s, m = sizes(cfg), matmul_params(cfg)
    h, heads = s["hidden"], cfg["mamba_num_heads"]
    small_mamba = s["conv_dim"] * (cfg["conv_kernel"] + 1) + s["inner"] \
        + 3 * heads + h
    return (m["dense"] + h * cfg["vocab_size"] + h      # table, final norm
            + s["mamba_layers"] * small_mamba
            + s["attention_layers"] * h
            + s["expert_layers"] * (s["held"] * m["expert"] + s["router"]
                                    + h))


def experts_work(cfg, pairs, touched):
    """(FLOPs, bytes) of the grouped expert computation of the expert
    layers of ONE launch, given what its `engine/step` / `engine/prefill`
    span carries: `pairs` token-expert pairs that landed on held experts
    and `touched` held experts with at least one pair (both summed over the
    launch's expert layers).  2 FLOPs a weight a pair; the touched experts'
    matrices read once, a pair's latent row read (bfloat16) and its result
    written (float32)."""
    m = matmul_params(cfg)
    lat = cfg["moe_latent_size"]
    return (2 * m["expert"] * pairs,
            BF16 * m["expert"] * touched + pairs * lat * (BF16 + F32))


def _state_and_scan(cfg):
    return (work_hybrid.state_bytes_per_row(cfg),
            work_hybrid.scan_flops_per_token(cfg))


def decode_step_work(cfg, active, context_sum, pairs, touched):
    """(FLOPs, bytes) of one decode step over `active` rows whose cache
    lengths add up to `context_sum`, whose expert layers computed `pairs`
    pairs on `touched` held experts.  Bytes: every always-read matmul
    weight and the head once, the touched experts, the state of the active
    rows read and written, their KV read (the bfloat16 dense view) and a
    new column written."""
    s, m = sizes(cfg), matmul_params(cfg)
    (ssm, conv), scan = _state_and_scan(cfg)
    e_flops, e_bytes = experts_work(cfg, pairs, touched)
    kv_col = 2 * s["attention_layers"] * s["kv_dim"]
    flops = active * (2 * m["dense"] + s["mamba_layers"] * scan) + e_flops \
        + 4 * s["attention_layers"] * s["q_dim"] * context_sum
    bytes_moved = BF16 * m["dense"] + e_bytes + 2 * active * (ssm + conv) \
        + kv_col * BF16 * (context_sum + active)
    return flops, bytes_moved


def prefill_work(cfg, tokens, pairs, touched):
    """(FLOPs, bytes) of one prompt of `tokens` tokens: 2 FLOPs an
    always-read matmul weight a token (the head for one row), the experts'
    pairs, the recurrence, causal attention (4 q_dim a pair of positions,
    half of them masked)."""
    s, m = sizes(cfg), matmul_params(cfg)
    (ssm, conv), scan = _state_and_scan(cfg)
    e_flops, e_bytes = experts_work(cfg, pairs, touched)
    flops = tokens * (2 * (m["dense"] - m["head"])
                      + s["mamba_layers"] * scan) + 2 * m["head"] + e_flops \
        + 2 * s["attention_layers"] * s["q_dim"] * tokens * tokens
    bytes_moved = BF16 * m["dense"] + e_bytes + ssm + conv \
        + 2 * s["attention_layers"] * s["kv_dim"] * BF16 * tokens
    return flops, bytes_moved
