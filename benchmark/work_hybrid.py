"""Required work of the hybrid (Mamba-2 + grouped-query attention)
decoder, from a configuration's published sizes — the yardstick of the
`hyb.*_roofline` shares.  What the *algorithm* needs: no padding to a
bucket, idle rows do nothing, the embedding lookup is not a matmul.
"""

BF16, F32 = 2, 4


def sizes(cfg):
    """The derived widths, from the published keys."""
    h = cfg["hidden_size"]
    inner = cfg["mamba_expand"] * h
    conv_dim = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    head_dim = h // cfg["num_attention_heads"]
    return {"hidden": h, "inner": inner, "conv_dim": conv_dim,
            "head_dim": head_dim,
            "kv_dim": cfg["num_key_value_heads"] * head_dim,
            "mamba_layers": cfg["layer_types"].count("mamba"),
            "attention_layers": cfg["layer_types"].count("attention")}


def matmul_params(cfg):
    """Weights that take part in a matmul, a layer kind and in all: the
    Mamba in/out projections, the attention q/k/v/o, the shared MLP's two
    matrices, and the tied table once (the head)."""
    s = sizes(cfg)
    h, f = s["hidden"], cfg["shared_intermediate_size"]
    mlp = h * 2 * f + f * h
    mamba = h * (s["inner"] + s["conv_dim"] + cfg["mamba_n_heads"]) \
        + s["inner"] * h + mlp
    attention = 2 * h * h + 2 * h * s["kv_dim"] + mlp
    head = h * cfg["vocab_size"]
    return {"mamba": mamba, "attention": attention, "head": head,
            "blocks": s["mamba_layers"] * mamba
            + s["attention_layers"] * attention}


def all_params(cfg):
    """Every parameter (the issue's sum: 3,191 M at published sizes)."""
    s, m = sizes(cfg), matmul_params(cfg)
    h, heads = s["hidden"], cfg["mamba_n_heads"]
    small_mamba = s["conv_dim"] * (cfg["mamba_d_conv"] + 1) + s["inner"] \
        + 3 * heads + 2 * h
    return (m["blocks"] + m["head"] + h
            + s["mamba_layers"] * small_mamba
            + s["attention_layers"] * 2 * h)


def state_bytes_per_row(cfg):
    """(SSM state, conv tails) bytes one sequence holds."""
    s = sizes(cfg)
    ssm = s["mamba_layers"] * cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
        * cfg["mamba_d_state"] * F32
    conv = s["mamba_layers"] * (cfg["mamba_d_conv"] - 1) * s["conv_dim"] \
        * BF16
    return ssm, conv


def scan_flops_per_token(cfg):
    """The recurrence's own FLOPs a token a Mamba layer, token by token:
    decay and outer-product update of the [H, P, N] state (3 a state
    element) and the read-out against C (2 a state element), plus the conv
    (2 K a channel): ~2.6 MFLOP at the published sizes."""
    s = sizes(cfg)
    state = cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    return 5 * state + 2 * cfg["mamba_d_conv"] * s["conv_dim"]


def decode_step_work(cfg, active, context_sum, weight_itemsize=BF16,
                     kv_itemsize=F32):
    """(FLOPs, bytes) of one decode step over `active` rows whose cache
    lengths add up to `context_sum`.  Bytes: every matmul weight once and
    the table once for the head, the state of the active rows read and
    written, their KV read (as the pool stores it) and a new column
    written."""
    s, m = sizes(cfg), matmul_params(cfg)
    ssm, conv = state_bytes_per_row(cfg)
    kv_col = 2 * s["attention_layers"] * s["kv_dim"]
    flops = active * (2 * (m["blocks"] + m["head"])
                      + s["mamba_layers"] * scan_flops_per_token(cfg)) \
        + 4 * s["attention_layers"] * s["hidden"] * context_sum
    bytes_moved = weight_itemsize * (m["blocks"] + m["head"]) \
        + 2 * active * (ssm + conv) \
        + kv_col * kv_itemsize * (context_sum + active)
    return flops, bytes_moved


def prefill_work(cfg, tokens, weight_itemsize=BF16):
    """(FLOPs, bytes) of one prompt of `tokens` tokens: 2 FLOPs a matmul
    weight a token (the head for one row), the recurrence, causal
    attention (4 h a pair of positions, half of them masked)."""
    s, m = sizes(cfg), matmul_params(cfg)
    ssm, conv = state_bytes_per_row(cfg)
    flops = tokens * (2 * m["blocks"]
                      + s["mamba_layers"] * scan_flops_per_token(cfg)) \
        + 2 * m["head"] \
        + 2 * s["attention_layers"] * s["hidden"] * tokens * tokens
    bytes_moved = weight_itemsize * (m["blocks"] + m["head"]) + ssm + conv \
        + 2 * s["attention_layers"] * s["kv_dim"] * BF16 * tokens
    return flops, bytes_moved


def ssm_update_work(cfg, active):
    """(FLOPs, bytes) of ONE Mamba layer's one-token state update over
    `active` rows: its slice of the state read once and written once."""
    state = cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    return 5 * state * active, 2 * state * F32 * active


def ssm_scan_work(cfg, tokens):
    """(FLOPs, bytes) of ONE Mamba layer's scan over a prompt of `tokens`
    tokens, at the recurrence's own count (the chunked form spends more
    and is charged no more): x, B, C, dt read and y written in bfloat16,
    the final state written."""
    s = sizes(cfg)
    state = cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    io = tokens * BF16 * (2 * s["inner"] + 2 * cfg["mamba_n_groups"]
                          * cfg["mamba_d_state"] + cfg["mamba_n_heads"])
    return 5 * state * tokens, io + state * F32
