"""Required work from shapes, and the table of peaks.

The yardstick for roofline shares and MFU: operations and bytes the
*algorithm* needs (no padding, no recomputation, embedding lookups are
not matmuls), computed from a configuration's published sizes.  The
program's own walker (`static.analyze_flops`) is printed beside these as
a cross-check and never used for a metric.
"""
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The peaks of one chip of `device_kind`; an unknown kind raises."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r} in "
            f"benchmark/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def roofline_seconds(flops, bytes_moved, peak):
    """The least time one chip could take, and which term bounds it."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = bytes_moved / peak["hbm_bytes_per_s"]
    return max(t_compute, t_memory), \
        ("compute" if t_compute >= t_memory else "memory")


# ---------------------------------------------------------------------------
# BERT-style encoder, masked-LM head, trained (forward + backward)
# ---------------------------------------------------------------------------
def bert_block_matmul_params(cfg):
    """Weights that take part in a matmul in the encoder blocks: Q, K, V
    and output projections (4 h^2) and the two FFN matrices (2 h f)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * h * h + 2 * h * f)


def bert_head_matmul_params(cfg):
    """The hidden -> vocabulary output projection."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def bert_train_flops_per_token(cfg, seq_len):
    """Forward + backward matmul FLOPs one token requires: 6 per matmul
    weight (2 forward, 4 backward) plus attention's QK^T and AV products
    (4 s h forward per layer, three times that with the backward pass).
    The token and position tables are lookups and do not count."""
    dense = 6 * (bert_block_matmul_params(cfg) + bert_head_matmul_params(cfg))
    attention = 12 * cfg["num_hidden_layers"] * seq_len * cfg["hidden_size"]
    return dense + attention


def bert_all_params(cfg):
    """Every trained parameter of the program's BERT (tables, blocks with
    biases and layer norms, head with bias)."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    per_layer = 4 * (h * h + h) + (h * f + f) + (f * h + h) + 4 * h
    return (v * h + cfg["max_position_embeddings"] * h + 2 * h
            + cfg["num_hidden_layers"] * per_layer + h * v + v)


def bert_train_bytes_per_step(cfg, compute_itemsize=2):
    """HBM traffic one optimizer step cannot avoid: Adam reads the fp32
    master weight, both moments and the gradient and writes the first
    three (28 B a parameter), and the matmul weights are read once in the
    compute type by the forward and once by the backward pass."""
    return 28 * bert_all_params(cfg) + 2 * compute_itemsize * (
        bert_block_matmul_params(cfg) + bert_head_matmul_params(cfg))


# ---------------------------------------------------------------------------
# GPT-2-style decoder, served (forward only)
# ---------------------------------------------------------------------------
def gpt_block_matmul_params(cfg):
    h = cfg["n_embd"]
    f = cfg.get("n_inner") or 4 * h
    return cfg["n_layer"] * (4 * h * h + 2 * h * f)


def gpt_all_params(cfg):
    h, v = cfg["n_embd"], cfg["vocab_size"]
    f = cfg.get("n_inner") or 4 * h
    per_layer = 4 * (h * h + h) + (h * f + f) + (f * h + h) + 4 * h
    return v * h + cfg["n_positions"] * h + cfg["n_layer"] * per_layer + 2 * h


def gpt_forward_work(cfg, rows, context_sum, logit_rows, forwards,
                     weight_itemsize=4, kv_itemsize=4):
    """(FLOPs, bytes) that `forwards` forward passes require when together
    they process `rows` token rows, of which `logit_rows` need logits (the
    last row of a prompt, every decoded row), and each row attends over a
    context whose lengths add up to `context_sum`.

    FLOPs: 2 per block matmul weight per row, 2 h V per logits row, and
    4 h per (row, context position, layer) for QK^T and AV.  Bytes: every
    weight is read once per forward pass (the tied table only where logits
    are computed, counted once with the rest), each context position's
    keys and values are read once per row that attends over it, and each
    row's own keys and values are written once."""
    h, layers = cfg["n_embd"], cfg["n_layer"]
    flops = (2 * gpt_block_matmul_params(cfg) * rows
             + 2 * h * cfg["vocab_size"] * logit_rows
             + 4 * h * layers * context_sum)
    kv_col = 2 * layers * h * kv_itemsize
    bytes_moved = (forwards * weight_itemsize * gpt_all_params(cfg)
                   + kv_col * (context_sum + rows))
    return flops, bytes_moved
