"""LFM2-MoE style decoder (`lfm2_moe`): a stack of GATED SHORT-CONVOLUTION
layers with a grouped-query attention layer every few, each a sequential
block ``h = h + mixer(N(h))``, ``h = h + ffn(N(h))`` of RMSNorms (scale
``w``, not ``1 + w``).  The short convolution: ``[B | C | x] = x W_in``
(three parts of `hidden_size`), ``u = B * x``, a depthwise causal conv of
`conv_L_cache` taps over ``u`` (no bias, no activation), ``(C * conv)
W_out``; a sequence carries its last ``conv_L_cache - 1`` rows of ``u``.
Attention: an RMSNorm over each head's 64 dims of q and of k, rotary
positions in the HALF-SPLIT form (``x cos + rotate_half(x) sin``) over all
head dims at `rope_theta`, softmax of ``q . k / sqrt(head_dim)``.  The
first `num_dense_layers` feed-forwards are a dense gated MLP
(``W2(silu(W1 y) * W3 y)``); the rest a float32 sigmoid router over all
`num_experts` with a selection bias (`use_expert_bias`), top
`num_experts_per_tok`, weights normalised over the picks (``s / (sum s +
eps)``: the router op's 1e-20 here, the family's 1e-6 in the reference — a
difference far under bfloat16's rounding of the weights) and times
`routed_scaling_factor`, gated experts and NO shared expert.  A final
RMSNorm (`embedding_norm`) and a tied head.

Written from the published configuration
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json) and
the family's dense modelling code (transformers' `lfm2`); the layer
equations are in `benchmark/reference/lfm2_moe.py`, the plain float32 twin
the tests and the benchmark compare this model with.

This file is the family's DESCRIPTION (`models/hybrid_decoder.py` has the
layers, the loops and the serving step contract).  The experts held are
`held_experts` from `first_held` on (all of them by default: a layer's
experts on one chip); the layers run are the published `layer_types`'
first `num_hidden_layers` (the rest are further pipeline stages).  The
cache: the attention layers' K and V on the device, every column, and the
short-convolution layers' tails as a `state` group (docs/serving.md); a
prompt bucket past `hybrid_decoder.GQA_PROMPT_MAX` tokens goes through the
blocked attention kernel: prompts of thousands of tokens at 32 heads are
past what `[T, T]` scores afford.
"""
from __future__ import annotations

from .hybrid_decoder import (AttentionSpec, HybridDecoder,
                             HybridDecoderConfig, LayerSpec)

__all__ = ["Lfm2MoeConfig", "Lfm2MoeModel", "lfm2_moe_tiny"]

_KINDS = {"conv": "short_conv", "full_attention": "attention"}
# the published pattern: conv, conv, attention, then (conv x3, attention)
_PATTERN = ("conv", "conv", "full_attention") \
    + ("conv", "conv", "conv", "full_attention") * 4 \
    + ("conv", "conv", "full_attention", "conv", "conv")


class Lfm2MoeConfig(HybridDecoderConfig):
    """The keys of the published config that shape the model, under their
    published names, this chip's share of it (`held_experts`,
    `first_held`; `layer_types` the layers run), and what serving needs
    (`max_position`: the longest context served; `dtype`)."""

    tie_word_embeddings = True
    embedding_multiplier = residual_multiplier = logits_scaling = 1.0
    norm_kind, block_form = "rms", "sequential"
    expert_form = "gated_silu"
    n_shared_experts = 0
    moe_shared_expert_intermediate_size = 0

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 layer_types=_PATTERN, num_attention_heads=32,
                 num_key_value_heads=8, conv_L_cache=3,
                 intermediate_size=7168, moe_intermediate_size=1792,
                 num_dense_layers=2, num_experts=32, num_experts_per_tok=4,
                 norm_topk_prob=True, routed_scaling_factor=1.0,
                 use_expert_bias=True, norm_eps=1e-5, rope_theta=1000000.0,
                 held_experts=None, first_held=0, max_position=128000,
                 bos_id=1, eos_id=7, dtype="bfloat16", embed_init_rms=0.05):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.layer_types = list(layer_types)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = self.hidden_size // self.num_attention_heads
        self.attention_multiplier = self.head_dim ** -0.5
        self.short_conv_width = int(conv_L_cache)
        self.shared_intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.n_routed_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.router_bias = bool(use_expert_bias)
        self.rms_norm_eps = float(norm_eps)
        self.rope_theta = float(rope_theta)
        self.held_experts = self.n_routed_experts if held_experts is None \
            else int(held_experts)
        self.first_held = int(first_held)
        self.max_position = int(max_position)
        self.bos_id, self.eos_id = int(bos_id), int(eos_id)
        self.dtype = dtype
        self.embed_init_rms = float(embed_init_rms)
        bad = sorted(set(self.layer_types) - set(_KINDS))
        if bad:
            raise NotImplementedError(
                f"layer_types: {bad} are not described; built: "
                + ", ".join(_KINDS))
        dense = int(num_dense_layers)
        if not 0 <= dense <= len(self.layer_types):
            raise ValueError(f"{dense} leading dense layers of "
                             f"{len(self.layer_types)}")
        if not 0 < self.held_experts <= self.n_routed_experts \
                - self.first_held or self.first_held < 0:
            raise ValueError(
                f"experts [{self.first_held}, {self.first_held} + "
                f"{self.held_experts}) are not among the "
                f"{self.n_routed_experts} routed")
        self.blocks = [LayerSpec(_KINDS[kind], "mlp" if i < dense
                                 else "experts")
                       for i, kind in enumerate(self.layer_types)]
        self.attention_specs = {
            i: AttentionSpec(None, self.rope_theta, "half", True)
            for i, kind in enumerate(self.layer_types)
            if kind == "full_attention"}
        self._check()

    _HF_KEYS = ("vocab_size", "hidden_size", "layer_types",
                "num_attention_heads", "num_key_value_heads", "conv_L_cache",
                "intermediate_size", "moe_intermediate_size",
                "num_dense_layers", "num_experts", "num_experts_per_tok",
                "norm_topk_prob", "routed_scaling_factor", "use_expert_bias",
                "norm_eps", "rope_theta")
    # what the published config can ask for that is not built, by key:
    # (the value that is built, what the other would need)
    _NOT_BUILT = {
        "conv_bias": (False, "biases on the short convolution and its "
                      "projections"),
        "block_auto_adjust_ff_dim": (False, "a dense width adjusted from "
                                     "intermediate_size"),
        "tie_word_embeddings": (True, "an untied head in this family"),
        "hidden_act": ("silu", "another activation"),
    }

    @classmethod
    def from_published(cls, published: dict, layers=None, held=None,
                       first_held=0, **serving):
        """From the keys of an `lfm2_moe` config.json (others are
        ignored), the first `layers` of its `layer_types` (the rest are
        further pipeline stages; `num_hidden_layers` is read as the length
        of that list), `held` experts from `first_held` on (default: all),
        and serving's own (`max_position`, `eos_id`, `dtype`).  Raises by
        name on what is not built."""
        for key, (built, what) in cls._NOT_BUILT.items():
            if key in published and published[key] != built:
                raise NotImplementedError(
                    f"{key}={published[key]!r}: {what} is not built")
        keys = {k: published[k] for k in cls._HF_KEYS if k in published}
        rope = published.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(
                f"rope_type={rope['rope_type']!r}: scaled rotary "
                "frequencies are not built")
        keys.setdefault("rope_theta", rope.get("rope_theta", 1000000.0))
        types = list(keys.get("layer_types", _PATTERN))
        n = int(published.get("num_hidden_layers", len(types)))
        keys["layer_types"] = types[:n if layers is None else int(layers)]
        return cls(**keys, held_experts=held, first_held=first_held,
                   **serving)


class Lfm2MoeModel(HybridDecoder):
    """The decoder of this description (`HybridDecoder` has the passes)."""

    def __init__(self, cfg: Lfm2MoeConfig = None, **kw):
        super().__init__(cfg or Lfm2MoeConfig(**kw))


def lfm2_moe_tiny(**kw):
    """The CPU tests' size: the published pattern's first six layers (two
    dense, attention at 2), nothing published."""
    base = dict(vocab_size=128, hidden_size=64,
                layer_types=_PATTERN[:6], num_attention_heads=4,
                num_key_value_heads=2, conv_L_cache=3, intermediate_size=96,
                moe_intermediate_size=32, num_dense_layers=2, num_experts=8,
                num_experts_per_tok=4, max_position=64, bos_id=0,
                eos_id=127, dtype="float32", embed_init_rms=0.05)
    base.update(kw)
    return Lfm2MoeModel(Lfm2MoeConfig(**base))
