"""Cohere2-MoE style decoder: every layer is a PARALLEL block — one
mean-subtracting LayerNorm (scale only), then grouped-query attention and
routed experts both from that same normed input, ``h = h + attn(x) +
ffn(x)`` — whose attention is, by `layer_types`, either
`sliding_attention` (a window of `sliding_window` keys, rotary positions in
interleaved pairs over all head dims) or `full_attention` (every earlier
key, no positions), and whose feed-forward is a sigmoid router over all
`num_experts` with top-`num_experts_per_tok` (no selection bias, weights
normalised over the picks), gated experts ``D(silu(G x) * U x)`` and
`num_shared_experts` shared experts of the same form, averaged.  Tied
head, `logit_scale`.

Written from the published `cohere2_moe` configuration
(https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json).
The layer equations are in `benchmark/reference/cohere2_moe.py`, the plain
float32 twin the tests and the benchmark compare this model with.

This file is the family's DESCRIPTION (`models/hybrid_decoder.py` has the
layers, the loops and the serving step contract, shared with
`models/granite_hybrid.py` and `models/nemotron_h.py`), and it describes
ONE CHIP'S SHARE of a deployment that divides each layer over several chips
by expert parallelism: the `held_experts` experts from `first_held` on of
each layer, rows `[0, vocab_rows)` of the tied table, and a range of the
published layers (the rest are further pipeline stages).  The router keeps
its published width and top-k; what the experts held elsewhere would add
is left out, and no code stands in for the other chips or the exchange
with them (ROADMAP R-d).  The KV cache is on the device only, a ring of
`sliding_window` columns a window layer and every column a full layer
(docs/serving.md).
"""
from __future__ import annotations

from .hybrid_decoder import (AttentionSpec, HybridDecoder,
                             HybridDecoderConfig, LayerSpec)

__all__ = ["Cohere2MoeConfig", "Cohere2MoeModel", "cohere2_moe_tiny"]

_PERIOD = ("sliding_attention", "sliding_attention", "sliding_attention",
           "full_attention")


class Cohere2MoeConfig(HybridDecoderConfig):
    """The keys of the published config that shape the model, under their
    published names, this chip's share of it (`held_experts`,
    `first_held`; `vocab_size` is the rows of the vocabulary held), and
    what serving needs (`max_position`: the longest context served;
    `dtype`)."""

    tie_word_embeddings = True
    embedding_multiplier = residual_multiplier = 1.0
    block_form, norm_kind = "parallel", "layer"
    expert_form = "gated_silu"
    routed_scaling_factor = 1.0

    def __init__(self, vocab_size=262144, hidden_size=4096,
                 layer_types=_PERIOD * 8, num_attention_heads=128,
                 num_key_value_heads=8, head_dim=128, sliding_window=4096,
                 rope_theta=50000.0, intermediate_size=4096,
                 num_experts=128, num_experts_per_tok=8,
                 num_shared_experts=4, norm_topk_prob=True,
                 layer_norm_eps=1e-5, logit_scale=1.0, held_experts=None,
                 first_held=0, max_position=200000, bos_id=0, eos_id=1,
                 dtype="bfloat16", embed_init_rms=0.05):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.layer_types = list(layer_types)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.sliding_window = int(sliding_window)
        self.rope_theta = float(rope_theta)
        # softmax(q . k / sqrt(head_dim)): no other scale is published
        self.attention_multiplier = self.head_dim ** -0.5
        # under the names the shared layers read: `intermediate_size` is
        # ONE expert's width, routed or shared
        self.n_routed_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.moe_intermediate_size = int(intermediate_size)
        self.moe_shared_expert_intermediate_size = int(intermediate_size)
        self.n_shared_experts = int(num_shared_experts)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_norm_eps = float(layer_norm_eps)   # the norms' epsilon
        self.logits_scaling = 1.0 / float(logit_scale)
        self.held_experts = self.n_routed_experts if held_experts is None \
            else int(held_experts)
        self.first_held = int(first_held)
        self.max_position = int(max_position)
        self.bos_id, self.eos_id = int(bos_id), int(eos_id)
        self.dtype = dtype
        self.embed_init_rms = float(embed_init_rms)
        bad = sorted(set(self.layer_types) - set(_PERIOD))
        if bad:
            raise NotImplementedError(
                f"layer_types: {bad} are not described; built: "
                "sliding_attention, full_attention")
        if self.n_shared_experts < 1:
            raise NotImplementedError("a layer without shared experts")
        if not 0 < self.held_experts <= self.n_routed_experts \
                - self.first_held or self.first_held < 0:
            raise ValueError(
                f"experts [{self.first_held}, {self.first_held} + "
                f"{self.held_experts}) are not among the "
                f"{self.n_routed_experts} routed")
        self.blocks = [LayerSpec("attention", "experts")] \
            * len(self.layer_types)
        # a full_attention layer sees every earlier key and has no positions
        self.attention_specs = {
            i: AttentionSpec(self.sliding_window, self.rope_theta)
            for i, kind in enumerate(self.layer_types)
            if kind == "sliding_attention"}
        self._check()

    _HF_KEYS = ("vocab_size", "hidden_size", "layer_types",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "sliding_window", "rope_theta", "intermediate_size",
                "num_experts", "num_experts_per_tok", "num_shared_experts",
                "norm_topk_prob", "layer_norm_eps", "logit_scale")
    # what the published config can ask for that is not built, by key:
    # (the value that is built, what the other would need)
    _NOT_BUILT = {
        "use_qk_norm": (False, "a norm on q and k"),
        "attention_bias": (False, "biases on q / k / v / o"),
        "rotary_pct": (1, "rotary positions on a part of the head dims"),
        "first_k_dense_replace": (0, "leading dense layers"),
        "use_parallel_block": (True, "a sequential block in this family"),
        "expert_selection_fn": ("sigmoid", "another gate than a sigmoid"),
        "shared_expert_combination_strategy": (
            "average", "another combination of the shared experts"),
        "use_gated_activation": (True, "experts without a gate"),
        "hidden_act": ("silu", "another expert activation"),
        "position_embedding_type": (
            "rope_gptj", "another form of positions than interleaved "
            "rotary pairs"),
        "tie_word_embeddings": (True, "an untied head in this family"),
        "use_parallel_embedding": (False, "a parallel embedding"),
    }

    @classmethod
    def from_published(cls, published: dict, held_experts=None,
                       first_held=0, vocab_rows=None, layers=None,
                       **serving):
        """From the keys of a `cohere2_moe` config.json (others are
        ignored), this chip's share — `held_experts` experts from
        `first_held` on, the first `vocab_rows` rows of the vocabulary,
        `layers` = (first, end) of the published `layer_types` — and
        serving's own (`max_position`, `eos_id`, `dtype`).  Raises by name
        on what is not built."""
        for key, (built, what) in cls._NOT_BUILT.items():
            if key in published and published[key] != built:
                raise NotImplementedError(
                    f"{key}={published[key]!r}: {what} is not built")
        if published.get("vision_config") or published.get("image_token_id"):
            raise NotImplementedError(
                "a vision input: the tower and its projector are not "
                "built; this is the language model, text only")
        keys = {k: published[k] for k in cls._HF_KEYS if k in published}
        rope = published.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(
                f"rope_type={rope['rope_type']!r}: scaled rotary "
                "frequencies are not built")
        keys.setdefault("rope_theta", rope.get("rope_theta", 50000.0))
        if layers is not None:
            first, end = layers
            keys["layer_types"] = list(
                keys.get("layer_types", _PERIOD * 8))[first:end]
        if vocab_rows is not None:
            keys["vocab_size"] = int(vocab_rows)
        return cls(**keys, held_experts=held_experts, first_held=first_held,
                   **serving)


class Cohere2MoeModel(HybridDecoder):
    """The decoder of this description (`HybridDecoder` has the passes)."""

    def __init__(self, cfg: Cohere2MoeConfig = None, **kw):
        super().__init__(cfg or Cohere2MoeConfig(**kw))


def cohere2_moe_tiny(**kw):
    """The CPU tests' size: a period of the pattern with a window of 8,
    nothing published."""
    base = dict(vocab_size=128, hidden_size=64, layer_types=_PERIOD,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                sliding_window=8, rope_theta=50000.0, intermediate_size=48,
                num_experts=16, num_experts_per_tok=4, num_shared_experts=2,
                max_position=64, bos_id=0, eos_id=127, dtype="float32",
                embed_init_rms=0.05)
    base.update(kw)
    return Cohere2MoeModel(Cohere2MoeConfig(**base))
