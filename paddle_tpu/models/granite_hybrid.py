"""Granite-4.0-H style hybrid decoder: Mamba-2 state-space layers beside
grouped-query attention layers, RMSNorm, a gated (SwiGLU) MLP in every
block, scaled residuals, no positions, tied head.

Written from the published `granitemoehybrid` configuration
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json;
`num_local_experts` 0: the shared MLP is the only one).  The layer
equations are in `benchmark/reference/granite_hybrid.py`, the plain
float32 twin the tests and the benchmark compare this model with.

This file is the family's DESCRIPTION: every layer "a mixer, then the
gated MLP", granite's three multipliers and logits divisor, the tied
table.  The layers, the loops and the serving step contract are
`models/hybrid_decoder.py`'s, shared with every other family described
that way (`models/nemotron_h.py`).
"""
from __future__ import annotations

from .hybrid_decoder import HybridDecoder, HybridDecoderConfig, LayerSpec

__all__ = ["GraniteHybridConfig", "GraniteHybridModel", "granite_hybrid_tiny"]

_PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4   # the published one


class GraniteHybridConfig(HybridDecoderConfig):
    """The keys of the published config that shape the model, under their
    published names, plus what serving needs (`max_position`: the longest
    context served, the model itself has no positions; `dtype`)."""

    tie_word_embeddings = True
    mamba_norm_groups = 1       # the mixer's output norm: one group

    def __init__(self, vocab_size=100352, hidden_size=2048,
                 layer_types=None, num_attention_heads=32,
                 num_key_value_heads=8, shared_intermediate_size=8192,
                 mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
                 mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
                 mamba_chunk_size=256, embedding_multiplier=12.0,
                 residual_multiplier=0.22, attention_multiplier=0.015625,
                 logits_scaling=8.0, rms_norm_eps=1e-5,
                 max_position=131072, bos_id=100257, eos_id=100257,
                 dtype="bfloat16", embed_init_rms=0.05):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.layer_types = list(layer_types or _PERIOD * 4)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.shared_intermediate_size = int(shared_intermediate_size)
        self.mamba_n_heads = int(mamba_n_heads)
        self.mamba_d_head = int(mamba_d_head)
        self.mamba_d_state = int(mamba_d_state)
        self.mamba_n_groups = int(mamba_n_groups)
        self.mamba_d_conv = int(mamba_d_conv)
        self.mamba_expand = int(mamba_expand)
        self.mamba_chunk_size = int(mamba_chunk_size)
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.attention_multiplier = float(attention_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.rms_norm_eps = float(rms_norm_eps)
        self.max_position = int(max_position)
        self.bos_id, self.eos_id = int(bos_id), int(eos_id)
        self.dtype = dtype
        self.embed_init_rms = float(embed_init_rms)
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size / attention heads do not divide")
        # the description: a mixer, then the gated MLP, in every layer
        self.blocks = [LayerSpec(kind, "mlp") for kind in self.layer_types]
        self._check()

    _HF_KEYS = ("vocab_size", "hidden_size", "layer_types",
                "num_attention_heads", "num_key_value_heads",
                "shared_intermediate_size", "mamba_n_heads", "mamba_d_head",
                "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
                "mamba_expand", "mamba_chunk_size", "embedding_multiplier",
                "residual_multiplier", "attention_multiplier",
                "logits_scaling", "rms_norm_eps")

    @classmethod
    def from_published(cls, published: dict, **serving):
        """From the keys of a `granitemoehybrid` config.json (others are
        ignored) plus serving's own (`max_position`, `eos_id`, `dtype`)."""
        if int(published.get("num_local_experts", 0)):
            raise NotImplementedError(
                "this family's routed experts (softmax top-k over "
                "num_local_experts, no latent) are not described here; "
                "num_local_experts must be 0 (routed experts as "
                "models/nemotron_h.py describes them are built)")
        if published.get("position_embedding_type", "nope") != "nope":
            raise NotImplementedError(
                "rotary positions are not built (ROADMAP R-a)")
        return cls(**{k: published[k] for k in cls._HF_KEYS
                      if k in published}, **serving)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


class GraniteHybridModel(HybridDecoder):
    """The decoder of this description (`HybridDecoder` has the passes)."""

    def __init__(self, cfg: GraniteHybridConfig = None, **kw):
        super().__init__(cfg or GraniteHybridConfig(**kw))


def granite_hybrid_tiny(**kw):
    """The CPU tests' size: every kind of layer, nothing published."""
    base = dict(vocab_size=128, hidden_size=64,
                layer_types=["mamba", "mamba", "attention", "mamba"],
                num_attention_heads=4, num_key_value_heads=2,
                shared_intermediate_size=96, mamba_n_heads=8,
                mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
                max_position=256, bos_id=0, eos_id=127, dtype="float32",
                embed_init_rms=0.01)
    base.update(kw)
    return GraniteHybridModel(GraniteHybridConfig(**base))
