"""Nemotron-H style hybrid decoder with latent routed experts: every layer
is ONE of a Mamba-2 mixer (`M`), grouped-query attention (`*`) or an
expert layer (`E`), applied as ``h = h + f(RMSNorm(h))`` with no
multipliers, no positions and an untied head.  An expert layer is a
sigmoid router over all `n_routed_experts` with a selection bias and
top-`num_experts_per_tok`, experts of ``relu(x)^2`` in a
`moe_latent_size`-wide latent between one shared pair of projections, and
one shared expert on the full width.

Written from the published `nemotron_h` configuration
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json).
The layer equations are in `benchmark/reference/nemotron_h.py`, the plain
float32 twin the tests and the benchmark compare this model with.

This file is the family's DESCRIPTION (`models/hybrid_decoder.py` has the
layers, the loops and the serving step contract, shared with
`models/granite_hybrid.py`), and it describes ONE CHIP'S SHARE of a
deployment that divides each layer over several chips by expert
parallelism: the `held_experts` experts from `first_held` on of each
expert layer, rows `[0, vocab_rows)` of the embedding and the head, and
a range of the published layers (the rest are further pipeline stages).
The router keeps its published width and top-k; what the experts held
elsewhere would add is left out, and no code stands in for the other
chips or the exchange with them (ROADMAP R-d).
"""
from __future__ import annotations

from .hybrid_decoder import HybridDecoder, HybridDecoderConfig, LayerSpec

__all__ = ["NemotronHConfig", "NemotronHModel", "nemotron_h_tiny"]

_KINDS = {"M": LayerSpec("mamba", None), "*": LayerSpec("attention", None),
          "E": LayerSpec(None, "experts")}
_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


class NemotronHConfig(HybridDecoderConfig):
    """The keys of the published config that shape the model, under their
    published names, this chip's share of it (`held_experts`,
    `first_held`; `vocab_size` is the rows of the vocabulary held), and
    what serving needs (`max_position`: the longest context served;
    `dtype`)."""

    tie_word_embeddings = False
    embedding_multiplier = residual_multiplier = logits_scaling = 1.0
    shared_intermediate_size = 0        # no gated MLP in this family

    def __init__(self, vocab_size=131072, hidden_size=4096,
                 hybrid_override_pattern=_PATTERN, num_attention_heads=32,
                 num_key_value_heads=2, head_dim=128, mamba_num_heads=128,
                 mamba_head_dim=64, ssm_state_size=128, n_groups=8,
                 conv_kernel=4, expand=2, chunk_size=128,
                 n_routed_experts=512, num_experts_per_tok=22,
                 moe_intermediate_size=2688, moe_latent_size=1024,
                 moe_shared_expert_intermediate_size=5376,
                 norm_topk_prob=True, routed_scaling_factor=5.0,
                 layer_norm_epsilon=1e-5, held_experts=None, first_held=0,
                 max_position=262144, bos_id=1, eos_id=2, dtype="bfloat16",
                 embed_init_rms=0.05):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.hybrid_override_pattern = str(hybrid_override_pattern)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        # scores * head_dim^-0.5: the modelling code's scale, no rotary
        self.attention_multiplier = self.head_dim ** -0.5
        # under the names the shared layers read
        self.mamba_n_heads = int(mamba_num_heads)
        self.mamba_d_head = int(mamba_head_dim)
        self.mamba_d_state = int(ssm_state_size)
        self.mamba_n_groups = self.mamba_norm_groups = int(n_groups)
        self.mamba_d_conv = int(conv_kernel)
        self.mamba_expand = int(expand)
        self.mamba_chunk_size = int(chunk_size)
        self.n_routed_experts = int(n_routed_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.moe_latent_size = int(moe_latent_size)
        self.moe_shared_expert_intermediate_size = int(
            moe_shared_expert_intermediate_size)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_norm_eps = float(layer_norm_epsilon)
        self.held_experts = self.n_routed_experts if held_experts is None \
            else int(held_experts)
        self.first_held = int(first_held)
        self.max_position = int(max_position)
        self.bos_id, self.eos_id = int(bos_id), int(eos_id)
        self.dtype = dtype
        self.embed_init_rms = float(embed_init_rms)
        bad = sorted(set(self.hybrid_override_pattern) - set(_KINDS))
        if bad:
            raise NotImplementedError(
                f"hybrid_override_pattern: layer kinds {bad} are not "
                "described ('-', a dense MLP layer, among them); built: "
                "M (Mamba-2), * (attention), E (experts)")
        if not 0 < self.held_experts <= self.n_routed_experts \
                - self.first_held or self.first_held < 0:
            raise ValueError(
                f"experts [{self.first_held}, {self.first_held} + "
                f"{self.held_experts}) are not among the "
                f"{self.n_routed_experts} routed")
        self.blocks = [_KINDS[c] for c in self.hybrid_override_pattern]
        self._check()

    _HF_KEYS = ("vocab_size", "hidden_size", "hybrid_override_pattern",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
                "n_groups", "conv_kernel", "expand", "chunk_size",
                "n_routed_experts", "num_experts_per_tok",
                "moe_intermediate_size", "moe_latent_size",
                "moe_shared_expert_intermediate_size", "norm_topk_prob",
                "routed_scaling_factor", "layer_norm_epsilon")
    # what the published config can ask for that is not built, by key:
    # (the value that is built, what the other would need)
    _NOT_BUILT = {
        "use_bias": (False, "biases on the mixers' projections"),
        "mlp_bias": (False, "biases in the experts"),
        "attention_bias": (False, "biases on q / k / v / o"),
        "mamba_proj_bias": (False, "biases on the Mamba projections"),
        "use_conv_bias": (True, "a conv without its bias"),
        "n_group": (1, "group-limited routing (n_group > 1)"),
        "topk_group": (1, "group-limited routing (topk_group > 1)"),
        "n_shared_experts": (1, "more or fewer than one shared expert"),
        "mlp_hidden_act": ("relu2", "another expert activation"),
        "mamba_hidden_act": ("silu", "another Mamba activation"),
        "tie_word_embeddings": (False, "a tied head in this family"),
    }

    @classmethod
    def from_published(cls, published: dict, held_experts=None,
                       first_held=0, vocab_rows=None, layers=None,
                       drop_mtp=False, **serving):
        """From the keys of a `nemotron_h` config.json (others are
        ignored), this chip's share — `held_experts` experts from
        `first_held` on, the first `vocab_rows` rows of the vocabulary,
        `layers` = (first, end) of the published pattern — and serving's
        own (`max_position`, `eos_id`, `dtype`).  Raises by name on what
        is not built."""
        for key, (built, what) in cls._NOT_BUILT.items():
            if key in published and published[key] != built:
                raise NotImplementedError(
                    f"{key}={published[key]!r}: {what} is not built")
        if int(published.get("num_nextn_predict_layers", 0)) and not drop_mtp:
            raise NotImplementedError(
                "num_nextn_predict_layers > 0: the multi-token-prediction "
                "module is a draft head, and drafts need rollback of the "
                "recurrent state (ROADMAP R-h); pass drop_mtp=True to "
                "serve the main model without it")
        keys = {k: published[k] for k in cls._HF_KEYS if k in published}
        if "norm_eps" in published and "layer_norm_epsilon" not in keys:
            keys["layer_norm_epsilon"] = published["norm_eps"]
        if layers is not None:
            first, end = layers
            keys["hybrid_override_pattern"] = keys.get(
                "hybrid_override_pattern", _PATTERN)[first:end]
        if vocab_rows is not None:
            keys["vocab_size"] = int(vocab_rows)
        return cls(**keys, held_experts=held_experts, first_held=first_held,
                   **serving)


class NemotronHModel(HybridDecoder):
    """The decoder of this description (`HybridDecoder` has the passes)."""

    def __init__(self, cfg: NemotronHConfig = None, **kw):
        super().__init__(cfg or NemotronHConfig(**kw))


def nemotron_h_tiny(**kw):
    """The CPU tests' size: every kind of layer, nothing published."""
    base = dict(vocab_size=128, hidden_size=64,
                hybrid_override_pattern="MEM*EME", num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
                mamba_head_dim=16, ssm_state_size=16, n_groups=2,
                chunk_size=8, n_routed_experts=16, num_experts_per_tok=4,
                moe_intermediate_size=48, moe_latent_size=32,
                moe_shared_expert_intermediate_size=96, max_position=256,
                bos_id=0, eos_id=127, dtype="float32", embed_init_rms=0.05)
    base.update(kw)
    return NemotronHModel(NemotronHConfig(**base))
