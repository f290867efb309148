"""A hybrid decoder from a per-layer block description: each layer has a
mixer (Mamba-2 state-space, a gated short convolution, grouped-query
attention, multi-head latent attention, or none) and a feed-forward part
(a gated MLP, routed experts, or none), each applied as ``h = h +
residual_multiplier * f(norm(h))`` one after the other, or, in the
`parallel` block form, both from ONE norm of the same `h`, or, in the
`sandwich` form, each sublayer's output normed again before it is added;
the norm is RMSNorm or a mean-subtracting LayerNorm (scale only); an
attention layer may state a `window`, rotary positions in interleaved
pairs or in halves, and a norm on each head's q and k (`AttentionSpec`); a
latent layer states its ranks and head dims (`LatentSpec`,
`config.latent_spec`); the experts are `latent_relu2` (a latent space,
``relu^2``, a selection bias, one shared expert) or `gated_silu` (full
width, ``silu(g) * u``, `n_shared` shared experts averaged — none where
it is 0 — a selection bias where `router_bias` says); an embedding
multiplier, a logits divisor, and a tied or untied head.  `cache_spec`,
`param_shapes`, the full `forward` and the two steps of the serving
contract are emitted ONCE from that description; a model family is a
config class that gives the description (`models/granite_hybrid.py`,
`models/nemotron_h.py`), not a copy of the layers or the loops.

Serving only: the ops (`ops/kernels/ssm.py`, `ops/kernels/moe.py`,
`ops/kernels/window_attention.py`) are forward only, so the parameters do
not ask for gradients and no tape is ever kept.  The model states its
cache (`cache_spec`): one `kv` group per kind of retention of its attention
layers (`retain`: a window's columns in a ring, or `"all"`), one `latent`
group for the latent attention layers (one row a token and layer, shared
by the heads) and a `state` group each for the Mamba layers and for the
short-convolution layers (their last inputs) — `serving.PagedKVPool`,
`static.page_budget`
and the engine size themselves from it.  ALL of it lives on the device, a
row a slot, and rides the step contract: two cache-aware entry points
(ids, per-row lengths, cache in; last-row logits, cache out),
`prefill_step` and `decode_step`, flat tensor arguments and a tuple
result, so `jit.to_static` turns each into one compiled program per shape
bucket (`serving.step_program`); the decode step is given the KV and state
arrays whole and every layer writes its own entry where the array lies.
Two forks are read from the description and the shapes.  `kv_ring`: with
a window layer the decode step is one program that reads what its rows
hold; without, it reads its caller's static bound on the columns
(`decode_step(columns=)`).  `blocked_prompt(T)` (a window layer, or a
prompt bucket past `GQA_PROMPT_MAX` tokens): its attention is the blocked
kernel (`ops/kernels/window_attention.py`); else `gqa_attention`, whose
`[T, T]` scores a short prompt affords.  A model with routed experts also
counts what its steps routed (`step_counters`): one int32 vector a call,
after the state in the result tuple.
"""
from __future__ import annotations

import collections
import math

import numpy as np

from ..core.generator import global_seed
from ..dygraph.layers import Layer, ParamBase, parameter_footprint
from ..nn import functional as F
from ..nn.initializer import Constant, Normal, Uniform
from ..ops.kernels.latent_attention import DECODE_BLOCK, mla_attrs
from ..profiler import Phase
from ..tensor._dispatch import dispatch
from ..tensor.linalg import matmul
from ..tensor.manipulation import (cast, gather, reshape, split, squeeze,
                                   stack, transpose, unsqueeze)
from ..tensor.math import add, multiply, scale

__all__ = ["LayerSpec", "AttentionSpec", "LatentSpec", "HybridDecoderConfig",
           "HybridDecoder", "MOE_COUNTERS"]

# one layer: `mixer` "mamba" | "short_conv" | "attention" | "latent" |
# None; `ffn` "mlp" | "experts" | None
LayerSpec = collections.namedtuple("LayerSpec", "mixer ffn")
# what an attention mixer may state beside (`config.attention_specs`, by
# layer): `window` (None: every earlier token is seen), `rotary` (None: no
# positions, else theta), `rotary_form` ("interleaved": pairs (2i, 2i + 1);
# "half": dim i with i + D/2) and `qk_norm` (an RMSNorm over each head's
# dims of q and of k, before positions)
AttentionSpec = collections.namedtuple(
    "AttentionSpec", "window rotary rotary_form qk_norm",
    defaults=(None, None, "interleaved", False))
# what every latent layer states (`config.latent_spec`): the query's and the
# keys' low ranks, a head's dims without and with positions, a value's, and
# the rotary theta of the positional dims
LatentSpec = collections.namedtuple(
    "LatentSpec", "q_lora_rank kv_lora_rank qk_nope_head_dim "
    "qk_rope_head_dim v_head_dim rope_theta")
MIXERS, FFNS = ("mamba", "short_conv", "attention", "latent", None), \
    ("mlp", "experts", None)
# what `moe_grouped_experts` counts a call (ops/kernels/moe.py `STATS`),
# summed over a step's expert layers
MOE_COUNTERS = ("moe_routed", "moe_pairs", "moe_touched", "moe_max_load")
BLOCK_FORMS, NORM_KINDS = ("sequential", "parallel", "sandwich"), \
    ("rms", "layer")
EXPERT_FORMS = ("latent_relu2", "gated_silu")
# the longest prompt bucket whose attention is `gqa_attention` where no
# layer keeps a ring: [B, heads, T, T] float32 scores, 33.5 MB at 32 heads
GQA_PROMPT_MAX = 512


class HybridDecoderConfig:
    """What the decoder reads, whatever family gave it.  A family's config
    class sets, under these names: `vocab_size`, `hidden_size`, `blocks`
    (a `LayerSpec` a layer), `num_attention_heads`, `num_key_value_heads`,
    `head_dim`, `attention_multiplier` (the score scale), the `mamba_*`
    sizes with `mamba_norm_groups` (groups of the mixer's output norm),
    `short_conv_width` (the short convolution's taps, its `L_cache`),
    `shared_intermediate_size` (the gated MLP), the `moe_*` /
    `n_routed_experts` / `held_experts` / `first_held` sizes of an expert
    layer, `embedding_multiplier`, `residual_multiplier`, `logits_scaling`,
    `tie_word_embeddings`, `rms_norm_eps`, and serving's own
    (`max_position`, `bos_id`, `eos_id`, `dtype`, `embed_init_rms`).  A
    family that differs from the defaults below says so under their
    names."""

    attention_specs = {}            # layer index -> AttentionSpec
    latent_spec = None              # a LatentSpec where a layer is "latent"
    block_form = "sequential"       # | "parallel": one norm, both from x
                                    # | "sandwich": the outputs normed too
    norm_kind = "rms"               # | "layer": mean-subtracting, scale only
    expert_form = "latent_relu2"    # | "gated_silu"
    router_bias = False             # gated_silu: a selection bias (the
                                    # latent_relu2 router always has one)
    n_shared_experts = 1            # averaged where there are several;
                                    # gated_silu: 0, none
    # a family without Mamba layers states no `mamba_*` sizes
    mamba_n_heads = mamba_d_head = mamba_d_state = mamba_n_groups = 0
    mamba_expand = mamba_d_conv = 0
    short_conv_width = 0
    shared_intermediate_size = 0

    def _check(self):
        for spec in self.blocks:
            if spec.mixer not in MIXERS or spec.ffn not in FFNS:
                raise ValueError(f"unknown layer description {spec}")
        for i, spec in self.attention_specs.items():
            if self.blocks[i].mixer != "attention":
                raise ValueError(f"layer {i} has no attention to describe")
        if self.layers_of("latent") and self.latent_spec is None:
            raise ValueError("a latent layer needs the config's latent_spec")
        if self.layers_of("short_conv") and self.short_conv_width < 2:
            raise ValueError("a short_conv layer needs short_conv_width >= 2")
        if self.expert_form == "latent_relu2" and self.n_shared_experts < 1:
            raise ValueError("latent_relu2 experts have one shared expert")
        if self.block_form not in BLOCK_FORMS \
                or self.norm_kind not in NORM_KINDS \
                or self.expert_form not in EXPERT_FORMS:
            raise ValueError(
                f"unknown form: block {self.block_form!r}, norm "
                f"{self.norm_kind!r}, experts {self.expert_form!r}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("attention heads / kv heads do not divide")
        if self.mamba_inner != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"mamba_expand * hidden_size = {self.mamba_inner} is not "
                f"mamba_n_heads * mamba_d_head = "
                f"{self.mamba_n_heads * self.mamba_d_head}")

    # -- derived sizes ------------------------------------------------------
    # the names page_budget and the engine read on every decoder config
    @property
    def num_layers(self):
        return len(self.blocks)

    @property
    def num_heads(self):
        return self.num_attention_heads

    @property
    def intermediate_size(self):
        return self.shared_intermediate_size

    @property
    def mamba_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self):
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def layers_of(self, kind):
        """Indices of the layers whose mixer or feed-forward is `kind`."""
        return [i for i, s in enumerate(self.blocks)
                if kind in (s.mixer, s.ffn)]

    def attention_spec(self, index):
        return self.attention_specs.get(index, AttentionSpec())

    def kv_groups(self):
        """The attention layers by what they retain, in order of first
        appearance: [(window or None, [layer indices])]."""
        groups = {}
        for i in self.layers_of("attention"):
            groups.setdefault(self.attention_spec(i).window or None,
                              []).append(i)
        return list(groups.items())

    @property
    def kv_ring(self):
        """Whether some attention layer keeps a window's columns in a
        ring: the description's one fork (the blocked attention ops and
        ONE decode program, against `gqa_attention` and a decode program
        a bound on the columns)."""
        return any(window for window, _ in self.kv_groups())

    def blocked_prompt(self, tokens):
        """Whether a prompt of `tokens` (its static bucket) goes through the
        blocked kernel — with a ring, or past `GQA_PROMPT_MAX` — rather
        than `gqa_attention`."""
        return self.kv_ring or tokens > GQA_PROMPT_MAX

    @property
    def expert_layers(self):
        return len(self.layers_of("experts"))

    @property
    def router_biased(self):
        return self.expert_form == "latent_relu2" or bool(self.router_bias)

    def cache_spec(self):
        """What one sequence keeps between steps, by layer group, all of
        it in its slot on the device (`serving.kv_pool.StateSlots`): a
        `kv` group per kind of retention — arrays K, V [layers, slots, kv
        heads, columns, head dim], a window's ring or every column
        (`"all"`: a column a token, which the pool's pages count) — and
        the latent layers' `latent` group — ONE array [layers, slots,
        columns, kv_lora_rank + qk_rope_head_dim], a row a token shared by
        every head, read `read_block` rows at a time by the decode kernel —
        and, of fixed size, the Mamba layers' `state` group and the short
        convolution layers' (each one's last `short_conv_width - 1`
        inputs)."""
        groups = [{"kind": "kv", "layers": len(layers),
                   "kv_heads": self.num_key_value_heads,
                   "head_dim": self.head_dim, "dtype": self.dtype,
                   "retain": int(window) if window else "all"}
                  for window, layers in self.kv_groups()]
        if self.layers_of("latent"):
            spec = self.latent_spec
            groups.append({
                "kind": "latent", "layers": len(self.layers_of("latent")),
                "width": spec.kv_lora_rank + spec.qk_rope_head_dim,
                "dtype": self.dtype, "read_block": DECODE_BLOCK})
        if self.layers_of("mamba"):
            groups.append({
                "kind": "state", "layers": len(self.layers_of("mamba")),
                "arrays": [
                    {"name": "ssm", "dtype": "float32",
                     "shape": [self.mamba_n_heads, self.mamba_d_head,
                               self.mamba_d_state]},
                    {"name": "conv", "dtype": self.dtype,
                     "shape": [self.mamba_d_conv - 1, self.conv_dim]}]})
        if self.layers_of("short_conv"):
            groups.append({
                "kind": "state", "layers": len(self.layers_of("short_conv")),
                "arrays": [{"name": "short_conv", "dtype": self.dtype,
                            "shape": [self.short_conv_width - 1,
                                      self.hidden_size]}]})
        return groups

    def param_shapes(self):
        """{name: shape} of the whole model's parameters, under the names
        `named_parameters()` gives them, from the sizes alone (nothing is
        allocated)."""
        h = self.hidden_size
        heads, inner = self.mamba_n_heads, self.mamba_inner
        q, kv = (n * self.head_dim for n in (self.num_attention_heads,
                                             self.num_key_value_heads))
        out = {"embed": (self.vocab_size, h), "norm_f": (h,)}
        if not self.tie_word_embeddings:
            out["head"] = (h, self.vocab_size)
        for i, spec in enumerate(self.blocks):
            p = f"layers.{i}."
            if spec.mixer:
                out[p + "norm1"] = (h,)
                if self.block_form == "sandwich":
                    out[p + "post_norm1"] = (h,)
            if spec.mixer == "latent":
                ls, n = self.latent_spec, self.num_attention_heads
                qk = ls.qk_nope_head_dim + ls.qk_rope_head_dim
                out[p + "mixer.wq_a"] = (h, ls.q_lora_rank)
                out[p + "mixer.q_norm"] = (ls.q_lora_rank,)
                out[p + "mixer.wq_b"] = (ls.q_lora_rank, n * qk)
                out[p + "mixer.wkv_a"] = (h, ls.kv_lora_rank
                                          + ls.qk_rope_head_dim)
                out[p + "mixer.kv_norm"] = (ls.kv_lora_rank,)
                out[p + "mixer.wkv_b"] = (ls.kv_lora_rank, n * (
                    ls.qk_nope_head_dim + ls.v_head_dim))
                out[p + "mixer.wo"] = (n * ls.v_head_dim, h)
            if spec.mixer == "attention":
                out[p + "mixer.wq"] = (h, q)
                out[p + "mixer.wo"] = (q, h)
                out[p + "mixer.wk"] = out[p + "mixer.wv"] = (h, kv)
                if self.attention_spec(i).qk_norm:
                    out[p + "mixer.q_norm"] = out[p + "mixer.k_norm"] = \
                        (self.head_dim,)
            elif spec.mixer == "short_conv":
                out[p + "mixer.in_proj"] = (h, 3 * h)
                out[p + "mixer.conv_w"] = (h, self.short_conv_width)
                out[p + "mixer.out_proj"] = (h, h)
            elif spec.mixer == "mamba":
                out[p + "mixer.w_in"] = (h, inner + self.conv_dim + heads)
                out[p + "mixer.w_out"] = (inner, h)
                out[p + "mixer.conv_w"] = (self.conv_dim, self.mamba_d_conv)
                out[p + "mixer.conv_b"] = (self.conv_dim,)
                out[p + "mixer.norm_w"] = (inner,)
                out[p + "mixer.a_log"] = out[p + "mixer.dt_bias"] = \
                    out[p + "mixer.d"] = (heads,)
            if spec.ffn and (self.block_form != "parallel"
                             or not spec.mixer):
                out[p + "norm2"] = (h,)
                if self.block_form == "sandwich":
                    out[p + "post_norm2"] = (h,)
            if spec.ffn == "mlp":
                f = self.shared_intermediate_size
                out[p + "mlp.w_in"] = (h, 2 * f)
                out[p + "mlp.w_out"] = (f, h)
            elif spec.ffn == "experts":
                f = self.moe_intermediate_size
                sf = self.moe_shared_expert_intermediate_size \
                    * self.n_shared_experts
                out[p + "experts.router_w"] = (h, self.n_routed_experts)
                if self.router_biased:
                    out[p + "experts.router_b"] = (self.n_routed_experts,)
                if self.expert_form == "gated_silu":
                    out[p + "experts.w1"] = (self.held_experts, h, 2 * f)
                    out[p + "experts.w2"] = (self.held_experts, f, h)
                    if sf:
                        out[p + "experts.shared_in"] = (h, 2 * sf)
                else:
                    lat = self.moe_latent_size
                    out[p + "experts.w_down"] = (h, lat)
                    out[p + "experts.w_up"] = (lat, h)
                    out[p + "experts.w1"] = (self.held_experts, lat, f)
                    out[p + "experts.w2"] = (self.held_experts, f, lat)
                    out[p + "experts.shared_in"] = (h, sf)
                if sf:
                    out[p + "experts.shared_out"] = (sf, h)
        return out

    def param_count(self):
        return sum(int(np.prod(s)) for s in self.param_shapes().values())


def _rms_norm(x, weight, eps):
    return dispatch("rms_norm", {"X": x, "Scale": weight}, {"epsilon": eps})


def _norm(kind, x, weight, eps):
    """`rms`, or `layer`: mean-subtracting over the last axis, scale only."""
    if kind == "rms":
        return _rms_norm(x, weight, eps)
    return F.layer_norm(x, [x.shape[-1]], weight=weight, epsilon=eps)


def _scaled(x, factor):
    return x if factor == 1.0 else scale(x, factor)


class _MLP(Layer):
    """`[g, u] = split(h W_in)`, `out = (silu(g) * u) W_out`."""

    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        h, f = cfg.hidden_size, cfg.shared_intermediate_size
        self.w_in = self.create_parameter([h, 2 * f])
        self.w_out = self.create_parameter([f, h])

    def forward(self, x):
        g, u = split(matmul(x, self.w_in), 2, axis=-1)
        return matmul(multiply(F.silu(g), u), self.w_out)


def _relu2(x):
    x = F.relu(x)
    return multiply(x, x)


class _Experts(Layer):
    """Routed experts, this chip's share of them, beside the shared
    expert(s): a sigmoid router over ALL `n_routed_experts` and top-k
    (`moe_router_topk`); the `held_experts` experts from `first_held` on,
    dropless (`moe_grouped_experts`); the shared experts on the full
    width.  What the experts held elsewhere would add is left out.

    `latent_relu2`: a selection bias on the router; one pair of
    projections a layer into and out of the `moe_latent_size`-wide latent
    the experts work in; ``relu(.)^2`` experts; one shared expert.
    `gated_silu`: no latent, a selection bias where the config's
    `router_bias` says; experts ``D(silu(G x) * U x)`` with
    `w1` = [G | U]; `n_shared_experts` shared experts of the same form,
    AVERAGED — kept side by side as one wide expert (`shared_in` = [G_1 ..
    G_n | U_1 .. U_n], `shared_out` the D_j stacked) whose output is
    divided by n — or none, no shared projections at all, where n is 0.

    `forward(x, lengths)` -> (out, the op's counts [4] int32, the picks
    [B, T, k])."""

    def __init__(self, cfg, index):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.gated = cfg.expert_form == "gated_silu"
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        sf = cfg.moe_shared_expert_intermediate_size * cfg.n_shared_experts
        held = cfg.held_experts
        self.router_w = self.create_parameter([h, cfg.n_routed_experts])
        width = h
        if cfg.router_biased:
            # seeded small and NON-ZERO, so that selecting by `s + b` and
            # weighting by `s` are told apart (the published bias is a
            # buffer the load balancer moves; zero would hide a swapped
            # pair)
            rng = np.random.default_rng([global_seed(), 0x0E0E, index])
            self.router_b = ParamBase(
                rng.uniform(-0.02, 0.02, cfg.n_routed_experts).astype(
                    np.float32), name=self._full_name + ".router_b",
                trainable=False)
        if not self.gated:
            width = cfg.moe_latent_size
            self.w_down = self.create_parameter([h, width])
            self.w_up = self.create_parameter([width, h])
        # Xavier uniform an expert's matrix (the default reads a 3-D
        # shape as a convolution's)
        bound = math.sqrt(6.0 / (width + f))
        self.w1 = self.create_parameter(
            [held, width, 2 * f if self.gated else f],
            default_initializer=Uniform(-bound, bound))
        self.w2 = self.create_parameter(
            [held, f, width], default_initializer=Uniform(-bound, bound))
        if not self.gated:
            self.shared_in = self.create_parameter([h, sf])
            self.shared_out = self.create_parameter([sf, h])
        elif sf:            # each shared expert's matrix as its own
            one = cfg.moe_shared_expert_intermediate_size
            bound = math.sqrt(6.0 / (h + one))
            self.shared_in = self.create_parameter(
                [h, 2 * sf], default_initializer=Uniform(-bound, bound))
            self.shared_out = self.create_parameter(
                [sf, h], default_initializer=Uniform(-bound, bound))

    def forward(self, x, lengths):
        c = self.cfg
        router = {"X": x, "Weight": self.router_w}
        if c.router_biased:
            router["Bias"] = self.router_b
        experts, weights = dispatch(
            "moe_router_topk", router,
            {"top_k": c.num_experts_per_tok,
             "norm_topk_prob": c.norm_topk_prob,
             "routed_scaling_factor": c.routed_scaling_factor},
            ["Experts", "Weights"])
        ins = {"X": x if self.gated else matmul(x, self.w_down),
               "Experts": experts, "Weights": weights, "W1": self.w1,
               "W2": self.w2}
        if lengths is not None:
            ins["Lengths"] = lengths
        attrs = {"n_experts": c.n_routed_experts,
                 "first_held": c.first_held, "held": c.held_experts}
        if self.gated:
            attrs["activation"] = "silu_gated"
        routed, stats = dispatch("moe_grouped_experts", ins, attrs,
                                 ["Out", "Stats"])
        if self.gated and not c.n_shared_experts:
            return cast(routed, c.dtype), stats, experts
        if self.gated:
            out = cast(routed, c.dtype)
            g, u = split(matmul(x, self.shared_in), 2, axis=-1)
            shared = _scaled(matmul(multiply(F.silu(g), u), self.shared_out),
                             1.0 / c.n_shared_experts)
        else:
            out = matmul(cast(routed, c.dtype), self.w_up)
            shared = matmul(_relu2(matmul(x, self.shared_in)),
                            self.shared_out)
        return add(out, shared), stats, experts


class _Attention(Layer):
    """Grouped-query attention with the config's own score multiplier;
    of its layer's description, a `window`, rotary positions (`rotary`:
    theta; `rotary_form`) and a norm on each head's q and k (`qk_norm`),
    each or none.  `forward(x, lengths)` runs whole sequences from empty
    caches — through the blocked kernel where `blocked_prompt` says so for
    the prompt's bucket (given `lengths` it leaves blocks of pads out), else
    `gqa_attention` — and returns (out, (k, v)), k, v the tokens' [B, Hkv,
    T, D]; `step` one token a row over the cache group's arrays."""

    def __init__(self, cfg, spec=AttentionSpec()):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.window, self.rotary = spec.window, spec.rotary
        self.rotary_form = spec.rotary_form
        h = cfg.hidden_size
        q, kv = (n * cfg.head_dim for n in (cfg.num_attention_heads,
                                            cfg.num_key_value_heads))
        self.wq = self.create_parameter([h, q])
        self.wk = self.create_parameter([h, kv])
        self.wv = self.create_parameter([h, kv])
        self.wo = self.create_parameter([q, h])
        self.qk_norm = bool(spec.qk_norm)
        if self.qk_norm:
            ones = Constant(1.0)
            self.q_norm = self.create_parameter([cfg.head_dim],
                                                default_initializer=ones)
            self.k_norm = self.create_parameter([cfg.head_dim],
                                                default_initializer=ones)

    def _heads(self, x, positions=None):
        """q, k, v [B, heads, T, D] of x [B, T, hidden], q and k turned to
        their positions where the layer has them."""
        c = self.cfg
        b, t = x.shape[0], x.shape[1]

        def heads(y, n):
            return transpose(reshape(y, [b, t, n, c.head_dim]), [0, 2, 1, 3])

        q = heads(matmul(x, self.wq), c.num_attention_heads)
        k = heads(matmul(x, self.wk), c.num_key_value_heads)
        v = heads(matmul(x, self.wv), c.num_key_value_heads)
        if self.qk_norm:
            q = _rms_norm(q, self.q_norm, c.rms_norm_eps)
            k = _rms_norm(k, self.k_norm, c.rms_norm_eps)
        if self.rotary:
            ins = {} if positions is None else {"Positions": positions}
            attrs = {"theta": float(self.rotary)}
            if self.rotary_form != "interleaved":
                attrs["form"] = self.rotary_form
            q, k = (dispatch("rotary_embedding", dict(ins, X=y), attrs)
                    for y in (q, k))
        return q, k, v

    def _out(self, ctx):
        b, t = ctx.shape[0], ctx.shape[2]
        c = self.cfg
        ctx = reshape(transpose(ctx, [0, 2, 1, 3]),
                      [b, t, c.num_attention_heads * c.head_dim])
        return matmul(ctx, self.wo)

    def forward(self, x, lengths=None):
        c = self.cfg
        q, k, v = self._heads(x)
        attrs = {"scale": c.attention_multiplier}
        if c.blocked_prompt(x.shape[1]):    # blocks of queries
            ins = {"Q": q, "K": k, "V": v}
            if lengths is not None:     # blocks of pads are not computed
                ins["Lengths"] = lengths
            ctx = dispatch("windowed_prefill_attention", ins,
                           dict(attrs, window=int(self.window or 0)))
        else:
            ctx = dispatch("gqa_attention", {"Q": q, "K": k, "V": v}, attrs)
        return self._out(ctx), (k, v)

    def step(self, x, lengths, active, k_cache, v_cache, index, columns):
        """One token a row: `k_cache`, `v_cache` the whole arrays [Lg, S,
        Hkv, columns, D] of this layer's cache group; this layer writes
        its new column into entry `index` of each (a ring: at ``length
        mod window``) and reads its row's: up to the longest row's
        (`columns` None), or the first `columns` of every row."""
        q, k, v = self._heads(x, unsqueeze(lengths, 1))
        ctx, k_cache, v_cache = dispatch(
            "cached_decode_attention",
            {"Q": q, "K": k, "V": v, "KCache": k_cache, "VCache": v_cache,
             "CacheLengths": lengths, "Active": active},
            {"scale": self.cfg.attention_multiplier, "slab_index": index,
             "window": int(self.window or 0), "columns": int(columns or 0)},
            ["Out", "NewKCache", "NewVCache"])
        return self._out(ctx), (k_cache, v_cache)


class _LatentAttention(Layer):
    """Multi-head latent attention (`ops/kernels/latent_attention.py` has
    the equations): the query through a normed `q_lora_rank` latent; keys
    and values through ONE normed `kv_lora_rank` latent a token, beside a
    `qk_rope_head_dim` key of rotary positions shared by every head.
    `forward(x, lengths)` runs whole sequences in the EXPANDED form (per-head
    keys and values, `latent_prefill_attention`) and returns (out, the
    tokens' cache rows [B, T, kv_lora_rank + qk_rope_head_dim]); `step` one
    token a row in the ABSORBED form over the latent group's whole array
    (`latent_decode_attention`), writing its new row in place."""

    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        ls, n, h = cfg.latent_spec, cfg.num_attention_heads, cfg.hidden_size
        ones = Constant(1.0)
        self.wq_a = self.create_parameter([h, ls.q_lora_rank])
        self.q_norm = self.create_parameter([ls.q_lora_rank],
                                            default_initializer=ones)
        self.wq_b = self.create_parameter(
            [ls.q_lora_rank, n * (ls.qk_nope_head_dim + ls.qk_rope_head_dim)])
        self.wkv_a = self.create_parameter(
            [h, ls.kv_lora_rank + ls.qk_rope_head_dim])
        self.kv_norm = self.create_parameter([ls.kv_lora_rank],
                                             default_initializer=ones)
        self.wkv_b = self.create_parameter(
            [ls.kv_lora_rank, n * (ls.qk_nope_head_dim + ls.v_head_dim)])
        self.wo = self.create_parameter([n * ls.v_head_dim, h])

    def _attrs(self, part, form):
        c = self.cfg
        return mla_attrs(c.latent_spec, c.num_attention_heads,
                         c.rms_norm_eps, part, form)

    def _in(self, x, form, positions=None):
        ins = {"X": x, "WqA": self.wq_a, "QNorm": self.q_norm,
               "WqB": self.wq_b, "WkvA": self.wkv_a, "KvNorm": self.kv_norm,
               "WkvB": self.wkv_b}
        if positions is not None:
            ins["Positions"] = positions
        outs = ["Q", "K", "V", "Latent"] if form == "expanded" \
            else ["Q", "Latent"]
        return dispatch("mla", ins, self._attrs("in", form), outs)

    def forward(self, x, lengths=None):
        q, k, v, latent = self._in(x, "expanded")
        ins = {"Q": q, "K": k, "V": v}
        if lengths is not None:         # blocks of pads are not computed
            ins["Lengths"] = lengths
        ctx = dispatch("latent_prefill_attention", ins,
                       {"scale": self.cfg.attention_multiplier})
        out = dispatch("mla", {"Ctx": ctx, "Wo": self.wo},
                       self._attrs("out", "expanded"))
        return out, latent

    def step(self, x, lengths, active, cache, index):
        """One token a row: `cache` the latent group's whole array [Lg, S,
        columns, width]; this layer writes its row into entry `index` at
        column `lengths` and reads each row's own earlier rows."""
        q, new = self._in(x, "absorbed", unsqueeze(lengths, 1))
        ctx, cache = dispatch(
            "latent_decode_attention",
            {"Q": q, "New": new, "Cache": cache, "CacheLengths": lengths,
             "Active": active},
            {"scale": self.cfg.attention_multiplier, "slab_index": index,
             "kv_rank": self.cfg.latent_spec.kv_lora_rank},
            ["Out", "NewCache"])
        out = dispatch("mla", {"Ctx": ctx, "WkvB": self.wkv_b,
                               "Wo": self.wo},
                       self._attrs("out", "absorbed"))
        return out, cache


class _ShortConv(Layer):
    """The gated short-convolution mixer (`short_conv`: ``[B | C | x] = x
    W_in``, a depthwise causal conv of ``B * x`` over `short_conv_width`
    taps, ``(C * conv) W_out``; no biases).  `scan(x, lengths)` runs a
    whole (padded) prompt from an empty tail; `update(x, lengths, tails,
    index)` one token on the carried tail, entry `index` of the state
    array `tails` [Lc, B, K-1, hidden] replaced where it lies.  Both return
    (out, the tail: the last K-1 inputs of the conv)."""

    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        h, k = cfg.hidden_size, cfg.short_conv_width
        self.in_proj = self.create_parameter([h, 3 * h])
        # torch's conv1d default, as the Mamba conv's
        bound = 1.0 / math.sqrt(k)
        self.conv_w = self.create_parameter(
            [h, k], default_initializer=Uniform(-bound, bound))
        self.out_proj = self.create_parameter([h, h])

    def _run(self, x, lengths, tails=None, index=-1):
        ins = {"X": x, "WIn": self.in_proj, "Weight": self.conv_w,
               "WOut": self.out_proj, "Lengths": lengths}
        if tails is not None:
            ins["Tail"] = tails
        return dispatch("short_conv", ins, {"slab_index": index},
                        ["Out", "NewTail"])

    def scan(self, x, lengths):
        return self._run(x, lengths)

    def update(self, x, lengths, tails, index):
        return self._run(x, lengths, tails, index)


class _Mamba(Layer):
    """The Mamba-2 mixer.  `scan(x, lengths)` runs a whole (padded) prompt
    from a zero state; `update(x, lengths, ssm, conv)` one token on the
    carried state.  Both return (out, (ssm state, conv tail))."""

    def __init__(self, cfg, index):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        h, heads, inner = cfg.hidden_size, cfg.mamba_n_heads, cfg.mamba_inner
        self.w_in = self.create_parameter([h, inner + cfg.conv_dim + heads])
        self.w_out = self.create_parameter([inner, h])
        # torch's conv1d default (which the published Mamba-2 code keeps):
        # uniform in +-1/sqrt(fan_in), fan_in = the kernel's width
        bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
        self.conv_w = self.create_parameter(
            [cfg.conv_dim, cfg.mamba_d_conv],
            default_initializer=Uniform(-bound, bound))
        self.conv_b = self.create_parameter([cfg.conv_dim], is_bias=True)
        self.norm_w = self.create_parameter(
            [inner], default_initializer=Constant(1.0))
        # the Mamba-2 convention: A = -a with a uniform in [1, 16]; dt
        # log-uniform in [0.001, 0.1] through the inverse softplus; D = 1 —
        # the state neither dies in a few tokens nor swamps the residual
        rng = np.random.default_rng([global_seed(), 0x5517, index])
        dt = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1), heads))
        self.a_log = ParamBase(np.log(rng.uniform(1.0, 16.0, heads)).astype(
            np.float32), name=self._full_name + ".a_log", trainable=False)
        self.dt_bias = ParamBase((dt + np.log(-np.expm1(-dt))).astype(
            np.float32), name=self._full_name + ".dt_bias", trainable=False)
        self.d = ParamBase(np.ones(heads, np.float32),
                           name=self._full_name + ".d", trainable=False)

    def _project(self, x):
        c = self.cfg
        return split(matmul(x, self.w_in),
                     [c.mamba_inner, c.conv_dim, c.mamba_n_heads], axis=-1)

    def _xbc(self, xbc, lead):
        c = self.cfg
        gn = c.mamba_n_groups * c.mamba_d_state
        x, bm, cm = split(xbc, [c.mamba_inner, gn, gn], axis=-1)
        return (reshape(x, lead + [c.mamba_n_heads, c.mamba_d_head]),
                reshape(bm, lead + [c.mamba_n_groups, c.mamba_d_state]),
                reshape(cm, lead + [c.mamba_n_groups, c.mamba_d_state]))

    def _finish(self, y, z, lead):
        attrs = {"epsilon": self.cfg.rms_norm_eps}
        if self.cfg.mamba_norm_groups != 1:
            attrs["groups"] = self.cfg.mamba_norm_groups
        y = dispatch("gated_rms_norm",
                     {"X": reshape(y, lead + [self.cfg.mamba_inner]),
                      "Gate": z, "Scale": self.norm_w}, attrs)
        return matmul(y, self.w_out)

    def _a(self):
        return scale(dispatch("exp", {"X": self.a_log}), -1.0)

    def scan(self, x, lengths):
        b, t = x.shape[0], x.shape[1]
        z, xbc, dt = self._project(x)
        xbc, tail = dispatch(
            "causal_conv1d", {"X": xbc, "Weight": self.conv_w,
                              "Bias": self.conv_b, "Lengths": lengths},
            {"activation": "silu"}, ["Out", "NewTail"])
        xs, bm, cm = self._xbc(xbc, [b, t])
        y, state = dispatch(
            "mamba2_chunk_scan",
            {"X": xs, "Dt": dt, "A": self._a(), "B": bm, "C": cm,
             "D": self.d, "DtBias": self.dt_bias, "Lengths": lengths},
            {"chunk_size": self.cfg.mamba_chunk_size}, ["Y", "FinalState"])
        return self._finish(y, z, [b, t]), (state, tail)

    def update(self, x, lengths, ssm, conv, index):
        """`ssm`, `conv`: the state pool's whole arrays [Lm, B, ...]; this
        layer reads and replaces entry `index` of each."""
        b = x.shape[0]                                     # x [B, 1, hidden]
        z, xbc, dt = self._project(x)
        xbc, tail = dispatch(
            "causal_conv1d", {"X": xbc, "Weight": self.conv_w,
                              "Bias": self.conv_b, "Tail": conv,
                              "Lengths": lengths},
            {"activation": "silu", "slab_index": index},
            ["Out", "NewTail"])
        xs, bm, cm = self._xbc(squeeze(xbc, 1), [b])
        y, state = dispatch(
            "mamba2_state_update",
            {"X": xs, "Dt": squeeze(dt, 1), "A": self._a(), "B": bm,
             "C": cm, "D": self.d, "State": ssm, "DtBias": self.dt_bias,
             "Lengths": lengths}, {"slab_index": index},
            ["Y", "NewState"])
        out = self._finish(y, squeeze(z, 1), [b])
        return unsqueeze(out, 1), (state, tail)


class _Block(Layer):
    """One layer of the description: its mixer, then its feed-forward,
    each present or not and each behind its own norm — or, in the
    `parallel` form, both from ONE norm of the same input; in the
    `sandwich` form each output has a norm of its own too, before it is
    added."""

    def __init__(self, cfg, index):
        super().__init__(dtype=cfg.dtype)
        spec = cfg.blocks[index]
        self.kind, self.ffn = spec.mixer, spec.ffn
        self.eps, self.res = cfg.rms_norm_eps, cfg.residual_multiplier
        self.norm_kind = cfg.norm_kind
        self.parallel = cfg.block_form == "parallel" and self.kind \
            and self.ffn
        self.sandwich = cfg.block_form == "sandwich"
        ones = Constant(1.0)
        if self.kind:
            self.norm1 = self.create_parameter([cfg.hidden_size],
                                               default_initializer=ones)
            if self.kind == "attention":
                self.mixer = _Attention(cfg, cfg.attention_spec(index))
            elif self.kind == "latent":
                self.mixer = _LatentAttention(cfg)
            elif self.kind == "short_conv":
                self.mixer = _ShortConv(cfg)
            else:
                self.mixer = _Mamba(cfg, index)
            if self.sandwich:
                self.post_norm1 = self.create_parameter(
                    [cfg.hidden_size], default_initializer=ones)
        if self.ffn and not self.parallel:
            self.norm2 = self.create_parameter([cfg.hidden_size],
                                               default_initializer=ones)
            if self.sandwich:
                self.post_norm2 = self.create_parameter(
                    [cfg.hidden_size], default_initializer=ones)
        if self.ffn == "mlp":
            self.mlp = _MLP(cfg)
        elif self.ffn == "experts":
            self.experts = _Experts(cfg, index)

    def _feed(self, x, lengths):
        if self.ffn == "mlp":
            return self.mlp(x), None
        out, *routed = self.experts(x, lengths)
        return out, routed

    def forward(self, h, mix, lengths):
        """`mix(mixer, normed h)` -> (mixer output, whatever cache it
        made); `lengths` [B] the valid positions a row (None: all), which
        the experts route.  Returns (h, that cache or None, the expert
        layer's (counts, picks) or None)."""
        made = routed = None
        if self.parallel:
            x = _norm(self.norm_kind, h, self.norm1, self.eps)
            out, made = mix(self.mixer, x)
            fed, routed = self._feed(x, lengths)
            return add(h, _scaled(add(out, fed), self.res)), made, routed
        if self.kind:
            out, made = mix(self.mixer, _norm(self.norm_kind, h, self.norm1,
                                              self.eps))
            if self.sandwich:
                out = _norm(self.norm_kind, out, self.post_norm1, self.eps)
            h = add(h, _scaled(out, self.res))
        if self.ffn:
            out, routed = self._feed(
                _norm(self.norm_kind, h, self.norm2, self.eps), lengths)
            if self.sandwich:
                out = _norm(self.norm_kind, out, self.post_norm2, self.eps)
            h = add(h, _scaled(out, self.res))
        return h, made, routed


class HybridDecoder(Layer):
    """The decoder.  `forward(ids)` is the plain full pass (logits for
    every position, no cache); `prefill_step` / `decode_step` are the
    serving step contract."""

    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.config = cfg
        # once a model: the weights drawn from the seed and placed
        with Phase("model/build") as phase:
            self._build(cfg)
            phase.set(**parameter_footprint(self))

    def _build(self, c):
        # seeded weights: the table is drawn so that the scaled embedding
        # has RMS `embed_init_rms` — small beside what the mixers and
        # feed-forwards add to the residual.  At the Embedding layer's
        # default (std 1) a tied head would read the last token's own row
        # back out of the residual ~20 row-sigmas above every other logit,
        # and nothing a mixer computes, right or wrong, could change the
        # served token.
        self.embed = self.create_parameter(
            [c.vocab_size, c.hidden_size], default_initializer=Normal(
                0.0, c.embed_init_rms / c.embedding_multiplier))
        from ..nn import LayerList
        self.layers = LayerList([_Block(c, i) for i in range(c.num_layers)])
        self.norm_f = self.create_parameter(
            [c.hidden_size], default_initializer=Constant(1.0))
        if not c.tie_word_embeddings:
            self.head = self.create_parameter([c.hidden_size, c.vocab_size])
        for p in self.parameters():     # forward-only ops: never a tape
            p.stop_gradient = True
            p.trainable = False
        self.eval()

    @property
    def step_counters(self):
        """Names of the int32 counts the steps return after the state
        (`serving.step_program`): none without expert layers."""
        return MOE_COUNTERS if self.config.expert_layers else ()

    def step_metrics(self, got):
        """What the engine counts of one call's `step_counters` readings
        `got` {name: int}: ({`serving.` counter: increment}, {`serving.`
        gauge: value})."""
        c = self.config
        counted = {"moe.pairs_routed": got["moe_routed"],
                   "moe.pairs_held": got["moe_pairs"],
                   "moe.experts_touched": got["moe_touched"],
                   "moe.expert_steps": c.expert_layers}
        gauged = {}
        if got["moe_pairs"]:
            # largest expert's load (a layer, summed over the layers) over
            # the mean load of the held experts
            gauged["moe.load_max_over_mean"] = (
                got["moe_max_load"] * c.held_experts / got["moe_pairs"])
        return counted, gauged

    def cache_spec(self):
        return self.config.cache_spec()

    # -- pieces ---------------------------------------------------------------
    def _embed(self, ids):
        return _scaled(F.embedding(ids, self.embed),
                       self.config.embedding_multiplier)

    def _logits(self, h):
        """Final norm and the head on rows `h` [..., hidden]: float32
        logits (the matmul's own accumulator, not a rounded bf16 row)."""
        c = self.config
        h = _norm(c.norm_kind, h, self.norm_f, c.rms_norm_eps)
        tied = c.tie_word_embeddings
        out = dispatch("matmul_v2",
                       {"X": h, "Y": self.embed if tied else self.head},
                       {"trans_x": False, "trans_y": tied,
                        "out_dtype": "float32"})
        return _scaled(out, 1.0 / c.logits_scaling)

    def _counted(self, routed, picks=False):
        """The steps' trailing result: the expert layers' counts summed
        ([4] int32), nothing for a model without them; `picks`: then the
        experts each expert layer picked, stacked [Le, rows, T, k]."""
        if not routed:
            return ()
        total = routed[0][0]
        for counts, _ in routed[1:]:
            total = add(total, counts)
        if picks:
            return total, stack([p for _, p in routed])
        return (total,)

    def _scan_layers(self, ids, lengths, upto=None):
        """The whole (padded) sequences from empty caches: (h, new K, new
        V per attention layer, ssm state and conv tail per Mamba layer,
        (counts, picks) per expert layer, cache rows per latent layer, the
        tail per short-convolution layer); `upto`: the first layers
        only."""
        h = self._embed(ids)
        ks, vs, ssm, conv, routed, lat, tails = [], [], [], [], [], [], []
        for blk in list(self.layers)[:upto]:
            scan = blk.kind in ("mamba", "short_conv")
            h, made, expert = blk(
                h, lambda m, x: (m.scan if scan else m)(x, lengths), lengths)
            if blk.kind == "attention":
                ks.append(made[0])
                vs.append(made[1])
            elif blk.kind == "mamba":
                ssm.append(made[0])
                conv.append(made[1])
            elif blk.kind == "latent":
                lat.append(made)
            elif blk.kind == "short_conv":
                tails.append(made)
            if expert is not None:
                routed.append(expert)
        return h, ks, vs, ssm, conv, routed, lat, tails

    def forward(self, ids):
        """Logits [B, T, V] of whole sequences (every position valid)."""
        return self._logits(self._scan_layers(ids, None)[0])

    def routes(self, ids, lengths):
        """The experts every position picked in every expert layer, [Le,
        B, T, k] int32, on the path `prefill_step` takes (ids [B, T]
        padded, lengths [B]): what a comparison of the served gate with a
        reference's reads.  Not part of the step contract."""
        return stack([picks for _, picks in
                      self._scan_layers(ids, lengths)[5]])

    def hidden_row(self, ids, lengths, last, upto):
        """The residual stream after the first `upto` layers at row `last`
        [B] of a padded prompt, [B, hidden], on the path `prefill_step`
        takes: what a comparison of one layer's served arithmetic with a
        reference's reads.  Not part of the step contract."""
        return _take_rows(self._scan_layers(ids, lengths, int(upto))[0],
                          last)

    # -- the step contract ----------------------------------------------------
    def prefill_step(self, ids, lengths, last, picks=False):
        """A prompt padded to its bucket, from empty caches.

        ids [B, T]; lengths [B] valid tokens a row; last [B] = lengths - 1
        (the row whose logits sampling needs).  Returns (logits [B, V]
        float32; a K, V per cache group of `cache_spec`, in its order:
        [Lg, B, Hkv, T, D] for a group that keeps every column, and for a
        window group the RING as it stands after the prompt's last valid
        token, [Lg, B, Hkv, window, D] (`kv_ring_pack`); the latent
        group's rows [Ll, B, T, width] where the model has latent layers;
        ssm [Lm, B, H, P, N] float32 and conv [Lm, B, K-1, C] where it has
        Mamba layers, the tails [Lc, B, K-1, hidden] where it has short
        convolution layers: the state after each row's last VALID token;
        then, for a model with expert layers, the counts of
        `step_counters`, and where `picks` (static) says so the experts
        every position picked, [Le, B, T, k]: what a comparison with a
        reference reads, never the engine)."""
        h, ks, vs, ssm, conv, routed, lat, tails = self._scan_layers(
            ids, lengths)
        rows = _take_rows(h, last)
        at = {i: n for n, i in enumerate(
            self.config.layers_of("attention"))}
        kv = []
        for window, layers in self.config.kv_groups():
            for made in (ks, vs):
                group = [made[at[i]] for i in layers]
                if window:
                    group = [dispatch("kv_ring_pack",
                                      {"X": x, "Lengths": lengths},
                                      {"window": int(window)})
                             for x in group]
                kv.append(stack(group))
        if lat:
            kv.append(stack(lat))
        state = [stack(ssm), stack(conv)] if ssm else []
        if tails:
            state.append(stack(tails))
        return (self._logits(rows), *kv, *state,
                *self._counted(routed, picks))

    def decode_step(self, ids, cache_lengths, active, *cache, columns=None,
                    picks=False):
        """One token a row on the carried caches.

        ids [S, 1]; cache_lengths [S] valid columns of each row's KV cache;
        active [S] 1 for a row that takes its token, 0 for an idle row
        (its state comes back unchanged and it routes to no expert);
        `cache` = a K, V per cache group of `cache_spec`, [Lg, S, Hkv,
        columns, D], the latent group's array [Ll, S, columns, width]
        where the model has latent layers, then ssm [Lm, S, H, P, N] and
        conv [Lm, S, K-1, C] where it has Mamba layers, then the tails
        [Lc, S, K-1, hidden] where it has short-convolution layers.  They
        come back WHOLE in the result, each attention or latent layer's
        new column written into its entry (`_Attention.step`,
        `_LatentAttention.step`) and each Mamba or short-convolution
        layer's state replaced in its: the caller donates them.  Returns
        (logits [S, V] float32, *cache; then, for a model with expert
        layers, the counts of `step_counters`, and where `picks` (static)
        says so the experts each row picked, [Le, S, 1, k]).

        `columns` (static, not a tensor): None, a row reads what it holds,
        block by block up to the longest row's — what a description with a
        ring takes, ONE program; n, every row's earlier tokens lie in the
        first n columns of the arrays and that many are read — a program
        a bound, for a description without a ring.  A latent layer reads
        what its row holds, whatever `columns` says."""
        c = self.config
        cache = list(cache)
        # layer -> (its group's position in `cache`, its entry there)
        where = {i: (2 * g, n) for g, (_, layers) in
                 enumerate(c.kv_groups()) for n, i in enumerate(layers)}
        n_kv = 2 * len(c.kv_groups())
        where.update({i: (n_kv, n) for n, i in
                      enumerate(c.layers_of("latent"))})
        n_kv += 1 if c.layers_of("latent") else 0
        state = cache[n_kv:]
        ssm, conv = state[:2] if c.layers_of("mamba") else (None, None)
        tails = state[-1] if c.layers_of("short_conv") else None
        h = self._embed(ids)
        routed, n_mamba, n_conv = [], 0, 0
        for i, blk in enumerate(self.layers):
            if blk.kind == "attention":
                at, entry = where[i]
                h, (cache[at], cache[at + 1]), expert = blk(
                    h, lambda m, x: m.step(x, cache_lengths, active,
                                           cache[at], cache[at + 1], entry,
                                           columns),
                    active)
            elif blk.kind == "latent":
                at, entry = where[i]
                h, cache[at], expert = blk(
                    h, lambda m, x: m.step(x, cache_lengths, active,
                                           cache[at], entry), active)
            elif blk.kind == "mamba":
                # the state arrays go through the Mamba layers whole, each
                # replacing its own entry: no unstack / stack copies
                h, (ssm, conv), expert = blk(h, lambda m, x: m.update(
                    x, active, ssm, conv, n_mamba), active)
                n_mamba += 1
            elif blk.kind == "short_conv":
                h, tails, expert = blk(h, lambda m, x: m.update(
                    x, active, tails, n_conv), active)
                n_conv += 1
            else:
                h, _, expert = blk(h, None, active)
            if expert is not None:
                routed.append(expert)
        state = [ssm, conv] if ssm is not None else []
        if tails is not None:
            state.append(tails)
        return (self._logits(squeeze(h, 1)), *cache[:n_kv], *state,
                *self._counted(routed, picks))


def _take_rows(h, index):
    """h [B, T, D], index [B] -> [B, D]: row index[b] of sequence b."""
    if h.shape[0] != 1:
        raise NotImplementedError("prefill_step runs one prompt a call")
    return squeeze(gather(h, index, axis=1), 1)
