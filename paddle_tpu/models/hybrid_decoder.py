"""A hybrid decoder from a per-layer block description: each layer has a
mixer (Mamba-2 state-space, grouped-query attention, or none) and a
feed-forward part (a gated MLP, routed experts, or none), each applied as
``h = h + residual_multiplier * f(norm(h))`` one after the other, or, in
the `parallel` block form, both from ONE norm of the same `h`; the norm is
RMSNorm or a mean-subtracting LayerNorm (scale only); an attention layer
may state a `window` and rotary positions (`AttentionSpec`); the experts
are `latent_relu2` (a latent space, ``relu^2``, a selection bias, one
shared expert) or `gated_silu` (full width, ``silu(g) * u``, `n_shared`
shared experts averaged, no bias); an embedding multiplier, a logits
divisor, and a tied or untied head.  `cache_spec`,
`param_shapes`, the full `forward` and the two steps of the serving
contract are emitted ONCE from that description; a model family is a
config class that gives the description (`models/granite_hybrid.py`,
`models/nemotron_h.py`), not a copy of the layers or the loops.

Serving only: the ops (`ops/kernels/ssm.py`, `ops/kernels/moe.py`,
`ops/kernels/window_attention.py`) are forward only, so the parameters do
not ask for gradients and no tape is ever kept.  The model states its
cache (`cache_spec`): one `kv` group per kind of retention of its attention
layers (`retain`: a window's columns in a ring, or `"all"`) and one `state`
group for the Mamba layers — `serving.PagedKVPool`, `static.page_budget`
and the engine size themselves from it.  ALL of it lives on the device, a
row a slot, and rides the step contract: two cache-aware entry points
(ids, per-row lengths, cache in; last-row logits, cache out),
`prefill_step` and `decode_step`, flat tensor arguments and a tuple
result, so `jit.to_static` turns each into one compiled program per shape
bucket (`serving.step_program`); the decode step is given the KV and state
arrays whole and every layer writes its own entry where the array lies.
The one fork is read from the description (`kv_ring`): with a window layer
the attention ops are the blocked ones (`ops/kernels/window_attention.py`)
and the decode step one program that reads what its rows hold; without,
a prompt goes through `gqa_attention` and the decode step reads its
caller's static bound on the columns (`decode_step(columns=)`).  A model
with routed experts also counts what its steps routed (`step_counters`):
one int32 vector a call, after the state in the result tuple.
"""
from __future__ import annotations

import collections
import math

import numpy as np

from ..core.generator import global_seed
from ..dygraph.layers import Layer, ParamBase, parameter_footprint
from ..nn import functional as F
from ..nn.initializer import Constant, Normal, Uniform
from ..profiler import Phase
from ..tensor._dispatch import dispatch
from ..tensor.linalg import matmul
from ..tensor.manipulation import (cast, gather, reshape, split, squeeze,
                                   stack, transpose, unsqueeze)
from ..tensor.math import add, multiply, scale

__all__ = ["LayerSpec", "AttentionSpec", "HybridDecoderConfig",
           "HybridDecoder", "MOE_COUNTERS"]

# one layer: `mixer` "mamba" | "attention" | None; `ffn` "mlp" | "experts"
# | None
LayerSpec = collections.namedtuple("LayerSpec", "mixer ffn")
# what an attention mixer may state beside (`config.attention_specs`, by
# layer): `window` (None: every earlier token is seen) and `rotary` (None:
# no positions, else theta)
AttentionSpec = collections.namedtuple("AttentionSpec", "window rotary",
                                       defaults=(None, None))
MIXERS, FFNS = ("mamba", "attention", None), ("mlp", "experts", None)
# what `moe_grouped_experts` counts a call (ops/kernels/moe.py `STATS`),
# summed over a step's expert layers
MOE_COUNTERS = ("moe_routed", "moe_pairs", "moe_touched", "moe_max_load")
BLOCK_FORMS, NORM_KINDS = ("sequential", "parallel"), ("rms", "layer")
EXPERT_FORMS = ("latent_relu2", "gated_silu")


class HybridDecoderConfig:
    """What the decoder reads, whatever family gave it.  A family's config
    class sets, under these names: `vocab_size`, `hidden_size`, `blocks`
    (a `LayerSpec` a layer), `num_attention_heads`, `num_key_value_heads`,
    `head_dim`, `attention_multiplier` (the score scale), the `mamba_*`
    sizes with `mamba_norm_groups` (groups of the mixer's output norm),
    `shared_intermediate_size` (the gated MLP), the `moe_*` /
    `n_routed_experts` / `held_experts` / `first_held` sizes of an expert
    layer, `embedding_multiplier`, `residual_multiplier`, `logits_scaling`,
    `tie_word_embeddings`, `rms_norm_eps`, and serving's own
    (`max_position`, `bos_id`, `eos_id`, `dtype`, `embed_init_rms`).  A
    family that differs from the defaults below says so under their
    names."""

    attention_specs = {}            # layer index -> AttentionSpec
    block_form = "sequential"       # | "parallel": one norm, both from x
    norm_kind = "rms"               # | "layer": mean-subtracting, scale only
    expert_form = "latent_relu2"    # | "gated_silu"
    n_shared_experts = 1            # averaged where there are several
    # a family without Mamba layers states no `mamba_*` sizes
    mamba_n_heads = mamba_d_head = mamba_d_state = mamba_n_groups = 0
    mamba_expand = mamba_d_conv = 0
    shared_intermediate_size = 0

    def _check(self):
        for spec in self.blocks:
            if spec.mixer not in MIXERS or spec.ffn not in FFNS:
                raise ValueError(f"unknown layer description {spec}")
        for i, spec in self.attention_specs.items():
            if self.blocks[i].mixer != "attention":
                raise ValueError(f"layer {i} has no attention to describe")
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

    @property
    def expert_layers(self):
        return len(self.layers_of("experts"))

    def cache_spec(self):
        """What one sequence keeps between steps, by layer group, all of
        it in its slot on the device (`serving.kv_pool.StateSlots`): a
        `kv` group per kind of retention — arrays K, V [layers, slots, kv
        heads, columns, head dim], a window's ring or every column
        (`"all"`: a column a token, which the pool's pages count) — and
        the Mamba layers' `state` group, of fixed size."""
        groups = [{"kind": "kv", "layers": len(layers),
                   "kv_heads": self.num_key_value_heads,
                   "head_dim": self.head_dim, "dtype": self.dtype,
                   "retain": int(window) if window else "all"}
                  for window, layers in self.kv_groups()]
        if self.layers_of("mamba"):
            groups.append({
                "kind": "state", "layers": len(self.layers_of("mamba")),
                "arrays": [
                    {"name": "ssm", "dtype": "float32",
                     "shape": [self.mamba_n_heads, self.mamba_d_head,
                               self.mamba_d_state]},
                    {"name": "conv", "dtype": self.dtype,
                     "shape": [self.mamba_d_conv - 1, self.conv_dim]}]})
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
            if spec.mixer == "attention":
                out[p + "mixer.wq"] = (h, q)
                out[p + "mixer.wo"] = (q, h)
                out[p + "mixer.wk"] = out[p + "mixer.wv"] = (h, kv)
            elif spec.mixer == "mamba":
                out[p + "mixer.w_in"] = (h, inner + self.conv_dim + heads)
                out[p + "mixer.w_out"] = (inner, h)
                out[p + "mixer.conv_w"] = (self.conv_dim, self.mamba_d_conv)
                out[p + "mixer.conv_b"] = (self.conv_dim,)
                out[p + "mixer.norm_w"] = (inner,)
                out[p + "mixer.a_log"] = out[p + "mixer.dt_bias"] = \
                    out[p + "mixer.d"] = (heads,)
            if spec.ffn and (self.block_form == "sequential"
                             or not spec.mixer):
                out[p + "norm2"] = (h,)
            if spec.ffn == "mlp":
                f = self.shared_intermediate_size
                out[p + "mlp.w_in"] = (h, 2 * f)
                out[p + "mlp.w_out"] = (f, h)
            elif spec.ffn == "experts":
                f = self.moe_intermediate_size
                sf = self.moe_shared_expert_intermediate_size \
                    * self.n_shared_experts
                out[p + "experts.router_w"] = (h, self.n_routed_experts)
                if self.expert_form == "gated_silu":
                    out[p + "experts.w1"] = (self.held_experts, h, 2 * f)
                    out[p + "experts.w2"] = (self.held_experts, f, h)
                    out[p + "experts.shared_in"] = (h, 2 * sf)
                else:
                    lat = self.moe_latent_size
                    out[p + "experts.router_b"] = (self.n_routed_experts,)
                    out[p + "experts.w_down"] = (h, lat)
                    out[p + "experts.w_up"] = (lat, h)
                    out[p + "experts.w1"] = (self.held_experts, lat, f)
                    out[p + "experts.w2"] = (self.held_experts, f, lat)
                    out[p + "experts.shared_in"] = (h, sf)
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
    `gated_silu`: no bias, no latent; experts ``D(silu(G x) * U x)`` with
    `w1` = [G | U]; `n_shared_experts` shared experts of the same form,
    AVERAGED — kept side by side as one wide expert (`shared_in` = [G_1 ..
    G_n | U_1 .. U_n], `shared_out` the D_j stacked) whose output is
    divided by n.

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
        if not self.gated:
            width = cfg.moe_latent_size
            # seeded small and NON-ZERO, so that selecting by `s + b` and
            # weighting by `s` are told apart (the published bias is a
            # buffer the load balancer moves; zero would hide a swapped
            # pair)
            rng = np.random.default_rng([global_seed(), 0x0E0E, index])
            self.router_b = ParamBase(
                rng.uniform(-0.02, 0.02, cfg.n_routed_experts).astype(
                    np.float32), name=self._full_name + ".router_b",
                trainable=False)
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
        if self.gated:      # each shared expert's matrix as its own
            one = cfg.moe_shared_expert_intermediate_size
            bound = math.sqrt(6.0 / (h + one))
            self.shared_in = self.create_parameter(
                [h, 2 * sf], default_initializer=Uniform(-bound, bound))
            self.shared_out = self.create_parameter(
                [sf, h], default_initializer=Uniform(-bound, bound))
        else:
            self.shared_in = self.create_parameter([h, sf])
            self.shared_out = self.create_parameter([sf, h])

    def forward(self, x, lengths):
        c = self.cfg
        router = {"X": x, "Weight": self.router_w}
        if not self.gated:
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
    of its layer's description, a `window` and rotary positions (`rotary`:
    theta), each or neither.  `forward(x, lengths)` runs whole sequences
    from empty caches — through the blocked kernel where the description
    has a ring (`kv_ring`; given `lengths` it leaves blocks of pads out),
    else `gqa_attention` — and returns (out, (k, v)), k, v the tokens' [B,
    Hkv, T, D]; `step` one token a row over the cache group's arrays."""

    def __init__(self, cfg, spec=AttentionSpec()):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.window, self.rotary = spec.window, spec.rotary
        h = cfg.hidden_size
        q, kv = (n * cfg.head_dim for n in (cfg.num_attention_heads,
                                            cfg.num_key_value_heads))
        self.wq = self.create_parameter([h, q])
        self.wk = self.create_parameter([h, kv])
        self.wv = self.create_parameter([h, kv])
        self.wo = self.create_parameter([q, h])

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
        if self.rotary:
            ins = {} if positions is None else {"Positions": positions}
            q, k = (dispatch("rotary_embedding", dict(ins, X=y),
                             {"theta": float(self.rotary)}) for y in (q, k))
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
        if c.kv_ring:       # blocks of queries, the window's key blocks
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
    `parallel` form, both from ONE norm of the same input."""

    def __init__(self, cfg, index):
        super().__init__(dtype=cfg.dtype)
        spec = cfg.blocks[index]
        self.kind, self.ffn = spec.mixer, spec.ffn
        self.eps, self.res = cfg.rms_norm_eps, cfg.residual_multiplier
        self.norm_kind = cfg.norm_kind
        self.parallel = cfg.block_form == "parallel" and self.kind \
            and self.ffn
        ones = Constant(1.0)
        if self.kind:
            self.norm1 = self.create_parameter([cfg.hidden_size],
                                               default_initializer=ones)
            self.mixer = _Attention(cfg, cfg.attention_spec(index)) \
                if self.kind == "attention" else _Mamba(cfg, index)
        if self.ffn and not self.parallel:
            self.norm2 = self.create_parameter([cfg.hidden_size],
                                               default_initializer=ones)
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
            h = add(h, _scaled(out, self.res))
        if self.ffn:
            out, routed = self._feed(
                _norm(self.norm_kind, h, self.norm2, self.eps), lengths)
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

    def _counted(self, routed):
        """The steps' trailing result: the expert layers' counts summed
        ([4] int32), nothing for a model without them."""
        if not routed:
            return ()
        total = routed[0][0]
        for counts, _ in routed[1:]:
            total = add(total, counts)
        return (total,)

    def _scan_layers(self, ids, lengths, upto=None):
        """The whole (padded) sequences from empty caches: (h, new K, new
        V per attention layer, ssm state and conv tail per Mamba layer,
        (counts, picks) per expert layer); `upto`: the first layers only."""
        h = self._embed(ids)
        ks, vs, ssm, conv, routed = [], [], [], [], []
        for blk in list(self.layers)[:upto]:
            scan = blk.kind == "mamba"
            h, made, expert = blk(
                h, lambda m, x: (m.scan if scan else m)(x, lengths), lengths)
            if blk.kind == "attention":
                ks.append(made[0])
                vs.append(made[1])
            elif blk.kind == "mamba":
                ssm.append(made[0])
                conv.append(made[1])
            if expert is not None:
                routed.append(expert)
        return h, ks, vs, ssm, conv, routed

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
    def prefill_step(self, ids, lengths, last):
        """A prompt padded to its bucket, from empty caches.

        ids [B, T]; lengths [B] valid tokens a row; last [B] = lengths - 1
        (the row whose logits sampling needs).  Returns (logits [B, V]
        float32; a K, V per cache group of `cache_spec`, in its order:
        [Lg, B, Hkv, T, D] for a group that keeps every column, and for a
        window group the RING as it stands after the prompt's last valid
        token, [Lg, B, Hkv, window, D] (`kv_ring_pack`); ssm [Lm, B, H, P,
        N] float32 and conv [Lm, B, K-1, C] where the model has Mamba
        layers: the state after each row's last VALID token; then, for a
        model with expert layers, the counts of `step_counters`)."""
        h, ks, vs, ssm, conv, routed = self._scan_layers(ids, lengths)
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
        state = [stack(ssm), stack(conv)] if ssm else []
        return (self._logits(rows), *kv, *state, *self._counted(routed))

    def decode_step(self, ids, cache_lengths, active, *cache, columns=None):
        """One token a row on the carried caches.

        ids [S, 1]; cache_lengths [S] valid columns of each row's KV cache;
        active [S] 1 for a row that takes its token, 0 for an idle row
        (its state comes back unchanged and it routes to no expert);
        `cache` = a K, V per cache group of `cache_spec`, [Lg, S, Hkv,
        columns, D], then ssm [Lm, S, H, P, N] and conv [Lm, S, K-1, C]
        where the model has Mamba layers.  They come back WHOLE in the
        result, each attention layer's new column written into its entry
        (`_Attention.step`) and each Mamba layer's state replaced in its:
        the caller donates them.  Returns (logits [S, V] float32, *cache;
        then, for a model with expert layers, the counts of
        `step_counters`).

        `columns` (static, not a tensor): None, a row reads what it holds,
        block by block up to the longest row's — what a description with a
        ring takes, ONE program; n, every row's earlier tokens lie in the
        first n columns of the arrays and that many are read — a program
        a bound, for a description without a ring."""
        c = self.config
        cache = list(cache)
        # layer -> (its group's position in `cache`, its entry there)
        where = {i: (2 * g, n) for g, (_, layers) in
                 enumerate(c.kv_groups()) for n, i in enumerate(layers)}
        n_kv = 2 * len(c.kv_groups())
        ssm, conv = cache[n_kv:] if c.layers_of("mamba") else (None, None)
        h = self._embed(ids)
        routed, n_mamba = [], 0
        for i, blk in enumerate(self.layers):
            if blk.kind == "attention":
                at, entry = where[i]
                h, (cache[at], cache[at + 1]), expert = blk(
                    h, lambda m, x: m.step(x, cache_lengths, active,
                                           cache[at], cache[at + 1], entry,
                                           columns),
                    active)
            elif blk.kind == "mamba":
                # the state arrays go through the Mamba layers whole, each
                # replacing its own entry: no unstack / stack copies
                h, (ssm, conv), expert = blk(h, lambda m, x: m.update(
                    x, active, ssm, conv, n_mamba), active)
                n_mamba += 1
            else:
                h, _, expert = blk(h, None, active)
            if expert is not None:
                routed.append(expert)
        state = [ssm, conv] if ssm is not None else []
        return (self._logits(squeeze(h, 1)), *cache[:n_kv], *state,
                *self._counted(routed))


def _take_rows(h, index):
    """h [B, T, D], index [B] -> [B, D]: row index[b] of sequence b."""
    if h.shape[0] != 1:
        raise NotImplementedError("prefill_step runs one prompt a call")
    return squeeze(gather(h, index, axis=1), 1)
