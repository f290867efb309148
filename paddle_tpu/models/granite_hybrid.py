"""Granite-4.0-H style hybrid decoder: Mamba-2 state-space layers beside
grouped-query attention layers, RMSNorm, a gated (SwiGLU) MLP, scaled
residuals, no positions, tied head.

Written from the published `granitemoehybrid` configuration
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json;
`num_local_experts` 0: the shared MLP is the only one).  The layer
equations are in `benchmark/reference/granite_hybrid.py`, the plain
float32 twin the tests and the benchmark compare this model with.

Serving only: the new ops (`ops/kernels/ssm.py`) are forward only, so the
parameters do not ask for gradients and no tape is ever kept.  The model
states its cache (`cache_spec`): one `kv` group for the attention layers
and one `state` group for the Mamba layers — `serving.PagedKVPool`,
`static.page_budget` and the engine size themselves from it.  Two
cache-aware entry points make the step contract (ids, per-row lengths,
cache in; last-row logits, cache out): `prefill_step` and `decode_step`,
flat tensor arguments and a tuple result, so `jit.to_static` turns each
into one compiled program per shape bucket (`serving.step_program`).
"""
from __future__ import annotations

import math

import numpy as np

from ..core.generator import global_seed
from ..dygraph.layers import Layer, ParamBase
from ..nn import functional as F
from ..nn.initializer import Constant, Normal, Uniform
from ..tensor._dispatch import dispatch
from ..tensor.linalg import matmul
from ..tensor.manipulation import (cast, gather, reshape, split, squeeze,
                                   stack, transpose, unsqueeze, unstack)
from ..tensor.math import add, multiply, scale

__all__ = ["GraniteHybridConfig", "GraniteHybridModel", "granite_hybrid_tiny"]

_PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4   # the published one


class GraniteHybridConfig:
    """The keys of the published config that shape the model, under their
    published names, plus what serving needs (`max_position`: the longest
    context served, the model itself has no positions; `dtype`)."""

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
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("hidden_size / attention heads / kv heads do "
                             "not divide")
        if self.mamba_inner != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"mamba_expand * hidden_size = {self.mamba_inner} is not "
                f"mamba_n_heads * mamba_d_head = "
                f"{self.mamba_n_heads * self.mamba_d_head}")

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
                "routed experts are not built (ROADMAP R-c); "
                "num_local_experts must be 0")
        if published.get("position_embedding_type", "nope") != "nope":
            raise NotImplementedError(
                "rotary positions are not built (ROADMAP R-a)")
        return cls(**{k: published[k] for k in cls._HF_KEYS
                      if k in published}, **serving)

    # -- derived sizes ------------------------------------------------------
    # the names page_budget and the engine read on every decoder config
    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def num_heads(self):
        return self.num_attention_heads

    @property
    def intermediate_size(self):
        return self.shared_intermediate_size

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self):
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def layers_of(self, kind):
        return [i for i, t in enumerate(self.layer_types) if t == kind]

    def cache_spec(self):
        """What one sequence keeps between steps, by layer group: `kv`
        groups grow a column a token and live in pool pages; `state`
        groups are of fixed size and live in a state slot."""
        groups = []
        if self.layers_of("attention"):
            # dense_dtype: what `decode_step` reads its dense KV cache
            # in; the pool keeps that view of the live sequences on the
            # device, per slot, beside the state
            groups.append({"kind": "kv",
                           "layers": len(self.layers_of("attention")),
                           "kv_heads": self.num_key_value_heads,
                           "head_dim": self.head_dim,
                           "dense_dtype": self.dtype})
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
        h, f = self.hidden_size, self.shared_intermediate_size
        heads, inner = self.mamba_n_heads, self.mamba_inner
        kv = self.num_key_value_heads * self.head_dim
        out = {"embed": (self.vocab_size, h), "norm_f": (h,)}
        for i, kind in enumerate(self.layer_types):
            p = f"layers.{i}."
            out[p + "norm1"] = out[p + "norm2"] = (h,)
            out[p + "mlp.w_in"] = (h, 2 * f)
            out[p + "mlp.w_out"] = (f, h)
            if kind == "attention":
                out[p + "mixer.wq"] = out[p + "mixer.wo"] = (h, h)
                out[p + "mixer.wk"] = out[p + "mixer.wv"] = (h, kv)
            else:
                out[p + "mixer.w_in"] = (h, inner + self.conv_dim + heads)
                out[p + "mixer.w_out"] = (inner, h)
                out[p + "mixer.conv_w"] = (self.conv_dim, self.mamba_d_conv)
                out[p + "mixer.conv_b"] = (self.conv_dim,)
                out[p + "mixer.norm_w"] = (inner,)
                out[p + "mixer.a_log"] = out[p + "mixer.dt_bias"] = \
                    out[p + "mixer.d"] = (heads,)
        return out

    def param_count(self):
        return sum(int(np.prod(s)) for s in self.param_shapes().values())


def _rms_norm(x, weight, eps):
    return dispatch("rms_norm", {"X": x, "Scale": weight}, {"epsilon": eps})


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


class _Attention(Layer):
    """Grouped-query attention, no positions, the config's own score
    multiplier.  `cache`: None (prefill: the new tokens alone) or (k, v,
    lengths) of the earlier tokens.  Returns (out, (k, v)) with k, v the
    new tokens' [B, Hkv, T, D]."""

    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        h, kv = cfg.hidden_size, cfg.num_key_value_heads * cfg.head_dim
        self.wq = self.create_parameter([h, h])
        self.wk = self.create_parameter([h, kv])
        self.wv = self.create_parameter([h, kv])
        self.wo = self.create_parameter([h, h])

    def forward(self, x, cache=None):
        c = self.cfg
        b, t = x.shape[0], x.shape[1]

        def heads(y, n):
            return transpose(reshape(y, [b, t, n, c.head_dim]), [0, 2, 1, 3])

        q = heads(matmul(x, self.wq), c.num_attention_heads)
        k = heads(matmul(x, self.wk), c.num_key_value_heads)
        v = heads(matmul(x, self.wv), c.num_key_value_heads)
        ins = {"Q": q, "K": k, "V": v}
        if cache is not None:
            ins.update(KCache=cache[0], VCache=cache[1],
                       CacheLengths=cache[2])
        ctx = dispatch("gqa_attention", ins,
                       {"scale": c.attention_multiplier})
        ctx = reshape(transpose(ctx, [0, 2, 1, 3]), [b, t, c.hidden_size])
        return matmul(ctx, self.wo), (k, v)


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
        y = dispatch("gated_rms_norm",
                     {"X": reshape(y, lead + [self.cfg.mamba_inner]),
                      "Gate": z, "Scale": self.norm_w},
                     {"epsilon": self.cfg.rms_norm_eps})
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
    def __init__(self, cfg, index):
        super().__init__(dtype=cfg.dtype)
        self.kind = cfg.layer_types[index]
        self.eps, self.res = cfg.rms_norm_eps, cfg.residual_multiplier
        ones = Constant(1.0)
        self.norm1 = self.create_parameter([cfg.hidden_size],
                                           default_initializer=ones)
        self.mixer = _Attention(cfg) if self.kind == "attention" \
            else _Mamba(cfg, index)
        self.norm2 = self.create_parameter([cfg.hidden_size],
                                           default_initializer=ones)
        self.mlp = _MLP(cfg)

    def forward(self, h, mix):
        """`mix(mixer, normed h)` -> (mixer output, whatever cache it
        made); returns (h, that cache)."""
        out, made = mix(self.mixer, _rms_norm(h, self.norm1, self.eps))
        h = add(h, scale(out, self.res))
        out = self.mlp(_rms_norm(h, self.norm2, self.eps))
        return add(h, scale(out, self.res)), made


class GraniteHybridModel(Layer):
    """The decoder.  `forward(ids)` is the plain full pass (logits for
    every position, no cache); `prefill_step` / `decode_step` are the
    serving step contract."""

    def __init__(self, cfg: GraniteHybridConfig = None, **kw):
        super().__init__(dtype=(cfg or GraniteHybridConfig(**kw)).dtype)
        self.config = c = cfg or GraniteHybridConfig(**kw)
        # seeded weights: the table is drawn so that the scaled embedding
        # has RMS `embed_init_rms` — small beside what 80 mixers and MLPs
        # add to the residual.  At the Embedding layer's default (std 1)
        # the tied head would read the last token's own row back out of
        # the residual ~20 row-sigmas above every other logit, and nothing
        # a mixer computes, right or wrong, could change the served token.
        self.embed = self.create_parameter(
            [c.vocab_size, c.hidden_size], default_initializer=Normal(
                0.0, c.embed_init_rms / c.embedding_multiplier))
        from ..nn import LayerList
        self.layers = LayerList([_Block(c, i) for i in range(c.num_layers)])
        self.norm_f = self.create_parameter(
            [c.hidden_size], default_initializer=Constant(1.0))
        for p in self.parameters():     # forward-only ops: never a tape
            p.stop_gradient = True
            p.trainable = False
        self.eval()

    def cache_spec(self):
        return self.config.cache_spec()

    # -- pieces ---------------------------------------------------------------
    def _embed(self, ids):
        return scale(F.embedding(ids, self.embed),
                     self.config.embedding_multiplier)

    def _logits(self, h):
        """Final norm and the tied head on rows `h` [..., hidden]: float32
        logits (the matmul's own accumulator, not a rounded bf16 row)."""
        c = self.config
        h = _rms_norm(h, self.norm_f, c.rms_norm_eps)
        out = dispatch("matmul_v2", {"X": h, "Y": self.embed},
                       {"trans_x": False, "trans_y": True,
                        "out_dtype": "float32"})
        return scale(out, 1.0 / c.logits_scaling)

    def _scan_layers(self, ids, lengths):
        """The whole (padded) sequences from empty caches: (h, new K, new
        V per attention layer, ssm state and conv tail per Mamba layer)."""
        h = self._embed(ids)
        ks, vs, ssm, conv = [], [], [], []
        for blk in self.layers:
            if blk.kind == "attention":
                h, (k, v) = blk(h, lambda m, x: m(x))
                ks.append(k)
                vs.append(v)
            else:
                h, (s, t) = blk(h, lambda m, x: m.scan(x, lengths))
                ssm.append(s)
                conv.append(t)
        return h, ks, vs, ssm, conv

    def forward(self, ids):
        """Logits [B, T, V] of whole sequences (every position valid)."""
        return self._logits(self._scan_layers(ids, None)[0])

    # -- the step contract ----------------------------------------------------
    def prefill_step(self, ids, lengths, last):
        """A prompt padded to its bucket, from empty caches.

        ids [B, T]; lengths [B] valid tokens a row; last [B] = lengths - 1
        (the row whose logits sampling needs).  Returns (logits [B, V]
        float32, K, V [La, B, Hkv, T, D] of the attention layers, ssm
        [Lm, B, H, P, N] float32 and conv [Lm, B, K-1, C] of the Mamba
        layers: the state after each row's last VALID token)."""
        h, ks, vs, ssm, conv = self._scan_layers(ids, lengths)
        rows = _take_rows(h, last)
        return (self._logits(rows), stack(ks), stack(vs), stack(ssm),
                stack(conv))

    def decode_step(self, ids, cache_lengths, active, k_cache, v_cache,
                    ssm, conv):
        """One token a row on the carried caches.

        ids [S, 1]; cache_lengths [S] valid columns of each row's KV cache;
        active [S] 1 for a row that takes its token, 0 for an idle row
        (its state comes back unchanged); k_cache, v_cache [La, S, Hkv, L,
        D]; ssm [Lm, S, H, P, N]; conv [Lm, S, K-1, C].  Returns (logits
        [S, V] float32, the new K, V columns [La, S, Hkv, 1, D], ssm,
        conv)."""
        c = self.config
        h = self._embed(ids)
        kc = unstack(k_cache, 0) if c.layers_of("attention") else []
        vc = unstack(v_cache, 0) if c.layers_of("attention") else []
        ks, vs, n_mamba = [], [], 0
        for blk in self.layers:
            if blk.kind == "attention":
                cache = (cast(kc[len(ks)], c.dtype),
                         cast(vc[len(vs)], c.dtype), cache_lengths)
                h, (k, v) = blk(h, lambda m, x: m(x, cache))
                ks.append(k)
                vs.append(v)
            else:
                # the state arrays go through the Mamba layers whole, each
                # replacing its own entry: no unstack / stack copies
                h, (ssm, conv) = blk(h, lambda m, x: m.update(
                    x, active, ssm, conv, n_mamba))
                n_mamba += 1
        return (self._logits(squeeze(h, 1)), stack(ks), stack(vs), ssm,
                conv)


def _take_rows(h, index):
    """h [B, T, D], index [B] -> [B, D]: row index[b] of sequence b."""
    if h.shape[0] != 1:
        raise NotImplementedError("prefill_step runs one prompt a call")
    return squeeze(gather(h, index, axis=1), 1)


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
