"""GPT-style decoder-only language model + generation API.

Reference surface: the fluid-era transformer decode loop
(beam_search_op.cc / beam_search_decode_op.cc driving seq2seq decode) and
the 2.x `generate()` contract (greedy / sampling / beam search).  The
reference repo carries decoder LMs through its transformer examples; a
decoder-only family is the capability users reach for first on TPU, so it
ships as a first-class model here.

TPU design: attention runs through MultiHeadAttention with an explicit
additive causal mask (cached per sequence length; the dense-mask path —
flash attention's mask-free causal route is a follow-up once MHA grows a
`causal` flag).  Generation is host-orchestrated over the registered
`beam_search` op (dense [batch, beam] axis, shared loop in
models/_decode.py) exactly like TransformerModel.beam_search.
"""
from __future__ import annotations

import numpy as np

import paddle_tpu
from .. import nn
from ..dygraph.layers import Layer, parameter_footprint
from ..profiler import Phase

__all__ = ["GPTConfig", "GPTModel", "GPTForGeneration", "gpt_small"]


class GPTConfig:
    def __init__(self, vocab_size=5000, hidden_size=256, num_layers=4,
                 num_heads=4, intermediate_size=None, max_position=512,
                 bos_id=0, eos_id=1, dropout=0.1):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size or hidden_size * 4
        self.max_position = max_position
        self.bos_id = bos_id
        self.eos_id = eos_id
        self.dropout = dropout

    def cache_spec(self):
        """What one sequence keeps between steps (`serving/kv_pool.py`):
        one `kv` group, every layer a full-attention layer."""
        return [{"kind": "kv", "layers": self.num_layers,
                 "kv_heads": self.num_heads,
                 "head_dim": self.hidden_size // self.num_heads}]


class _Block(Layer):
    """Pre-norm decoder block (GPT-2 style)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = nn.MultiHeadAttention(cfg.hidden_size, cfg.num_heads,
                                          dropout=cfg.dropout)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        self.fc1 = nn.Linear(cfg.hidden_size, cfg.intermediate_size)
        self.fc2 = nn.Linear(cfg.intermediate_size, cfg.hidden_size)

    def forward(self, x, mask, cache=None):
        h = self.ln1(x)
        if cache is None:
            x = x + self.attn(h, h, h, attn_mask=mask)
        else:
            # incremental decode: attn consumes + extends the per-layer
            # KV cache (MultiHeadAttention.Cache concat path)
            out, cache = self.attn(h, h, h, attn_mask=mask, cache=cache)
            x = x + out
        h = self.ln2(x)
        x = x + self.fc2(nn.functional.gelu(self.fc1(h)))
        return x if cache is None else (x, cache)


class GPTModel(Layer):
    def __init__(self, cfg: GPTConfig = None, **kw):
        super().__init__()
        self.config = cfg or GPTConfig(**kw)
        c = self.config
        # once a model: the weights drawn from the seed and placed
        with Phase("model/build") as phase:
            self.wte = nn.Embedding(c.vocab_size, c.hidden_size)
            self.wpe = nn.Embedding(c.max_position, c.hidden_size)
            self.blocks = nn.LayerList(
                [_Block(c) for _ in range(c.num_layers)])
            self.ln_f = nn.LayerNorm(c.hidden_size)
            phase.set(**parameter_footprint(self))
        self._mask_cache = {}

    def _mask(self, seq):
        # cache per length: decode loops call every step and should not
        # re-upload an [S, S] mask host->device each time
        m = self._mask_cache.get(seq)
        if m is None:
            m = paddle_tpu.to_tensor(
                np.triu(np.full((seq, seq), -1e9, np.float32), k=1))
            self._mask_cache[seq] = m
        return m

    def forward(self, input_ids, cache=None, pos_offset=None,
                attn_mask=None):
        """Plain LM forward, or — when ``cache`` (list of per-block
        ``MultiHeadAttention.Cache``) is given — one incremental decode
        step that returns ``(logits, new_caches)``.

        ``pos_offset``: per-row absolute position of ``input_ids[:, 0]``
        (int array [batch]); continuous batching feeds sequences of
        different lengths in one fixed-slot batch, so each row needs its
        own position base.  ``attn_mask`` overrides the causal mask —
        the serving engine passes an additive mask that hides each
        slot's KV padding columns."""
        seq = input_ids.shape[1]
        if pos_offset is None:
            pos = paddle_tpu.to_tensor(
                np.arange(seq, dtype=np.int64)[None].repeat(
                    input_ids.shape[0], 0))
        else:
            off = np.asarray(pos_offset, np.int64).reshape(-1, 1)
            pos = paddle_tpu.to_tensor(
                off + np.arange(seq, dtype=np.int64)[None])
        x = self.wte(input_ids) + self.wpe(pos)
        mask = attn_mask if attn_mask is not None else self._mask(seq)
        if cache is None:
            for blk in self.blocks:
                x = blk(x, mask)
            x = self.ln_f(x)
            # tied LM head
            return paddle_tpu.matmul(x, self.wte.weight, transpose_y=True)
        new_caches = []
        for blk, c in zip(self.blocks, cache):
            x, c = blk(x, mask, cache=c)
            new_caches.append(c)
        x = self.ln_f(x)
        logits = paddle_tpu.matmul(x, self.wte.weight, transpose_y=True)
        return logits, new_caches

    def cache_spec(self):
        return self.config.cache_spec()

    def gen_cache(self, batch_size):
        """Fresh empty per-block KV caches for ``batch_size`` rows (the
        serving engine's slot-admission entry point)."""
        c = self.config
        head_dim = c.hidden_size // c.num_heads
        return [nn.MultiHeadAttention.Cache(
            paddle_tpu.to_tensor(np.zeros(
                (batch_size, c.num_heads, 0, head_dim), np.float32)),
            paddle_tpu.to_tensor(np.zeros(
                (batch_size, c.num_heads, 0, head_dim), np.float32)))
            for _ in self.blocks]


class GPTForGeneration(Layer):
    """generate() with greedy / sampling / beam_search strategies (the
    paddle 2.x generation contract), built on the beam_search op."""

    def __init__(self, gpt: GPTModel):
        super().__init__()
        self.gpt = gpt

    def forward(self, input_ids):
        return self.gpt(input_ids)

    def generate(self, input_ids, max_length=20,
                 decode_strategy="greedy_search", num_beams=4, top_k=0,
                 temperature=1.0, seed=0, length_penalty=0.0):
        cfg = self.gpt.config
        ids = np.asarray(input_ids.numpy() if hasattr(input_ids, "numpy")
                         else input_ids).astype(np.int64)
        if decode_strategy not in ("greedy_search", "sampling",
                                   "beam_search"):
            raise ValueError(
                f"unknown decode_strategy {decode_strategy!r}; expected "
                "'greedy_search', 'sampling', or 'beam_search'")
        if ids.shape[1] + max_length > cfg.max_position:
            raise ValueError(
                f"prefix ({ids.shape[1]}) + max_length ({max_length}) "
                f"exceeds max_position ({cfg.max_position}); longer "
                "sequences would silently clamp position embeddings")
        if decode_strategy == "beam_search":
            return self._beam(ids, max_length, num_beams, length_penalty)
        rng = np.random.RandomState(seed)
        batch = ids.shape[0]
        finished = np.zeros(batch, bool)
        for _ in range(max_length):
            logits = np.asarray(self.gpt(
                paddle_tpu.to_tensor(ids)).numpy())[:, -1]
            if decode_strategy == "sampling":
                logits = logits / max(temperature, 1e-6)
                if top_k:
                    kth = np.sort(logits, -1)[:, -top_k][:, None]
                    logits = np.where(logits < kth, -1e9, logits)
                p = np.exp(logits - logits.max(-1, keepdims=True))
                p /= p.sum(-1, keepdims=True)
                nxt = np.array([rng.choice(p.shape[1], p=row)
                                for row in p])
            else:  # greedy_search
                nxt = logits.argmax(-1)
            nxt = np.where(finished, cfg.eos_id, nxt)
            finished |= nxt == cfg.eos_id
            ids = np.concatenate([ids, nxt[:, None].astype(np.int64)], 1)
            if finished.all():
                break
        return ids

    def _beam(self, ids, max_length, W, length_penalty=0.0):
        from ._decode import beam_search_loop

        def step_logits(trg):
            return np.asarray(self.gpt(
                paddle_tpu.to_tensor(trg)).numpy())[:, -1]

        return beam_search_loop(step_logits, ids, W, self.gpt.config.eos_id,
                                max_length, length_penalty)


def gpt_small(**kw):
    return GPTForGeneration(GPTModel(GPTConfig(
        hidden_size=256, num_layers=4, num_heads=4, **kw)))
