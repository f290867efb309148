"""Draft/target speculative decoding (serving/speculative.py + the
engine's batched verify step).

Covers the pure acceptance rule (full-accept, reject-all, mid-chain
rejection), the engine's verify/rollback protocol against scripted
drafts whose proposals are forced to accept or reject (page-table tail
truncation, token-equality either way), real stamped drafts (exact
full-depth stamp accepts everything; a shallow stamp rejects and stays
token-equal), sampling rows riding the spec batch at width 1, and
leak-free pool drain after mixed radix + speculative churn."""
import numpy as np
import pytest

from paddle_tpu.serving import (ContinuousBatchingEngine, PagedKVPool,
                                RadixPrefixCache, SpeculativeDecoder,
                                longest_accepted, metrics, stamp_draft)


# -- acceptance rule (pure math) --------------------------------------------
def test_longest_accepted_matrix():
    # full accept: every draft matches the target's greedy chain
    assert longest_accepted([3, 4, 5], [3, 4, 5, 6]) == 3
    # reject-all: first draft already disagrees -> zero accepted
    assert longest_accepted([9, 4, 5], [3, 4, 5, 6]) == 0
    # chain acceptance: a mid-chain miss invalidates the (coincidental)
    # later match too
    assert longest_accepted([3, 9, 5], [3, 4, 5, 6]) == 1
    # no proposals (the k=0 degenerate row) accepts nothing
    assert longest_accepted([], [3]) == 0


def test_decoder_validation():
    class _Cfg:
        vocab_size, max_position, eos_id, num_layers = 48, 64, 1, 2
        num_heads, hidden_size = 2, 16

    class _M:
        config = _Cfg()

    with pytest.raises(ValueError, match="k must be"):
        SpeculativeDecoder(_M(), k=0)
    spec = SpeculativeDecoder(_M(), k=4)

    class _Other:
        vocab_size, max_position, eos_id = 99, 64, 1
    with pytest.raises(ValueError, match="vocab_size"):
        spec.geometry_check(_Other())


# -- scripted drafts: force the accept/reject matrix through the engine -----
class _ScriptedDecoder(SpeculativeDecoder):
    """Proposals scripted from a known greedy reference sequence: the
    ``mode`` decides whether every proposal matches the target's chain
    (accept) or is perturbed off it (reject).  No draft model runs —
    open/commit/close are bookkeeping no-ops — so the test isolates the
    ENGINE's verify/rollback protocol."""

    def __init__(self, model, script, mode, k=3):
        super().__init__(model, k=k)
        self.script = [int(t) for t in script]
        self.mode = mode
        self.calls = 0

    def open(self, slot, prompt_tokens):
        pass

    def close(self, slot):
        pass

    def commit(self, slot, committed, pending):
        pass

    def propose(self, slot, committed, pending, n=None):
        self.calls += 1
        n = self.k if n is None else min(int(n), self.k)
        pos = len(committed) + 1        # stream = committed + [pending]
        out = self.script[pos:pos + n]
        if self.mode == "reject":
            out = [(t + 1) % self.config.vocab_size for t in out]
        return out


@pytest.fixture(scope="module")
def tiny_lm():
    import paddle_tpu.dygraph as dg
    from paddle_tpu.models import GPTConfig, GPTModel, GPTForGeneration
    import paddle_tpu
    from paddle_tpu.core import generator
    from paddle_tpu.core.program import (default_main_program,
                                         default_startup_program)
    with dg.guard():
        cfg = GPTConfig(vocab_size=48, hidden_size=16, num_layers=2,
                        num_heads=2, max_position=64, dropout=0.0)
        # seeded: the scripted reject case counts one target step a token,
        # which holds only while the reference chain meets no EOS, and
        # that hangs on the weights — drawn unseeded, on whatever ran
        # before in this worker.  The generator is put back as found.
        state = generator.get_rng_state()
        seeds = (default_main_program().random_seed,
                 default_startup_program().random_seed)
        paddle_tpu.seed(1234)
        try:
            m = GPTForGeneration(GPTModel(cfg))
        finally:
            generator.set_rng_state(state)
            default_main_program().random_seed, \
                default_startup_program().random_seed = seeds
        m.eval()
        yield m


def _ref(model, prompt, max_new):
    pool = PagedKVPool(2, 2, 8, page_tokens=4, num_pages=64)
    eng = ContinuousBatchingEngine(model, max_slots=2,
                                   kv_pool=pool).start()
    try:
        out = np.asarray(eng.submit(prompt, max_length=max_new)
                         .result(timeout=60))
    finally:
        eng.stop()
    pool.assert_drained()
    return out


@pytest.mark.parametrize("mode", ["accept", "reject"])
def test_scripted_accept_reject_token_equal(tiny_lm, mode):
    rng = np.random.RandomState(7)
    prompt = rng.randint(2, 48, (6,)).astype(np.int64)
    ref = _ref(tiny_lm, prompt, 6)
    pool = PagedKVPool(2, 2, 8, page_tokens=4, num_pages=64)
    spec = _ScriptedDecoder(tiny_lm, ref, mode, k=3)
    eng = ContinuousBatchingEngine(tiny_lm, max_slots=2, kv_pool=pool,
                                   speculative=spec).start()
    pre_acc = metrics.counter("spec.accepted")
    pre_prop = metrics.counter("spec.proposed")
    pre_roll = metrics.counter("spec.rollback_cols")
    pre_steps = metrics.counter("spec.steps")
    try:
        out = np.asarray(eng.submit(prompt, max_length=6)
                         .result(timeout=60))
    finally:
        eng.stop()
    np.testing.assert_array_equal(out, ref)
    accepted = metrics.counter("spec.accepted") - pre_acc
    proposed = metrics.counter("spec.proposed") - pre_prop
    rolled = metrics.counter("spec.rollback_cols") - pre_roll
    steps = metrics.counter("spec.steps") - pre_steps
    assert spec.calls > 0 and proposed > 0
    if mode == "accept":
        # full accept: every proposal verified, nothing rolled back,
        # strictly fewer target steps than tokens emitted
        assert accepted == proposed
        assert rolled == 0
        assert steps < 6
    else:
        # reject-all: nothing accepted, every proposed column rolled
        # back through pool.truncate, one target step per token (the
        # plain-greedy floor — never worse than no speculation)
        assert accepted == 0
        assert rolled == proposed
        assert steps == 6 - 1   # prefill emits the first of 6 tokens
    pool.assert_drained()


def test_stamped_draft_full_depth_accepts_all(tiny_lm):
    rng = np.random.RandomState(8)
    prompt = rng.randint(2, 48, (6,)).astype(np.int64)
    ref = _ref(tiny_lm, prompt, 6)
    draft = stamp_draft(tiny_lm, num_layers=2)   # exact copy
    pool = PagedKVPool(2, 2, 8, page_tokens=4, num_pages=64)
    spec = SpeculativeDecoder(draft, k=3)
    eng = ContinuousBatchingEngine(tiny_lm, max_slots=2, kv_pool=pool,
                                   speculative=spec).start()
    pre_steps = metrics.counter("spec.steps")
    pre_tokens = metrics.counter("gen.tokens")
    try:
        out = np.asarray(eng.submit(prompt, max_length=6)
                         .result(timeout=60))
    finally:
        eng.stop()
    np.testing.assert_array_equal(out, ref)
    steps = metrics.counter("spec.steps") - pre_steps
    tokens = metrics.counter("gen.tokens") - pre_tokens
    assert tokens / max(1, steps) > 1.0, (tokens, steps)
    assert spec.draft_tokens > 0
    assert spec.open_slots == 0        # retire closed the draft state
    pool.assert_drained()


def test_shallow_stamp_rejections_stay_token_equal(tiny_lm):
    rng = np.random.RandomState(9)
    prompts = [rng.randint(2, 48, (n,)).astype(np.int64)
               for n in (5, 9)]
    refs = [_ref(tiny_lm, p, 6) for p in prompts]
    draft = stamp_draft(tiny_lm, num_layers=1)   # genuinely wrong draft
    pool = PagedKVPool(2, 2, 8, page_tokens=4, num_pages=64)
    eng = ContinuousBatchingEngine(tiny_lm, max_slots=2, kv_pool=pool,
                                   speculative=SpeculativeDecoder(
                                       draft, k=4)).start()
    try:
        futs = [eng.submit(p, max_length=6) for p in prompts]
        outs = [np.asarray(f.result(timeout=60)) for f in futs]
    finally:
        eng.stop()
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)
    pool.assert_drained()


def test_sampling_rides_spec_batch_at_width_one(tiny_lm):
    rng = np.random.RandomState(10)
    prompt = rng.randint(2, 48, (5,)).astype(np.int64)
    pool0 = PagedKVPool(2, 2, 8, page_tokens=4, num_pages=64)
    eng0 = ContinuousBatchingEngine(tiny_lm, max_slots=2,
                                    kv_pool=pool0).start()
    try:
        ref = np.asarray(eng0.submit(
            prompt, max_length=6, decode_strategy="sampling", top_k=5,
            seed=21).result(timeout=60))
    finally:
        eng0.stop()
    pool = PagedKVPool(2, 2, 8, page_tokens=4, num_pages=64)
    spec = SpeculativeDecoder(stamp_draft(tiny_lm, num_layers=2), k=3)
    eng = ContinuousBatchingEngine(tiny_lm, max_slots=2, kv_pool=pool,
                                   speculative=spec).start()
    try:
        out = np.asarray(eng.submit(
            prompt, max_length=6, decode_strategy="sampling", top_k=5,
            seed=21).result(timeout=60))
    finally:
        eng.stop()
    # a sampling row never consumes draft proposals, so its per-request
    # RNG stream is untouched and output matches the plain engine
    np.testing.assert_array_equal(out, ref)
    assert spec.draft_tokens == 0
    pool.assert_drained()


def test_pool_drained_after_mixed_radix_spec_churn(tiny_lm):
    rng = np.random.RandomState(11)
    pool = PagedKVPool(2, 2, 8, page_tokens=4, num_pages=32)
    radix = RadixPrefixCache(pool, low_watermark=3, high_watermark=6)
    spec = SpeculativeDecoder(stamp_draft(tiny_lm, num_layers=1), k=3)
    eng = ContinuousBatchingEngine(tiny_lm, max_slots=2, kv_pool=pool,
                                   prefix_cache=radix,
                                   speculative=spec).start()
    head = rng.randint(2, 48, (8,)).astype(np.int64)
    try:
        futs = []
        for i in range(8):
            if i % 2:
                p = np.concatenate([head, [2 + i]]).astype(np.int64)
            else:
                p = rng.randint(2, 48, (4 + i,)).astype(np.int64)
            futs.append(eng.submit(p, max_length=5))
        for f in futs:
            f.result(timeout=120)
    finally:
        eng.stop()
    # retention is active (shared head retired into the tree) yet the
    # drained pool is leak-free; dropping retention frees everything
    assert pool.pages_retained > 0
    pool.assert_drained()
    radix.clear()
    pool.assert_drained()
    assert pool.pages_free == pool.num_pages
    assert spec.open_slots == 0
