"""The program held to the benchmark's plain references at a tiny size on
the CPU: BERT's loss through the Executor, GPT-2's logits through prefill
and then decoding over the paged KV pool."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import loadgen                            # noqa: E402
from benchmark.reference import bert_mlm, gpt2           # noqa: E402


# fp32 program against the fp32 reference: both are float32 sums of a few
# hundred terms in different orders — 1e-5 relative holds with room, and a
# dropped bias or a wrong epsilon moves the loss by 1e-3 or more.  With bf16
# AMP the matmul inputs keep 8 bits of mantissa: 1e-2, as on the chip.
@pytest.mark.parametrize("use_amp,rtol", [(False, 1e-5), (True, 1e-2)])
def test_bert_program_loss_equals_the_reference(use_amp, rtol):
    import bench
    import paddle_tpu.static as static
    vocab, seq, hidden, layers_n, heads, batch = 96, 16, 32, 2, 2, 8
    main, startup, loss = bench.build_bert_base(
        vocab, seq, hidden, layers_n, heads, batch, use_amp=use_amp)
    main.random_seed = startup.random_seed = 11
    feed = next(loadgen.training_batches(
        {"seq_len": seq, "steps_per_dispatch": 1, "zipf_exponent": 1.0},
        vocab, 11, batch))
    exe, scope = static.Executor(), static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        params = [np.asarray(scope.get(p.name))
                  for p in main.all_parameters()]
        (got,) = exe.run(main, feed=feed, fetch_list=[loss])
    assert len(params) == 4 + layers_n * bert_mlm.PARAMS_PER_LAYER + 2
    want = bert_mlm.mlm_loss_chunked(params, feed["ids"], feed["ids"],
                                     layers_n, heads, chunk=4)
    assert float(got) == pytest.approx(want, rel=rtol)
    if not use_amp:
        # the reference notices what the tolerance must catch
        broken = list(params)
        broken[4] = params[4] * 1.05            # one Q matrix 5% off
        off = bert_mlm.mlm_loss_chunked(broken, feed["ids"], feed["ids"],
                                        layers_n, heads, chunk=4)
        assert abs(off - want) / want > 10 * rtol


def test_gpt2_prefill_then_paged_decode_equals_the_reference_logits():
    """Every logits row the engine's forward produces — the prompt's last
    position at prefill, then one row a decode step over the gathered
    pages — against the reference's full forward over the served sequence.
    float32 on both sides, different summation orders and a -1e9 mask
    against -inf: 2e-4 absolute on logits of order 1 holds with room, and a
    misplaced KV column or position moves them by 1e-1."""
    import paddle_tpu
    import paddle_tpu.dygraph as dg
    import paddle_tpu.static as static
    from paddle_tpu.models import GPTConfig, GPTForGeneration, GPTModel
    from paddle_tpu.serving import ContinuousBatchingEngine
    from benchmark import serving

    cfg = {"vocab_size": 96, "n_embd": 32, "n_layer": 2, "n_head": 2,
           "n_positions": 64, "eos_token_id": 95}
    rows = []
    with dg.guard():
        paddle_tpu.seed(5)
        model = GPTForGeneration(GPTModel(GPTConfig(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=2,
            max_position=64, bos_id=95, eos_id=95, dropout=0.0)))
        model.eval()
        plan = static.page_budget(model, page_tokens=4, max_context=64,
                                  hbm_bytes=4 << 20, max_slots_cap=2)
        eng = ContinuousBatchingEngine(model, kv_pool=plan)
        forward = eng._model.forward

        def recording(ids, cache=None, pos_offset=None, attn_mask=None):
            logits, caches = forward(ids, cache=cache, pos_offset=pos_offset,
                                     attn_mask=attn_mask)
            rows.append((np.asarray(ids.numpy()),
                         np.asarray(logits.numpy())))
            return logits, caches

        eng._model.forward = recording
        eng.start()
        try:
            prompt = np.arange(3, 14)                    # 11 tokens
            served = eng.submit(prompt, max_length=6).result(timeout=120)
        finally:
            eng.stop()
            eng._model.forward = forward
        holder = type("S", (), {"model": model, "cfg": cfg})()
        params = serving.Served.reference_params(holder)
    served = np.asarray(served)
    assert served.shape == (17,) and (served[:11] == prompt).all()
    want = np.asarray(gpt2.logits(params, served, 2, 2))
    # prefill: the padded prompt's row 10 predicts token 11
    ids0, logits0 = rows[0]
    assert ids0.shape == (1, 16)
    np.testing.assert_allclose(logits0[0, 10], want[10], atol=2e-4)
    # decode steps: slot 0's single row at positions 11..15
    assert len(rows) == 6
    for step, (ids, logits) in enumerate(rows[1:]):
        assert ids.shape[1] == 1 and ids[0, 0] == served[11 + step]
        np.testing.assert_allclose(logits[0, 0], want[11 + step], atol=2e-4)
        assert served[12 + step] == want[11 + step].argmax()
    # and the reference notices a shifted position
    shifted = np.asarray(gpt2.logits(params, np.roll(served, 1), 2, 2))
    assert np.abs(shifted[10] - want[10]).max() > 1e-2
