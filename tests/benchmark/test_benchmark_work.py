"""benchmark/work.py against hand-worked numbers, and the table of peaks."""
import json
import os

import pytest

from benchmark import work

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_bert_base_needs_707_mflop_a_token_at_s512():
    cfg = _cfg("bert-base")
    # blocks: 12 layers x (4 x 768^2 + 2 x 768 x 3072) weights
    assert work.bert_block_matmul_params(cfg) == 12 * (
        4 * 768 * 768 + 2 * 768 * 3072) == 84_934_656
    assert work.bert_head_matmul_params(cfg) == 768 * 30522 == 23_440_896
    # 6 FLOPs a matmul weight, + attention 12 x L x s x h
    assert work.bert_train_flops_per_token(cfg, 512) == \
        6 * (84_934_656 + 23_440_896) + 12 * 12 * 512 * 768 == 706_876_416
    # a quarter of the attention at s128: the phase-1 mix
    assert work.bert_train_flops_per_token(cfg, 128) == \
        650_253_312 + 14_155_776
    # tables 23,440,896 + 393,216, embedding norm 1,536, blocks 85,054,464,
    # head 23,471,418
    assert work.bert_all_params(cfg) == 132_361_530
    assert work.bert_train_bytes_per_step(cfg) == \
        28 * 132_361_530 + 4 * (84_934_656 + 23_440_896)


def test_gpt2_medium_weights_and_forward_work():
    cfg = _cfg("gpt2-medium")
    assert work.gpt_block_matmul_params(cfg) == 24 * 12 * 1024 * 1024 \
        == 301_989_888
    # tables 51,463,168 + 1,048,576, blocks 24 x 12,596,224, final norm
    assert work.gpt_all_params(cfg) == 354_823_168        # 1.42 GB in fp32
    # one decode step of 8 rows over 100 tokens of context each
    flops, moved = work.gpt_forward_work(cfg, rows=8, context_sum=800,
                                         logit_rows=8, forwards=1)
    assert flops == 2 * 301_989_888 * 8 + 2 * 1024 * 50257 * 8 \
        + 4 * 1024 * 24 * 800
    assert moved == 4 * 354_823_168 + 2 * 24 * 1024 * 4 * 808
    # such a step is memory-bound on the v5e: 1.58 GB at 819 GB/s
    least, bound = work.roofline_seconds(flops, moved,
                                         work.peaks("TPU v5 lite"))
    assert bound == "memory" and 0.0019 < least < 0.0020
    # gpt2-xl, the size the memory floor first pointed to: 6.23 GB in fp32
    xl = dict(cfg, n_embd=1600, n_layer=48, n_head=25)
    assert work.gpt_all_params(xl) == 1_557_611_200


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["ici_bits_per_s"] == 1600e9
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            work.peaks(kind)
    least, bound = work.roofline_seconds(197e12, 1.0, v5e)
    assert bound == "compute" and least == pytest.approx(1.0)
