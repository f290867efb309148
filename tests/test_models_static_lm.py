"""`models.build_bert_base`: the trainer's model of the two `bert-base`
benchmark cells (BENCHMARK.json).  The fingerprints below were taken at
commit b4e225c, when the builder still lived in bench.py, at the
benchmark's arguments (`benchmark/drivers/train_job.py`:
`build_bert_base(use_amp=True, batch=64)`), so that an edit of the
builder — or of a layer or pass under it — that changes the cell's
Program fails here, on the CPU, and not as a moved number on the chip."""
import hashlib
import json

import pytest

from paddle_tpu import models
from paddle_tpu.core.pass_framework import applied_passes
from paddle_tpu.core.program import _reset_unique_names

_FORWARD = ["lookup_table_v2", "elementwise_add", "layer_norm", "mul",
            "reshape2", "transpose2", "scale", "matmul", "softmax", "gelu",
            "linear_softmax_xent", "mean"]
_BACKWARD = ["layer_norm_grad", "elementwise_add_grad", "mul_grad",
             "gelu_grad", "sum", "reshape2_grad", "transpose2_grad",
             "matmul_grad", "softmax_grad", "scale_grad",
             "lookup_table_v2_grad"]

# use_amp -> ops in block 0, ops in the startup program, the distinct op
# types in order of first appearance, the applied-pass registry, and the
# sha256 of json.dumps(<ordered list of every op's type>)
FINGERPRINT = {
    True: dict(
        n_ops=1288, n_startup_ops=992,
        op_types=(_FORWARD[:3] + ["cast"] + _FORWARD[3:]
                  + ["elementwise_mul", "fill_constant",
                     "elementwise_mul_grad", "mean_grad",
                     "linear_softmax_xent_grad", "cast_grad"]
                  + _BACKWARD + ["check_finite_and_unscale", "adam"]),
        passes=[{"pass": "amp", "dest_dtype": "bfloat16"},
                {"pass": "head_loss", "heads": 1}],
        op_sequence_sha256="30367a850197125f05de697feecabfde3f1a05c61d72844"
                           "cb2d3771b81035d73"),
    False: dict(
        n_ops=931, n_startup_ops=991,
        op_types=(_FORWARD + ["fill_constant", "mean_grad",
                              "linear_softmax_xent_grad"]
                  + _BACKWARD + ["adam"]),
        passes=[{"pass": "head_loss", "heads": 1}],
        op_sequence_sha256="e78b29ae975c3ed46db6c4c94f8bef13114377036b4ce6"
                           "6573fb9af090cf6ce7"),
}


def _expected_parameters(vocab=30522, seq=512, hidden=768, layers_n=12):
    """Names from the unique-name generator in build order: two tables,
    the embedding norm, then per layer q, k, v, out, norm, FFN up, FFN
    down, norm; the head's fc last.  AMP adds no parameter."""
    want = {"embedding_0.w_0": [vocab, hidden],
            "embedding_1.w_0": [seq, hidden]}
    for i in range(2 * layers_n + 1):
        want[f"layer_norm_{i}.w_0"] = want[f"layer_norm_{i}.b_0"] = [hidden]
    for layer in range(layers_n):
        for j, (n_in, n_out) in enumerate(
                [(hidden, hidden)] * 4
                + [(hidden, 4 * hidden), (4 * hidden, hidden)]):
            fc = f"fc_{6 * layer + j}"
            want[fc + ".w_0"], want[fc + ".b_0"] = [n_in, n_out], [n_out]
    head = f"fc_{6 * layers_n}"
    want[head + ".w_0"], want[head + ".b_0"] = [hidden, vocab], [vocab]
    return sorted(want.items())


def test_bench_alias_is_the_models_builder():
    import bench
    assert bench.build_bert_base is models.build_bert_base
    assert models.build_bert_base is models.static_lm.build_bert_base


@pytest.mark.parametrize("use_amp", [True, False], ids=["amp", "fp32"])
def test_bert_base_program_fingerprint(use_amp):
    _reset_unique_names()
    main, startup, loss = models.build_bert_base(use_amp=use_amp, batch=64)
    want = FINGERPRINT[use_amp]
    sequence = [op.type for op in main.global_block().ops]
    assert len(sequence) == want["n_ops"]
    assert list(dict.fromkeys(sequence)) == want["op_types"]
    assert hashlib.sha256(json.dumps(sequence).encode()).hexdigest() == \
        want["op_sequence_sha256"]
    assert len(startup.global_block().ops) == want["n_startup_ops"]
    assert applied_passes(main) == want["passes"]
    assert loss.name == "mean_0.tmp_0"
    params = sorted((p.name, list(p.shape)) for p in main.all_parameters())
    assert len(params) == 198
    assert params == _expected_parameters()
