"""`build_bert_base` under the name the benchmark imports it by.

The builder lives in `paddle_tpu/models/static_lm.py`.  This module stays
because `bench.build_bert_base` is a bound entry point (PERF.md §3):
`benchmark/drivers/train_job.py`, `benchmark/tools/record_tiny_trace.py`
and `tests/benchmark/test_benchmark_reference.py` import it by that name.
Everything else imports it from `paddle_tpu.models`.  To measure, run
`benchmark/run.py` (BENCHMARK.json, PERF.md).
"""
from paddle_tpu.models.static_lm import build_bert_base  # noqa: F401
