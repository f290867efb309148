"""paddle_tpu.serving — the request-coalescing tier between the HTTP
surface (inference/server.py) and the compiled model.

Four pieces:

* ``DynamicBatcher`` (batcher.py) — bounded admission queue + scheduler
  thread that coalesces concurrent ``/predict`` requests into one padded
  device batch per tick and slices result rows back per caller.
* ``ContinuousBatchingEngine`` (generation.py) — continuous-batching
  decode; sequences join free slots between steps and retire on
  EOS/max-len (``/generate``).  KV is per-slot dense arrays, or the
  block-paged pool when ``kv_pool=`` is given.
* ``PagedKVPool`` (kv_pool.py) — fixed-size KV pages + per-sequence page
  tables with refcounted copy-on-write prefix sharing; admission is by
  free-page reservation, sizing by ``static.page_budget`` (the HBM
  walker), drift detection by ``budget_drift``.  Geometry comes from the
  model's cache description; a model with recurrent layers gets
  ``StateSlots`` (device-resident per-sequence state) in the same
  manager, and the engine runs it through ``StepPrograms``
  (step_program.py): one compiled program per (phase, bucket).
* ``RadixPrefixCache`` (prefix_cache.py) — retained radix tree over
  committed prefixes: pages pinned past last-sharer retirement
  (watermark-bounded LRU), radix hits skip prefill compute over the hit
  tokens (reused prefill).
* ``SpeculativeDecoder`` (speculative.py) — draft/target speculative
  decoding: ``stamp_draft`` builds the small sibling, the engine
  verifies k proposals per batched step and rolls rejections back via
  page-table truncation.
* int8 decode (int8_decode.py + ``PagedKVPool(kv_dtype="int8")``) —
  weight-only quantized decode matmuls (``Int8Linear`` /
  ``quantize_decode_model`` for tp=1, ``slim.freeze_weights_int8``
  stamped inside ``TPShardedDecoder`` for tp>1) over int8 KV pages
  with fp32 scale sidecars, carving ~2x the pages at equal HBM.
* metrics (metrics.py) — the ``serving.*`` counter/gauge/histogram
  namespace over core/monitor, dumped by ``/stats``.

See docs/serving.md for the architecture and the backpressure contract.
"""
from .batcher import (  # noqa: F401
    DynamicBatcher, BatcherError, QueueFullError, DeadlineExceededError,
    BatcherStoppedError,
)
from .generation import (  # noqa: F401
    ContinuousBatchingEngine, GenerationRequest,
)
from .kv_pool import (  # noqa: F401
    PagedKVPool, PageTable, PagePoolExhaustedError, StateSlots,
    budget_drift, cache_spec_of,
)
from .step_program import StepPrograms  # noqa: F401
from .prefix_cache import RadixPrefixCache  # noqa: F401
from .tp_decode import TPShardedDecoder, build_decode_program  # noqa: F401
from .int8_decode import Int8Linear, quantize_decode_model  # noqa: F401
from .speculative import (  # noqa: F401
    SpeculativeDecoder, stamp_draft, longest_accepted,
)
from .metrics import serving_stats, reset_serving_stats  # noqa: F401

__all__ = [
    "DynamicBatcher", "BatcherError", "QueueFullError",
    "DeadlineExceededError", "BatcherStoppedError",
    "ContinuousBatchingEngine", "GenerationRequest",
    "PagedKVPool", "PageTable", "PagePoolExhaustedError", "budget_drift",
    "StateSlots", "StepPrograms", "cache_spec_of",
    "RadixPrefixCache", "TPShardedDecoder", "build_decode_program",
    "Int8Linear", "quantize_decode_model",
    "SpeculativeDecoder", "stamp_draft",
    "longest_accepted", "serving_stats", "reset_serving_stats",
]
