"""Continuous-batching generation engine (Orca-style iteration-level
scheduling) for autoregressive decode.

``GPTForGeneration.generate`` decodes one request at a time and
recomputes the whole prefix every step — fine for a notebook, hopeless
for serving: the device runs batch-1 matmuls and a long request blocks
every short one behind it.  This engine keeps a decode batch of up to
``max_slots`` rows stepping continuously; sequences are admitted
BETWEEN steps and retired the moment they emit EOS or hit their length
budget, so a finished short request never waits for the longest
sequence in its batch (the continuous-batching lesson).

KV storage comes in two modes:

* **Fixed-slot (default, the A/B baseline)** — each slot owns dense
  per-layer K/V arrays ([heads, len, head_dim]) built at admission and
  extended one column per step.  HBM pays worst case per slot.
* **Paged (``kv_pool=``)** — KV lives in a shared ``PagedKVPool``
  (serving/kv_pool.py): fixed-size pages, per-sequence page tables,
  refcounted copy-on-write sharing of common prompt-prefix pages, and
  ADMISSION BY FREE-PAGE RESERVATION instead of slot count.  The decode
  step reads through a gather-by-page-table view into the very same
  dense batched cache the fixed-slot path feeds ``GPTModel.forward
  (cache=...)``, so compiled shapes stay bounded at (max_slots, log2
  lengths) and greedy output stays token-equal to the fixed-slot
  engine.  ``kv_pool="auto"`` sizes the pool with
  ``static.page_budget`` — the HBM-walker budget path — and adopts its
  batch ceiling / max-context.

Backpressure mirrors the DynamicBatcher contract: queue overflow raises
a load-scaled, JITTERED ``QueueFullError`` (a deterministic Retry-After
synchronizes rejected clients into a thundering herd), requests whose
page demand exceeds the whole pool are rejected at submit (they could
only ever expire in the queue), and queued requests expire at their
deadline.

Decode strategies reuse the ``generate()`` contract: ``greedy_search``
(deterministic — token-for-token equal to per-sequence ``generate``)
and ``sampling`` (temperature / top-k, per-request seeded RNG).  Beam
search is whole-sequence search and cannot join a running batch; the
engine rejects it at submit.

Two optional paged-mode subsystems turn page sharing into compute
sharing:

* ``prefix_cache=`` (serving/prefix_cache.py) — a retained radix tree
  over committed prefixes.  On admission the engine looks the prompt up
  (capped at ``len(prompt) - 1`` so the model always sees at least one
  suffix token), adopts the hit pages into the fresh page table and
  runs prefill attention ONLY over the uncovered suffix; at retirement
  the committed full-page prefix is inserted (pages pinned past
  last-sharer close, watermark-bounded).
* ``speculative=`` (serving/speculative.py) — draft/target speculative
  decoding.  Each decode step, the draft proposes up to ``k`` tokens
  per greedy row; the target verifies every proposal in ONE batched
  step (width ``k+1`` instead of 1); accepted chains commit, the first
  rejection rolls the page-table tail back via ``pool.truncate``.
  Greedy output stays token-equal to the target alone — acceptance
  replays the exact plain-greedy emission loop over the verified chain.

Geometry (KV layers, kv heads, head dim) comes from the model's cache
description (``kv_pool.cache_spec_of``).  WHICH ROUTE A MODEL LANDS ON: a
model that states the step contract — ``prefill_step`` and ``decode_step``
(step_program.py) — runs through ``StepPrograms``, the compiled route;
every other (``GPTModel``) through the eager forwards above.  The compiled
route needs the paged pool and keeps ONE cache, on the device: what a
sequence holds — the KV of its attention layers (every ``kv`` group of the
description states its ``retain``, a window's ring among them) and its
recurrent state (a ``state`` group) — lives in the pool's ``StateSlots``,
reserved with the pages at admission and released with them; the pages
only account, and no KV byte crosses the host link.  Prefill and decode are
ONE compiled program per (phase, bucket), prompts carry their lengths so
that bucket padding never enters a recurrence, an idle decode row's state
comes back unchanged, and the decode program writes each row's new column
where the arrays lie (what a decode bucket is, with and without a ring:
``_step_compiled``).  On that route the loop keeps ONE decode step in
flight: a step is read and booked after the next compiled program has been
dispatched behind it, from the ids the step left on the device
(``_step_compiled``).  ``prefix_cache=`` and ``speculative=`` refuse such
a model (they need state snapshots at page boundaries and rollback of a
state, ROADMAP R-h; a ring can be neither shared nor truncated by page).
"""
from __future__ import annotations

import itertools
import threading
import time
from concurrent.futures import Future
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

from . import metrics
from ..core.compile_cache import next_pow2 as _next_pow2
from ..dygraph.tensor import Tensor
from ..profiler import RecordEvent
from .batcher import (BatcherStoppedError, DeadlineExceededError,
                      QueueFullError, _jittered)
from .kv_pool import (PagedKVPool, PageTable, cache_spec_of, kv_geometry,
                      retained_kv_groups)

__all__ = ["ContinuousBatchingEngine", "GenerationRequest"]

_NEG_INF = -1e9


class GenerationRequest:
    """One admitted generation request; resolves its Future with the full
    token sequence (prompt + generated, truncated at EOS) as int64[n]."""

    __slots__ = ("prompt", "max_new", "strategy", "top_k", "temperature",
                 "rng", "future", "deadline", "t_enqueue", "req_id")

    def __init__(self, prompt, max_new, strategy, top_k, temperature,
                 seed, timeout_s, req_id=0):
        self.req_id = int(req_id)   # the `req` field of its spans
        self.prompt = np.asarray(prompt, np.int64).reshape(-1)
        self.max_new = int(max_new)
        self.strategy = strategy
        self.top_k = int(top_k)
        self.temperature = float(temperature)
        self.rng = np.random.RandomState(seed)
        self.future: Future = Future()
        self.t_enqueue = time.monotonic()
        self.deadline = self.t_enqueue + timeout_s


class _Slot:
    __slots__ = ("req", "kv", "table", "tokens", "next_id", "n_new")

    def __init__(self, req, kv, tokens, next_id, table=None):
        self.req = req
        self.kv = kv          # fixed mode: per-layer (k [H,len,Dh], v)
        self.table = table    # paged mode: PageTable into the pool
        self.tokens = tokens  # prompt + generated so far (python list)
        self.next_id = next_id  # sampled, not yet fed through the model
        self.n_new = 1

    @property
    def kv_len(self) -> int:
        if self.table is not None:
            return self.table.length
        return self.kv[0][0].shape[1]


class _Launch(NamedTuple):
    """A compiled decode step whose results the host has not read: what it
    was dispatched for and its results, on the device."""
    pairs: list             # the (row, _Slot) it was dispatched for
    lengths: np.ndarray     # the cache lengths it went in with, [S]
    logits: Optional[Tensor]    # where a row of it samples, else None
    next_ids: Tensor


class ContinuousBatchingEngine:
    """Serve ``generate()`` traffic from one continuously-stepping batch.

        eng = ContinuousBatchingEngine(model, max_slots=4).start()
        fut = eng.submit([2, 17, 5], max_length=20)
        tokens = fut.result()          # np.int64 [prompt+generated]
        eng.stop()

    ``model`` is a ``GPTForGeneration`` (or bare ``GPTModel``) — anything
    exposing ``config``, ``gen_cache(batch)`` and the cache-aware
    ``forward(ids, cache, pos_offset, attn_mask)``.

    ``kv_pool``: ``None`` keeps the dense fixed-slot cache; ``"auto"``
    builds a ``PagedKVPool`` sized by ``static.page_budget(model)`` (the
    planner/HBM-walker path) and adopts the plan's batch ceiling unless
    ``max_slots`` is given explicitly; a plan dict or a ready
    ``PagedKVPool`` is consumed as-is.

    ``prefix_cache``: ``"auto"`` builds a ``RadixPrefixCache`` with the
    plan's ``retained_watermarks``; a ready cache (bound to this pool)
    is consumed as-is.  ``speculative``: ``"auto"`` stamps a 2-layer
    draft from the model and wraps it in a ``SpeculativeDecoder``; a
    ready decoder is consumed as-is.  Both require paged mode.
    """

    def __init__(self, model, max_slots: Optional[int] = None,
                 max_queue: int = 64, default_timeout_s: float = 120.0,
                 kv_bucket_floor: int = 16, kv_pool=None,
                 prefix_cache=None, speculative=None,
                 tp_degree: Optional[int] = None,
                 weight_dtype: Optional[str] = None):
        # tp-sharded decode: resolve the degree (explicit arg wins, else
        # a planner plan / ready pool carries it), then wrap the model's
        # forward in the mesh-dispatching backend.  TPShardedDecoder has
        # no .gpt attr, so the unwrap below keeps the sharded path.
        if tp_degree is None:
            if isinstance(kv_pool, PagedKVPool):
                tp_degree = kv_pool.tp_degree
            elif isinstance(kv_pool, dict):
                tp_degree = int(kv_pool.get("tp_degree", 1))
            else:
                tp_degree = 1
        self.tp_degree = max(1, int(tp_degree))
        # int8 decode matmuls: resolved exactly like tp_degree — the
        # explicit arg wins, else the pool's recorded plan carries it
        if weight_dtype is None:
            if isinstance(kv_pool, PagedKVPool):
                weight_dtype = (kv_pool.plan or {}).get(
                    "weight_dtype", "float32")
            elif isinstance(kv_pool, dict):
                weight_dtype = kv_pool.get("weight_dtype", "float32")
            else:
                weight_dtype = "float32"
        self.weight_dtype = str(weight_dtype)
        if self.weight_dtype not in ("float32", "int8"):
            raise ValueError(
                f"weight_dtype must be float32 or int8, got "
                f"{weight_dtype!r}")
        # the float model is the sizing authority: page_budget's weight
        # walk must see the fp32 parameters, not the quantized sibling's
        float_model = getattr(model, "gpt", model)
        if self.tp_degree > 1:
            from .tp_decode import TPShardedDecoder
            if not isinstance(model, TPShardedDecoder):
                model = TPShardedDecoder(model, self.tp_degree,
                                         weight_dtype=self.weight_dtype)
        elif self.weight_dtype == "int8":
            from .tp_decode import TPShardedDecoder
            if not isinstance(model, TPShardedDecoder):
                from .int8_decode import quantize_decode_model
                model = quantize_decode_model(model)
        self._model = getattr(model, "gpt", model)
        self.config = self._model.config
        # the model's cache description: KV geometry for the pool and the
        # dense step caches, and whether sequences carry recurrent state
        spec = cache_spec_of(self.config)
        self._kv_layers, self._kv_heads, self._kv_head_dim = \
            kv_geometry(spec)
        # the compiled route is for a model that states the step contract
        self._compiled = all(callable(getattr(self._model, name, None))
                             for name in ("prefill_step", "decode_step"))
        self._kv_groups = retained_kv_groups(spec)
        if self._compiled:
            self._refuse_for_state(kv_pool, prefix_cache, speculative)
        self._pool: Optional[PagedKVPool] = None
        if kv_pool is not None:
            if kv_pool == "auto":
                from ..static.planner import page_budget
                self._pool = PagedKVPool.from_plan(
                    page_budget(float_model, tp_degree=self.tp_degree,
                                weight_dtype=self.weight_dtype))
            elif isinstance(kv_pool, PagedKVPool):
                self._pool = kv_pool
            elif isinstance(kv_pool, dict):
                self._pool = PagedKVPool.from_plan(kv_pool)
            else:
                raise ValueError(
                    f"kv_pool must be None, 'auto', a plan dict or a "
                    f"PagedKVPool, got {type(kv_pool).__name__}")
            for name, want, got in (
                    ("num_layers", self._kv_layers, self._pool.num_layers),
                    ("num_heads", self._kv_heads, self._pool.num_heads),
                    ("head_dim", self._kv_head_dim, self._pool.head_dim)):
                if int(want) != int(got):
                    raise ValueError(
                        f"kv_pool geometry mismatch: model {name}={want} "
                        f"but pool was built for {got}")
            if self._pool.tp_degree != self.tp_degree:
                raise ValueError(
                    f"tp_degree mismatch: engine runs tp={self.tp_degree} "
                    f"but the pool plan was sized for "
                    f"tp={self._pool.tp_degree} — per-chip page budgets "
                    "would not match the sharded slabs")
            plan_wd = str((self._pool.plan or {}).get(
                "weight_dtype", self.weight_dtype))
            if plan_wd != self.weight_dtype:
                raise ValueError(
                    f"weight_dtype mismatch: engine serves "
                    f"{self.weight_dtype} weights but the pool plan "
                    f"budgeted for {plan_wd} — the weight-byte carve "
                    "would not match what is resident")
        plan = self._pool.plan if self._pool is not None else None
        if max_slots is None:
            max_slots = int(plan["max_slots"]) if plan else 4
        self.max_slots = int(max_slots)
        self._steps = None
        # the compiled route's one decode step in flight (dispatched, not
        # yet read) and the counts of the last step read, which wait for
        # the next `engine/step` span
        self._in_flight: Optional[_Launch] = None
        self._step_counts = None
        if self._compiled:
            state = self._pool.state
            if state is None or not state.device_kv \
                    or not state.built_for(spec):
                raise ValueError(
                    "kv_pool holds no state slots for this model's cache "
                    "description — build it with PagedKVPool.from_plan("
                    "static.page_budget(model))")
            if state.slots < self.max_slots:
                raise ValueError(
                    f"max_slots={self.max_slots} but the pool holds "
                    f"{state.slots} state slots")
            from .step_program import StepPrograms
            self._steps = StepPrograms(self._model)
        # paged max-context: what the plan granted (never beyond the
        # model's positions); fixed mode keeps max_position
        self.max_context = int(self.config.max_position)
        if self._pool is not None:
            pool_ctx = self._pool.num_pages * self._pool.page_tokens
            self.max_context = min(
                self.max_context,
                int(plan["max_context"]) if plan else pool_ctx)
        self.max_queue = int(max_queue)
        self.default_timeout_s = float(default_timeout_s)
        self._kv_floor = int(kv_bucket_floor)
        self._queue: List[GenerationRequest] = []
        self._req_ids = itertools.count(1)
        self._slots: List[Optional[_Slot]] = [None] * self.max_slots
        self._kv_buckets = set()   # distinct compiled KV lengths seen
        self._radix = None
        if prefix_cache is not None:
            if self._pool is None:
                raise ValueError(
                    "prefix_cache requires paged KV (kv_pool=)")
            if prefix_cache == "auto":
                from .prefix_cache import RadixPrefixCache
                self._radix = RadixPrefixCache.from_plan(self._pool)
            else:
                if prefix_cache.pool is not self._pool:
                    raise ValueError(
                        "prefix_cache is bound to a different pool")
                self._radix = prefix_cache
        self._spec = None
        if speculative is not None:
            if self._pool is None:
                raise ValueError(
                    "speculative decoding requires paged KV (kv_pool=) "
                    "— rollback is page-table truncation")
            if speculative == "auto":
                from .speculative import SpeculativeDecoder, stamp_draft
                self._spec = SpeculativeDecoder(
                    stamp_draft(self._model, num_layers=2),
                    kv_bucket_floor=self._kv_floor)
            else:
                self._spec = speculative
            self._spec.geometry_check(self.config)
            self._spec.track_buckets(
                self._kv_buckets,
                on_change=lambda: metrics.gauge(
                    "gen.kv_buckets", len(self._kv_buckets)))
        self._mu = threading.Lock()
        self._work = threading.Condition(self._mu)
        self._idle = threading.Condition(self._mu)
        self._running = False
        self._draining = False
        self._thread: Optional[threading.Thread] = None

    def _refuse_for_state(self, kv_pool, prefix_cache, speculative):
        """What a model on the compiled route cannot have yet, each with
        what is missing (ROADMAP R-h's remainder)."""
        name = type(self._model).__name__
        ring = any(g["retain"] != "all" for g in self._kv_groups)
        if prefix_cache is not None:
            raise NotImplementedError(
                f"prefix_cache= with {name}: " + (
                    "a window layer keeps a ring of its last columns, "
                    "which cannot be shared by page" if ring else
                    "a retained prefix would need "
                    "a snapshot of the recurrent state at each page "
                    "boundary to resume from; only KV pages are retained"))
        if speculative is not None:
            raise NotImplementedError(
                f"speculative= with {name}: " + (
                    "rejecting a draft would need the columns a ring has "
                    "overwritten; a ring cannot be truncated" if ring else
                    "rejecting a draft would need "
                    "rollback of the recurrent state; only the page table "
                    "can be truncated"))
        if kv_pool is None:
            raise ValueError(
                f"{name} serves through compiled steps: it needs the paged "
                "pool (kv_pool='auto', a plan or a PagedKVPool), whose "
                "manager holds the state slots")
        if self.tp_degree > 1 or self.weight_dtype != "float32":
            raise NotImplementedError(
                f"{name} serves at tp_degree 1 in its own weight dtype "
                "(sharded state and int8 stamps are not built)")

    @property
    def kv_pool(self) -> Optional[PagedKVPool]:
        return self._pool

    @property
    def step_programs(self):
        """The compiled route's `StepPrograms` (None on the eager route):
        the programs this engine serves with, for whoever replays a
        sequence through them while the engine is idle."""
        return self._steps

    @property
    def paged(self) -> bool:
        return self._pool is not None

    @property
    def prefix_cache(self):
        return self._radix

    @property
    def speculative(self):
        return self._spec

    @property
    def kv_buckets(self) -> int:
        """Distinct padded KV lengths the model has been asked to
        compile — growth after warmup means a retrace."""
        with self._mu:
            return len(self._kv_buckets)

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        with self._mu:
            if self._running:
                return self
            self._running, self._draining = True, False
        self._thread = threading.Thread(target=self._decode_loop,
                                        name="paddle-tpu-genloop",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, drain: bool = True, timeout: float = 60.0):
        with self._mu:
            if not self._running:
                return
            self._draining = True
            self._work.notify_all()
            if drain:
                deadline = time.monotonic() + timeout
                while self._queue or any(self._slots):
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    self._idle.wait(left)
            for req in self._queue:
                req.future.set_exception(BatcherStoppedError(
                    "generation engine stopped before request started"))
            self._queue.clear()
            self._running = False
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        # the decode thread is dead now: fail whatever it left in-flight
        # (drain=False, or a drain that timed out) instead of letting
        # callers hang on their futures — and give its pages back
        self._in_flight = self._step_counts = None
        for i, slot in enumerate(self._slots):
            if slot is not None:
                if not slot.req.future.done():
                    slot.req.future.set_exception(BatcherStoppedError(
                        "generation engine stopped mid-decode"))
                if slot.table is not None:
                    self._pool.close_sequence(slot.table)
                self._slots[i] = None
        if self._spec is not None:
            self._spec.close_all()

    # -- admission ----------------------------------------------------------
    def _retry_hint(self, depth: int) -> float:
        """Load-scaled jittered Retry-After: time for the backlog to
        drain at the decode batch's width, inflated by page-pool
        admission pressure (a nearly-full pool retires slower than the
        queue math alone suggests)."""
        base = max(0.05, 0.1 * depth / max(1, self.max_slots))
        if self._pool is not None:
            occupancy = 1.0 - (self._pool.pages_available
                               / max(1, self._pool.num_pages))
            base *= 1.0 + occupancy
        return _jittered(base)

    def submit(self, input_ids, max_length: int = 20,
               decode_strategy: str = "greedy_search", top_k: int = 0,
               temperature: float = 1.0, seed: int = 0,
               timeout_s: Optional[float] = None,
               req_id: Optional[int] = None) -> Future:
        """Queue one sequence.  ``req_id`` (from ``next_request_id()``)
        ties the engine's spans to the caller's: the HTTP handler gives
        every sequence of one POST the id its ``server/generate`` span
        carries; without one the engine draws its own."""
        if decode_strategy not in ("greedy_search", "sampling"):
            raise ValueError(
                f"continuous batching supports 'greedy_search' and "
                f"'sampling', got decode_strategy={decode_strategy!r} "
                "(beam search is whole-sequence and cannot join a "
                "running batch)")
        prompt = np.asarray(input_ids, np.int64).reshape(-1)
        if prompt.size == 0:
            raise ValueError("input_ids must hold at least one token")
        if prompt.size + max_length > self.max_context:
            limit = ("max_position" if self.max_context ==
                     self.config.max_position else "the pool's max_context")
            raise ValueError(
                f"prefix ({prompt.size}) + max_length ({max_length}) "
                f"exceeds {limit} ({self.max_context})")
        if self._pool is not None:
            worst = self._pool.pages_for_request(prompt.size, max_length)
            if worst > self._pool.num_pages:
                metrics.count("gen.rejected")
                metrics.count("gen.rejected_pages")
                raise ValueError(
                    f"request can never fit: needs {worst} KV pages, the "
                    f"pool holds {self._pool.num_pages} "
                    f"({self._pool.page_tokens} tokens/page)")
        req = GenerationRequest(
            prompt, max_length, decode_strategy, top_k, temperature, seed,
            self.default_timeout_s if timeout_s is None else timeout_s,
            req_id=self.next_request_id() if req_id is None else req_id)
        with self._mu:
            if not self._running or self._draining:
                metrics.count("gen.rejected")
                raise BatcherStoppedError(
                    "generation engine is not accepting work")
            if len(self._queue) >= self.max_queue:
                metrics.count("gen.rejected")
                metrics.count("gen.rejected_queue_full")
                raise QueueFullError(len(self._queue),
                                     self._retry_hint(len(self._queue)))
            self._queue.append(req)
            metrics.count("gen.admitted")
            metrics.gauge("gen.queue.depth", len(self._queue))
            self._work.notify()
        return req.future

    def next_request_id(self) -> int:
        return next(self._req_ids)

    # -- decode loop --------------------------------------------------------
    # Spans (docs/observability.md "Spans"): every stretch of this thread
    # is inside one of engine/idle, engine/admit, engine/prefill or
    # engine/step, and inside the last two each host<->device crossing
    # has a child with its `bytes`.  On the compiled route a decode step
    # is read AFTER the next launch has been dispatched (`_step_compiled`,
    # `_prefill_compiled`), so the loop neither idles nor skips a step
    # while one is in flight.
    def _decode_loop(self):
        while True:
            with self._mu:
                while self._running and not self._queue \
                        and not any(self._slots) \
                        and self._in_flight is None:
                    self._idle.notify_all()
                    if self._draining:
                        return
                    with RecordEvent("engine/idle"):
                        self._work.wait(timeout=0.05)
                if not self._running:
                    return
                with RecordEvent("engine/admit") as span:
                    pending = self._admit_locked()
                    span.set(admitted=len(pending),
                             queued=len(self._queue))
            for req, table in pending:
                # the wait a caller feels: from submit until the engine
                # takes the prompt up (behind the prefills admitted with
                # it, not only until admission)
                waited = time.monotonic() - req.t_enqueue
                metrics.count("gen.prefills")
                metrics.count("gen.queue_wait_us", int(waited * 1e6))
                try:
                    with RecordEvent("engine/prefill", req=req.req_id,
                                     prompt=int(req.prompt.size),
                                     waited_ms=round(waited * 1e3, 3)
                                     ) as span:
                        if self._compiled:
                            self._prefill_compiled(req, table, span)
                        else:
                            self._prefill(req, table, span)
                except Exception as e:  # noqa: BLE001 — this request only
                    metrics.count("gen.failed")
                    if table is not None:
                        self._pool.close_sequence(table)
                    req.future.set_exception(e)
            try:
                if any(self._slots) or self._in_flight is not None:
                    with RecordEvent("engine/step") as span:
                        if self._compiled:
                            self._step_compiled(span)
                        elif self._spec is not None:
                            self._step_spec(span)
                        else:
                            self._step(span)
            except Exception as e:  # noqa: BLE001 — fail loud, stay alive
                self._fail_all(e)

    @staticmethod
    def _upload(*arrays):
        """Host arrays to device tensors inside one `engine/upload` span
        that carries their bytes."""
        import paddle_tpu
        n = sum(a.nbytes for a in arrays)
        metrics.count("gen.h2d_bytes", n)
        with RecordEvent("engine/upload", bytes=n):
            return [paddle_tpu.to_tensor(a) for a in arrays]

    @classmethod
    def _upload_batch(cls, ids, mask, k, v):
        """`_upload` of a forward's ids, mask and dense caches `k`, `v`
        ``[L, ...]``: (ids, mask, per-layer attention caches)."""
        from ..nn import MultiHeadAttention
        ids_t, mask_t, *kv = cls._upload(
            ids, mask, *[c[li] for li in range(len(k)) for c in (k, v)])
        return ids_t, mask_t, [MultiHeadAttention.Cache(a, b)
                               for a, b in zip(kv[0::2], kv[1::2])]

    @staticmethod
    def _download(span, *tensors):
        """Device tensors to host arrays (waits for the device, then
        copies WHOLE tensors down, whatever slice the caller keeps);
        their bytes go on `span`."""
        out = [np.asarray(t.numpy()) for t in tensors]
        n = sum(a.nbytes for a in out)
        metrics.count("gen.d2h_bytes", n)
        span.set(bytes=n)
        return out

    def _admit_locked(self) -> List[Tuple[GenerationRequest,
                                          Optional[PageTable]]]:
        """Pick queued requests for the free slots (FIFO, expired
        dropped); paged mode additionally requires a worst-case page
        reservation and stops at the first request the pool cannot
        cover (strict FIFO — skipping ahead would starve big
        requests).  Called with the lock held, prefill happens outside
        it."""
        now = time.monotonic()
        keep = []
        for req in self._queue:
            if req.future.cancelled():
                pass  # caller gave up (e.g. /generate handler timeout)
            elif req.deadline <= now:
                metrics.count("gen.timeout")
                req.future.set_exception(DeadlineExceededError(
                    f"request expired after {now - req.t_enqueue:.2f}s "
                    "in queue"))
            elif self._pool is not None and self._pool.pages_for_request(
                    req.prompt.size, req.max_new) > self._pool.num_pages:
                # defensive queue-expiry: a request no pool state could
                # ever admit must not sit until its deadline (reachable
                # only if the pool shrank after submit)
                metrics.count("gen.rejected_pages")
                req.future.set_exception(ValueError(
                    "request can never fit in the KV page pool"))
            else:
                keep.append(req)
        self._queue = keep
        free = sum(s is None for s in self._slots)
        pending: List[Tuple[GenerationRequest, Optional[PageTable]]] = []
        blocked = False
        while self._queue and len(pending) < free:
            req = self._queue[0]
            table = None
            if self._pool is not None:
                worst = self._pool.pages_for_request(
                    req.prompt.size, req.max_new)
                if not self._pool.can_reserve(worst):
                    blocked = True
                    metrics.count("kv.admit_blocked")
                    break
                table = self._pool.reserve(worst)
            pending.append((self._queue.pop(0), table))
        metrics.gauge("kv.admission_blocked", int(blocked))
        metrics.gauge("gen.queue.depth", len(self._queue))
        return pending

    def _fail_all(self, err):
        with self._mu:
            if self._compiled:
                # a step that raised had already given its state arrays
                # away: every sequence is failed below — the rows of a step
                # still in flight with them, which is dropped unread — so
                # the pool starts again from zeroed arrays in place of the
                # dead ones
                self._in_flight = None
                self._pool.state.recover()
            for i, slot in enumerate(self._slots):
                if slot is not None:
                    if not slot.req.future.done():
                        slot.req.future.set_exception(err)
                    if slot.table is not None:
                        self._pool.close_sequence(slot.table)
                    self._slots[i] = None
            if self._spec is not None:
                self._spec.close_all()
            metrics.gauge("gen.active_slots", 0)
            self._idle.notify_all()

    # -- model plumbing -----------------------------------------------------
    def _prefill(self, req: GenerationRequest,
                 table: Optional[PageTable], span: RecordEvent):
        """Run the prompt through the model once: fills this sequence's
        KV (dense slot arrays, or pool pages through the prefix-sharing
        write path) and samples its first token, then installs it in a
        free slot (or retires it immediately on EOS/budget).

        With a radix prefix cache attached, a retained-prefix hit maps
        the hit pages into the page table (``adopt_prefix``) and runs
        prefill attention ONLY over the uncovered suffix — the hit
        tokens never touch the model (compute sharing, counted by
        ``kv.radix_hit_tokens``).  The hit is capped at ``p - 1`` so at
        least one suffix token always runs for next-token logits."""
        if req.future.cancelled():
            if table is not None:
                self._pool.close_sequence(table)
            return
        p = req.prompt.size
        m, hit_pids = 0, []
        if self._radix is not None and table is not None:
            m, hit_pids = self._radix.match(req.prompt, max_tokens=p - 1)
        if m:
            self._pool.adopt_prefix(table, hit_pids, m)
            self._radix.hits += 1
            self._radix.hit_tokens += m
            metrics.count("kv.radix_hits")
            metrics.count("kv.radix_hit_tokens", m)
            sp = p - m
            # cached columns and suffix rows both pad to pow2 buckets;
            # suffix pad capped so pad positions stay inside wpe
            mpad = _next_pow2(m, self._kv_floor)
            spp = min(_next_pow2(sp, self._kv_floor),
                      int(self.config.max_position) - m)
            span.set(bucket=spp, radix_hit=m)
            with self._mu:
                self._kv_buckets.add(("reuse_prefill", mpad, spp))
                metrics.gauge("gen.kv_buckets", len(self._kv_buckets))
            cfg = self.config
            heads, head_dim = self._kv_heads, self._kv_head_dim
            with RecordEvent("engine/build"):
                k_hit, v_hit = self._pool.gather(table)   # [L, H, m, Dh]
                k_c = np.zeros((self._kv_layers, 1, heads, mpad, head_dim),
                               np.float32)
                v_c = np.zeros_like(k_c)
                k_c[:, 0, :, :m] = k_hit
                v_c[:, 0, :, :m] = v_hit
                ids = np.full((1, spp), cfg.eos_id, np.int64)
                ids[0, :sp] = req.prompt[m:]
                # suffix row u sees every adopted column plus suffix
                # columns <= u (causal); pad cache columns stay -inf
                mask = np.full((1, 1, spp, mpad + spp), _NEG_INF,
                               np.float32)
                mask[0, 0, :, :m] = 0.0
                for u in range(spp):
                    mask[0, 0, u, mpad:mpad + u + 1] = 0.0
            ids_t, mask_t, caches = self._upload_batch(ids, mask, k_c, v_c)
            with RecordEvent("engine/forward"):
                logits, caches = self._model.forward(
                    ids_t, cache=caches,
                    pos_offset=np.asarray([m], np.int64),
                    attn_mask=mask_t)
            with RecordEvent("engine/fetch") as fetch:
                last = self._download(fetch, logits)[0][0, sp - 1]
            metrics.count("gen.prefill_tokens", sp)
        else:
            # pad the prompt to a pow2 length bucket so prefill compiles
            # at most log2(max_position) shapes (same bounded-shape
            # discipline as decode); causality makes the pad tokens
            # invisible to rows < p, and their K/V columns are sliced
            # away below
            pp = min(_next_pow2(p, self._kv_floor),
                     int(self.config.max_position))
            span.set(bucket=pp, radix_hit=0)
            with self._mu:
                self._kv_buckets.add(("prefill", pp))
                metrics.gauge("gen.kv_buckets", len(self._kv_buckets))
            with RecordEvent("engine/build"):
                ids = np.full((1, pp), self.config.eos_id, np.int64)
                ids[0, :p] = req.prompt
                caches = self._model.gen_cache(1)
                mask_t = self._model._mask(pp)
            ids_t, = self._upload(ids)
            with RecordEvent("engine/forward"):
                logits, caches = self._model.forward(
                    ids_t, cache=caches, pos_offset=np.zeros(1, np.int64),
                    attn_mask=mask_t)
            with RecordEvent("engine/fetch") as fetch:
                last = self._download(fetch, logits)[0][0, p - 1]
            metrics.count("gen.prefill_tokens", p)
        with RecordEvent("engine/sample"):
            nxt = self._sample(req, last)
        if nxt == self.config.eos_id or req.max_new <= 1:
            # never occupied a slot; adopted pages (if any) just drop
            # their refcount at close
            with RecordEvent("engine/finish"):
                if table is not None:
                    self._pool.close_sequence(table)
                slot = _Slot(req, None, list(req.prompt), nxt)
                slot.tokens.append(nxt)
                self._finish(slot)
            return
        with RecordEvent("engine/kv_install") as install:
            n_layers = len(caches)
            kv = self._download(install, *[c.k for c in caches],
                                *[c.v for c in caches])
            if table is not None:
                # KV column t is a pure function of tokens <= t, so the
                # pool may satisfy whole prompt-head pages from another
                # sequence's bitwise-identical prefill (COW prefix
                # sharing).  On a radix hit only the suffix columns
                # install (start=m); adopted pages are already in the
                # table.
                off = mpad if m else 0
                k_stack = np.stack([a[0, :, off:off + p - m]
                                    for a in kv[:n_layers]])
                v_stack = np.stack([a[0, :, off:off + p - m]
                                    for a in kv[n_layers:]])
                self._pool.open_sequence(req.prompt, k_stack, v_stack,
                                         table=table, start=m)
                slot = _Slot(req, None, list(req.prompt), nxt,
                             table=table)
            else:
                slot = _Slot(req, [(k[0, :, :p], v[0, :, :p]) for k, v in
                                   zip(kv[:n_layers], kv[n_layers:])],
                             list(req.prompt), nxt)
        with self._mu:
            idx = self._slots.index(None)
            self._slots[idx] = slot
            metrics.gauge("gen.active_slots",
                          sum(s is not None for s in self._slots))
        if self._spec is not None:
            # seed the draft's dense KV for this slot (the decode-loop
            # thread owns both engines, so this cannot race a step)
            self._spec.open(idx, slot.tokens)

    def _step(self, span: RecordEvent):
        """One decode step over every active slot (ONE device batch).
        Paged and fixed slots feed the SAME batched dense cache — the
        pool's gather-by-page-table view never changes compiled
        shapes."""
        with self._mu:
            # a cancelled future means the caller stopped waiting — free
            # the slot (and its pages) instead of decoding tokens nobody
            # will read
            for i, s in enumerate(self._slots):
                if s is not None and s.req.future.cancelled():
                    metrics.count("gen.cancelled")
                    if s.table is not None:
                        self._pool.close_sequence(s.table)
                    self._slots[i] = None
            active = [(i, s) for i, s in enumerate(self._slots)
                      if s is not None]
        if not active:
            return
        S = self.max_slots
        cfg = self.config
        heads, head_dim = self._kv_heads, self._kv_head_dim
        n_layers = self._kv_layers
        lpad = _next_pow2(max(s.kv_len for _, s in active), self._kv_floor)
        span.set(active=len(active), lpad=lpad)
        with self._mu:
            self._kv_buckets.add(("decode", lpad))
            metrics.gauge("gen.kv_buckets", len(self._kv_buckets))

        with RecordEvent("engine/gather") as gather:
            ids = np.full((S, 1), cfg.eos_id, np.int64)
            pos = np.zeros(S, np.int64)
            # additive mask over [cache columns 0..lpad-1, new-token
            # column]: valid history + self are 0, pad columns and idle
            # rows -inf
            mask = np.full((S, 1, 1, lpad + 1), _NEG_INF, np.float32)
            mask[:, :, :, lpad] = 0.0
            k_b = np.zeros((n_layers, S, heads, lpad, head_dim),
                           np.float32)
            v_b = np.zeros_like(k_b)
            gathered = 0
            for i, s in active:
                ln = s.kv_len
                ids[i, 0] = s.next_id
                pos[i] = ln
                mask[i, :, :, :ln] = 0.0
                if s.table is not None:
                    k_all, v_all = self._pool.gather(s.table)
                    k_b[:, i, :, :ln] = k_all
                    v_b[:, i, :, :ln] = v_all
                    gathered += k_all.nbytes + v_all.nbytes
                else:
                    for li, (k, v) in enumerate(s.kv):
                        k_b[li, i, :, :ln] = k
                        v_b[li, i, :, :ln] = v
                        gathered += k.nbytes + v.nbytes
            gather.set(bytes=gathered)
        ids_t, mask_t, caches = self._upload_batch(ids, mask, k_b, v_b)
        with RecordEvent("engine/forward"):
            logits, new_caches = self._model.forward(
                ids_t, cache=caches, pos_offset=pos, attn_mask=mask_t)
        with RecordEvent("engine/fetch") as fetch:
            step_logits, *kv = self._download(
                fetch, logits, *[t for c in new_caches
                                 for t in (c.k, c.v)])
            step_logits = step_logits[:, 0]
            # the new K/V column for every slot sits at index lpad
            new_cols = [(kv[2 * li][:, :, lpad], kv[2 * li + 1][:, :, lpad])
                        for li in range(n_layers)]
        metrics.count("gen.steps")
        metrics.count("gen.tokens", len(active))
        metrics.observe("gen.step_occupancy", len(active))

        retired = []
        appended = 0
        with RecordEvent("engine/kv_append") as append:
            for i, s in active:
                if s.table is not None:
                    # write-through the page table: a fresh page at the
                    # boundary, a COW copy when the target page is shared
                    k_col = np.stack([new_cols[li][0][i]
                                      for li in range(n_layers)])
                    v_col = np.stack([new_cols[li][1][i]
                                      for li in range(n_layers)])
                    self._pool.append_column(s.table, k_col, v_col)
                    appended += k_col.nbytes + v_col.nbytes
                else:
                    for li, (k, v) in enumerate(s.kv):
                        s.kv[li] = (
                            np.concatenate(
                                [k, new_cols[li][0][i][:, None]], 1),
                            np.concatenate(
                                [v, new_cols[li][1][i][:, None]], 1))
                        appended += s.kv[li][0].nbytes + s.kv[li][1].nbytes
            append.set(bytes=appended)
        with RecordEvent("engine/sample"):
            for i, s in active:
                s.tokens.append(s.next_id)
                nxt = self._sample(s.req, step_logits[i])
                s.next_id = nxt
                s.n_new += 1
                if nxt == self.config.eos_id or s.n_new >= s.req.max_new:
                    s.tokens.append(nxt)
                    retired.append(i)
        with RecordEvent("engine/finish"), self._mu:
            for i in retired:
                slot, self._slots[i] = self._slots[i], None
                self._finish(slot)
            metrics.gauge("gen.active_slots",
                          sum(s is not None for s in self._slots))

    # -- the compiled route (models that state the step contract) -----------
    def _prefill_compiled(self, req: GenerationRequest, table: PageTable,
                          span: RecordEvent):
        """`_prefill` on the compiled route: ONE compiled program per
        prompt bucket.  The prompt is padded to its bucket
        and its length goes in with it, so the pads never enter the
        recurrence; out come the one logits row sampling needs and its
        argmax (a greedy request fetches the 4 bytes of the id and leaves
        the logits on the device), the attention layers' KV by cache
        group and the sequence's state after its last prompt token, all
        of which go from the result into the sequence's slot on the
        device (the table books the prompt's pages; no byte of them
        crosses the host link).  A decode step in flight is read here,
        between this program's dispatch and the fetch of its id: the
        prompt runs behind that step while the host books it (`_retire`);
        after a prefill nothing is in flight."""
        if req.future.cancelled():
            self._pool.close_sequence(table)
            return
        p = req.prompt.size
        slot_id = table.state_slot
        pp = min(_next_pow2(p, self._kv_floor),
                 int(self.config.max_position))
        span.set(bucket=pp, radix_hit=0, state_slot=slot_id)
        with self._mu:
            self._kv_buckets.add(("prefill", pp))
            metrics.gauge("gen.kv_buckets", len(self._kv_buckets))
        with RecordEvent("engine/build"):
            ids = np.zeros((1, pp), np.int32)
            ids[0, :p] = req.prompt
        ids_t, len_t, last_t = self._upload(
            ids, np.asarray([p], np.int32), np.asarray([p - 1], np.int32))
        with RecordEvent("engine/forward", bucket=pp, rows=1):
            logits, next_id, *made = self._steps.prefill(
                ids_t, len_t, last_t)
        ahead, self._in_flight = self._in_flight, None
        if ahead is not None:
            try:
                self._retire(ahead)
            except Exception as e:  # noqa: BLE001 — the step's rows, not
                self._fail_all(e)   # this request: its program is its own
        samples = req.strategy == "sampling"
        counted = bool(self._steps.counters)
        with RecordEvent("engine/fetch") as fetch:      # 4 bytes if greedy
            # the id (what the step counted rides behind it), then the
            # logits row for a request that samples
            got = self._download(
                fetch, *([next_id] if counted or not samples else []),
                *([logits] if samples else []))
        if counted:
            span.set(**self._count_step(got[0][1:]))
        metrics.count("gen.prefill_tokens", p)
        metrics.count("gen.prefill_pad_tokens", pp - p)
        metrics.count("gen.logits_rows_fetched" if samples
                      else "gen.sampled_on_device")
        with RecordEvent("engine/sample"):
            nxt = self._sample(req, got[-1][0]) if samples \
                else int(got[0][0])
        if nxt == self.config.eos_id or req.max_new <= 1:
            with RecordEvent("engine/finish"):
                self._pool.close_sequence(table)
                slot = _Slot(req, None, list(req.prompt), nxt)
                slot.tokens.append(nxt)
                self._finish(slot)
            return
        pool_state = self._pool.state
        # the prompt's KV stays where the program left it: a window
        # group's ring and the other groups' columns go from the result
        # into the slot's rows of the device arrays below; the table books
        # the pages
        with RecordEvent("engine/kv_install", bytes=0):
            self._pool.account_prompt(table, p)
        # the prompt's writes at a multiple of a window's columns
        wraps = sum((p - 1) // a["window"]
                    for a in pool_state.device_kv[::2] if a["window"])
        metrics.count("kv.ring_wraps", wraps)
        with RecordEvent("engine/state_install", slot=slot_id,
                         bytes=pool_state.slot_bytes):
            pool_state.install(slot_id, **{
                n: t._value for n, t in zip(pool_state.names, made)})
        slot = _Slot(req, None, list(req.prompt), nxt, table=table)
        with self._mu:
            # the engine's row IS the state slot: the decode step runs
            # over the state arrays whole, row i of the batch on row i
            self._slots[slot_id] = slot
            metrics.gauge("gen.active_slots",
                          sum(s is not None for s in self._slots))

    def _step_compiled(self, span: RecordEvent):
        """`_step` on the compiled route: ONE compiled program per
        KV-length bucket over all `max_slots` rows.  In go the rows'
        pending tokens, their cache lengths, which rows are active, and
        the KV and state arrays as they sit on the device (nothing is
        gathered or uploaded); out come a logits row a slot and its
        argmax, and the same arrays, each row's new KV column written at
        its own position and its state updated (an idle row's state comes
        back as it went in).  The step is GIVEN the arrays: they are
        donated through the compiled program and dead when it returns, so
        `rebind` follows the call at once (a step that raises: `_fail_all`
        -> `StateSlots.recover`).  `lpad`, the bucket: with a ring in the
        description the arrays' length — ONE decode program that reads,
        block by block, what its rows hold; without, the power of two over
        the longest live row, the static bound the program reads the
        arrays to (`StepPrograms.decode(columns=)`).

        ONE LAUNCH IS KEPT IN FLIGHT: a step is dispatched and left
        unread (`_in_flight`), and read (`_retire`) right after the next
        compiled program — the next step, here, or an admission's prefill
        — has been dispatched behind it, while the device runs.  The next
        step needs nothing the host has not got: its ids are the step in
        flight's `next_ids`, on the device (greedy rows: the device's own
        picks), a row's length is its last one + 1, and a row whose
        budget ends with the step in flight goes in idle.  A row that
        ends on EOS is found out a step late: the step ahead computed one
        row too many (`serving.gen.rows_past_end`), which `_retire`
        skips.  A step with a row that samples is read at once, as ever:
        that row's next id is made on the host from the logits."""
        ahead = self._in_flight
        with self._mu:
            for i, s in enumerate(self._slots):
                if s is not None and s.req.future.cancelled():
                    metrics.count("gen.cancelled")
                    self._pool.close_sequence(s.table)
                    self._slots[i] = None
            # with a step in flight every live row is one of its rows (an
            # admission's prefill reads it), a token ahead of `n_new`
            active = [(i, s) for i, s in enumerate(self._slots)
                      if s is not None and s.n_new + (ahead is not None)
                      < s.req.max_new]
        if self._step_counts:   # of a step read since the last step span
            span.set(**self._step_counts)
            self._step_counts = None
        if not active:          # every row ends with the step in flight
            self._in_flight = None
            if ahead is not None:
                self._retire(ahead, span)
            return
        S = self.max_slots
        state = self._pool.state
        with RecordEvent("engine/build"):   # nothing to gather: the KV is
            lengths = np.zeros(S, np.int32)     # on the device
            alive = np.zeros(S, np.int32)
            for i, s in active:
                # `table.length` lags by the append not yet made
                lengths[i] = s.kv_len if ahead is None \
                    else ahead.lengths[i] + 1
                alive[i] = 1
            feeds = [lengths, alive]
            if ahead is None:   # in the form a step returns its ids
                ids = np.zeros(S + len(self._steps.counters), np.int32)
                for i, s in active:
                    ids[i] = s.next_id
                feeds.insert(0, ids)
        device_kv = state.device_kv
        live = lengths[alive > 0]
        window = min((a["window"] for a in device_kv if a["window"]),
                     default=0)
        if window:
            columns, lpad = None, max(a["shape"][1] for a in device_kv)
            past = int((live >= window).sum())
            wraps = sum(int(((live > 0) & (live % a["window"] == 0)).sum())
                        for a in device_kv[::2] if a["window"])
            metrics.count("kv.ring_wraps", wraps)
            metrics.gauge("kv.rows_past_window", past)
            span.set(ring_rows=past)
        else:
            columns = lpad = _next_pow2(int(lengths.max()), self._kv_floor)
        span.set(active=len(active), lpad=lpad, context=int(lengths.sum()),
                 ahead=int(ahead is not None), kv_columns=int(sum(
                     a["layers"] * np.minimum(live + 1, a["shape"][1]).sum()
                     for a in device_kv[::2])))
        with self._mu:
            self._kv_buckets.add(("decode", lpad))
            metrics.gauge("gen.kv_buckets", len(self._kv_buckets))
        uploaded = self._upload(*feeds)
        if ahead is not None:
            uploaded.insert(0, ahead.next_ids)
        with RecordEvent("engine/forward", bucket=lpad, rows=len(active)):
            # the KV and state arrays: donated, rebound
            logits, next_ids, *new_state = self._steps.decode(
                *uploaded, *state.arrays.values(), columns=columns)
            state.rebind(**{n: t._value
                            for n, t in zip(state.names, new_state)})
        samples = any(s.req.strategy == "sampling" for _, s in active)
        launch = _Launch(active, lengths, logits if samples else None,
                         next_ids)
        self._in_flight = None if samples else launch
        metrics.count("gen.steps")
        metrics.count("gen.steps_ahead", int(ahead is not None))
        metrics.observe("gen.step_occupancy", len(active))
        if ahead is not None:
            self._retire(ahead, span)
        if samples:
            self._retire(launch)

    def _retire(self, launch: _Launch, step_span: RecordEvent = None):
        """Read a decode step's results and book them: the ids (the
        logits too, whole, where a row of it samples: a download costs the
        link a round trip, hardly its bytes), a column to each row's page
        table (the step wrote it on the device), the tokens to their
        sequences; finished rows resolve their futures and free their
        slots.  A row whose slot has meanwhile
        finished, was cancelled or belongs to another request is skipped:
        the step computed it past its sequence's end, its column is booked
        to no table, its id goes to no sequence, and its state and column
        lie in a slot the next prefill overwrites.  A launch with
        no row left is dropped unread.  What the step counted becomes
        fields of the first `engine/step` span to open after the step's
        own — `step_span` where that is the one it is read under, else the
        next to open (a prefill's span carries the prefill's own counts):
        every step span has them, one step behind its own launch."""
        pairs = [(i, s) for i, s in launch.pairs if self._slots[i] is s]
        metrics.count("gen.rows_past_end", len(launch.pairs) - len(pairs))
        if not pairs:
            return
        S = self.max_slots
        with RecordEvent("engine/fetch") as fetch:
            picked, *step_logits = self._download(
                fetch, launch.next_ids,
                *([launch.logits] if launch.logits is not None else []))
        if self._steps.counters:
            self._step_counts = self._count_step(picked[S:])
            if step_span is not None:
                step_span.set(**self._step_counts)
                self._step_counts = None
        greedy = sum(s.req.strategy != "sampling" for _, s in pairs)
        metrics.count("gen.tokens", len(pairs))
        metrics.count("gen.sampled_on_device", greedy)
        metrics.count("gen.logits_rows_fetched", S if step_logits else 0)
        retired = []
        with RecordEvent("engine/kv_append", bytes=0):
            for i, s in pairs:
                self._pool.account_column(s.table)
        with RecordEvent("engine/sample"):
            for i, s in pairs:
                s.tokens.append(s.next_id)
                nxt = self._sample(s.req, step_logits[0][i]) \
                    if s.req.strategy == "sampling" else int(picked[i])
                s.next_id = nxt
                s.n_new += 1
                if nxt == self.config.eos_id or s.n_new >= s.req.max_new:
                    s.tokens.append(nxt)
                    retired.append(i)
        with RecordEvent("engine/finish"), self._mu:
            for i in retired:
                slot, self._slots[i] = self._slots[i], None
                self._finish(slot)
            metrics.gauge("gen.active_slots",
                          sum(s is not None for s in self._slots))

    def _count_step(self, counts) -> dict:
        """What a compiled step counted on the device (the model's
        `step_counters`, downloaded behind the ids) under the model's
        names — fields for a span: a prefill's own, the next `engine/step`
        span's for a decode step (`_retire`) — and, counted here, whatever
        `serving.*` counters and gauges the model makes of them
        (`step_metrics`: the engine knows no model's names)."""
        got = dict(zip(self._steps.counters, (int(c) for c in counts)))
        counted, gauged = self._model.step_metrics(got)
        for name, n in counted.items():
            metrics.count(name, n)
        for name, value in gauged.items():
            metrics.gauge(name, value)
        return got

    def _step_spec(self, span: RecordEvent):
        """One SPECULATIVE decode step over every active slot: the
        draft proposes up to k tokens per greedy row, the target
        verifies pending + proposals in ONE batched forward (query
        width W instead of 1 — nearly free in the memory-bound decode
        regime), accepted chains commit, and the first rejection rolls
        the page-table tail back with ``pool.truncate``.  Emission
        replays the plain-greedy retire loop over the verified chain
        token by token, so output is token-equal to ``_step`` whatever
        the draft proposed.  Sampling rows ride along at width 1 (the
        plain path inside the spec batch).  Spans as in ``_step``; the
        commit loop (append every fed column, emit, roll back) is one
        ``engine/kv_append``."""
        with self._mu:
            for i, s in enumerate(self._slots):
                if s is not None and s.req.future.cancelled():
                    metrics.count("gen.cancelled")
                    if s.table is not None:
                        self._pool.close_sequence(s.table)
                    self._spec.close(i)
                    self._slots[i] = None
            active = [(i, s) for i, s in enumerate(self._slots)
                      if s is not None]
        if not active:
            return
        S = self.max_slots
        cfg = self.config
        heads, head_dim = self._kv_heads, self._kv_head_dim
        n_layers = self._kv_layers
        max_ln = max(s.kv_len for _, s in active)
        # batch query width: pending token + up to k proposals, shrunk
        # only when a row's pad-query positions would leave the wpe
        # table (every row's positions run ln .. ln+W-1)
        W = max(1, min(1 + self._spec.k, int(cfg.max_position) - max_ln))
        # per-row fed tokens: [pending x0, d1..d_{w-1}] — proposals only
        # for greedy rows with emission budget left
        fed = {}
        for i, s in active:
            w = max(1, min(W, s.req.max_new - s.n_new,
                           self.max_context - s.kv_len))
            row = [s.next_id]
            if w > 1 and s.req.strategy == "greedy_search":
                row += self._spec.propose(i, s.tokens, s.next_id,
                                          n=w - 1)
            fed[i] = row
        lpad = _next_pow2(max_ln, self._kv_floor)
        span.set(active=len(active), lpad=lpad)
        with self._mu:
            self._kv_buckets.add(("spec", lpad, W))
            metrics.gauge("gen.kv_buckets", len(self._kv_buckets))
        with RecordEvent("engine/gather") as gather:
            ids = np.full((S, W), cfg.eos_id, np.int64)
            pos = np.zeros(S, np.int64)
            # additive mask over [cache cols 0..lpad-1, W new cols]: every
            # query sees its row's valid history, new cols are causal
            # among themselves (query u sees new cols <= u), pads stay
            # -inf
            mask = np.full((S, 1, W, lpad + W), _NEG_INF, np.float32)
            for u in range(W):
                mask[:, :, u, lpad:lpad + u + 1] = 0.0
            k_b = np.zeros((n_layers, S, heads, lpad, head_dim),
                           np.float32)
            v_b = np.zeros_like(k_b)
            gathered = 0
            for i, s in active:
                ln = s.kv_len
                row = fed[i]
                ids[i, :len(row)] = row
                pos[i] = ln
                mask[i, :, :, :ln] = 0.0
                k_all, v_all = self._pool.gather(s.table)
                k_b[:, i, :, :ln] = k_all
                v_b[:, i, :, :ln] = v_all
                gathered += k_all.nbytes + v_all.nbytes
            gather.set(bytes=gathered)
        ids_t, mask_t, caches = self._upload_batch(ids, mask, k_b, v_b)
        with RecordEvent("engine/forward"):
            logits, new_caches = self._model.forward(
                ids_t, cache=caches, pos_offset=pos, attn_mask=mask_t)
        with RecordEvent("engine/fetch") as fetch:
            step_logits, *kv = self._download(      # logits [S, W, V]
                fetch, logits, *[t for c in new_caches
                                 for t in (c.k, c.v)])
            Ks, Vs = kv[0::2], kv[1::2]
        metrics.count("gen.steps")
        metrics.count("spec.steps")
        metrics.observe("gen.step_occupancy", len(active))

        retired = []
        with RecordEvent("engine/kv_append") as append:
            append.set(bytes=self._commit_spec(
                active, fed, step_logits, Ks, Vs, lpad, retired))
        with RecordEvent("engine/finish"), self._mu:
            for i in retired:
                slot, self._slots[i] = self._slots[i], None
                self._finish(slot)
            metrics.gauge("gen.active_slots",
                          sum(s is not None for s in self._slots))

    def _commit_spec(self, active, fed, step_logits, Ks, Vs, lpad,
                     retired) -> int:
        """The speculative step's commit loop over the verified chains;
        appends retired slot indices to `retired` and returns the bytes
        written through the page tables."""
        n_layers = len(Ks)
        appended = 0
        for i, s in active:
            row = fed[i]
            w = len(row)
            base = s.kv_len
            # the batched verify produced a KV column for every fed
            # token — write them all through the page table, then roll
            # the rejected tail back below
            for t in range(w):
                k_col = np.stack([Ks[li][i, :, lpad + t]
                                  for li in range(n_layers)])
                v_col = np.stack([Vs[li][i, :, lpad + t]
                                  for li in range(n_layers)])
                self._pool.append_column(s.table, k_col, v_col)
                appended += k_col.nbytes + v_col.nbytes
            # emission: the plain-greedy loop replayed over the chain —
            # commit fed[t], derive the next token from the target's
            # own logits at t, continue only while the next draft
            # matches it exactly
            committed, t, done = 0, 0, False
            while True:
                s.tokens.append(row[t])
                nxt = self._sample(s.req, step_logits[i, t])
                s.next_id = nxt
                s.n_new += 1
                committed = t + 1
                if nxt == self.config.eos_id \
                        or s.n_new >= s.req.max_new:
                    s.tokens.append(nxt)
                    done = True
                    break
                if t + 1 < w and row[t + 1] == nxt:
                    t += 1
                    continue
                break
            if committed < w:
                self._pool.truncate(s.table, base + committed)
                metrics.count("spec.rollback_cols", w - committed)
            metrics.observe("spec.accepted_per_step", committed)
            metrics.count("spec.proposed", w - 1)
            metrics.count("spec.accepted", committed - 1)
            metrics.count("gen.tokens", committed)
            if done:
                self._spec.close(i)
                retired.append(i)
            else:
                # mirror the outcome into the draft's dense KV (its
                # truncate-to-committed rollback)
                self._spec.commit(i, s.tokens, s.next_id)
        return appended

    def _sample(self, req: GenerationRequest, logits: np.ndarray) -> int:
        if req.strategy == "sampling":
            logits = logits / max(req.temperature, 1e-6)
            if req.top_k:
                kth = np.sort(logits)[-req.top_k]
                logits = np.where(logits < kth, _NEG_INF, logits)
            p = np.exp(logits - logits.max())
            p /= p.sum()
            return int(req.rng.choice(p.shape[0], p=p))
        return int(np.argmax(logits))

    def _finish(self, slot: _Slot):
        """Resolve a finished sequence and retire its pages the moment
        it completes — freed pages are the admission currency.  With a
        radix cache attached, the committed full-page prefix is
        retained FIRST (pins ride on the still-live refcounts), then
        the table closes normally."""
        if slot.table is not None:
            if self._radix is not None and slot.table.pages:
                self._radix.insert(np.asarray(slot.tokens, np.int64),
                                   slot.table)
            self._pool.close_sequence(slot.table)
            slot.table = None
        metrics.count("gen.completed")
        metrics.observe("gen.seq_len", len(slot.tokens))
        metrics.latency_ms(time.monotonic() - slot.req.t_enqueue)
        if not slot.req.future.done():
            slot.req.future.set_result(np.asarray(slot.tokens, np.int64))

    @property
    def active_slots(self) -> int:
        with self._mu:
            return sum(s is not None for s in self._slots)

    @property
    def queue_depth(self) -> int:
        with self._mu:
            return len(self._queue)
