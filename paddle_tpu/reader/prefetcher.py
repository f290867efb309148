"""Async double-buffered feed prefetch — overlap host→device transfer of
batch N+1 with device compute of batch N.

The executor hot loop used to block on a synchronous `jnp.asarray` /
`device_put` of every feed before dispatching the step.  `Prefetcher`
moves that placement onto a worker thread behind a small bounded queue
(depth 2 = classic double buffering): while the device chews on step N,
the host is already casting + shipping step N+1's arrays.  That hides
the transfer behind the step; on CPU it still hides the int-cast +
layout copy.

Contracts (tests/test_compile_cache.py):
  * order-preserving — one worker thread, FIFO queue;
  * exception-propagating — a worker error re-raises at the consumer's
    `next()` call *after* all batches that preceded it;
  * bounded — at most `depth` placed batches exist ahead of the consumer,
    so device memory for staged feeds is capped;
  * closeable — `close()` (or exhausting the iterator, or `with` exit)
    stops the worker without deadlocking on a full queue.
"""
from __future__ import annotations

import queue as _queue
import threading
from typing import Any, Callable, Iterable, Iterator, Optional
from ..profiler import RecordEvent

__all__ = ["Prefetcher", "place_feed"]

_END = object()


def _x64_enabled() -> bool:
    import jax
    return bool(jax.config.jax_enable_x64)


def _canonical_array(arr, x64: bool):
    """Cast 64-bit host arrays down BEFORE device_put on x64-disabled
    backends — jnp would truncate anyway, but with a per-call UserWarning
    and an extra on-device cast."""
    import numpy as np
    from ..core.dtype import canonical_np_dtype
    a = np.asarray(arr)
    tgt = canonical_np_dtype(a.dtype, x64)
    return a if tgt == a.dtype else a.astype(tgt)


def place_feed(feed: Any, device=None, sharding=None):
    """Ship one batch to the device: dict values / list items / bare
    arrays each get the x64-aware cast + `jax.device_put`.  Values that
    are already `jax.Array`s pass through untouched (idempotent, so a
    pre-staged feed can ride the same code path)."""
    import jax

    target = sharding if sharding is not None else device

    def _one(v):
        if isinstance(v, jax.Array):
            return v if target is None else jax.device_put(v, target)
        v = _canonical_array(v, _x64_enabled())
        return jax.device_put(v, target)

    if isinstance(feed, dict):
        return {k: _one(v) for k, v in feed.items()}
    if isinstance(feed, (list, tuple)):
        return type(feed)(_one(v) for v in feed)
    return _one(feed)


class Prefetcher:
    """Iterate `source`, applying `place_fn` on a background thread,
    `depth` batches ahead of the consumer.

        for feed in Prefetcher(batches, depth=2):
            exe.run(main, feed=feed, fetch_list=[])

    `place_fn` defaults to :func:`place_feed` (device placement with the
    x64-aware integer cast); pass `device=`/`sharding=` to aim it, or a
    custom callable (e.g. ``CompiledProgram.place_feed`` for the
    dp-sharded path).  ``place_fn=None`` with ``place=False`` turns the
    Prefetcher into a plain read-ahead buffer.
    """

    def __init__(self, source: Iterable, depth: int = 2,
                 place_fn: Optional[Callable[[Any], Any]] = None,
                 device=None, sharding=None, place: bool = True):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        if place_fn is None and place:
            place_fn = lambda b: place_feed(b, device=device,  # noqa: E731
                                            sharding=sharding)
        self._place_fn = place_fn or (lambda b: b)
        self._source = iter(source)
        self.position = 0  # batches HANDED TO the consumer (checkpointable
        # resume cursor: staged-but-unconsumed batches are not counted, so
        # a restart re-reads them instead of skipping them)
        self._q: "_queue.Queue" = _queue.Queue(maxsize=depth)
        self._err: Optional[BaseException] = None
        self._closed = threading.Event()
        self._done = False
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="paddle-tpu-prefetch")
        self._thread.start()

    # -- worker --------------------------------------------------------------
    def _worker(self):
        try:
            while True:
                # the source builds a batch when it is asked for the next
                with RecordEvent("prefetcher/build"):
                    item = next(self._source, _END)
                if item is _END:
                    break
                # closed-check BEFORE placing: a close() racing a blocked
                # put must not pull + device_put yet another source batch
                if self._closed.is_set():
                    return
                with RecordEvent("prefetcher/place"):
                    staged = self._place_fn(item)
                if not self._put(staged):
                    return  # closed mid-stream; drop silently
        except BaseException as e:  # noqa: BLE001 - re-raised at consumer
            self._err = e
        finally:
            self._put(_END)

    def _put(self, item) -> bool:
        # bounded put that never deadlocks against close(): poll the
        # closed flag instead of blocking forever on a full queue
        while not self._closed.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except _queue.Full:
                continue
        return False

    # -- consumer ------------------------------------------------------------
    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        with RecordEvent("prefetcher/wait"):
            item = self._q.get()
        if item is _END:
            self._done = True
            if self._err is not None:
                err, self._err = self._err, None
                raise err
            raise StopIteration
        self.position += 1
        return item

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def close(self):
        """Stop the worker and release staged batches.  Idempotent."""
        self._closed.set()

        def drain():
            while True:
                try:
                    self._q.get_nowait()
                except _queue.Empty:
                    break

        drain()  # unblock a worker stuck on put()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
        drain()  # an in-flight put may have slipped into the freed slot
