"""Persistent XLA compilation cache + process-level trace accounting.

Two related jobs, one subsystem:

1. **On-disk compilation cache** — `initialize()` enables JAX's
   persistent compilation cache, so a process restart *loads* the
   serialized XLA executable instead of re-running HLO passes (a cold
   BERT-base compile is minutes of a run).  The directory is placed from
   outside with JAX's own ``JAX_COMPILATION_CACHE_DIR``; when that is
   unset it is the fixed ``<repo>/.jax_cache`` (the path is part of the
   cache key, so a directory that moves never hits).

2. **Trace/hit/miss counters** — every in-process step-cache consult in
   `static/executor.py` / `distributed/compiled_program.py` records here
   (through `core/monitor.py`'s StatRegistry), so tests and the benchmark
   can assert hard properties like "zero new traces after warmup" and
   `Executor.cache_stats()` has one source of truth.

Counter semantics:
  * ``trace``  — a whole-block (re)trace: `jax.jit` is about to run the
    Python step function.  The thing shape-bucketing exists to minimize.
  * ``hit``    — a step served by an already-jitted callable; ``bucket_hit``
    additionally marks hits that required padding feeds up to a bucket.
  * ``miss``   — a step-cache lookup that found nothing (every miss is
    followed by exactly one trace).
"""
from __future__ import annotations

import os
from typing import Dict, Optional

from .monitor import stat_add, stat_reset, stats_with_prefix

__all__ = ["initialize", "cache_dir", "record_trace", "record_hit",
           "record_miss", "cache_stats", "reset_stats",
           "persistent_entries", "next_pow2", "REPO_CACHE_DIR"]


def next_pow2(n: int, floor: int = 16) -> int:
    """Smallest power-of-two bucket >= ``n`` (>= ``floor``) — the shape
    policy that keeps compiled-executable counts logarithmic; shared by
    the serving engine's KV padding and the planner's workspace
    sizing so the two can never disagree about bucket geometry."""
    b = int(floor)
    n = int(n)
    while b < n:
        b <<= 1
    return b


# the checkout's own cache: <repo>/.jax_cache (git-ignored), derived from
# this file's location — never HOME, a temp name, a pid or a time
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# monitor counter names (STAT_ADD-style registry keys)
STAT_TRACES = "compile_cache_traces"
STAT_HITS = "compile_cache_hits"
STAT_MISSES = "compile_cache_misses"
STAT_BUCKET_HITS = "compile_cache_bucket_hits"

_state = {"initialized": False, "dir": None, "floor": None}


def initialize(cache_dir: Optional[str] = None, *,
               min_compile_time_s: Optional[float] = None,
               force: bool = False) -> str:
    """Idempotently enable JAX's persistent on-disk compilation cache and
    return the active directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX already holds the
    directory: nothing is set in code, the value is read back.  Unset,
    the directory is ``REPO_CACHE_DIR``.  An explicit ``cache_dir`` with
    ``force=True`` re-points an already-initialized process (tests aim
    at a tmpdir this way); ``min_compile_time_s`` lowers JAX's
    persist-only-slow-compiles floor for them, and a later call without
    it restores the floor JAX started with.  A directory that cannot be
    created raises.
    """
    if _state["initialized"] and not force:
        return _state["dir"]
    from ..profiler import Phase
    with Phase("compile_cache/initialize") as phase:
        _initialize(cache_dir, min_compile_time_s)
        phase.set(entries=persistent_entries())
    return _state["dir"]


def _initialize(cache_dir, min_compile_time_s):
    import jax
    if _state["floor"] is None:
        _state["floor"] = \
            jax.config.jax_persistent_cache_min_compile_time_secs
    if cache_dir is None:
        # JAX read its own variable at import: the config already equals
        # it and the update below is skipped
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
            REPO_CACHE_DIR
    else:
        cache_dir = os.path.abspath(cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != cache_dir:
        # JAX materializes its cache backend on first use and never
        # re-reads the config — re-pointing an initialized process must
        # drop that object so the new dir takes effect
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    # JAX leaves op metadata out of the cache key by default, so a cache
    # could hand back an executable compiled before an op carried its
    # `named_scope` (BlockTracer.run_op): the device trace would then name
    # nothing.  With it in the key a program is served only what was
    # compiled from the same scopes and source lines.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        _state["floor"] if min_compile_time_s is None
        else min_compile_time_s)
    _state["initialized"] = True
    _state["dir"] = cache_dir


def cache_dir() -> Optional[str]:
    return _state["dir"]


def persistent_entries() -> int:
    """Number of serialized executables currently in the on-disk cache."""
    d = _state["dir"]
    if not d or not os.path.isdir(d):
        return 0
    return sum(1 for f in os.listdir(d) if f.endswith("-cache"))


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------
def record_trace():
    stat_add(STAT_TRACES)


def record_hit(bucketed: bool = False):
    stat_add(STAT_HITS)
    if bucketed:
        stat_add(STAT_BUCKET_HITS)


def record_miss():
    stat_add(STAT_MISSES)


def cache_stats() -> Dict[str, int]:
    """Process-level snapshot: traces / hits / misses / bucket_hits plus
    the persistent-cache location and entry count."""
    snap = stats_with_prefix("compile_cache_")
    return {
        "traces": snap.get(STAT_TRACES, 0),
        "hits": snap.get(STAT_HITS, 0),
        "misses": snap.get(STAT_MISSES, 0),
        "bucket_hits": snap.get(STAT_BUCKET_HITS, 0),
        "persistent_dir": _state["dir"],
        "persistent_entries": persistent_entries(),
    }


def reset_stats():
    for name in (STAT_TRACES, STAT_HITS, STAT_MISSES, STAT_BUCKET_HITS):
        stat_reset(name)
