"""The engine's step contract, compiled: one program per (phase, bucket).

A model that serves through this route has two cache-aware methods of
flat tensor arguments and a tuple result:

    prefill_step(ids [1, T], lengths [1], last [1])
        -> (logits [1, V], K, V [La, 1, Hkv, T, D], *state [Ls, 1, ...])
    decode_step(ids [S, 1], cache_lengths [S], active [S],
                k_cache, v_cache [La, S, Hkv, L, D], *state [Ls, S, ...])
        -> (logits [S, V], K, V columns [La, S, Hkv, 1, D], *state)

ids, per-row lengths and the caches go in; the ONE logits row a sequence's
sampling needs, the new KV columns and the updated state come out.  Each
is wrapped in `paddle_tpu.jit.to_static`'s `StaticFunction`: traced once
per argument signature — the engine's power-of-two buckets — into a
Program (from shapes alone, `abstract_trace`: nothing runs eagerly) and
run as one jitted XLA computation, under `eval()` and `no_grad`, so no
tape is kept.

WHAT `StepPrograms` RETURNS, AND THE FORM IT TAKES THE IDS IN.  Picking a
token is the engine's business, and the greedy pick is made here, inside
the same program: `prefill` / `decode` return the model's tuple with
`next_ids = argmax(logits, axis=-1)` (int32, the FIRST maximal index as
`np.argmax` gives it, on the float32 logits themselves) put in after the
logits, and behind the ids whatever the step counted (`C` =
`len(StepPrograms.counters)`, 0 for most models):

    prefill(ids [1, T], lengths [1], last [1])
        -> (logits [1, V], next_id  [1 + C], K, V, *state)
    decode(ids [S + C], cache_lengths [S], active [S], k_cache, v_cache,
           *state)
        -> (logits [S, V], next_ids [S + C], K, V columns, *state)

`decode` TAKES ITS IDS IN THE FORM IT RETURNS THEM: int32 `[S + C]`, row
`i`'s token at `i`, the tail ignored; the reshape to the model's `[S, 1]`
is inside the program.  So a step's `next_ids` result IS the next step's
`ids` argument, on the device: the engine dispatches step N+1 from step
N's ids before it has read them (`generation.py`: one launch in flight),
with no further launch and no second trace of a bucket, and a step whose
ids come from the host uploads an array of that same shape.  The model's
`prefill_step` / `decode_step` contract above is untouched.

A greedy row costs the host link 4 bytes; the logits stay a result, left
on the device.  Only a step in which some active row's request samples
(temperature, top-k, its own seeded host RNG) has them fetched, and
whole, with no gather of those rows first: a download here costs a round
trip, hardly the bytes (PERF.md §6, PR 30).  An idle row's
`next_ids` entry means as little as its logits do.  A model that lands
on this route gets all of it from this wrapper: its methods return
logits and know nothing of sampling.

WHAT A STEP COUNTED RIDES WITH THE IDS.  A model whose step has
data-dependent work names it (`model.step_counters`, e.g. the routed
experts' `moe_pairs`, `moe_touched`) and returns one int32 vector of those
counts after the state; the wrapper takes it off the tuple and appends it
to `next_ids`, the ids first, so the counts reach the host in the download
the ids already cost (a download costs a link round trip whatever its
size) and the tuple keeps its positions.  `StepPrograms.counters` has the
names and `model.step_metrics(counts)` says which `serving.*` counters
and gauges the engine makes of them.

THE DECODE STEP OWNS THE STATE ARRAYS while it runs: the contract's
`*state` arguments of `decode_step` — every position from `DECODE_STATE_AT`
on — are donated through the compiled program, whose `*state` results
have their shapes and dtypes, so XLA writes each layer's new state where
the old one lies and no second copy of the state exists.  After `decode`
returns (or raises) the arrays that went in are dead: the caller takes the
results in their place (`StateSlots.rebind`).  Nothing else is donated:
not the ids, which the engine still has to read where they are the step
before's result, nor the lengths or the KV view's slices.

A MODEL WHOSE CACHE DESCRIPTION STATES `retain` (several `kv` groups, a
window's ring among them; `kv_pool.retained_kv_groups`) keeps its KV on
the device only, and its contract carries the groups' arrays in place of
the one K, V and the columns:

    prefill_step(ids [1, T], lengths [1], last [1])
        -> (logits [1, V], *kv [Lg, 1, Hkv, T | window, D], *state)
    decode_step(ids [S, 1], cache_lengths [S], active [S],
                *kv [Lg, S, Hkv, columns, D], *state)
        -> (logits [S, V], *kv, *state)

`*kv`: a K and a V per group in the description's order
(`kv_pool.device_kv_arrays`).  A prefill returns a window group's RING as
it stands after the prompt's last valid token; the decode step takes the
arrays whole — every position from `DECODE_CACHE_AT` on is donated, the KV
like the state — writes each row's new column where the array lies (a ring
at ``length mod window``) and returns them.  The greedy pick, the ids as
picked, the counts behind the ids and the launch in flight are the same.

`GPTModel` is not on this route yet (ROADMAP S2b): its eager forward has
no such methods.
"""
from __future__ import annotations

from ..dygraph.base import no_grad
from ..dygraph.tensor import Tensor
from ..tensor.manipulation import concat, reshape, slice as slice_
from ..tensor.search import argmax

__all__ = ["StepPrograms", "DECODE_STATE_AT", "DECODE_CACHE_AT"]

# decode_step(ids, cache_lengths, active, k_cache, v_cache, *state)
DECODE_STATE_AT = 5
# decode_step(ids, cache_lengths, active, *kv, *state) of a description
# with `retain`: the KV arrays are donated too
DECODE_CACHE_AT = 3


class StepPrograms:
    def __init__(self, model):
        from ..jit import StaticFunction
        from .kv_pool import (cache_spec_of, retained_kv_groups,
                              state_groups)
        for name in ("prefill_step", "decode_step"):
            if not callable(getattr(model, name, None)):
                raise TypeError(
                    f"{type(model).__name__} has no {name}(): the compiled "
                    "step route needs the model's cache-aware entry points")
        model.eval()
        self.counters = tuple(getattr(model, "step_counters", ()))
        # traced from shapes: the steps' Python never reads a tensor's
        # value, and an eager pass of a 3 B-parameter model a bucket would
        # compile hundreds of per-op programs to throw their results away
        self._prefill = StaticFunction(
            _with_greedy(model.prefill_step, bool(self.counters)),
            layer=model, abstract_trace=True)
        spec = cache_spec_of(model.config)
        n_state = sum(len(g["arrays"]) for g in state_groups(spec))
        n_kv = 2 * len(retained_kv_groups(spec))
        first = DECODE_CACHE_AT if n_kv else DECODE_STATE_AT
        self._decode = StaticFunction(
            _with_greedy(model.decode_step, bool(self.counters),
                         ids_as_picked=True),
            layer=model, abstract_trace=True,
            donate_args=range(first, first + n_kv + n_state))

    @property
    def programs(self) -> int:
        """Traced signatures so far: growth after warm-up is a retrace."""
        return len(self._prefill._cache) + len(self._decode._cache)

    def prefill(self, ids, lengths, last):
        """ids [1, T] -> (logits [1, V], next_id [1 + C], K, V, *state)."""
        with no_grad():
            return self._prefill(ids, lengths, last)

    def decode(self, ids, cache_lengths, active, *cache):
        """One decode step, ids [S + C] -> (logits [S, V], next_ids
        [S + C], K, V columns, *state).  `cache` = k_cache, v_cache (the
        dense view's slices, tensors) then the state's raw device arrays,
        DONATED — dead when this returns; the `*state` results replace
        them.  For a description with `retain`, `cache` = the device KV
        arrays then the state's, all raw and all donated, and the result
        is (logits, next_ids, *kv, *state)."""
        with no_grad():
            return self._decode(ids, cache_lengths, active, *[
                c if isinstance(c, Tensor) else Tensor(c) for c in cache])


def _with_greedy(step, counted=False, ids_as_picked=False):
    """`step` with the greedy pick of its logits put in after them, and,
    where the model's step ends in a vector of counts (`counted`), that
    vector taken off the end and appended to the ids.  `ids_as_picked`:
    the ids arrive as this wrapper returns them, `[S + C]`, and are given
    to `step` as `[S, 1]`.  The arguments keep their positions
    (`DECODE_STATE_AT`, the donation)."""
    def step_and_pick(ids, *args):
        if ids_as_picked:
            rows = args[0].shape[0]             # cache_lengths [S]
            ids = reshape(slice_(ids, [0], [0], [rows]), [rows, 1])
        logits, *rest = step(ids, *args)
        picked = argmax(logits, axis=-1, dtype="int32")
        if counted:
            *rest, counts = rest
            picked = concat([picked, counts])
        return (logits, picked, *rest)
    return step_and_pick
