"""The engine's step contract, compiled: one program per (phase, bucket).

A model that serves through this route keeps what a sequence holds between
steps ON THE DEVICE, a row a slot — a K and a V array per `kv` group of its
cache description (`kv_pool.device_kv_arrays`: every group states what it
retains, a window's ring or `"all"`), then its recurrent state's arrays —
and has two cache-aware methods of flat tensor arguments and a tuple
result:

    prefill_step(ids [1, T], lengths [1], last [1])
        -> (logits [1, V], *kv [Lg, 1, Hkv, T | window, D], *state)
    decode_step(ids [S, 1], cache_lengths [S], active [S],
                *kv [Lg, S, Hkv, columns, D], *state [Ls, S, ...],
                columns=None)
        -> (logits [S, V], *kv, *state)

ids and per-row lengths go in; the ONE logits row a sequence's sampling
needs and the caches come out.  A prefill returns each group's K, V of the
prompt (a window group's RING as it stands after the prompt's last valid
token) and the state after it; the decode step takes the arrays WHOLE,
writes each row's new column where the array lies (a ring at ``length mod
window``) and each layer's new state over the old, and returns them.  Each
method is wrapped in `paddle_tpu.jit.to_static`'s `StaticFunction`: traced
once per argument signature — the engine's power-of-two buckets — into a
Program (from shapes alone, `abstract_trace`: nothing runs eagerly) and
run as one jitted XLA computation, under `eval()` and `no_grad`, so no
tape is kept.

`columns`, THE BOUND A DECODE PROGRAM IS TRACED FOR, is static (a Python
int, not a tensor): a program a value.  None: a row reads what it holds,
block by block to a trip count read from the data — ONE decode program
whatever the contexts, what the engine asks of a description with a ring.
n: every row's earlier tokens lie in the first n columns of the arrays
and the program reads those, in turns fixed at trace time — what the
engine asks of a description without a ring, n its power-of-two bucket
over the longest live row, so short contexts do not pay for the arrays'
length.  Either way the arrays go in and come out whole.

WHAT `StepPrograms` RETURNS, AND THE FORM IT TAKES THE IDS IN.  Picking a
token is the engine's business, and the greedy pick is made here, inside
the same program: `prefill` / `decode` return the model's tuple with
`next_ids = argmax(logits, axis=-1)` (int32, the FIRST maximal index as
`np.argmax` gives it, on the float32 logits themselves) put in after the
logits, and behind the ids whatever the step counted (`C` =
`len(StepPrograms.counters)`, 0 for most models):

    prefill(ids [1, T], lengths [1], last [1])
        -> (logits [1, V], next_id  [1 + C], *kv, *state)
    decode(ids [S + C], cache_lengths [S], active [S], *kv, *state,
           columns=None)
        -> (logits [S, V], next_ids [S + C], *kv, *state)

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

THE DECODE STEP OWNS THE CACHE ARRAYS while it runs: the contract's `*kv`
and `*state` arguments of `decode_step` — every position from
`DECODE_CACHE_AT` on — are donated through the compiled program, whose
results of the same shapes and dtypes XLA writes where the old arrays lie,
so ONE copy of the KV and of the state exists.  After `decode` returns (or
raises) the arrays that went in are dead: the caller takes the results in
their place (`StateSlots.rebind`).  Nothing else is donated: not the ids,
which the engine still has to read where they are the step before's
result, nor the lengths.

`GPTModel` is not on this route yet (ROADMAP S2b): its eager forward has
no such methods.
"""
from __future__ import annotations

import functools

from ..dygraph.base import no_grad
from ..dygraph.tensor import Tensor
from ..tensor.manipulation import concat, reshape, slice as slice_
from ..tensor.search import argmax

__all__ = ["StepPrograms", "DECODE_CACHE_AT"]

# decode_step(ids, cache_lengths, active, *kv, *state): the arrays donated
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
        self._model = model
        self.counters = tuple(getattr(model, "step_counters", ()))
        # traced from shapes: the steps' Python never reads a tensor's
        # value, and an eager pass of a 3 B-parameter model a bucket would
        # compile hundreds of per-op programs to throw their results away
        self._prefill = StaticFunction(
            _with_greedy(model.prefill_step, bool(self.counters)),
            layer=model, abstract_trace=True,
            describe=lambda ids, *_: {
                "kind": "prefill", "bucket": ids.shape[1],
                "rows": ids.shape[0]})
        spec = cache_spec_of(model.config)
        n_kv = 2 * len(retained_kv_groups(spec))
        if not n_kv:
            raise TypeError(
                f"{type(model).__name__}'s cache description states no "
                "`retain` on its kv groups: the step contract carries the "
                "KV as device arrays a group")
        n_state = sum(len(g["arrays"]) for g in state_groups(spec))
        self._donated = range(DECODE_CACHE_AT,
                              DECODE_CACHE_AT + n_kv + n_state)
        self._decode = {}       # `columns` -> its StaticFunction

    def decode_program(self, columns=None):
        """The decode step's `StaticFunction` at the static bound
        `columns` (None: the one that reads what its rows hold)."""
        if columns not in self._decode:
            from ..jit import StaticFunction
            step = self._model.decode_step if columns is None else \
                functools.partial(self._model.decode_step,
                                  columns=int(columns))
            self._decode[columns] = StaticFunction(
                _with_greedy(step, bool(self.counters), ids_as_picked=True),
                layer=self._model, abstract_trace=True,
                donate_args=self._donated,
                describe=lambda ids, lengths, *_: {
                    "kind": "decode", "rows": lengths.shape[0],
                    "columns": -1 if columns is None else int(columns)})
        return self._decode[columns]

    def _decode_traces(self) -> list:
        """Every traced decode program (`ConcreteProgram`), all bounds."""
        return [cp for fn in self._decode.values()
                for cp in fn._cache.values()]

    @property
    def programs(self) -> int:
        """Traced signatures so far: growth after warm-up is a retrace."""
        return len(self._prefill._cache) + len(self._decode_traces())

    def prefill(self, ids, lengths, last):
        """ids [1, T] -> (logits [1, V], next_id [1 + C], *kv, *state)."""
        with no_grad():
            return self._prefill(ids, lengths, last)

    def decode(self, ids, cache_lengths, active, *cache, columns=None):
        """One decode step, ids [S + C] -> (logits [S, V], next_ids
        [S + C], *kv, *state).  `cache` = the device KV arrays then the
        state's, raw, all DONATED — dead when this returns; the results
        replace them.  `columns`: the static bound the program reads the
        arrays to (module docstring)."""
        with no_grad():
            return self.decode_program(columns)(
                ids, cache_lengths, active,
                *[c if isinstance(c, Tensor) else Tensor(c) for c in cache])


def _with_greedy(step, counted=False, ids_as_picked=False):
    """`step` with the greedy pick of its logits put in after them, and,
    where the model's step ends in a vector of counts (`counted`), that
    vector taken off the end and appended to the ids.  `ids_as_picked`:
    the ids arrive as this wrapper returns them, `[S + C]`, and are given
    to `step` as `[S, 1]`.  The arguments keep their positions
    (`DECODE_CACHE_AT`, the donation)."""
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
