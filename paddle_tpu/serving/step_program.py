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
tape is kept.  The route does not donate its arguments:
a decode step's state arrays are copied (old and new both live until the
engine rebinds them; `static.page_budget` prices the second copy).

`GPTModel` is not on this route yet (ROADMAP S2b): its eager forward has
no such methods.
"""
from __future__ import annotations

from ..dygraph.base import no_grad
from ..dygraph.tensor import Tensor

__all__ = ["StepPrograms"]


class StepPrograms:
    def __init__(self, model):
        from ..jit import StaticFunction
        for name in ("prefill_step", "decode_step"):
            if not callable(getattr(model, name, None)):
                raise TypeError(
                    f"{type(model).__name__} has no {name}(): the compiled "
                    "step route needs the model's cache-aware entry points")
        model.eval()
        # traced from shapes: the steps' Python never reads a tensor's
        # value, and an eager pass of a 3 B-parameter model a bucket would
        # compile hundreds of per-op programs to throw their results away
        self._prefill = StaticFunction(model.prefill_step, layer=model,
                                       abstract_trace=True)
        self._decode = StaticFunction(model.decode_step, layer=model,
                                      abstract_trace=True)

    @property
    def programs(self) -> int:
        """Traced signatures so far: growth after warm-up is a retrace."""
        return len(self._prefill._cache) + len(self._decode._cache)

    def prefill(self, ids, lengths, last):
        with no_grad():
            return self._prefill(ids, lengths, last)

    def decode(self, ids, cache_lengths, active, k_cache, v_cache, *state):
        with no_grad():
            return self._decode(ids, cache_lengths, active, k_cache,
                                v_cache, *[Tensor(s) for s in state])
