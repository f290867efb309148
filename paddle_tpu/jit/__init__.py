"""paddle_tpu.jit: dygraph -> static translation + save/load.

Reference: /root/reference/python/paddle/fluid/dygraph/jit.py
(`declarative`/@to_static, jit.save :230, jit.load :426, TranslatedLayer in
dygraph/io.py) and dygraph_to_static/program_translator.py
(ProgramTranslator, ConcreteProgram), with the capture mechanism of
imperative/jit/program_desc_tracer.cc.

TPU-native redesign — TRACE, DON'T TRANSPILE: the reference rewrites Python
AST (24 transformer files) because its dygraph ops can't be captured
mid-flight.  Here every dygraph op already flows through one chokepoint
(dygraph/tracer.py trace_op), so to_static simply records each op into a
Program while the eager forward runs (program_desc_tracer.cc's approach,
promoted to the only mechanism).  Python control flow is resolved at trace
time per input signature — exactly jax.jit's tracing contract, which is the
idiomatic TPU behaviour.  Data-dependent control flow belongs in the static
layers (layers.cond / layers.While / layers.StaticRNN).

Execution of a traced function is ONE jitted XLA computation (BlockTracer
composition under jax.jit); in training mode jax.vjp over that computation
bridges back into the dygraph tape, so `loss.backward()` runs a compiled
backward and parameter grads land on the eager Parameters.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core.program import Program, unique_name
from ..ops.registry import OpContext
from ..dygraph import tracer as dytracer
from ..dygraph.tensor import Tensor
from ..dygraph.layers import Layer
from ..profiler import Phase

__all__ = ["to_static", "declarative", "save", "load", "TranslatedLayer",
           "ProgramTranslator", "InputSpec", "StaticFunction",
           "not_to_static"]


class InputSpec:
    """Shape/dtype spec for a traced input (paddle.static.InputSpec)."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name

    @staticmethod
    def from_tensor(t: Tensor, name=None):
        return InputSpec(t.shape, t.dtype, name or t.name)

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"


# ---------------------------------------------------------------------------
# op recorder: hooks dygraph trace_op and mirrors each op into a Program
# ---------------------------------------------------------------------------
class _Recorder:
    """program_desc_tracer.cc analog: id(Tensor) -> var name mapping and an
    OpDesc append per traced op."""

    def __init__(self, program: Program):
        self.program = program
        self.block = program.global_block()
        self.names: Dict[int, str] = {}
        self.keepalive: List[Tensor] = []   # id() stability
        self.params: Dict[str, Tensor] = {}  # persistable captures
        self.initial_raw: Dict[str, Any] = {}  # value at first capture

    def name_of(self, t: Tensor) -> str:
        key = id(t)
        if key in self.names:
            return self.names[key]
        # unseen tensor: a parameter or an eagerly-created constant —
        # either way it becomes persistable state of the program; captures
        # always land in block 0 so sub-block recording (dy2static cond)
        # keeps them visible from every block
        name = t.name if t.persistable else unique_name("@captured")
        gb = self.program.global_block()
        gb.create_var(name=name, shape=tuple(t.shape),
                      dtype=t.dtype, persistable=True,
                      stop_gradient=t.stop_gradient)
        if not t.stop_gradient:
            gb.vars[name].is_parameter = True
            gb.vars[name].trainable = getattr(t, "trainable", True)
        self.names[key] = name
        self.keepalive.append(t)
        self.params[name] = t
        self.initial_raw[name] = t._value
        return name

    def register(self, t: Tensor, name: str):
        self.names[id(t)] = name
        self.keepalive.append(t)

    def record(self, op_type, ins, attrs, out_slot_tensors):
        in_names = {}
        for slot, v in ins.items():
            if v is None:
                continue
            if isinstance(v, (list, tuple)):
                in_names[slot] = [self.name_of(t) for t in v
                                  if isinstance(t, Tensor)]
            elif isinstance(v, Tensor):
                in_names[slot] = [self.name_of(v)]
        out_names = {}
        for slot, ts in out_slot_tensors.items():
            names = []
            for t in ts:
                name = unique_name(t.name or "jit_tmp")
                self.block.create_var(name=name, shape=tuple(t.shape),
                                      dtype=t.dtype)
                self.register(t, name)
                names.append(name)
            out_names[slot] = names
        a = {k: v for k, v in (attrs or {}).items() if k != "op_uid"}
        self.block.append_op(op_type, in_names, out_names, a)


# ---------------------------------------------------------------------------
# concrete (per-signature) traced program
# ---------------------------------------------------------------------------
class ConcreteProgram:
    """One traced signature: Program + feed/fetch names + captured state
    (program_translator.py ConcreteProgram analog).  `updates` maps a
    captured buffer name -> the program var holding its new value (BN
    running stats etc., whose dygraph layers rebind via set_value)."""

    def __init__(self, program, feed_names, fetch_names, params,
                 out_struct, updates=None, donated=()):
        self.program = program
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        self.params = params            # name -> Tensor (live, mutable)
        self.out_struct = out_struct    # "single" | "tuple" | "list"
        self.updates = dict(updates or {})
        # indices into feed_names whose buffers the run may write into:
        # those feeds are dead after every call (`StaticFunction`)
        self.donated = tuple(sorted(set(donated)))
        self._composed = None

    def split_feeds(self, raws):
        """The feeds' raw values, in feed order, as `composed`'s
        (input_raws, donated_raws)."""
        return (tuple(r for i, r in enumerate(raws)
                      if i not in self.donated),
                tuple(raws[i] for i in self.donated))

    def composed(self):
        """(seed, param_raws, input_raws, is_test[, donated_raws]) ->
        (fetch raws + buffer-update raws), jitted.  `input_raws` are the
        feeds that are kept, in feed order; the `donated` feeds go in as
        the last argument, which is donated to XLA."""
        if self._composed is None:
            from ..static.executor import BlockTracer
            tracer = BlockTracer(self.program.global_block())
            pnames = list(self.params)
            fnames = [n for i, n in enumerate(self.feed_names)
                      if i not in self.donated] \
                + [self.feed_names[i] for i in self.donated]
            onames = list(self.fetch_names) + list(self.updates.values())

            def fn(seed, param_raws, input_raws, is_test, donated_raws=()):
                env = dict(zip(pnames, param_raws))
                env.update(zip(fnames, (*input_raws, *donated_raws)))
                ctx = OpContext(seed=seed, is_test=is_test)
                # sub-block ops (dy2static cond) resolve their blocks here
                ctx.program = self.program
                tracer.run(env, ctx)
                return tuple(env[n] for n in onames)

            self._composed = jax.jit(
                fn, static_argnames=("is_test",),
                donate_argnames=("donated_raws",) if self.donated else ())
        return self._composed


class StaticFunction:
    """Callable produced by @to_static (program_translator.py
    StaticFunction).  Traces once per input signature; runs as one jitted
    XLA computation; training mode bridges grads to the dygraph tape via
    jax.vjp over the whole computation."""

    def __init__(self, fn, input_spec=None, layer: Optional[Layer] = None,
                 abstract_trace: bool = False,
                 donate_args: Sequence[int] = (), describe=None):
        """``donate_args``: positions of the call's Tensor arguments whose
        device buffers the compiled run may write its results into (XLA
        aliases an output of the same shape and dtype to each).  After
        every call those arguments are DEAD — their arrays deleted — so
        the caller must take the results in their place; such a function
        runs forward only.  Default none: nothing is donated.

        ``abstract_trace``: record the Program from shapes alone — the
        trace-time call of ``fn`` sees its tensor arguments as abstract
        values (``jax.eval_shape``), so no kernel runs and no per-op
        executable is compiled for a result the compiled run recomputes
        anyway.  For functions whose Python never reads a tensor's value
        (a 3 B-parameter decode step: seconds instead of minutes, and none
        of the trace's activations held on the device).

        ``describe``: what the caller knows of a program this function is
        about to obtain, as fields of its `jit/program` phase
        (docs/observability.md §6): called with the arguments of the call
        that found no traced entry, returns a dict."""
        self._describe = describe
        self._fn = self._maybe_ast_transform(fn)
        self._input_spec = input_spec
        self._layer = layer
        self._abstract_trace = bool(abstract_trace)
        self._donate_args = tuple(int(i) for i in donate_args)
        self._cache: Dict[Tuple, ConcreteProgram] = {}

    @staticmethod
    def _maybe_ast_transform(fn):
        """Rewrite tensor-dependent `if`s into recorded cond ops
        (dy2static.py); anything the transform can't express falls back to
        pure tracing — jax.jit's trace-time-specialization contract."""
        import inspect as _inspect
        from .dy2static import ast_transform
        target = fn.__func__ if _inspect.ismethod(fn) else fn
        try:
            new = ast_transform(target)
        except Exception:
            # any transform failure (unsupported construct, unparseable
            # lambda source, empty closure cell, ...) falls back to pure
            # tracing — to_static must never be stricter than the tracer
            return fn
        if _inspect.ismethod(fn):
            import types as _types
            return _types.MethodType(new, fn.__self__)
        return new

    @property
    def __name__(self):
        return getattr(self._fn, "__name__", "static_fn")

    def _sig(self, args):
        key = []
        for a in args:
            if isinstance(a, Tensor):
                key.append((tuple(a.shape), a.dtype))
            else:
                key.append(("py", repr(a)))
        return tuple(key)

    def _to_tensors(self, args):
        out = []
        for a in args:
            if isinstance(a, Tensor):
                out.append(a)
            elif isinstance(a, (np.ndarray, jnp.ndarray, list, float, int)):
                out.append(Tensor(np.asarray(a)))
            else:
                out.append(a)
        return out

    def concrete_program(self, *args) -> ConcreteProgram:
        args = self._to_tensors(args)
        return self._cache.get(self._sig(args)) or self._record(args)

    def _record(self, args) -> ConcreteProgram:
        """The miss path: `_trace` the arguments' signature into its
        entry, as the kept phase `jit/record`."""
        with Phase("jit/record") as phase:
            cp = self._cache[self._sig(args)] = self._trace(args)
            phase.set(ops=len(cp.program.global_block().ops))
        return cp

    def _trace(self, args) -> ConcreteProgram:
        program = Program()
        rec = _Recorder(program)
        feed_names, donated = [], []
        for i, a in enumerate(args):
            if not isinstance(a, Tensor):
                continue
            name = unique_name(f"feed_{i}")
            program.global_block().create_var(
                name=name, shape=tuple(a.shape), dtype=a.dtype,
                is_data=True)
            rec.register(a, name)
            if i in self._donate_args:
                donated.append(len(feed_names))
            feed_names.append(name)
        if len(donated) != len(self._donate_args):
            raise TypeError(
                f"to_static: donate_args {self._donate_args} must be "
                f"positions of Tensor arguments, the call has {len(args)}")

        prev = dytracer._PROGRAM_RECORDER
        dytracer._PROGRAM_RECORDER = rec
        try:
            from ..dygraph.base import enable_grad
            with enable_grad():
                result = self._call_for_trace(args)
        finally:
            dytracer._PROGRAM_RECORDER = prev

        if isinstance(result, (tuple, list)):
            struct = "tuple" if isinstance(result, tuple) else "list"
            outs = list(result)
        else:
            struct = "single"
            outs = [result]
        fetch_names = []
        for t in outs:
            if not isinstance(t, Tensor) or id(t) not in rec.names:
                raise TypeError(
                    "to_static: traced function must return Tensors "
                    "produced by the traced ops, got "
                    f"{type(t).__name__}")
            nm = rec.names[id(t)]
            if not program.global_block().has_var(nm):
                # e.g. a value list.append'ed inside a tensor-dependent
                # loop body: its op lives in the while sub-block, so it
                # cannot escape the loop (only assigned names are
                # loop-carried)
                raise TypeError(
                    f"to_static: returned tensor {nm!r} was produced "
                    "inside a tensor-dependent loop body and is not "
                    "loop-carried — assign it to a variable before the "
                    "loop (loop-carried state) or accumulate through "
                    "static.layers.create_array/array_write")
            fetch_names.append(nm)
        # buffer rebindings (BatchNorm running stats): a layer that did
        # `buffer.set_value(traced_out)` left the buffer's raw value
        # identical to some traced output's — record the link so replays
        # keep updating the live buffer (the reference keeps these as
        # in-place MeanOut/VarianceOut wirings)
        updates = {}
        for pname, pt in rec.params.items():
            for t in rec.keepalive:
                nm = rec.names.get(id(t))
                if nm and nm != pname and t is not pt \
                        and t._value is pt._value:
                    updates[pname] = nm
                    # the trace ran the layer eagerly and already applied
                    # this update; roll it back so the compiled run (which
                    # always follows) doesn't apply it twice
                    pt._value = rec.initial_raw[pname]
                    break
        return ConcreteProgram(program, feed_names, fetch_names,
                               dict(rec.params), struct, updates, donated)

    def _call_for_trace(self, args):
        """The one call of the traced function: eager on the arguments'
        values, or — ``abstract_trace`` — with them swapped for abstract
        values while it runs.  Only the identity, shape and dtype of the
        tensors it makes are read afterwards."""
        if not self._abstract_trace:
            return self._fn(*args)
        tensors = [a for a in args if isinstance(a, Tensor)]
        concrete = [t._value for t in tensors]
        made = []

        def body(raws):
            for t, r in zip(tensors, raws):
                t._value = r
            made.append(self._fn(*args))

        try:
            jax.eval_shape(body, concrete)
        finally:
            for t, r in zip(tensors, concrete):
                t._value = r
        return made[0]

    def __call__(self, *args, **kwargs):
        if kwargs:
            raise TypeError("to_static functions take positional Tensor "
                            "arguments only (trace-time contract)")
        args = self._to_tensors(args)
        cp = self._cache.get(self._sig(args))
        if cp is not None:
            return self._run(cp, args)
        # a program obtained: recorded, then composed, lowered, compiled
        # (or loaded) and run once, all under one kept phase
        fields = self._describe(*args) if self._describe else {}
        with Phase("jit/program", fn=self.__name__, **fields) as phase:
            cp = self._record(args)
            phase.set(ops=len(cp.program.global_block().ops))
            return self._run(cp, args)

    def _run(self, cp: ConcreteProgram, args):
        """One call of the traced entry `cp` on tensor arguments `args`."""
        input_raws, donated_raws = cp.split_feeds(
            [a._value for a in args if isinstance(a, Tensor)])
        param_ts = [cp.params[n] for n in cp.params]
        param_raws = tuple(t._value for t in param_ts)
        from ..core.generator import global_seed
        from ..dygraph.base import is_grad_enabled
        # a host scalar: `jnp.uint32` would launch a convert on the device
        # for it, one more XLA module a call
        seed = np.uint32(global_seed())
        training = self._layer.training if self._layer is not None else True
        is_test = not training
        fn = cp.composed()

        needs_grad = is_grad_enabled() and (
            any(not t.stop_gradient for t in param_ts)
            or any(isinstance(a, Tensor) and not a.stop_gradient
                   for a in args))
        n_fetch = len(cp.fetch_names)
        if needs_grad and cp.donated:
            raise TypeError(
                "to_static: a function that donates arguments runs "
                "forward only (call it under no_grad)")
        if not needs_grad:
            out_raws = fn(seed, param_raws, input_raws, is_test,
                          donated_raws)
            outs = [Tensor(r) for r in out_raws[:n_fetch]]
        else:
            out_raws, vjp_fn = jax.vjp(
                lambda p, i: fn(seed, p, i, is_test),
                param_raws, input_raws)
            outs = [Tensor(r, stop_gradient=False)
                    for r in out_raws[:n_fetch]]
            in_tensors = param_ts + [a for a in args
                                     if isinstance(a, Tensor)]
            # buffer-update outputs join the node so the vjp cotangent
            # structure matches; they carry no user-visible gradient
            upd_outs = [Tensor(r, stop_gradient=True)
                        for r in out_raws[n_fetch:]]
            node = dytracer.GradNode(
                "__to_static__", {"X": in_tensors}, {},
                {"Out": out_raws}, {"Out": outs + upd_outs}, int(seed))

            def vjp_list(gs):
                dp, di = vjp_fn(tuple(gs))
                return list(dp) + list(di)

            node.vjp_fn = vjp_list
            node.vjp_multi = True
            node.n_vjp_inputs = len(in_tensors)
            for t in outs:
                t._grad_node = node
        # write buffer updates (BN running stats) back to the live tensors
        for pname, raw in zip(cp.updates, out_raws[n_fetch:]):
            cp.params[pname]._value = raw
        if cp.out_struct == "single":
            return outs[0]
        return tuple(outs) if cp.out_struct == "tuple" else list(outs)


def to_static(function=None, input_spec=None, build_strategy=None,
              **kwargs):
    """@paddle.jit.to_static (dygraph/jit.py declarative).  Wraps a
    function or a Layer's forward; tracing happens lazily at first call
    per input signature."""
    def wrap(fn):
        if isinstance(fn, Layer):
            layer = fn
            orig_forward = layer.forward  # bind BEFORE replacing
            sf = StaticFunction(orig_forward, input_spec, layer=layer)
            layer.forward = sf
            return layer
        return StaticFunction(fn, input_spec)
    if function is not None:
        return wrap(function)
    return wrap


declarative = to_static


def not_to_static(fn):
    """Marker passthrough (reference jit.not_to_static)."""
    return fn


class ProgramTranslator:
    """program_translator.py ProgramTranslator singleton (parity shim —
    tracing is always available here)."""

    _instance = None
    enable_to_static = True

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, enable_to_static: bool):
        ProgramTranslator.enable_to_static = bool(enable_to_static)


# ---------------------------------------------------------------------------
# save / load
# ---------------------------------------------------------------------------
def save(layer, path, input_spec=None, **configs):
    """jit.save (dygraph/jit.py:230): trace the layer and persist it in
    save_inference_model format (.pdmodel program json + params file) so
    the inference Predictor and jit.load both consume it."""
    from ..static import Executor, Scope, scope_guard
    from ..io.framework_io import save_inference_model

    if isinstance(layer, Layer):
        fwd = layer.forward
        if not isinstance(fwd, StaticFunction):
            sf = StaticFunction(lambda *a: layer.forward(*a), input_spec,
                                layer=layer)
        else:
            sf = fwd
    elif isinstance(layer, StaticFunction):
        sf = layer
    else:
        raise TypeError("jit.save expects a Layer or a @to_static "
                        f"function, got {type(layer).__name__}")

    if input_spec is None:
        raise ValueError("jit.save needs input_spec=[InputSpec(...)] to "
                         "know the traced signature")
    example = [Tensor(np.zeros([1 if s == -1 else s for s in spec.shape],
                               dtype=np.dtype(_np_dtype(spec.dtype))))
               for spec in input_spec]
    cp = sf.concrete_program(*example)
    _save_concrete_program(cp, path)
    return cp


def _save_concrete_program(cp, path, feed_names=None, fetch_names=None):
    """ONE writer for the jit on-disk layout (<path>.pdmodel JSON program
    + <path>.pdiparams), shared by jit.save and
    TracedLayer.save_inference_model so the format cannot drift."""
    from ..static import Executor, Scope, scope_guard
    from ..io.framework_io import save_inference_model

    dirname = os.path.dirname(path) or "."
    basename = os.path.basename(path)
    os.makedirs(dirname, exist_ok=True)
    scope = Scope()
    for name, t in cp.params.items():
        scope.set(name, t._value)
    exe = Executor()
    with scope_guard(scope):
        save_inference_model(
            dirname, list(feed_names or cp.feed_names),
            [cp.program.global_block().var(n)
             for n in (fetch_names or cp.fetch_names)],
            exe, main_program=cp.program,
            model_filename=basename + ".pdmodel",
            params_filename=basename + ".pdiparams")


def _np_dtype(dtype):
    from ..core.dtype import np_dtype as _np
    return _np(dtype)


class TranslatedLayer(Layer):
    """jit.load product (reference dygraph/io.py TranslatedLayer): a Layer
    whose forward runs the loaded program as one jitted computation;
    parameters are trainable eager Tensors, so fine-tuning works."""

    def __init__(self, program, feed_names, fetch_names, params):
        super().__init__()
        self._program = program
        self._feed_names = feed_names
        self._fetch_names = fetch_names
        self._jit_params = {}
        for name, val in params.items():
            var = program.global_block().vars.get(name)
            trainable = bool(var is not None and var.is_parameter
                             and var.trainable)
            t = Tensor(val, stop_gradient=not trainable,
                       persistable=True)
            t.name = name
            self._jit_params[name] = t
            if trainable:
                self._parameters[name.replace("/", "_")] = t
        self._cp = ConcreteProgram(program, feed_names, fetch_names,
                                   self._jit_params, "auto")
        self._sf = StaticFunction(None, layer=self)
        self._sf._cache = {}

    def forward(self, *args):
        args = [a if isinstance(a, Tensor) else Tensor(np.asarray(a))
                for a in args]
        cp = self._cp
        sf = StaticFunction.__new__(StaticFunction)
        sf._fn = None
        sf._input_spec = None
        sf._layer = self
        sf._cache = {(): cp}
        sf._sig = lambda a: ()
        sf._to_tensors = lambda a: list(a)
        out = StaticFunction.__call__(sf, *args)
        return out


def load(path, **configs):
    """jit.load (dygraph/jit.py:426): rebuild a TranslatedLayer from a
    jit.save / save_inference_model artifact."""
    from ..static import Executor, Scope, scope_guard
    from ..io.framework_io import load_inference_model

    dirname = os.path.dirname(path) or "."
    basename = os.path.basename(path)
    scope = Scope()
    exe = Executor()
    with scope_guard(scope):
        program, feed_names, fetch_vars = load_inference_model(
            dirname, exe, model_filename=basename + ".pdmodel",
            params_filename=basename + ".pdiparams")
        params = {}
        for b in program.blocks:
            for v in b.vars.values():
                if v.persistable and scope.get(v.name) is not None:
                    params[v.name] = scope.get(v.name)
    fetch_names = [v.name if hasattr(v, "name") else str(v)
                   for v in fetch_vars]
    tl = TranslatedLayer(program, feed_names, fetch_names, params)
    tl._cp.out_struct = "list" if len(fetch_names) > 1 else "single"
    return tl


# ---------------------------------------------------------------------------
# TracedLayer (dygraph/jit.py:1218) + dy2static logging knobs
# ---------------------------------------------------------------------------
_VERBOSITY = {"code_level": 0, "verbosity": 0}


def set_code_level(level=100):
    """jit.set_code_level: how much transformed code dy2static logs
    (stored knob; transforms consult it when printing)."""
    _VERBOSITY["code_level"] = int(level)


def set_verbosity(level=0):
    """jit.set_verbosity: dy2static logging verbosity."""
    _VERBOSITY["verbosity"] = int(level)


class TracedLayer:
    """Convert a data-independent dygraph Layer into a static-graph
    callable by tracing one forward (reference dygraph/jit.py
    TracedLayer).  Create via TracedLayer.trace(layer, inputs); call it
    with tensors to run the traced program; save_inference_model()
    persists it for the Predictor."""

    def __init__(self, static_function, layer, example_inputs):
        self._sf = static_function
        self._layer = layer
        self._inputs = example_inputs

    @staticmethod
    def trace(layer, inputs):
        if not isinstance(layer, Layer):
            raise TypeError("TracedLayer.trace needs a dygraph Layer")
        inputs = [i if isinstance(i, Tensor) else Tensor(i)
                  for i in inputs]
        sf = StaticFunction(layer.forward, layer=layer)
        out = sf(*inputs)
        return out, TracedLayer(sf, layer, inputs)

    def __call__(self, inputs):
        inputs = [i if isinstance(i, Tensor) else Tensor(i)
                  for i in inputs]
        return self._sf(*inputs)

    def set_strategy(self, build_strategy=None, exec_strategy=None):
        """Accepted for parity; the traced program already runs as one
        jitted XLA computation."""

    def save_inference_model(self, path, feed=None, fetch=None, **kw):
        """feed/fetch are INDEX lists selecting which traced inputs/
        outputs the saved model exposes (reference dygraph/jit.py
        TracedLayer.save_inference_model)."""
        cp = self._sf.concrete_program(*self._inputs)
        feed_names = list(cp.feed_names)
        fetch_names = list(cp.fetch_names)
        if feed is not None:
            feed_names = [feed_names[i] for i in feed]
        if fetch is not None:
            fetch_names = [fetch_names[i] for i in fetch]
        _save_concrete_program(cp, path, feed_names, fetch_names)


__all__ += ["TracedLayer", "set_code_level", "set_verbosity"]


class SaveLoadConfig:
    """jit save/load options bag (reference fluid/dygraph/jit.py
    SaveLoadConfig): carried fields are honored by jit.save/load where
    they exist; the rest are accepted for parity."""

    def __init__(self):
        self.output_spec = None
        self.model_filename = None
        self.params_filename = None
        self.separate_params = False
        self.keep_name_table = False


__all__ += ["SaveLoadConfig"]
