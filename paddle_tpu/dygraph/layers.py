"""nn.Layer — the dygraph module base class.

Analog of /root/reference/python/paddle/fluid/dygraph/layers.py:1 Layer
(parameters/sublayers/hooks/state_dict) with ParamBase
(/root/reference/python/paddle/fluid/framework.py:5169).

Parameters are eager Tensors materialised by running the SAME initializer
ops the static path would append to a startup program — a throwaway block is
built and interpreted, so init numerics are identical between modes.
"""
from __future__ import annotations

import collections
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from ..core.program import Program, program_guard, unique_name
from ..core.dtype import convert_dtype
from ..core.generator import global_seed, next_eager_uid
from ..ops.registry import OpContext
from ..static.initializer import (Initializer, Constant, Uniform,
                                  XavierInitializer)
from ..static.param_attr import ParamAttr
from .base import in_dygraph_mode
from .tensor import Tensor

__all__ = ["Layer", "Sequential", "LayerList", "ParameterList", "ParamBase",
           "parameter_footprint"]


def parameter_footprint(layer: "Layer") -> Dict[str, int]:
    """{"params": elements, "bytes": shape x dtype} over a layer's
    parameters, as `p.numpy().nbytes` would sum, without bringing one
    across the host link to count it."""
    from ..core.dtype import np_dtype
    sizes = [(int(np.prod(p.shape)), np_dtype(p.dtype).itemsize)
             for p in layer.parameters()]
    return {"params": sum(n for n, _ in sizes),
            "bytes": sum(n * item for n, item in sizes)}


class ParamBase(Tensor):
    """A trainable parameter tensor (framework.py:5169 ParamBase)."""

    __slots__ = ("optimize_attr", "regularizer", "need_clip")

    def __init__(self, value, name=None, trainable=True, regularizer=None,
                 need_clip=True):
        super().__init__(value, stop_gradient=not trainable, name=name,
                         persistable=True, trainable=trainable)
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = regularizer
        self.need_clip = need_clip

    def __repr__(self):
        return "Parameter " + super().__repr__()


def materialize_initializer(init: Initializer, shape, dtype="float32",
                            name=None) -> np.ndarray:
    """Run an initializer's op eagerly and return the value — shares kernels
    with the startup-program path so eager/static init match exactly."""
    from ..static.executor import BlockTracer
    prog = Program()
    prog.random_seed = global_seed()
    with program_guard(prog, prog):
        var = prog.global_block().create_var(
            name=name or unique_name("param_init"), shape=shape, dtype=dtype,
            persistable=True)
        init(var, prog.global_block())
    env = {}
    # fold a fresh uid so two layers built in a row get different samples
    ctx = OpContext(seed=global_seed() + next_eager_uid())
    BlockTracer(prog.global_block()).run(env, ctx)
    return env[var.name]


class HookRemoveHelper:
    def __init__(self, hooks, idx):
        self._hooks, self._idx = hooks, idx

    def remove(self):
        self._hooks.pop(self._idx, None)


class Layer:
    """Base network module."""

    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = convert_dtype(dtype) if dtype else "float32"
        self._full_name = unique_name(
            name_scope or type(self).__name__.lower())
        self._parameters: "collections.OrderedDict[str, ParamBase]" = \
            collections.OrderedDict()
        self._sub_layers: "collections.OrderedDict[str, Layer]" = \
            collections.OrderedDict()
        self._buffers: "collections.OrderedDict[str, Tensor]" = \
            collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._hook_id = 0

    # -- naming -------------------------------------------------------------
    def full_name(self):
        return self._full_name

    # -- parameter creation -------------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None) -> ParamBase:
        dtype = convert_dtype(dtype or self._dtype)
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        init = (attr.initializer if attr and attr.initializer is not None
                else default_initializer)
        if init is None:
            init = Constant(0.0) if is_bias else XavierInitializer()
        name = (attr.name if attr and attr.name
                else unique_name(self._full_name + ".w"))
        value = materialize_initializer(init, shape, dtype, name)
        p = ParamBase(value, name=name,
                      trainable=(attr.trainable if attr else True),
                      regularizer=(attr.regularizer if attr else None),
                      need_clip=(attr.need_clip if attr else True))
        if attr and attr.learning_rate != 1.0:
            p.optimize_attr["learning_rate"] = attr.learning_rate
        return p

    def create_variable(self, name=None, persistable=False, dtype=None):
        return Tensor(np.zeros([0], dtype=np_like(dtype or self._dtype)),
                      name=name or unique_name(self._full_name + ".var"),
                      persistable=persistable)

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def add_parameter(self, name, parameter) -> ParamBase:
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer) -> "Layer":
        self._sub_layers[str(name)] = sublayer
        return sublayer

    # -- attribute magic ----------------------------------------------------
    def _drop_from_stores(self, name, keep=None):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            if store == keep:
                continue
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]

    def __setattr__(self, name, value):
        if isinstance(value, ParamBase):
            if not hasattr(self, "_parameters"):
                raise RuntimeError("call Layer.__init__ first")
            self._drop_from_stores(name, keep="_parameters")
            self._parameters[name] = value
            object.__setattr__(self, name, value)
        elif isinstance(value, Layer):
            self._drop_from_stores(name, keep="_sub_layers")
            self._sub_layers[name] = value
            object.__setattr__(self, name, value)
        else:
            buffers = self.__dict__.get("_buffers")
            if buffers is not None and name in buffers and \
                    isinstance(value, Tensor):
                # `self.x = self.register_buffer("x", t)` (and later
                # re-assignments of a registered buffer) update the buffer
                # store rather than unregistering it
                buffers[name] = value
            else:
                # reassigning a former parameter/sublayer/buffer slot to
                # None or a plain value drops the stale registry entry
                self._drop_from_stores(name)
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        # only called when normal lookup fails
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

    # -- traversal ----------------------------------------------------------
    def parameters(self, include_sublayers=True) -> List[ParamBase]:
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, lay in self.named_sublayers(prefix=prefix,
                                              include_self=True):
            if not include_sublayers and lay is not self:
                continue
            for pname, p in lay._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (name + "." + pname if name else pname), p

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self):
        for name, l in self._sub_layers.items():
            if l is not None:
                yield name, l

    def sublayers(self, include_self=False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False):
        seen = set()
        stack: List[Tuple[str, Layer]] = [(prefix, self)]
        first = True
        while stack:
            name, lay = stack.pop(0)
            if id(lay) in seen:
                continue
            seen.add(id(lay))
            if include_self or not first:
                yield name, lay
            first = False
            for cname, child in lay._sub_layers.items():
                if child is None:
                    continue
                stack.append((name + "." + cname if name else cname, child))

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True,
                      persistable_only=False):
        for name, lay in self.named_sublayers(prefix=prefix,
                                              include_self=True):
            if not include_sublayers and lay is not self:
                continue
            for bname, b in lay._buffers.items():
                if b is None:
                    continue
                if persistable_only and \
                        bname in lay._non_persistable_buffer_names:
                    continue
                yield (name + "." + bname if name else bname), b

    # -- mode ---------------------------------------------------------------
    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- call ---------------------------------------------------------------
    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            o = hook(self, inputs, outputs)
            if o is not None:
                outputs = o
        return outputs

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix=""):
        dest = destination if destination is not None \
            else collections.OrderedDict()
        for name, p in self.named_parameters(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            dest[name] = p
        for name, b in self.named_buffers(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers,
                persistable_only=True):
            dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, t in own.items():
            if name in state_dict:
                val = state_dict[name]
                t.set_value(val.numpy() if isinstance(val, Tensor)
                            else np.asarray(val))
            else:
                missing.append(name)
        for name in state_dict:
            if name not in own:
                unexpected.append(name)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_gradient()

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dtype = convert_dtype(dtype)
            for p in self.parameters():
                p.set_value(p.numpy().astype(np_like(dtype)))
            self._dtype = dtype
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [f"{type(self).__name__}({extra}"]
        for name, child in self.named_children():
            child_repr = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({name}): {child_repr}")
        return ("\n".join(lines) + ")") if len(lines) > 1 else lines[0] + ")"


def np_like(dtype):
    from ..core.dtype import np_dtype
    return np_dtype(convert_dtype(dtype))


class Sequential(Layer):
    """nn.Sequential — accepts layers or (name, layer) tuples."""

    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)) and \
                layers[0] and isinstance(layers[0][0], (list, tuple)):
            layers = layers[0]
        for i, l in enumerate(layers):
            if isinstance(l, (list, tuple)):
                self.add_sublayer(str(l[0]), l[1])
            else:
                self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)

    def forward(self, x):
        for l in self._sub_layers.values():
            x = l(x)
        return x


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        for i, l in enumerate(sublayers or []):
            self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return list(self._sub_layers.values())[idx]
        n = len(self._sub_layers)
        if not -n <= idx < n:
            raise IndexError(
                f"index {idx} out of range for LayerList of length {n}")
        return self._sub_layers[str(idx % n)]

    def __setitem__(self, idx, layer):
        self._sub_layers[str(idx)] = layer

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer):
        self.add_sublayer(str(len(self)), layer)
        return self

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self

    def insert(self, index, layer):
        items = list(self._sub_layers.values())
        items.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(items):
            self._sub_layers[str(i)] = l


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        for i, p in enumerate(parameters or []):
            self.add_parameter(str(i), p)

    def __getitem__(self, idx):
        return self._parameters[str(idx)]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter):
        self.add_parameter(str(len(self)), parameter)
        return self
