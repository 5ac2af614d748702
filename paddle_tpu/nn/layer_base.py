"""nn.Layer — the module base class.

TPU-native counterpart of the reference's ``paddle.nn.Layer``
(python/paddle/nn/layer/layers.py:340): parameter/buffer/sublayer registries,
name-prefixed traversal, state_dict round-trips, train/eval flags, and
forward pre/post hooks. Parameters are eager Tensors (mutable cells over
jax.Arrays), so a Layer works identically under eager execution and under the
jit tracer (paddle_tpu.jit) — there is no separate static-graph Layer.
"""
from __future__ import annotations

import collections
from typing import Callable, Iterator, Optional

import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..profiler.ring import get_tracer
from ..tensor import Parameter, Tensor

__all__ = ["Layer"]

_layer_name_counts: dict = collections.defaultdict(int)


def _unique_layer_name(prefix: str) -> str:
    idx = _layer_name_counts[prefix]
    _layer_name_counts[prefix] += 1
    return f"{prefix}_{idx}"


class HookRemoveHelper:
    def __init__(self, container: dict, key: int):
        self._container = container
        self._key = key

    def remove(self):
        self._container.pop(self._key, None)


class Layer:
    """Base class for all neural network layers (reference:
    python/paddle/nn/layer/layers.py:340)."""

    def __init__(self, name_scope: Optional[str] = None, dtype: str = "float32"):
        prefix = name_scope or self.__class__.__name__.lower()
        object.__setattr__(self, "_full_name", _unique_layer_name(prefix))
        object.__setattr__(self, "_parameters", collections.OrderedDict())
        object.__setattr__(self, "_sub_layers", collections.OrderedDict())
        object.__setattr__(self, "_buffers", collections.OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        object.__setattr__(self, "_forward_pre_hooks", collections.OrderedDict())
        object.__setattr__(self, "_forward_post_hooks", collections.OrderedDict())
        object.__setattr__(self, "_hook_id", 0)
        object.__setattr__(self, "training", True)
        object.__setattr__(self, "_dtype", dtypes.convert_dtype(dtype) or jnp.float32)

    # ------------------------------------------------------------- registry
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            params[name] = value
            layers.pop(name, None) if layers else None
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            layers[name] = value
            params.pop(name, None) if params else None
            self.__dict__.pop(name, None)
        elif buffers is not None and name in buffers:
            if value is None or isinstance(value, Tensor):
                buffers[name] = value
            else:
                buffers[name] = Tensor(value)
        else:
            if params is not None and name in params:
                if value is None:
                    params.pop(name)
                    object.__setattr__(self, name, None)
                    return
                raise TypeError(
                    f"cannot assign non-Parameter to parameter attribute {name!r}"
                )
            if layers is not None and name in layers and value is None:
                layers.pop(name)
                object.__setattr__(self, name, None)
                return
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for registry in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(registry)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'"
        )

    def __delattr__(self, name):
        for registry in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(registry)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        extra = []
        for registry in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(registry)
            if d:
                extra += list(d.keys())
        return list(super().__dir__()) + extra

    # ----------------------------------------------------------- param mgmt
    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias: bool = False,
        default_initializer=None,
    ) -> Parameter:
        """reference: Layer.create_parameter (nn/layer/layers.py) — allocates
        + initializes a Parameter according to a ParamAttr."""
        from . import initializer as I
        from .param_attr import ParamAttr

        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = dtypes.convert_dtype(dtype) or self._dtype
        init = None
        if attr is not None and attr.initializer is not None:
            init = attr.initializer
        elif default_initializer is not None:
            init = default_initializer
        elif is_bias:
            init = I.Constant(0.0)
        else:
            init = I.XavierUniform()
        # one ``param.init`` span per parameter on the trace ring: the
        # initializer's call as the host sees it (JAX dispatches the
        # values asynchronously)
        tracer = get_tracer()
        sp = tracer.begin("param.init", "param")
        try:
            value = init(tuple(int(s) for s in shape), dtype)
        finally:
            tracer.end(sp)
        name = attr.name if attr is not None and attr.name else None
        p = Parameter(value, trainable=not (attr is not None and not attr.trainable), name=name)
        if attr is not None:
            p.optimize_attr["learning_rate"] = attr.learning_rate
            p.regularizer = attr.regularizer
        return p

    def add_parameter(self, name: str, parameter: Optional[Parameter]) -> Optional[Parameter]:
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter or None")
        if parameter is None:
            self._parameters.pop(name, None)
            object.__setattr__(self, name, None)
        else:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer") -> "Layer":
        if not isinstance(sublayer, Layer):
            raise TypeError("add_sublayer expects a Layer")
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable: bool = True):
        """reference: Layer.register_buffer — non-parameter state
        (e.g. BatchNorm running stats) carried in state_dict."""
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        else:
            self._non_persistable_buffer_names.discard(name)

    # ------------------------------------------------------------ traversal
    def named_sublayers(self, prefix: str = "", include_self: bool = False,
                        layers_set=None) -> Iterator:
        if layers_set is None:
            layers_set = set()
        if include_self and id(self) not in layers_set:
            layers_set.add(id(self))
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None or id(layer) in layers_set:
                continue
            sub_prefix = prefix + ("." if prefix else "") + name
            yield from layer.named_sublayers(
                prefix=sub_prefix, include_self=True, layers_set=layers_set
            )

    def sublayers(self, include_self: bool = False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_children(self):
        seen = set()
        for name, layer in self._sub_layers.items():
            if layer is not None and id(layer) not in seen:
                seen.add(id(layer))
                yield name, layer

    def children(self):
        for _, l in self.named_children():
            yield l

    def named_parameters(self, prefix: str = "", include_sublayers: bool = True):
        seen = set()
        layers = [(prefix, self)]
        if include_sublayers:
            layers += [
                (prefix + ("." if prefix else "") + n, l)
                for n, l in self.named_sublayers()
            ]
        for lp, layer in layers:
            for name, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield lp + ("." if lp else "") + name, p

    def parameters(self, include_sublayers: bool = True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix: str = "", include_sublayers: bool = True):
        seen = set()
        layers = [(prefix, self)]
        if include_sublayers:
            layers += [
                (prefix + ("." if prefix else "") + n, l)
                for n, l in self.named_sublayers()
            ]
        for lp, layer in layers:
            for name, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield lp + ("." if lp else "") + name, b

    def buffers(self, include_sublayers: bool = True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def apply(self, fn: Callable) -> "Layer":
        for layer in self.children():
            layer.apply(fn)
        fn(self)
        return self

    def full_name(self) -> str:
        return self._full_name

    # ------------------------------------------------------------ state_dict
    def state_dict(self, destination=None, include_sublayers: bool = True,
                   structured_name_prefix: str = "", use_hook: bool = True):
        """reference: Layer.state_dict (nn/layer/layers.py) — an ordered
        {structured_name: Tensor} mapping of params + persistable buffers."""
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self._parameters.items():
            if p is not None:
                dest[structured_name_prefix + name] = p
        for name, b in self._buffers.items():
            if b is not None and name not in self._non_persistable_buffer_names:
                dest[structured_name_prefix + name] = b
        if include_sublayers:
            for name, layer in self._sub_layers.items():
                if layer is not None:
                    layer.state_dict(
                        destination=dest,
                        include_sublayers=True,
                        structured_name_prefix=structured_name_prefix + name + ".",
                    )
        return dest

    def set_state_dict(self, state_dict, use_structured_name: bool = True):
        """reference: Layer.set_state_dict. Copies values INTO the existing
        parameter cells (in-place _set_value) so optimizers/jit captures keep
        their references. Returns (missing_keys, unexpected_keys)."""
        own = self.state_dict()
        missing, matched = [], set()
        for name, target in own.items():
            if name not in state_dict:
                missing.append(name)
                continue
            src = state_dict[name]
            arr = src._value if isinstance(src, Tensor) else jnp.asarray(np.asarray(src))
            if tuple(arr.shape) != tuple(target._value.shape):
                raise ValueError(
                    f"shape mismatch for {name}: got {tuple(arr.shape)}, "
                    f"expected {tuple(target._value.shape)}"
                )
            target._set_value(arr.astype(target._value.dtype))
            matched.add(name)
        unexpected = [k for k in state_dict if k not in matched and k not in own]
        return missing, unexpected

    # paddle aliases
    load_dict = set_state_dict
    set_dict = set_state_dict

    # ---------------------------------------------------------------- modes
    def train(self):
        object.__setattr__(self, "training", True)
        for layer in self.sublayers():
            object.__setattr__(layer, "training", True)
        return self

    def eval(self):
        object.__setattr__(self, "training", False)
        for layer in self.sublayers():
            object.__setattr__(layer, "training", False)
        return self

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    # ---------------------------------------------------------------- dtype
    def _transform(self, fn):
        for layer in self.sublayers(include_self=True):
            for d in (layer._parameters, layer._buffers):
                for name, t in d.items():
                    if t is not None:
                        t._set_value(fn(t._value))
        return self

    def astype(self, dtype):
        dt = dtypes.convert_dtype(dtype)
        return self._transform(lambda v: v.astype(dt) if jnp.issubdtype(v.dtype, jnp.floating) else v)

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self.astype(dtype)
        return self

    def float(self):
        return self.astype("float32")

    def bfloat16(self):
        return self.astype("bfloat16")

    # ---------------------------------------------------------------- hooks
    def _next_hook_id(self):
        hid = self.__dict__["_hook_id"]
        object.__setattr__(self, "_hook_id", hid + 1)
        return hid

    def register_forward_pre_hook(self, hook) -> HookRemoveHelper:
        hid = self._next_hook_id()
        self._forward_pre_hooks[hid] = hook
        return HookRemoveHelper(self._forward_pre_hooks, hid)

    def register_forward_post_hook(self, hook) -> HookRemoveHelper:
        hid = self._next_hook_id()
        self._forward_post_hooks[hid] = hook
        return HookRemoveHelper(self._forward_post_hooks, hid)

    # ---------------------------------------------------------------- call
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__} must implement forward()"
        )

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    # ---------------------------------------------------------------- repr
    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            sub = repr(layer).split("\n")
            sub = [sub[0]] + ["  " + s for s in sub[1:]]
            lines.append(f"({name}): " + "\n".join(sub))
        main = f"{type(self).__name__}({extra}"
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"
