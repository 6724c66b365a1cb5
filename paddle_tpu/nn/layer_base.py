"""nn.Layer: the stateful module base class.

Parity with the reference's ``paddle.nn.Layer``
(python/paddle/nn/layer/layers.py:353): parameter/buffer/sublayer registries,
state_dict round-trip, hooks, train/eval mode, apply/to. Parameters are
``paddle_tpu.Parameter`` handles over jax.Arrays, so a whole Layer's state
flows through jit/pjit as a pytree via ``state_dict``.
"""

from __future__ import annotations

import collections
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from paddle_tpu.framework import dtype as dtypes
from paddle_tpu.nn import initializer as I
from paddle_tpu.tensor import Parameter, Tensor


class HookRemoveHelper:
    _next_id = 0

    def __init__(self, hooks: dict):
        self._hooks = hooks
        self._id = HookRemoveHelper._next_id
        HookRemoveHelper._next_id += 1

    def remove(self):
        self._hooks.pop(self._id, None)


class _TreeEpoch:
    """Process-wide change counters of every Layer tree. ``structure``
    advances whenever what some ``named_parameters()`` / ``named_buffers()``
    / ``sublayers()`` would return may have changed (a write to any Layer's
    registries); ``mode`` whenever a ``training`` flag changes or the
    structure does (a sublayer brings its own flag). A reader that derived
    something from a tree keeps it with the counter it read BEFORE the walk
    and re-derives when the counter has moved: nobody polls the tree. A
    tensor's value (``_replace_value``, an optimizer step, ``.to(dtype)``)
    is not structure and advances neither."""

    __slots__ = ("structure", "mode")

    def __init__(self):
        self.structure = 0
        self.mode = 0


_epoch = _TreeEpoch()


def structure_epoch() -> int:
    return _epoch.structure


def mode_epoch() -> int:
    return _epoch.mode


def _structure_changed():
    _epoch.structure += 1
    _epoch.mode += 1


class _Registry(dict):
    """A Layer's parameter, buffer or sublayer table: a dict (insertion-
    ordered) whose every mutating method advances the structure epoch, so
    direct writes (``layer._sub_layers[name] = q``, a container's
    ``clear()`` + refill) invalidate as registration does."""

    __slots__ = ()

    def __setitem__(self, key, value):
        _structure_changed()
        dict.__setitem__(self, key, value)

    def __delitem__(self, key):
        _structure_changed()
        dict.__delitem__(self, key)

    def pop(self, *args):
        _structure_changed()
        return dict.pop(self, *args)

    def popitem(self):
        _structure_changed()
        return dict.popitem(self)

    def clear(self):
        _structure_changed()
        dict.clear(self)

    def update(self, *args, **kwargs):
        _structure_changed()
        dict.update(self, *args, **kwargs)

    def setdefault(self, key, default=None):
        _structure_changed()
        return dict.setdefault(self, key, default)

    def __ior__(self, other):
        _structure_changed()
        return dict.__ior__(self, other)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = dtypes.convert_dtype(dtype)
        self._parameters: Dict[str, Parameter] = _Registry()
        self._buffers: Dict[str, Tensor] = _Registry()
        self._sub_layers: Dict[str, "Layer"] = _Registry()
        self._forward_pre_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._forward_post_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._name_scope = name_scope or self.__class__.__name__.lower()
        self._non_persistable_buffer_names = set()

    # ------------------------------------------------------------ attribute routing
    def __setattr__(self, name, value):
        if name == "training":
            if self.__dict__.get("training") is not value:
                _epoch.mode += 1
            object.__setattr__(self, name, value)
            return
        params = self.__dict__.get("_parameters")
        if params is not None and isinstance(value, Parameter):
            params[name] = value
            self.__dict__.pop(name, None)
            return
        layers = self.__dict__.get("_sub_layers")
        if layers is not None and isinstance(value, Layer):
            layers[name] = value
            self.__dict__.pop(name, None)
            return
        if params is not None and name in params:
            if value is None:
                del params[name]
            else:
                params[name] = value
            return
        if layers is not None and name in layers:
            if value is None:
                del layers[name]
            else:
                layers[name] = value
            return
        buffers = self.__dict__.get("_buffers")
        if buffers is not None and name in buffers:
            if isinstance(value, Tensor):
                buffers[name] = value
            elif value is None:
                del buffers[name]
            else:
                buffers[name]._replace_value(jnp.asarray(value))
            return
        object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{self.__class__.__name__}' object has no attribute {name!r}"
        )

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        extra = []
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d:
                extra.extend(d.keys())
        return list(super().__dir__()) + extra

    # ---------------------------------------------------------------- registration
    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable: bool = True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None) -> Parameter:
        """paddle Layer.create_parameter parity (layers.py create_parameter)."""
        dtype = dtypes.convert_dtype(dtype) or self._dtype
        init = default_initializer
        name = None
        learning_rate = 1.0
        regularizer = None
        if attr is not None and attr is not False:
            from paddle_tpu.nn.param_attr import ParamAttr

            if isinstance(attr, ParamAttr):
                init = attr.initializer or init
                name = attr.name
                learning_rate = attr.learning_rate
                regularizer = getattr(attr, "regularizer", None)
            elif isinstance(attr, I.Initializer):
                init = attr
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierUniform()
        value = init(tuple(shape), dtype)
        p = Parameter(value, trainable=True, name=name or "")
        p.optimize_attr = {"learning_rate": learning_rate}
        if regularizer is not None:
            # per-param paddle.regularizer override, honored by
            # Optimizer.step (optimizer.py step loop)
            p.regularizer = regularizer
        return p

    def create_tensor(self, name=None, persistable=False, dtype=None):
        t = Tensor(jnp.zeros((), dtype=dtypes.convert_dtype(dtype) or self._dtype))
        t.persistable = persistable
        return t

    # --------------------------------------------------------------------- queries
    def parameters(self, include_sublayers=True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (prefix + name if not prefix else prefix + "." + name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = prefix + "." + lname if prefix else lname
                for n, p in layer.named_parameters(prefix=sub_prefix):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def buffers(self, include_sublayers=True) -> List[Tensor]:
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (prefix + "." + name if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = prefix + "." + lname if prefix else lname
                yield from layer.named_buffers(prefix=sub_prefix)

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self):
        for name, l in self._sub_layers.items():
            if l is not None:
                yield name, l

    def sublayers(self, include_self=False) -> List["Layer"]:
        out = []
        if include_self:
            out.append(self)
        for l in self.children():
            out.append(l)
            out.extend(l.sublayers())
        return out

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, l in self.named_children():
            sub_prefix = prefix + "." + name if prefix else name
            yield sub_prefix, l
            yield from l.named_sublayers(prefix=sub_prefix)

    # ---------------------------------------------------------------------- modes
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

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dt = dtypes.convert_dtype(dtype)
            for p in self.parameters():
                if dtypes.is_floating_point(p.dtype):
                    p._replace_value(p._value.astype(dt))
            for b in self.buffers():
                if b is not None and dtypes.is_floating_point(b.dtype):
                    b._replace_value(b._value.astype(dt))
            self._dtype = dt
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def half(self):
        return self.to(dtype="float16")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # ----------------------------------------------------------------- state dict
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix.rstrip(".")):
            dest[name] = p
        for name, b in self.named_buffers(prefix=structured_name_prefix.rstrip(".")):
            leaf = name.rsplit(".", 1)[-1]
            if leaf not in self._non_persistable_buffer_names:
                dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        missing, unexpected = [], []
        own = self.state_dict()
        for name, t in own.items():
            if name in state_dict:
                src = state_dict[name]
                v = src._value if isinstance(src, Tensor) else jnp.asarray(np.asarray(src))
                if tuple(v.shape) != tuple(t._value.shape):
                    raise ValueError(
                        f"shape mismatch for {name}: {v.shape} vs {t._value.shape}"
                    )
                t._replace_value(v.astype(t._value.dtype))
            else:
                missing.append(name)
        for name in state_dict:
            if name not in own:
                unexpected.append(name)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # ---------------------------------------------------------------------- hooks
    def register_forward_pre_hook(self, hook):
        helper = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[helper._id] = hook
        return helper

    def register_forward_post_hook(self, hook):
        helper = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[helper._id] = hook
        return helper

    # ----------------------------------------------------------------------- call
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

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

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [f"{self.__class__.__name__}({extra}"]
        child_lines = []
        for name, l in self.named_children():
            child_repr = repr(l).replace("\n", "\n  ")
            child_lines.append(f"  ({name}): {child_repr}")
        if child_lines:
            return lines[0] + "\n" + "\n".join(child_lines) + "\n)"
        return f"{self.__class__.__name__}({extra})"

    def full_name(self):
        return self._name_scope

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()
