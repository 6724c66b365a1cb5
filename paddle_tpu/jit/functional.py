"""Functionalization: run imperative Layer code under a jax trace.

This is the TPU-native replacement for the reference's entire graph-capture
machinery (dy2static AST transforms + SOT bytecode capture,
python/paddle/jit/): because every paddle_tpu op is a jax op on the Tensor's
payload, *tracing the imperative code directly with jax.jit* captures the
graph — no source rewriting, no bytecode interception. Mutable state (params,
buffers, RNG) is threaded in/out explicitly by temporarily swapping tracer
values into the live Tensor handles.
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Sequence, Tuple

import jax

from paddle_tpu.tensor import Tensor


def collect_state(layer) -> Tuple[Dict[str, Tensor], Dict[str, Tensor]]:
    """(params, buffers) name->Tensor for a Layer: the uncached walk, two
    recursive passes over the whole tree. A caller on a hot path reads
    ``StaticFunction._state_tensors()`` instead, which keeps the lists
    until a Layer registry is written (``nn/layer_base.py::_TreeEpoch``)."""
    params = dict(layer.named_parameters())
    buffers = dict(layer.named_buffers())
    return params, buffers


@contextlib.contextmanager
def swap_values(tensors: Sequence[Tensor], values):
    """Temporarily replace each Tensor's payload (and cut its history)."""
    saved = [(t._value, t._node) for t in tensors]
    try:
        for t, v in zip(tensors, values):
            t._value = v
            t._node = None
        yield
    finally:
        for t, (v, n) in zip(tensors, saved):
            t._value = v
            t._node = n


def tree_unwrap(obj):
    """Tensor -> jax array, recursively through containers."""
    if isinstance(obj, Tensor):
        return obj._value
    if isinstance(obj, dict):
        return {k: tree_unwrap(v) for k, v in obj.items()}
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):  # namedtuple
        return type(obj)(*(tree_unwrap(v) for v in obj))
    if isinstance(obj, (list, tuple)):
        return type(obj)(tree_unwrap(v) for v in obj)
    return obj


def tree_wrap(obj):
    """jax array -> Tensor, recursively."""
    if isinstance(obj, jax.Array) or hasattr(obj, "aval"):
        return Tensor._from_value(obj)
    if isinstance(obj, dict):
        return {k: tree_wrap(v) for k, v in obj.items()}
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):  # namedtuple
        return type(obj)(*(tree_wrap(v) for v in obj))
    if isinstance(obj, (list, tuple)):
        return type(obj)(tree_wrap(v) for v in obj)
    return obj
