"""RNG state management.

Parity with the reference's generator (paddle/phi/core/generator.h, python
paddle.seed) — TPU-native: state is a jax PRNG key, not a stateful Philox
engine. Random ops split the global key per call in eager mode; inside a
captured graph (to_static / TrainStep) a *traced* key can be pushed so that
randomness (dropout noise) is threaded functionally through the XLA program and
varies per step.
"""

from __future__ import annotations

import contextlib
import threading

import jax


class _RngState(threading.local):
    def __init__(self):
        # key is created LAZILY: jax.random.key materializes a device array,
        # and an import-time device touch would initialise the backend (and
        # take the chip) in processes that never use the framework RNG
        self.key = None
        self.traced_key = None  # set inside captured graphs
        self.counter = 0


_state = _RngState()


def _global_key():
    if _state.key is None:
        _state.key = jax.random.key(0)
    return _state.key


def seed(s: int) -> None:
    """paddle.seed parity."""
    _state.key = jax.random.key(int(s))
    _state.counter = 0


def get_rng_state():
    return (_global_key(), _state.counter)


def set_rng_state(st) -> None:
    _state.key, _state.counter = st


def next_key():
    """Return a fresh PRNG key for one random op."""
    if _state.traced_key is not None:
        # Functional path: fold a trace-time counter into the traced key so
        # multiple random ops in one program get distinct streams.
        _state.counter += 1
        return jax.random.fold_in(_state.traced_key, _state.counter)
    _state.key, sub = jax.random.split(_global_key())
    return sub


def rng_state_to_host() -> dict:
    """Serialize the framework RNG state to a JSON-able dict (checkpointing:
    key bits + split counter + key impl, enough for bit-identical resume)."""
    import numpy as np

    key, counter = get_rng_state()
    data = np.asarray(jax.random.key_data(key))
    try:
        impl = str(jax.random.key_impl(key))
    except Exception:
        impl = None
    return {"key_data": data.tolist(), "dtype": str(data.dtype),
            "impl": impl, "counter": int(counter)}


def rng_state_from_host(st: dict) -> None:
    """Restore the framework RNG from ``rng_state_to_host`` output. The
    subsequent ``next_key`` stream is bit-identical to the capture point."""
    import numpy as np

    data = jax.numpy.asarray(
        np.asarray(st["key_data"], dtype=st.get("dtype", "uint32")))
    key = None
    impl = st.get("impl")
    if impl:
        try:
            key = jax.random.wrap_key_data(data, impl=impl)
        except Exception:
            key = None  # impl string from another jax version: use default
    if key is None:
        key = jax.random.wrap_key_data(data)
    set_rng_state((key, int(st.get("counter", 0))))


def np_rng():
    """A numpy Generator seeded from the framework RNG stream — host-side
    randomness (data pipeline shuffles, graph sampling) that reproduces
    under paddle.seed."""
    import jax
    import numpy as np

    key = next_key()
    seed = int(np.asarray(jax.random.key_data(key)).reshape(-1)[-1])
    return np.random.default_rng(seed & 0x7FFFFFFF)


@contextlib.contextmanager
def traced_key(key):
    """Thread a (possibly traced) key through random ops inside a capture."""
    prev, prev_ctr = _state.traced_key, _state.counter
    _state.traced_key = key
    _state.counter = 0
    try:
        yield
    finally:
        _state.traced_key, _state.counter = prev, prev_ctr
