"""Device management (parity: python/paddle/device/__init__.py:265 set_device).

On TPU, "device" selection is degenerate: there is one device type and
placement is controlled by shardings; these APIs exist for source parity.
"""

from __future__ import annotations

import jax


def is_tpu(device=None) -> bool:
    """True when the (first) device's platform is ``"tpu"`` — the single
    gate for Pallas kernels and TPU-only fast paths. A backend that fails
    to initialise raises here; it never reads as "no TPU"."""
    d = device if device is not None else jax.devices()[0]
    return d.platform == "tpu"


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_device():
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def set_device(device):
    return device


def device_count():
    return jax.device_count()


def is_compiled_with_cuda():
    return False


def synchronize(device=None):
    """Block until all async device work completes (cuda.synchronize parity)."""
    for d in jax.live_arrays():
        try:
            d.block_until_ready()
        # graft-lint: disable-next=swallowed-exception (deleted/donated
        # buffers raise on ready-wait; synchronize must visit the rest)
        except Exception:
            pass


class Stream:
    """XLA executes a single ordered stream per device; exposed for parity."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()


def current_stream(device=None):
    return Stream(device)
