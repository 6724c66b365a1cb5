"""Measured compute ceilings of the current chip.

MFU percentages in bench.py divide by the chip's NOMINAL peak (the row
for its ``device_kind`` in ``program_inventory._CHIP_TABLE``). This script
measures what the chip/XLA build actually sustains on the two kernel
families the models live on — a big bf16 matmul and a ResNet-core conv —
so the MFU denominator is auditable and re-checkable when the chip or
toolchain changes.

Run directly (`python tools/chip_ceiling.py`) or let bench.py emit the
same numbers as `ceiling_matmul_tflops` / `ceiling_conv_tflops`.

Every timed region ends by reading a host scalar off the result, which
waits for the device.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _sync(x):
    return float(jnp.sum(x.astype(jnp.float32)))


def _time_chained(op, x0, w, iters):
    """Time ``iters`` data-dependent applications of ``op`` inside ONE
    jitted program — per-call dispatch latency never enters the
    measurement, and the data dependence stops XLA from eliding the
    loop."""

    @jax.jit
    def chained(x, w_):
        def body(_, h):
            return op(h, w_)

        return jax.lax.fori_loop(0, iters, body, x)

    _sync(chained(x0, w))  # compile + warm
    t0 = time.perf_counter()
    _sync(chained(x0, w))
    return (time.perf_counter() - t0) / iters


def matmul_ceiling(n=8192, iters=20, dtype=jnp.bfloat16):
    """Sustained TF/s of an [n,n] @ [n,n] bf16 matmul (MXU roofline)."""
    k = jax.random.PRNGKey(0)
    a = jax.random.normal(k, (n, n), dtype)
    b = jax.random.normal(k, (n, n), dtype) * 0.01  # keep the chain finite
    dt = _time_chained(lambda h, w: h @ w, a, b, iters)
    return 2.0 * n * n * n / dt / 1e12


def conv_ceiling(batch=128, hw=28, cin=256, cout=256, iters=20,
                 dtype=jnp.bfloat16):
    """Sustained TF/s of a ResNet-core 3x3 conv (NHWC, same padding;
    cin == cout so the loop chains)."""
    k = jax.random.PRNGKey(1)
    x = jax.random.normal(k, (batch, hw, hw, cin), dtype)
    w = jax.random.normal(k, (3, 3, cin, cout), dtype) * 0.03
    op = lambda h, w_: jax.lax.conv_general_dilated(
        h, w_, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    dt = _time_chained(op, x, w, iters)
    flops = 2.0 * batch * hw * hw * cout * 3 * 3 * cin
    return flops / dt / 1e12


def membw_ceiling(mb=512, iters=20, dtype=jnp.float32):
    """Sustained GB/s of a streaming triad ``h = h * c + w`` over an
    ``mb``-MiB array (reads h and w, writes h: 3 touches per element) —
    the HBM-bandwidth roofline denominator that
    ``serving_decode_bandwidth_util`` divides by when the nominal table
    in ``chip_specs()`` is being audited."""
    n = int(mb * 2 ** 20 / np.dtype(np.float32).itemsize)
    k = jax.random.PRNGKey(2)
    h = jax.random.normal(k, (n,), dtype)
    w = jax.random.normal(k, (n,), dtype) * 1e-3
    dt = _time_chained(lambda h_, w_: h_ * 0.999 + w_, h, w, iters)
    return 3.0 * h.nbytes / dt / 1e9


def measure(iters=10):
    """Matmul, ResNet-core conv, ideal conv and streaming-triad ceilings
    beside the nominal peaks (``chip_specs()``: an unknown chip raises, a
    CPU has none). Conv scales with channels, so both a model-shaped and
    an ideal conv are emitted: the first is the honest MFU denominator for
    ResNet, the second is the hardware's. Values on this round's chip:
    not measured."""
    from paddle_tpu.observability.program_inventory import chip_specs

    nominal = chip_specs()
    if nominal is None:
        raise RuntimeError("chip_ceiling needs a chip: the CPU has no "
                           "nominal peak to audit")
    # best of 2: a ceiling is a MAX by meaning
    best = lambda f: max(f(), f())
    return {
        "ceiling_matmul_tflops": round(
            best(lambda: matmul_ceiling(16384, iters=iters)), 1),
        "ceiling_conv_resnet_tflops": round(
            best(lambda: conv_ceiling(256, 28, 256, 256, iters=iters)), 1),
        "ceiling_conv_ideal_tflops": round(
            best(lambda: conv_ceiling(256, 28, 1024, 1024, iters=iters)), 1),
        "ceiling_membw_gbs": round(
            best(lambda: membw_ceiling(iters=iters)), 1),
        # the nominal table the roofline gauges (train_mfu,
        # serving_decode_bandwidth_util) divide by — emitted side by side
        # so a drifting toolchain shows up as measured-vs-nominal skew
        "nominal_peak_tflops": nominal["peak_tflops"],
        "nominal_peak_membw_gbs": nominal["peak_membw_gbs"],
        "device": str(jax.devices()[0].device_kind),
    }


if __name__ == "__main__":
    print(json.dumps(measure()))
