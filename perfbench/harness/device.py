"""The device as JAX reports it, the table of peaks, and compile counting."""

from __future__ import annotations

# Peaks of one chip, keyed by ``device_kind``. A kind that is not here is an
# error, never a default. (Copied from the program's
# observability/program_inventory.py::_CHIP_TABLE so that no later PR can
# move the yardstick; PERF.md lists the original under Open questions.)
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
    # 819 GB/s. ``TPU v5 lite`` is what JAX prints for it (chip run, PR 21).
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


class NoAccelerator(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"add a sourced row to perfbench/harness/device.py")
    return PEAKS[device_kind]


def describe(chips: int, rehearse_cpu: bool) -> dict:
    """Platform, kind and count of the devices this process holds. Raises
    ``NoAccelerator`` unless they are TPUs, at least ``chips`` of them (or
    CPUs, under the explicit rehearsal flag)."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    want = "cpu" if rehearse_cpu else "tpu"
    if info["platform"] != want:
        raise NoAccelerator(f"needs platform {want!r}, JAX found {info}")
    if info["count"] < chips:
        raise NoAccelerator(f"needs {chips} chips, JAX found {info}")
    return info


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the chips used; ``None`` where
    the backend keeps no such statistic (CPU)."""
    import jax

    peaks_ = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats()
        if st is None:
            return None
        peaks_.append(int(st["peak_bytes_in_use"]))
    return max(peaks_)


class CompileCounter:
    """Backend compiles of this process, from JAX's own monitoring events
    (every compile or cache read of any program, not only the runner's)."""

    def __init__(self):
        import jax.monitoring as mon

        self.count = 0
        mon.register_event_duration_secs_listener(self._duration)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1
