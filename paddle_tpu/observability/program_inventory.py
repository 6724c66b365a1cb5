"""ProgramInventory: XLA cost/memory analysis for every compiled program.

The CompileTracker already knows *when* each executable compiled
(TrainStep, SlotStep decode, every prefill bucket); this module records
*what each one costs*: FLOPs, bytes accessed, peak temp memory, argument
and output buffer sizes, and the donation (aliasing) map — the numbers
ROADMAP items 1 and 3 state their acceptance bars in.

How it stays off the hot path:

- **Capture is shape-only.** The jit wrappers call ``capture`` exactly
  once per newly compiled program (they detect program-cache growth, the
  same probe the CompileTracker uses) and hand over ShapeDtypeStruct
  pytrees — no device buffers are retained, so donation and pool
  rotation are untouched.
- **Analysis is lazy and AOT.** ``analyze`` re-lowers the jitted
  function against the captured specs via ``jit(...).lower().compile()``
  and reads XLA's ``cost_analysis()`` / ``memory_analysis()``. AOT
  lowering does NOT grow the wrapper's runtime program cache, so the
  zero-steady-state-recompile invariant (and its RecompileStorm alarm)
  cannot trip from a `/debug/programs` scrape. Results are cached on the
  entry; the jitted reference is dropped after a successful analysis.

``chip_specs()`` and ``roofline_utilization`` turn a program's
FLOPs/bytes and a device time taken from a trace into utilisation
(``step_profile.py`` is the caller).
"""

from __future__ import annotations

import os
import threading
import warnings
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from paddle_tpu.observability.metrics import MetricsRegistry, get_registry
from paddle_tpu.profiler import RecordEvent

__all__ = [
    "ProgramEntry",
    "ProgramInventory",
    "chip_specs",
    "get_program_inventory",
    "roofline_utilization",
]

_DTYPE_SHORT = {
    "float32": "f32", "float64": "f64", "float16": "f16",
    "bfloat16": "bf16", "int32": "i32", "int64": "i64", "int8": "i8",
    "uint32": "u32", "uint8": "u8", "bool": "b1", "complex64": "c64",
}


def _spec_of(v):
    """ShapeDtypeStruct of one call-argument leaf (no device access —
    ``shape``/``dtype`` are aval-derived and stay readable on donated
    shells). Python scalars get their numpy-promoted dtype, which is a
    close-enough stand-in for jax weak types at cost-analysis fidelity."""
    import jax

    if isinstance(v, jax.ShapeDtypeStruct):
        return v
    if not isinstance(v, (jax.Array, np.ndarray)):
        # unwrap Tensor-style holders only: jax arrays expose their own
        # `_value` (a host materialization that RAISES on donated shells)
        v = getattr(v, "_value", v)
    shape = getattr(v, "shape", None)
    dtype = getattr(v, "dtype", None)
    if shape is None or dtype is None:
        arr = np.asarray(v)
        shape, dtype = arr.shape, arr.dtype
    try:
        dtype = np.dtype(dtype)
    except TypeError:
        pass    # jax extended dtype (e.g. typed PRNG keys): use as-is
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _tree_specs(tree):
    import jax

    return jax.tree_util.tree_map(_spec_of, tree)


def _signature(spec_trees) -> Tuple[str, ...]:
    import jax

    out = []
    for leaf in jax.tree_util.tree_leaves(spec_trees):
        try:
            name = np.dtype(leaf.dtype).name
        except TypeError:
            name = str(leaf.dtype)
        short = _DTYPE_SHORT.get(name, name)
        dims = ",".join(str(d) for d in leaf.shape)
        out.append(f"{short}[{dims}]")
    return tuple(out)


# ---------------------------------------------------------------- chip peaks

# device_kind (exactly as ``jax.devices()[0].device_kind`` reports it) ->
# (peak dense bf16 TFLOP/s, peak HBM GB/s, source). A row is added when its
# device_kind string has been SEEN on that chip, never guessed; a device that
# is not here is an error, not a default.
_CHIP_TABLE = {
    "TPU v5 lite": (197.0, 819.0,
                    "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                    "bf16, 819 GB/s HBM per chip; device_kind seen on the "
                    "chip in PR 21"),
}


def chip_specs(device_kind: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """Peak FLOP/s and HBM bandwidth of the current (or named) chip, from
    the one table above.

    ``None`` on the CPU backend: a host has no peaks here, so utilisation
    gauges are absent on CPU rather than computed. Any other device_kind
    missing from the table raises ``KeyError``.
    """
    if device_kind is None:
        import jax

        dev = jax.devices()[0]
        if dev.platform == "cpu":
            return None
        device_kind = dev.device_kind
    if device_kind not in _CHIP_TABLE:
        raise KeyError(
            f"no peak specs for device_kind {device_kind!r}: add a sourced "
            f"row to program_inventory._CHIP_TABLE (known: "
            f"{sorted(_CHIP_TABLE)})")
    tflops, membw, source = _CHIP_TABLE[device_kind]
    return {"device_kind": device_kind, "peak_tflops": tflops,
            "peak_membw_gbs": membw, "source": source}


def roofline_utilization(flops: float, bytes_accessed: float,
                         step_seconds: float,
                         specs: Optional[dict] = None
                         ) -> Optional[Dict[str, Any]]:
    """MFU + bandwidth utilization of one program at a measured step time,
    or ``None`` where the device has no peaks (``chip_specs()`` on CPU).

    Raw ratios are reported alongside the clamped ``(0, 1]`` gauges: a
    raw value > 1 means the peak spec is wrong (or the step time was
    under-measured), which is itself a finding worth surfacing.
    """
    specs = specs or chip_specs()
    if specs is None:
        return None
    step_seconds = max(float(step_seconds), 1e-12)
    mfu_raw = float(flops) / step_seconds / (specs["peak_tflops"] * 1e12)
    bw_raw = (float(bytes_accessed) / step_seconds
              / (specs["peak_membw_gbs"] * 1e9))
    return {
        "mfu": min(1.0, mfu_raw),
        "mfu_raw": mfu_raw,
        "bandwidth_util": min(1.0, bw_raw),
        "bandwidth_util_raw": bw_raw,
        "flops_per_s": float(flops) / step_seconds,
        "bytes_per_s": float(bytes_accessed) / step_seconds,
        "chip": specs,
    }


# ------------------------------------------------------------- the inventory

class ProgramEntry:
    """One compiled executable: captured call specs + lazy XLA analysis."""

    __slots__ = ("name", "kind", "signature", "specs", "static_kwargs",
                 "donate_argnums", "jitted", "analysis", "hlo")

    def __init__(self, name, kind, signature, specs, static_kwargs,
                 donate_argnums, jitted):
        self.name = name
        self.kind = kind
        self.signature = signature
        self.specs = specs
        self.static_kwargs = dict(static_kwargs or {})
        self.donate_argnums = tuple(donate_argnums or ())
        self.jitted = jitted          # dropped after successful analysis
        self.analysis: Optional[dict] = None
        self.hlo: Optional[str] = None  # optimized-HLO text, kept by analyze


def _normalize_cost(ca) -> dict:
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = dict(ca or {})
    return {
        "flops": float(ca.get("flops", 0.0) or 0.0),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
        "transcendentals": float(ca.get("transcendentals", 0.0) or 0.0),
    }


class ProgramInventory:
    """Process-wide registry of compiled-program costs.

    Thread contract: ``capture`` is called from whatever thread runs the
    jit wrapper (scheduler thread, train loop); ``snapshot``/``analyze``
    from the endpoint scrape thread or a bench — one lock covers the
    entry list, and analysis itself runs outside the lock (XLA compile
    can take seconds; holding the lock would stall capture).
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._lock = threading.Lock()
        self._entries: List[ProgramEntry] = []
        self._by_key: Dict[Tuple[str, Tuple[str, ...]], ProgramEntry] = {}
        self._reg = registry
        self.enabled = os.environ.get(
            "PADDLE_TPU_PROGRAM_INVENTORY", "1") != "0"

    # ---- capture (jit-wrapper side) ------------------------------------

    def capture(self, name: str, kind: str, jitted, arg_trees,
                static_kwargs: Optional[dict] = None,
                donate_argnums=()) -> Optional[ProgramEntry]:
        """Record one newly compiled program's call shape.

        ``arg_trees`` is the positional-argument tuple as passed to the
        jitted callable (values or ShapeDtypeStructs — converted to
        specs immediately, nothing is retained). Deduped on
        ``(name, signature)``; tolerant of already-consumed buffers (a
        capture that cannot read a shape is skipped, never raised)."""
        if not self.enabled:
            return None
        try:
            specs = tuple(_tree_specs(t) for t in arg_trees)
            sig = _signature(specs)
        except Exception:
            return None
        key = (name, sig)
        with self._lock:
            hit = self._by_key.get(key)
            if hit is not None:
                return hit
            entry = ProgramEntry(name, kind, sig, specs, static_kwargs,
                                 donate_argnums, jitted)
            self._entries.append(entry)
            self._by_key[key] = entry
        return entry

    # ---- analysis (scrape/bench side) ----------------------------------

    def analyze(self, entry: ProgramEntry) -> dict:
        """XLA cost + memory analysis for one entry (cached).

        AOT ``lower().compile()`` against the captured specs: a separate
        executable from the wrapper's runtime cache, so the tracked
        program count — and the zero-steady-state-recompile invariant —
        is untouched. The donated-buffer usability warning XLA:CPU emits
        for AOT donation hints is suppressed (expected, not actionable).
        """
        if entry.analysis is not None:
            return entry.analysis
        jitted = entry.jitted
        if jitted is None:
            entry.analysis = {"error": "jitted function no longer available"}
            return entry.analysis
        try:
            with RecordEvent("device.program_analysis"), \
                    warnings.catch_warnings():
                warnings.simplefilter("ignore")
                compiled = jitted.lower(
                    *entry.specs, **entry.static_kwargs).compile()
                try:
                    # optimized-HLO text carries op_name metadata (the
                    # named_scope paths step_profile attributes against);
                    # kept on the entry so region attribution still works
                    # after the jitted ref is dropped below
                    entry.hlo = compiled.as_text()
                except Exception:
                    entry.hlo = None
                out = _normalize_cost(compiled.cost_analysis())
                try:
                    ma = compiled.memory_analysis()
                except Exception:
                    ma = None
                if ma is not None:
                    out.update({
                        "argument_bytes":
                            int(getattr(ma, "argument_size_in_bytes", 0)),
                        "output_bytes":
                            int(getattr(ma, "output_size_in_bytes", 0)),
                        "alias_bytes":
                            int(getattr(ma, "alias_size_in_bytes", 0)),
                        "peak_temp_bytes":
                            int(getattr(ma, "temp_size_in_bytes", 0)),
                    })
            entry.analysis = out
            entry.jitted = None       # analysis cached; drop the strong ref
        except Exception as exc:
            entry.analysis = {"error": f"{type(exc).__name__}: {exc}"}
        return entry.analysis

    def hlo_text(self, entry: ProgramEntry) -> Optional[str]:
        """Optimized-HLO text for one entry (cached on the entry).

        Rides the same AOT compile ``analyze`` performs; ``None`` when
        the program can no longer be lowered (jitted ref already dropped
        by an earlier analyze on an older-schema entry, or compile
        failure — recorded in ``entry.analysis['error']``)."""
        if entry.hlo is None:
            self.analyze(entry)
        return entry.hlo

    # ---- queries --------------------------------------------------------

    def entries(self, name_contains: Optional[str] = None,
                kind: Optional[str] = None) -> List[ProgramEntry]:
        with self._lock:
            out = list(self._entries)
        if name_contains is not None:
            out = [e for e in out if name_contains in e.name]
        if kind is not None:
            out = [e for e in out if e.kind == kind]
        return out

    def snapshot(self, analyze: bool = True) -> dict:
        """The ``/debug/programs`` face; publishes ``compiled_program_*``
        gauges as a side effect when a registry is attached."""
        rows = []
        for i, e in enumerate(self.entries()):
            row = {
                "name": e.name,
                "kind": e.kind,
                "signature": list(e.signature),
                "static_kwargs": {k: repr(v)
                                  for k, v in e.static_kwargs.items()},
                "donate_argnums": list(e.donate_argnums),
            }
            if analyze:
                row["analysis"] = self.analyze(e)
            elif e.analysis is not None:
                row["analysis"] = e.analysis
            rows.append(row)
            an = row.get("analysis") or {}
            if self._reg is not None and "flops" in an:
                labels = {"program": f"{e.name}/{i}"}
                self._reg.gauge(
                    "compiled_program_flops",
                    "XLA cost-analysis FLOPs per program"
                ).labels(**labels).set(an["flops"])
                self._reg.gauge(
                    "compiled_program_bytes_accessed",
                    "XLA cost-analysis bytes accessed per program",
                    unit="bytes").labels(**labels).set(an["bytes_accessed"])
                self._reg.gauge(
                    "compiled_program_peak_temp_bytes",
                    "XLA peak temp allocation per program",
                    unit="bytes").labels(**labels).set(
                        an.get("peak_temp_bytes", 0))
        if self._reg is not None:
            self._reg.gauge(
                "compiled_program_count",
                "programs known to the inventory").set(len(rows))
        return {"programs": rows, "count": len(rows)}

    def reset(self) -> None:
        """Test hygiene: forget every captured program."""
        with self._lock:
            self._entries.clear()
            self._by_key.clear()


_inventory: Optional[ProgramInventory] = None
_inv_lock = threading.Lock()


def get_program_inventory() -> ProgramInventory:
    global _inventory
    with _inv_lock:
        if _inventory is None:
            _inventory = ProgramInventory(registry=get_registry())
        return _inventory
