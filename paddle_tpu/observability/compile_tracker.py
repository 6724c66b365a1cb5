"""CompileTracker: observe XLA (re)compilation at the framework's jit seams.

The dominant silent failure mode on TPU is the recompile storm: a shape or
dtype drifting call-to-call makes jax.jit trace+compile a fresh program every
step and a 5 ms decode step becomes 900 ms with no error anywhere. The
reference framework surfaces this through profiler summaries; here every jit
entry point (``jit.to_static`` StaticFunctions — which also carry dy2static
and SOT captures — ``jit.TrainStep``, the serving ``SlotStep``) probes its
program-cache size around each call and reports growth to the process-wide
tracker:

- ``compiles_total`` / ``compile_seconds`` metrics in the default
  ``MetricsRegistry`` (compile wall time is the duration of the call that
  triggered the compile: trace + XLA compile + first run);
- a ``CompileEvent`` per compile capturing the triggering abstract
  shapes/dtypes;
- after ``mark_steady()``, any further compile of a marked function is a
  steady-state recompile: a loud ``RecompileStorm`` warning fires and
  ``steady_state_recompiles_total`` increments — tests pin
  "zero steady-state recompiles" through this instead of ad-hoc counters.

Where available, jax's monitoring hooks additionally feed true backend
compile durations into ``jax_backend_compile_seconds``.
"""

from __future__ import annotations

import itertools
import threading
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from paddle_tpu.observability import metrics as _metrics


class RecompileStorm(UserWarning):
    """A function declared steady-state compiled again (recompile storm)."""


@dataclass
class CompileEvent:
    name: str
    seq: int
    wall_s: float
    signature: Tuple[str, ...] = ()
    steady_state: bool = False
    n_programs: int = 1
    ts: float = field(default_factory=time.time)

    def describe(self) -> str:
        sig = ", ".join(self.signature) or "<no array args>"
        return (f"compile #{self.seq} of {self.name} "
                f"({self.wall_s * 1e3:.1f} ms, args: {sig})")


def abstract_signature(*trees, limit: int = 32) -> Tuple[str, ...]:
    """dtype[shape] strings for every array-like leaf of the given pytrees —
    the abstract values a jit cache key is made of."""
    import jax

    from paddle_tpu.tensor import Tensor

    leaves = jax.tree_util.tree_leaves(
        trees, is_leaf=lambda x: isinstance(x, Tensor))
    out = []
    for leaf in leaves:
        if isinstance(leaf, Tensor):
            leaf = leaf._value
        try:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
        except RuntimeError:
            # a buffer consumed by the call being signed (donated batch
            # Tensors guard their payload): sign it by type, post-mortem
            out.append(type(leaf).__name__)
            if len(out) >= limit:
                out.append("...")
                break
            continue
        if shape is not None and dtype is not None:
            out.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
        else:
            out.append(type(leaf).__name__)
        if len(out) >= limit:
            out.append("...")
            break
    return tuple(out)


class CompileTracker:
    """Per-function compile accounting over a MetricsRegistry."""

    def __init__(self, registry: Optional[_metrics.MetricsRegistry] = None):
        # `is None`, not `or`: an empty registry is falsy (len == 0)
        reg = registry if registry is not None else _metrics.get_registry()
        self.registry = reg
        self.compiles_total = reg.counter(
            "compiles_total",
            "XLA program compilations observed at framework jit entry points")
        self.compile_seconds = reg.histogram(
            "compile_seconds",
            "wall time of calls that triggered a compile "
            "(trace + XLA compile + first run)", unit="s")
        self.steady_recompiles_total = reg.counter(
            "steady_state_recompiles_total",
            "compilations of functions already declared steady-state "
            "(recompile storms)")
        self.state_walks_total = reg.counter(
            "jit_state_walks_total",
            "walks of a Layer tree by a StaticFunction collecting its "
            "parameter and buffer lists (one after each structural change; "
            "growth in steady state means something rewrites a registry "
            "every step)")
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._steady_counts: Dict[str, int] = {}
        self._steady: set = set()
        self.events: List[CompileEvent] = []

    # ---------------------------------------------------------- recording
    def record(self, name: str, wall_s: float,
               signature: Tuple[str, ...] = (), n_programs: int = 1):
        """One observed compile (or ``n_programs`` of them in one call)."""
        with self._lock:
            steady = name in self._steady
            self._counts[name] = self._counts.get(name, 0) + n_programs
            seq = self._counts[name]
            if steady:
                self._steady_counts[name] = (
                    self._steady_counts.get(name, 0) + n_programs)
            ev = CompileEvent(name=name, seq=seq, wall_s=wall_s,
                              signature=tuple(signature),
                              steady_state=steady, n_programs=n_programs)
            self.events.append(ev)
        self.compiles_total.inc(n_programs)
        self.compile_seconds.record(wall_s)
        if steady:
            self.steady_recompiles_total.inc(n_programs)
            warnings.warn(RecompileStorm(
                f"recompile storm: steady-state {ev.describe()} — a shape or "
                f"dtype is drifting call-to-call; the hot loop is paying a "
                f"fresh XLA compile per step"), stacklevel=3)
        return ev

    # ------------------------------------------------------- steady state
    def mark_steady(self, name: Optional[str] = None):
        """Declare function(s) warmed up: further compiles are storms.
        ``None`` marks every function that has compiled at least once."""
        with self._lock:
            if name is None:
                self._steady.update(self._counts)
            else:
                self._steady.add(name)

    def clear_steady(self, name: Optional[str] = None):
        with self._lock:
            if name is None:
                self._steady.clear()
            else:
                self._steady.discard(name)

    def is_steady(self, name: str) -> bool:
        return name in self._steady

    # -------------------------------------------------------------- stats
    def compiles(self, name: Optional[str] = None) -> int:
        with self._lock:
            if name is None:
                return sum(self._counts.values())
            return self._counts.get(name, 0)

    def steady_state_recompiles(self, name: Optional[str] = None) -> int:
        with self._lock:
            if name is None:
                return sum(self._steady_counts.values())
            return self._steady_counts.get(name, 0)

    def events_for(self, name: str) -> List[CompileEvent]:
        with self._lock:
            return [e for e in self.events if e.name == name]

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {
                "compiles_total": sum(self._counts.values()),
                "steady_state_recompiles_total":
                    sum(self._steady_counts.values()),
                "per_fn": dict(self._counts),
                "steady_fns": sorted(self._steady),
            }

    def reset(self):
        with self._lock:
            self._counts.clear()
            self._steady_counts.clear()
            self._steady.clear()
            self.events.clear()


_seq = itertools.count()


def next_tracked_name(base: str) -> str:
    """Unique tracker key for one jit-entry instance: two StaticFunctions
    over the same python function are distinct program caches and must not
    share steady-state flags or counts."""
    return f"jit.{base}#{next(_seq)}"


_tracker: Optional[CompileTracker] = None
_tracker_lock = threading.Lock()


def get_compile_tracker() -> CompileTracker:
    """The process-wide tracker all jit entry points report into."""
    global _tracker
    if _tracker is None:
        with _tracker_lock:
            if _tracker is None:
                _tracker = CompileTracker()
                _attach_jax_monitoring(_tracker.registry)
    return _tracker


_monitoring_attached = False


def _attach_jax_monitoring(registry: _metrics.MetricsRegistry):
    """Feed jax's own backend-compile duration events (when this jax exposes
    the monitoring hook) into the registry — the true XLA compile time,
    without the trace/first-run overhead our call-level probe includes."""
    global _monitoring_attached
    if _monitoring_attached:
        return
    try:
        from jax import monitoring

        hist = registry.histogram(
            "jax_backend_compile_seconds",
            "XLA backend compile durations from jax monitoring events",
            unit="s")

        def _listener(event, duration, **kw):
            if "compile" in event:
                hist.record(duration)

        monitoring.register_event_duration_secs_listener(_listener)
        _monitoring_attached = True
    except (ImportError, AttributeError):
        pass  # this jax build has no monitoring API: tracking stays manual
