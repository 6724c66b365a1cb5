"""Serving hot-path host-stall attribution + per-step flight recorder.

The serving mirror of ``train_stall.py``: ROADMAP item 4 (the async
zero-bubble serving engine) removes host-side scheduling work from the
critical path between device decode steps — this module ships the
MEASUREMENT first, so that refactor's win is provable rather than asserted.

- ``serving_host_stall_seconds{phase=...}`` — one labeled counter family
  (the first user of ``Counter.labels``) attributing every second the
  scheduler's ``step()`` spends on host work to a phase:

    * ``admission``       queue pops, request setup, slot packing
    * ``radix_match``     prefix-cache matching + pin bookkeeping
    * ``block_accounting``KV block alloc/extend/COW/preempt table rewrites
    * ``streaming``       per-token emit + user ``on_token`` callbacks
    * ``sampling_sync``   blocking ``.numpy()`` reads of sampled tokens —
                          the host<->device serialization the async engine
                          overlaps at ``dispatch_depth > 0``
    * ``dispatch``        host work building/enqueueing a device step in
                          the async engine (tensor staging, carry splice,
                          in-flight bookkeeping) — the residual critical-
                          path cost once the sync itself is overlapped.
                          The compiled-step invocation is excluded: it is
                          compute dispatch, not host scheduling (the same
                          rule that keeps prefill out of the family)
    * ``spec_propose``    host-side draft-token proposal (the n-gram
                          suffix match over each slot's committed
                          context) ahead of a speculative verify step

  The async engine's background drain thread meters its own device wait
  separately as ``serving_drain_wait_seconds`` (``record("drain", s)``):
  that wait overlaps in-flight decode, so it is deliberately NOT part of
  the critical-path stall family or its snapshot total.

- ``FlightRecorder`` — a bounded ring of per-step records (slot occupancy,
  prefill/decode token split, preemptions, cache hits, queue depth, free
  blocks): the last-N-iterations picture you dump when something is already
  wrong, on demand (``/debug/requests``) or on alarm.

- Alarms, RecompileStorm-style (loud warnings, not log lines):
  ``TTFTBreachStorm`` when ``streak`` consecutive finished requests breach
  the TTFT SLO, ``EvictionThrash`` when the prefix cache evicts in most of
  the recent steps (admissions and evictions are fighting over the pool).
  Both capture a flight-recorder dump at alarm time (``last_alarm_dump``).
"""

from __future__ import annotations

import threading
import time
import warnings
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

from paddle_tpu.observability.annotations import guarded_by
from paddle_tpu.observability.metrics import MetricsRegistry, get_registry
from paddle_tpu.profiler import RecordEvent

__all__ = [
    "AlarmMonitors",
    "EvictionThrash",
    "FlightRecorder",
    "STALL_PHASES",
    "ServingStall",
    "TTFTBreachStorm",
]

STALL_PHASES = ("admission", "radix_match", "block_accounting", "streaming",
                "sampling_sync", "dispatch", "spec_propose")

_STALL = "host_stall_seconds"
_DRAIN = "drain_wait_seconds"


class TTFTBreachStorm(UserWarning):
    """Consecutive requests finished over the TTFT SLO target."""


class EvictionThrash(UserWarning):
    """The prefix cache is evicting on most recent steps (pool thrash)."""


class ServingStall:
    """Phase-attributed host-stall accounting over one registry.

    ``registry=None`` records into the process-wide default registry under
    the full name ``serving_host_stall_seconds``; a scheduler passes its own
    ``serving``-namespaced ServingMetrics registry so the breakdown rides
    that instance's snapshot/prometheus surface instead.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        if registry is None:
            registry = get_registry()
            name = f"serving_{_STALL}"
            drain = f"serving_{_DRAIN}"
        else:
            # a serving-namespaced registry already prefixes "serving_"
            pre = "" if registry.namespace else "serving_"
            name = pre + _STALL
            drain = pre + _DRAIN
        self._family = registry.counter(
            name, "seconds of host-side scheduling work on the serving "
                  "critical path, by phase", unit="s")
        self._phase = {p: self._family.labels(phase=p)
                       for p in STALL_PHASES}
        # the async engine's drain thread blocks on the device HERE instead
        # of on the critical path — a separate counter, not a stall phase:
        # folding it into the family would re-count overlapped device time
        # as host stall and erase exactly the win the family measures
        self._drain_wait = registry.counter(
            drain, "seconds the background drain thread spent blocked on "
                   "device token fetches (overlapped with in-flight "
                   "decode — NOT critical-path host stall)", unit="s")

    def record(self, phase: str, seconds: float):
        if phase == "drain":
            self._drain_wait.inc(max(float(seconds), 0.0))
            return
        c = self._phase.get(phase)
        if c is None:
            raise KeyError(f"unknown serving stall phase {phase!r} "
                           f"(known: {STALL_PHASES} + 'drain')")
        c.inc(max(float(seconds), 0.0))

    @contextmanager
    def timed(self, phase: str):
        """Add the block's host time to ``phase``, under the span
        ``serving.<phase>``: the counter and the span in the profiler's
        trace are opened here together, so they cover the same code."""
        t0 = time.perf_counter()
        with RecordEvent(f"serving.{phase}"):
            try:
                yield
            finally:
                self.record(phase, time.perf_counter() - t0)

    def seconds(self, phase: str) -> float:
        return self._phase[phase].value

    @property
    def drain_wait_seconds(self) -> float:
        """Device wait accumulated by the async drain thread (overlapped
        time — excluded from ``total()``/``snapshot()`` by design)."""
        return self._drain_wait.value

    def total(self) -> float:
        return sum(c.value for c in self._phase.values())

    def snapshot(self) -> Dict[str, float]:
        out = {p: self._phase[p].value for p in STALL_PHASES}
        out["total"] = self.total()
        return out


class FlightRecorder:
    """Bounded ring of per-step scheduler records, dumpable on demand.

    One ``record_step(**fields)`` per scheduler iteration; the ring holds
    the last ``max_steps``. ``dump()`` returns a JSON-able list (oldest
    first). Alarm hooks snapshot the ring into ``last_alarm_dump`` so the
    iterations AROUND the incident survive even after the ring rolls on.

    Thread contract: the scheduler thread records while the endpoint
    thread dumps — ring, step counter, and the frozen alarm snapshot are
    all touched under ``_lock``.
    """

    _ring: guarded_by("_lock")
    _step: guarded_by("_lock")
    _last_alarm: guarded_by("_lock")
    _on_alarm: guarded_by("_lock")
    _cb_errors: guarded_by("_lock")

    def __init__(self, max_steps: int = 256):
        self.max_steps = int(max_steps)
        self._ring: deque = deque(maxlen=self.max_steps)
        self._lock = threading.Lock()
        self._step = 0
        self._last_alarm: Optional[Dict[str, object]] = None
        self._on_alarm = None
        self._cb_errors = 0

    def set_alarm_callback(self, cb) -> None:
        """``cb(kind, reason, alarm_dict)`` runs on every ``alarm()`` —
        the postmortem auto-capture hook. One callback slot (last wins);
        invoked OUTSIDE ``_lock`` so it may snapshot anything."""
        with self._lock:
            self._on_alarm = cb

    def record_step(self, **fields):
        with self._lock:
            self._step += 1
            fields["step"] = self._step
            fields["t"] = time.perf_counter()
            self._ring.append(fields)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    @property
    def steps_recorded(self) -> int:
        with self._lock:
            return self._step

    @property
    def alarm_callback_errors(self) -> int:
        with self._lock:
            return self._cb_errors

    @property
    def last_alarm_dump(self) -> Optional[Dict[str, object]]:
        with self._lock:
            return self._last_alarm

    def dump(self, last: Optional[int] = None) -> List[dict]:
        with self._lock:
            rows = list(self._ring)
        return rows[-last:] if last else rows

    def alarm(self, kind: str, reason: str):
        """Freeze the ring around an incident (called by alarm monitors);
        then fire the registered postmortem callback, outside the lock —
        it snapshots state that takes its own locks."""
        dump = self.dump()
        alarm = {
            "kind": kind, "reason": reason, "t": time.perf_counter(),
            "steps": dump,
        }
        with self._lock:
            self._last_alarm = alarm
            cb = self._on_alarm
        if cb is not None:
            try:
                cb(kind, reason, alarm)
            except Exception as e:
                # the capture path must never kill the alarm; the frozen
                # snapshot records that its auto-capture failed and why
                err = f"{type(e).__name__}: {e}"
                with self._lock:
                    self._cb_errors += 1
                    alarm["capture_error"] = err


class AlarmMonitors:
    """TTFT-breach-storm and eviction-thrash detectors over scheduler
    signals; owned by the scheduler, firing loud warnings + flight dumps."""

    def __init__(self, flight: Optional[FlightRecorder] = None, *,
                 ttft_streak: int = 4, thrash_window: int = 32,
                 thrash_frac: float = 0.5):
        self.flight = flight
        self.ttft_streak = int(ttft_streak)
        self._breach_run = 0
        self._storm_fired = False
        self.thrash_window = int(thrash_window)
        self.thrash_frac = float(thrash_frac)
        self._evict_steps: deque = deque(maxlen=self.thrash_window)
        self._thrash_fired = False

    # ---- TTFT breach storm --------------------------------------------
    def observe_ttft(self, breached: bool, ttft_s, target_s):
        if not breached:
            self._breach_run = 0
            self._storm_fired = False
            return
        self._breach_run += 1
        if self._breach_run >= self.ttft_streak and not self._storm_fired:
            self._storm_fired = True
            reason = (f"{self._breach_run} consecutive requests breached "
                      f"the TTFT SLO ({ttft_s:.3f}s latest vs "
                      f"{target_s:.3f}s target)")
            if self.flight is not None:
                self.flight.alarm("ttft_breach_storm", reason)
            warnings.warn(TTFTBreachStorm(
                f"TTFT breach storm: {reason} — inspect the flight-recorder "
                f"dump (queue depth vs prefill head-of-line vs preemption)"),
                stacklevel=3)

    # ---- eviction thrash ----------------------------------------------
    def observe_evictions(self, evicted_blocks_this_step: int):
        self._evict_steps.append(1 if evicted_blocks_this_step > 0 else 0)
        if len(self._evict_steps) < self.thrash_window:
            return
        frac = sum(self._evict_steps) / len(self._evict_steps)
        if frac >= self.thrash_frac and not self._thrash_fired:
            self._thrash_fired = True
            reason = (f"prefix cache evicted blocks in {frac:.0%} of the "
                      f"last {len(self._evict_steps)} steps")
            if self.flight is not None:
                self.flight.alarm("eviction_thrash", reason)
            warnings.warn(EvictionThrash(
                f"eviction thrash: {reason} — the KV pool is too small for "
                f"the working set; admissions and cached prefixes are "
                f"fighting over blocks"), stacklevel=3)
        elif frac < self.thrash_frac:
            self._thrash_fired = False
