"""paddle.profiler parity (reference: python/paddle/profiler/profiler.py:346
Profiler, RecordEvent in event_tracing.h, Chrome-trace export in
chrometracing_logger.cc).

TPU-native: device-side tracing delegates to the XLA/XPlane profiler
(jax.profiler.start_trace — the CUPTI analogue), viewable in TensorBoard /
Perfetto. A host-side RecordEvent span goes to two sinks: an in-process ring
on ``time.perf_counter`` (only while a ``Profiler`` records), exported as a
Chrome trace JSON with summary statistics mirroring profiler_statistic.py;
and a ``jax.profiler.TraceAnnotation`` of the same name, so that every span
is also in any open profiler session's ``.xplane.pb`` on the clock of the
device's events (``Profiler.device_trace_dir`` says where this module's own
sessions write theirs)."""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from enum import Enum
from typing import Callable, Iterable, Optional

from jax.profiler import TraceAnnotation


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1  # accepted for parity; maps to the accelerator
    CUSTOM_DEVICE = 2


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class TracerEventType(Enum):
    Operator = 0
    Dataloader = 1
    ProfileStep = 2
    Forward = 3
    Backward = 4
    Optimization = 5
    Communication = 6
    PythonOp = 7
    UserDefined = 8


class _HostEventRecorder:
    def __init__(self):
        self._events = []
        self._lock = threading.Lock()
        self.enabled = False

    def record(self, name, etype, t0, t1):
        if not self.enabled:
            return
        with self._lock:
            self._events.append({
                "name": name, "cat": etype.name if etype else "UserDefined",
                "ph": "X", "pid": os.getpid(),
                "tid": threading.get_ident() % 100000,
                "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6,
            })

    def drain(self):
        with self._lock:
            ev, self._events = self._events, []
        return ev


_recorder = _HostEventRecorder()


class RecordEvent:
    """RAII/context host span (platform/profiler/event_tracing.h parity).

    One span, two sinks: the ring above while a ``Profiler`` records, and a
    ``TraceAnnotation`` of the same name, which lands in the trace of
    whatever profiler session is open (this module's, the benchmark's, a
    ``StepProfiler``'s) and is a no-op with none. ``begin`` and ``end`` run
    on one thread."""

    def __init__(self, name: str, event_type: TracerEventType = TracerEventType.UserDefined):
        self.name = name
        self.event_type = event_type
        self._t0 = None
        self._annotation = None

    def begin(self):
        self._t0 = time.perf_counter()
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()

    def end(self):
        if self._t0 is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
            _recorder.record(self.name, self.event_type, self._t0,
                             time.perf_counter())
            self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """profiler.make_scheduler parity."""
    period = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


_export_seq = itertools.count()


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready factory writing chrome trace json. Filenames carry a
    process-wide monotonic suffix so two snapshots landing within the same
    wall-clock second never overwrite each other."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(
            dir_name, f"{name}_{int(time.time())}_{next(_export_seq)}.json")
        with open(path, "w") as f:
            json.dump({"traceEvents": prof._last_events}, f)
        prof._exported_path = path

    return handler


class Profiler:
    """paddle.profiler.Profiler (profiler.py:346)."""

    def __init__(self, *, targets: Optional[Iterable] = None, scheduler=None,
                 on_trace_ready=None, record_shapes=False, profile_memory=False,
                 timer_only=False, emit_nvtx=False, custom_device_types=None,
                 with_flops=False, device_trace_dir: Optional[str] = None):
        self._scheduler = scheduler if callable(scheduler) else None
        if isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self._scheduler = make_scheduler(
                closed=lo, ready=0, record=hi - lo, skip_first=0)
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self.step_num = 0
        self._state = ProfilerState.CLOSED
        self._device_tracing = False
        # where the XLA profiler session of each recording window writes its
        # .xplane.pb (<dir>/plugins/profile/<time>/): device events and every
        # RecordEvent span, on one clock. A temporary directory, made when
        # the first window opens, unless the caller names one.
        self.device_trace_dir = device_trace_dir
        self._last_events = []
        self._exported_path = None
        self._step_times = []
        self._step_t0 = None

    # ------------------------------------------------------------- lifecycle
    def start(self):
        self._state = (self._scheduler(self.step_num)
                       if self._scheduler else ProfilerState.RECORD)
        self._sync_recorder()
        self._maybe_start_device_trace()
        self._step_t0 = time.perf_counter()

    def _sync_recorder(self):
        _recorder.enabled = (not self._timer_only) and self._state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)

    def _maybe_start_device_trace(self):
        if self._timer_only or self._device_tracing:
            return
        if self._state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            import jax

            import tempfile

            self.device_trace_dir = self.device_trace_dir or tempfile.mkdtemp(
                prefix="paddle_tpu_xplane_")
            # the spans are TraceAnnotations, so Python call tracing adds
            # nothing but megabytes and host time
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            try:
                jax.profiler.start_trace(self.device_trace_dir,
                                         profiler_options=opts)
                self._device_tracing = True
            except Exception:
                self._device_tracing = False

    def _maybe_stop_device_trace(self):
        if self._device_tracing:
            import jax

            try:
                jax.profiler.stop_trace()
            finally:
                self._device_tracing = False

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._step_t0 is not None:
            self._step_times.append(now - self._step_t0)
        self._step_t0 = now
        self.step_num += 1
        if self._scheduler:
            new_state = self._scheduler(self.step_num)
            if new_state != self._state:
                recording = (ProfilerState.RECORD,
                             ProfilerState.RECORD_AND_RETURN)
                # snapshot on ANY exit from a recording state (CLOSED *or*
                # READY) — a RECORD→READY transition used to silently drop
                # every event of the window it just recorded
                if self._state in recording and new_state not in recording:
                    self._snapshot()
                self._state = new_state
                self._sync_recorder()
                self._maybe_start_device_trace()

    def _snapshot(self):
        self._last_events = _recorder.drain()
        self._maybe_stop_device_trace()
        if self._on_trace_ready:
            self._on_trace_ready(self)

    def stop(self):
        self._snapshot()
        _recorder.enabled = False
        self._state = ProfilerState.CLOSED

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # --------------------------------------------------------------- reports
    def export(self, path: str, format: str = "json"):
        with open(path, "w") as f:
            json.dump({"traceEvents": self._last_events}, f)

    def _event_stats(self):
        """name -> {calls, total_ms, cat} over the last snapshot."""
        stats = {}
        for e in self._last_events:
            s = stats.setdefault(e["name"], {"calls": 0, "total_ms": 0.0,
                                             "cat": e.get("cat",
                                                          "UserDefined")})
            s["calls"] += 1
            s["total_ms"] += e["dur"] / 1000.0
        return stats

    @staticmethod
    def _span_block(title, items):
        lines = [title,
                 f"{'span':<40}{'calls':>8}{'total(ms)':>12}{'mean(ms)':>12}"]
        for name, s in sorted(items.items(), key=lambda kv: -kv[1]["total_ms"]):
            mean = s["total_ms"] / max(s["calls"], 1)
            lines.append(f"{name:<40}{s['calls']:>8}"
                         f"{s['total_ms']:>12.3f}{mean:>12.3f}")
        return lines

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        stats = self._event_stats()
        lines = ["host event summary", f"{'name':<40}{'calls':>8}{'total(ms)':>12}"]
        for name, s in sorted(stats.items(), key=lambda kv: -kv[1]["total_ms"]):
            lines.append(f"{name:<40}{s['calls']:>8}{s['total_ms']:>12.3f}")
        # per-category blocks (TracerEventType): the training step, optimizer
        # update, collectives and dataloader each get their own table with
        # per-call means — not just the serving prefix
        by_cat = {}
        for name, s in stats.items():
            by_cat.setdefault(s["cat"], {})[name] = s
        for cat in sorted(by_cat):
            if cat == "UserDefined":
                continue  # generic spans stay in the overall table
            lines += self._span_block(f"[{cat}] spans", by_cat[cat])
        # serving line items: the continuous-batching scheduler's spans
        # (serving.prefill / serving.decode_step / serving.preempt) get a
        # dedicated block with per-call means, so a serving run's iteration
        # profile is readable at a glance
        serving = {n: s for n, s in stats.items() if n.startswith("serving.")}
        if serving:
            lines += self._span_block("serving spans", serving)
        if self._step_times:
            import numpy as np

            st = np.asarray(self._step_times[1:] or self._step_times) * 1000
            lines.append(
                f"steps: {len(self._step_times)}, mean {st.mean():.2f} ms, "
                f"p50 {np.percentile(st, 50):.2f} ms, "
                f"p99 {np.percentile(st, 99):.2f} ms")
        report = "\n".join(lines)
        print(report)
        return report

    def export_report(self, path: Optional[str] = None, *,
                      include_metrics: bool = True, registries=None,
                      request_tracers=None):
        """One merged observability artifact: host spans (per name AND per
        category), step times, metric snapshots (the process-wide registry
        plus any extra registries, e.g. a scheduler's ServingMetrics), and
        the CompileTracker's per-function compile accounting. Pass the
        serving scheduler's ``RequestTracer``(s) via ``request_tracers`` to
        fold per-request lifecycle timelines (phase durations, sub-spans)
        into the same artifact. Written as JSON when ``path`` is given;
        always returned as a dict."""
        stats = self._event_stats()
        by_cat = {}
        for name, s in stats.items():
            by_cat.setdefault(s["cat"], {})[name] = dict(s)
        report = {
            "host_events": list(self._last_events),
            "spans": {n: dict(s) for n, s in stats.items()},
            "categories": by_cat,
            "step_times_s": list(self._step_times),
        }
        if request_tracers:
            report["request_traces"] = [t.to_json() for t in request_tracers]
        if include_metrics:
            from paddle_tpu.observability import (
                get_compile_tracker,
                get_registry,
            )

            metrics = {"default": get_registry().snapshot()}
            for i, reg in enumerate(registries or ()):
                snap = reg.snapshot() if hasattr(reg, "snapshot") else dict(reg)
                metrics[getattr(reg, "namespace", "") or f"extra_{i}"] = snap
            report["metrics"] = metrics
            report["compiles"] = get_compile_tracker().snapshot()
        if path is not None:
            with open(path, "w") as f:
                json.dump(report, f, indent=2, default=str)
        return report


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)
