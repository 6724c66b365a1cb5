"""paddle_tpu.observability — framework-wide telemetry.

One surface answering "why did this step take 900 ms" across training,
serving, and distributed code:

- **MetricsRegistry** (``metrics.py``): Counter/Gauge/Histogram primitives
  with a process-wide default registry, JSON snapshots, and Prometheus
  text-exposition export. The serving tier's ``ServingMetrics`` is built on
  these (one private registry per scheduler instance).
- **CompileTracker** (``compile_tracker.py``): every jit entry point
  (``to_static`` StaticFunctions, ``TrainStep``, the serving ``SlotStep``)
  reports program-cache growth here — compile counts, wall time, triggering
  abstract shapes — and ``mark_steady()`` turns any further compile into a
  loud ``RecompileStorm`` warning. The TPU failure mode this exists for is
  silent recompilation.
- **Trace spans** are ``paddle_tpu.profiler.RecordEvent``; the training
  step, optimizer update, collectives, dataloader, and serving scheduler
  all emit them. A span is in two places: the profiler's in-process ring
  while a ``Profiler`` records (``Profiler.export_report()`` merges those
  with metric snapshots into one artifact), and, as an annotation of
  JAX's profiler, in the ``.xplane.pb`` of any open profiler session, on
  the clock of the device's events. The scheduler's
  ``step()`` is cut into phases this way (``serving.step`` > ``sweep`` /
  ``admit`` / ``stage`` / ``launch`` / ``sampling_sync`` / ``commit`` /
  ``account``), which is how device-idle time is given to host phases.
  Every literal span name is registered (owner + category) in
  ``span_manifest.py``; the ``tools/check_spans.py`` lint keeps the
  manifest and the code in sync.
- **Request lifecycle tracing** (``request_trace.py``): per-request linked
  spans keyed by ``request_id`` across the serving scheduler — queued →
  admit (prefix match + prefill) → running → preempted/resumed → done —
  with gapless phase durations (they sum to E2E latency), chrome-trace and
  JSON export.
- **Serving stall attribution + flight recorder** (``serving_stall.py``):
  ``serving_host_stall_seconds{phase=...}`` mirrors ``train_stall.py`` for
  the serving hot loop (admission / radix_match / block_accounting /
  streaming / sampling_sync); ``ServingStall.timed(phase)`` opens the span
  ``serving.<phase>`` round the code it meters. Plus a per-step ring buffer
  dumped on demand or on alarm (``TTFTBreachStorm``, ``EvictionThrash``).
- **Device memory ledger** (``device_memory.py``): every framework-owned
  device allocation site (KV pool, prefix-pinned blocks, weights,
  optimizer slots, fp32 masters, prefetch double-buffers, checkpoint
  staging) registers an owner-tagged footprint →
  ``device_memory_bytes{owner=...}`` live/watermark gauges, a queryable
  census, and OOM forensics (owner census + flight-recorder tail attached
  to the failing exception).
- **Program inventory** (``program_inventory.py``): XLA
  ``cost_analysis()``/``memory_analysis()`` for every compiled executable
  the CompileTracker sees (TrainStep, SlotStep decode, prefill buckets) —
  FLOPs, bytes accessed, peak temp memory, donation map — plus
  ``chip_specs()`` / ``roofline_utilization`` for a device time taken
  from a trace.
- **Fleet observability** (``fleet.py``): cross-replica request journeys
  (``FleetTracer`` — one chrome-trace track per router request spanning
  failovers), tiered metrics time-series history (``MetricsTimeline`` —
  1 s raw / 10 s / 60 s rings over every registry), and automated
  postmortem bundles (``PostmortemStore`` — one correlated artifact per
  alarm: timeline window + flight tail + journeys + breaker state +
  device census).
- **In-step profiling** (``step_profile.py``): named regions
  (``region("kv_gather")`` over ``jax.named_scope``, declared in
  ``REGION_MANIFEST`` and linted like spans) annotate the serving decode
  and train-step bodies; ``StepProfiler.capture`` wraps
  ``jax.profiler.trace`` around K steps and attributes measured device
  time per region per compiled program — region shares, per-region bytes
  estimates, and the decode roofline decomposed by region. A zero-sync
  in-program telemetry block (slot occupancy, sampled-token entropy /
  max-prob, kv blocks touched) rides the existing token drain.
- **Live endpoint** (``endpoint.py``): stdlib-http ``/metrics`` (Prometheus
  text across registries) + ``/debug`` index (``/debug/requests``,
  ``/debug/replicas``, ``/debug/programs``, ``/debug/memory``,
  ``/debug/timeline``, ``/debug/postmortem``, ``/debug/stepprofile``) +
  ``/healthz``.

Typical use::

    from paddle_tpu.observability import get_registry, get_compile_tracker
    reg = get_registry()
    reg.counter("my_events_total").inc()
    print(reg.prometheus_text())

    tracker = get_compile_tracker()
    ...warmup...
    tracker.mark_steady()            # further compiles warn loudly
    assert tracker.steady_state_recompiles() == 0
"""

from paddle_tpu.observability.compile_tracker import (  # noqa: F401
    CompileEvent,
    CompileTracker,
    RecompileStorm,
    abstract_signature,
    get_compile_tracker,
)
from paddle_tpu.observability.device_memory import (  # noqa: F401
    DeviceMemoryLedger,
    LedgerHandle,
    OWNERS,
    get_device_ledger,
    tree_nbytes,
)
from paddle_tpu.observability.endpoint import (  # noqa: F401
    ObservabilityEndpoint,
)
from paddle_tpu.observability.fleet import (  # noqa: F401
    FleetTracer,
    Journey,
    MetricsTimeline,
    PostmortemStore,
)
from paddle_tpu.observability.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsCardinalityOverflow,
    MetricsRegistry,
    get_registry,
    parse_prometheus_text,
)
from paddle_tpu.observability.program_inventory import (  # noqa: F401
    ProgramInventory,
    chip_specs,
    get_program_inventory,
    roofline_utilization,
)
from paddle_tpu.observability.request_trace import (  # noqa: F401
    RequestTrace,
    RequestTracer,
)
from paddle_tpu.observability.serving_stall import (  # noqa: F401
    EvictionThrash,
    FlightRecorder,
    STALL_PHASES,
    ServingStall,
    TTFTBreachStorm,
)
from paddle_tpu.observability.step_profile import (  # noqa: F401
    REGION_MANIFEST,
    REGION_PREFIX,
    StepProfiler,
    attribute_trace,
    load_trace_events,
    parse_hlo_instruction_bytes,
    parse_hlo_instruction_regions,
    region,
)
from paddle_tpu.observability.train_stall import (  # noqa: F401
    record_input_stall,
    record_sync_stall,
    set_offload_overlap_ratio,
    stall_snapshot,
)

__all__ = [
    "CompileEvent",
    "CompileTracker",
    "Counter",
    "DeviceMemoryLedger",
    "EvictionThrash",
    "FleetTracer",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "Journey",
    "LedgerHandle",
    "MetricsCardinalityOverflow",
    "MetricsRegistry",
    "MetricsTimeline",
    "OWNERS",
    "ObservabilityEndpoint",
    "PostmortemStore",
    "ProgramInventory",
    "REGION_MANIFEST",
    "REGION_PREFIX",
    "RecompileStorm",
    "RequestTrace",
    "RequestTracer",
    "STALL_PHASES",
    "ServingStall",
    "StepProfiler",
    "TTFTBreachStorm",
    "abstract_signature",
    "attribute_trace",
    "load_trace_events",
    "parse_hlo_instruction_bytes",
    "parse_hlo_instruction_regions",
    "region",
    "chip_specs",
    "get_compile_tracker",
    "get_device_ledger",
    "get_program_inventory",
    "get_registry",
    "parse_prometheus_text",
    "roofline_utilization",
    "tree_nbytes",
    "record_input_stall",
    "record_sync_stall",
    "set_offload_overlap_ratio",
    "stall_snapshot",
]
