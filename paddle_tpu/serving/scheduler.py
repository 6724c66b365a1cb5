"""Continuous (iteration-level) batching over a fixed-shape slot grid.

Orca-style scheduling on a vLLM-style paged KV pool, TPU-first:

- The decode step is ONE compiled XLA program over ``[max_num_seqs, 1]``
  token ids + per-layer ``PagedCacheSlot`` pools. Admissions, retirements
  and preemptions only rewrite the (host-side) block table / position /
  token arrays — the program never recompiles in steady state.
- Admission runs a prefill-then-pack path: a new request prefills alone at
  a bucketed prompt width (compiles once per bucket), writing its K/V into
  the SHARED block pool through its own block-table row; packing into the
  grid is then a pure host-side table update.
- When the ``BlockAllocator`` runs dry mid-decode, the lowest-priority
  (then youngest) running sequence is preempted: its blocks are freed and
  the request re-queued carrying its generated prefix, to be recomputed on
  a later admission. Graceful degradation instead of OOM.
- Every generated token streams to the request's ``on_token`` callback the
  iteration it is sampled; TTFT/TPOT are stamped per request and fold into
  ``ServingMetrics``.
- Request-lifecycle observability rides the same loop: a ``RequestTracer``
  keys linked phase spans off ``request_id`` (queued → admit → running →
  preempted/resumed → done), every second of host-side scheduling work is
  attributed to ``serving_host_stall_seconds{phase=...}``, a per-step
  flight recorder keeps the last-N-iterations picture, SLO targets turn
  into goodput/breach accounting, and ``start_endpoint()`` serves it all
  over ``/metrics`` + ``/debug/requests``.
- Failure semantics (``paddle_tpu.resilience``): every fault surface is
  behind a named ``inject()`` site, step faults classify transient vs
  fatal — transients retry with bounded backoff and retire the affected
  request as ``failed`` after K consecutive faults instead of poisoning
  the batch; requests carry deadlines and can be ``cancel()``-ed at any
  lifecycle stage (slot + blocks freed, peers token-identical); pressure
  drives a flush-cache → shrink-admission → reject degradation ladder; a
  step-latency watchdog fires ``StallStorm``; ``health()`` reports
  ``ok|degraded|draining|dead`` truthfully for ``/healthz``.
- ``dispatch_depth > 0`` turns the loop into an ASYNC engine: decode step
  N+1 is dispatched from the device-resident token carry before step N's
  tokens are synced, a background drain thread performs the only
  remaining D2H readback (one small token fetch per step), and admission
  / radix matching / block accounting overlap in-flight decode instead of
  serializing between steps. Host state splits into a COMMITTED view
  (``_pos``/``_next_tok``, advanced at drain) and a DISPATCHED view
  (``_disp_pos``/``_disp_emitted``, advanced at dispatch); retire/EOS,
  preemption, cancellation, degradation and fault retries resolve at
  drain time with bounded staleness — the token streams stay bit-identical
  to depth 0 and the ONE compiled decode program never recompiles in
  steady state at any depth.
"""

from __future__ import annotations

import threading
import time as _time
import weakref
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models.kv_cache import (
    BlockAllocator,
    KVPoolExhausted,
    PagedCacheSlot,
    cache_geometry,
    zero_pools,
    window_blocks_per_seq,
)
from paddle_tpu.models.serving import SlotStep, _bucket, splice_carry
from paddle_tpu.nn.layer_base import mode_epoch
from paddle_tpu.observability.annotations import (
    guarded_by,
    holds_lock,
    hot_path,
    thread_role,
)
from paddle_tpu.observability.device_memory import (
    DeviceMemoryLedger,
    tree_nbytes,
)
from paddle_tpu.observability.fleet import MetricsTimeline, PostmortemStore
from paddle_tpu.observability.program_inventory import get_program_inventory
from paddle_tpu.observability.request_trace import (
    PHASE_ADMIT,
    PHASE_PREEMPTED,
    PHASE_QUEUED,
    PHASE_RUNNING,
    RequestTracer,
)
from paddle_tpu.observability.serving_stall import (
    AlarmMonitors,
    FlightRecorder,
    ServingStall,
)
from paddle_tpu.observability.step_profile import (
    StepProfiler,
    parse_hlo_instruction_bytes,
    parse_hlo_instruction_regions,
)
from paddle_tpu.profiler import RecordEvent
from paddle_tpu.resilience import (
    DegradationLadder,
    InjectedFault,
    LEVEL_OK,
    LEVEL_REJECT,
    LEVEL_SHRINK,
    StepWatchdog,
    classify_error,
    get_injector,
    inject,
)
from paddle_tpu.serving.metrics import ServingMetrics
from paddle_tpu.serving.prefix_cache import (
    PrefixCache,
    RefCountingBlockAllocator,
    copy_block_in_pools,
)
from paddle_tpu.serving.request import (
    Request,
    RequestOutput,
    RequestQueue,
    RequestState,
    SchedulerConfig,
    SchedulerOverloaded,
)
from paddle_tpu.serving.spec import (
    ChunkPrefillStep,
    NgramProposer,
    SpecVerifyStep,
)


class _InFlight:
    """One dispatched-but-undrained device step: the device-resident
    sampled ids plus the (slot, request) snapshot they belong to. The
    drain thread fetches ``next_ids`` off the critical path and commits
    the tokens against the snapshot (retired slots discard as stale)."""

    __slots__ = ("kind", "next_ids", "slots", "stats")

    def __init__(self, kind: str, next_ids, slots, stats=None):
        self.kind = kind          # "decode" | "admit"
        self.next_ids = next_ids  # device int32: [S] (decode) / [1] (admit)
        self.slots = slots        # [(slot, Request), ...] at dispatch time
        self.stats = stats        # device f32[4] telemetry block (or None)


@thread_role("serving-drain")
def _drain_worker(sched_ref):
    """Background drain loop: fetch the oldest in-flight step's tokens
    (the device wait lands HERE, overlapped with the next dispatched
    step) and commit them under the engine lock. Holds only a weak
    reference between iterations so an abandoned scheduler can be
    garbage-collected — the thread then exits on its next wakeup."""
    while True:
        sched = sched_ref()
        if sched is None or sched._drain_stop:
            return
        entry = sched._next_drainable()
        if entry is not None:
            sched._drain_one(entry)
        del sched, entry


def _backend_donates() -> bool:
    """Whether the compiled steps donate their KV pools: on every backend
    but XLA:CPU (reasons at the call in ``__init__``). A function so that
    a CPU test can force the executables the chip runs."""
    import jax

    return jax.default_backend() != "cpu"


class ContinuousBatchingScheduler:
    """Iteration-level scheduler around one causal-LM's compiled slot step.

    ``model(input_ids, position_ids, caches)`` must return
    ``(logits, new_caches)`` when caches are given (the GPTForCausalLM /
    LlamaForCausalLM serving contract — same as ``DecodeEngine``)."""

    # shared with the drain thread; every access outside __init__ holds
    # the engine lock (lexically or via @holds_lock) — pinned by graft_lint
    _inflight: guarded_by("_elock")
    _carry: guarded_by("_elock")
    _done_async: guarded_by("_elock")
    _drain_exc: guarded_by("_elock")
    _last_telemetry: guarded_by("_elock")

    def __init__(self, model, config: Optional[SchedulerConfig] = None,
                 metrics: Optional[ServingMetrics] = None,
                 sharding=None):
        self.config = cfg = config or SchedulerConfig()
        mcfg = model.config
        self.model = model
        # what each layer caches, as the model states it: KV heads, K and V
        # row widths, and a window for the layers that need only the last
        # positions of a row (a second class of blocks, below)
        self._geometry = geometry = cache_geometry(model)
        self.num_layers = len(geometry)
        self.num_kv_heads = geometry[0].kv_heads
        self.head_dim = geometry[0].k_dim
        windows = sorted({g.window for g in geometry if g.window})
        if len(windows) > 1:
            raise ValueError(f"window layers of one model must share one "
                             f"window; got {windows}")
        self._window: Optional[int] = windows[0] if windows else None
        # these assume one class of blocks whose pages live as long as their
        # request and are read by every later token: a window layer's table
        # forgets a row's early pages, so a shared prefix, a chunk or a
        # draft verified against it would read what is no longer there; a
        # latent layer's chunk attends over its own expanded K and V (it
        # starts its row), and its one pool has no heads to shard
        refuses = (
            "sliding-window layers: it assumes one class of KV blocks that "
            "keep a row's whole context" if self._window is not None else
            "latent-cache layers: a chunk of several tokens does not read "
            "the rows cached before it" if any(g.latent for g in geometry)
            else None)
        if refuses:
            for on, feature in (
                    (cfg.enable_prefix_caching,
                     "prefix caching (enable_prefix_caching)"),
                    (cfg.prefill_chunk_size,
                     "chunked prefill (prefill_chunk_size)"),
                    (cfg.spec_k, "speculative decoding (spec_k)"),
                    (sharding is not None, "the sharded step (sharding)")):
                if on:
                    raise ValueError(f"{feature} is not supported for a "
                                     f"model with {refuses}")
        max_pos = getattr(mcfg, "max_position_embeddings", cfg.max_seq_len)
        self.max_seq_len = min(cfg.max_seq_len, max_pos)
        self.metrics = metrics or ServingMetrics()
        # the compiled steps donate the KV pools and nothing else (what is
        # staged for a launch is the same on every backend: ``_caches``).
        # Donation keeps the pools single-resident, and on TPU it is a
        # compile-time aliasing hint that composes with async dispatch —
        # so the TPU engine donates at every depth. XLA:CPU however
        # executes donated calls SYNCHRONOUSLY (the runtime hands buffers
        # over on the host), which would hide the device time inside the
        # dispatch call and re-serialize a dispatch-ahead pipeline; and
        # because donation changes the compiled executable (and thus
        # float rounding on near-tied logits), it must be uniform across
        # depths for the bit-identical-tokens guarantee to hold. CPU
        # therefore never donates here: transient double pool residency
        # bought overlap AND one executable for every dispatch_depth.
        self._donate = _backend_donates()
        # ``sharding`` (duck-typed: serving.sharded.TensorParallelSharding
        # or anything with prepare_model/make_step/shard_pools/describe) —
        # one replica spans a device mesh. Weights are committed to the
        # mesh BEFORE the step is built so the jit entry collects sharded
        # param values from the first call; written once here, read-only
        # for the scheduler's lifetime.
        self.sharding = sharding
        # layer_base.mode_epoch() when step() last put the model in eval
        # mode: while it stands no training flag has changed since
        self._eval_epoch = -1
        if sharding is not None:
            sharding.prepare_model(model)
            self._step_fn = sharding.make_step(model, cfg,
                                               donate=self._donate)
        else:
            self._step_fn = SlotStep(model, temperature=cfg.temperature,
                                     top_k=cfg.top_k, donate=self._donate,
                                     telemetry=cfg.enable_step_telemetry)
        # ---- latency subsystem (serving/spec/): chunked prefill +
        # speculative decoding. Both steps wrap self._step_fn's
        # ``_model_call`` seam, so a sharded step chunks/verifies under
        # its mesh unchanged; each owns its own jit cache, folded into
        # num_programs()/mark_steady()/compile_stats() below.
        self._chunk_size = 0
        self._chunk_step: Optional[ChunkPrefillStep] = None
        self._spec_step: Optional[SpecVerifyStep] = None
        self._proposer = None
        if cfg.prefill_chunk_size or cfg.spec_k:
            if cfg.temperature > 0:
                raise ValueError(
                    "chunked prefill / speculative decoding are greedy-only "
                    "(temperature == 0): speculative acceptance compares "
                    "drafts against the model's argmax, and a chunked "
                    "prefill must sample once per admission, not per chunk")
            if cfg.prefill_chunk_size:
                self._chunk_size = min(
                    _bucket(max(int(cfg.prefill_chunk_size), 1),
                            cfg.prefill_bucket),
                    self.max_seq_len)
                self._chunk_step = ChunkPrefillStep(self._step_fn,
                                                    donate=self._donate)
            if cfg.spec_k:
                self._spec_step = SpecVerifyStep(self._step_fn,
                                                 donate=self._donate)
                self._proposer = NgramProposer(max_n=cfg.spec_ngram_max,
                                               min_n=cfg.spec_ngram_min)
        self._step_chunked_tokens = 0    # chunk pump tokens, per step
        self._spec_steps = 0             # verify-step accounting
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_emitted = 0
        if cfg.enable_prefix_caching:
            # sharing-aware pool + radix tree: admissions match cached
            # prefixes and prefill only the uncached suffix
            self.allocator = RefCountingBlockAllocator(
                cfg.total_blocks, cfg.block_size)
            self.prefix_cache: Optional[PrefixCache] = PrefixCache(
                self.allocator, cfg.block_size,
                registry=self.metrics.registry)
        else:
            self.allocator = BlockAllocator(cfg.total_blocks, cfg.block_size)
            self.prefix_cache = None

        S, MB = cfg.max_num_seqs, cfg.max_blocks_per_seq
        # host-side slot grid: which request runs where, its block-table row
        # and current length. Device state is ONLY the per-layer K/V pools.
        self._slots: List[Optional[Request]] = [None] * S
        self._table = np.full((S, MB), -1, np.int32)
        self._pos = np.zeros(S, np.int32)
        self._next_tok = np.zeros(S, np.int32)   # token to feed next step
        # the window class: a row holds the pages its window still reaches
        # (at most WB), pages wholly behind it go back to this free list
        # while the request runs, and the pools do not grow with
        # max_seq_len: S * WB blocks, what every slot can hold at once.
        # ``allocator`` stays the full-context class.
        WB = (window_blocks_per_seq(self._window, cfg.block_size)
              if self._window else 0)
        self.window_allocator: Optional[BlockAllocator] = None
        if self._window:
            self.window_allocator = BlockAllocator(S * WB, cfg.block_size)
        self._window_blocks_per_seq = WB
        self._wtable = np.full((S, WB), -1, np.int32)
        self._wbase = np.zeros(S, np.int32)   # position of column 0
        self.window_blocks_peak = 0
        self._pools = []
        for g in geometry:
            n = (self.window_allocator.num_blocks if g.window
                 else cfg.total_blocks)
            self._pools.append(zero_pools(g, n, cfg.block_size,
                                          cfg.cache_dtype))
        if sharding is not None:
            # head-shard the K/V pools over the replica's mesh (~1/tp of
            # the KV bytes per chip); block tables and positions stay tiny
            # replicated host uploads
            self._pools = sharding.shard_pools(self._pools)
        self.queue = RequestQueue(cfg.max_queue_size)
        self._next_rid = 0
        self._finished: Dict[int, RequestOutput] = {}
        self._events: List[tuple] = []   # (rid, token) stream buffer
        # ---- request-lifecycle observability ---------------------------
        # request_id is the correlation ID threaded through every layer:
        # the tracer's lifecycle spans, the stall breakdown, the flight
        # recorder, and SLO breach attribution all key off it.
        self.tracer = RequestTracer(enabled=cfg.enable_request_tracing,
                                    max_completed=cfg.trace_ring)
        self.stall = ServingStall(self.metrics.registry)
        self.flight = FlightRecorder(cfg.flight_recorder_steps)
        self._alarms = AlarmMonitors(self.flight,
                                     ttft_streak=cfg.ttft_breach_streak)
        if cfg.ttft_slo_s is not None or cfg.tpot_slo_s is not None:
            self.metrics.configure_slo(cfg.ttft_slo_s, cfg.tpot_slo_s)
        self._step_evicted = 0           # eviction-thrash signal, per step
        if self.prefix_cache is not None:
            self.prefix_cache.set_evict_listener(self._on_evicted_blocks)
        # ---- resilience ------------------------------------------------
        self._ladder: Optional[DegradationLadder] = None
        self._watchdog: Optional[StepWatchdog] = None
        if cfg.enable_degradation:
            self._ladder = DegradationLadder(
                flush_at=cfg.shed_flush_occupancy,
                shrink_at=cfg.shed_shrink_occupancy,
                reject_at=cfg.shed_reject_occupancy,
                recover_at=cfg.shed_recover_occupancy,
                cooldown_steps=cfg.shed_cooldown_steps)
            self._watchdog = StepWatchdog(
                factor=cfg.watchdog_factor,
                min_history=cfg.watchdog_min_history,
                streak=cfg.watchdog_streak,
                abs_s=cfg.watchdog_abs_s,
                flight=self.flight)
        self._draining = False           # start_drain(): finish, admit no new
        self._driver = None              # optional driver thread, for health
        self._step_faults: Dict[str, int] = {}   # site -> count, per step
        # ---- async engine (dispatch-ahead decode) ----------------------
        # ``_pos``/``_next_tok`` above are the COMMITTED view (advanced
        # when a step's tokens drain); ``_disp_pos``/``_disp_emitted`` are
        # the DISPATCHED view (advanced when a step is enqueued on the
        # device) — depth 0 keeps them in lockstep. ``_carry`` is the last
        # dispatched step's device-resident [S] sampled ids, fed straight
        # back as the next step's input without a host round-trip; a slot
        # whose full token budget is in flight is FROZEN (excluded from
        # dispatch, table row masked) so speculation never outruns the
        # request's validated block budget.
        self.dispatch_depth = max(0, int(cfg.dispatch_depth))
        self._disp_pos = np.zeros(S, np.int32)
        self._disp_emitted = np.zeros(S, np.int32)
        # a decode launch samples every row at its one position: the
        # all-zero gather index is a constant, uploaded here once and kept
        # on the device (the steps donate the pools only)
        self._zero_gather = paddle.to_tensor(np.zeros(S, np.int32))
        self._elock = threading.Condition(threading.RLock())
        self._inflight: deque = deque()          # _InFlight, FIFO
        self._carry = None
        self._done_async: List[Request] = []     # retired at drain time
        self._drain_exc: Optional[BaseException] = None
        # last drained in-program telemetry block (None until the first
        # step with cfg.enable_step_telemetry lands)
        self._last_telemetry: Optional[dict] = None
        self._drain_thread: Optional[threading.Thread] = None
        self._drain_stop = False
        # ---- device-side observability (HBM ledger) --------------------
        # Coarse owner-tagged accounting registered HERE, at the one site
        # that constructs the pools — nothing below runs per decode step.
        # (a window layer's bytes do not grow with the tokens)
        pool_bytes = tree_nbytes([p for p, g in zip(self._pools, geometry)
                                  if not g.window])
        self._kv_bytes_per_token = (
            pool_bytes // max(1, cfg.total_blocks * cfg.block_size))
        if self._window:
            reg = self.metrics.registry
            self._window_used = reg.gauge(
                "kv_window_blocks_used",
                "window-class KV blocks held by running requests")
            self._window_released = reg.counter(
                "kv_window_blocks_released",
                "window-class KV blocks returned to the free list while "
                "their request was still running")
        self.device_ledger: Optional[DeviceMemoryLedger] = None
        if cfg.enable_device_observability:
            self.device_ledger = DeviceMemoryLedger(
                registry=self.metrics.registry)
            # register_arrays (not plain register): reads the pools' real
            # shardings so a sharded replica's per-chip census shows the
            # ~1/tp KV split
            self.device_ledger.register_arrays(
                "kv_pool", "paged_kv_pools", self._pools)
            self.device_ledger.register_arrays(
                "model_weights", "serving_model",
                [p for p in model.parameters()])
            self.metrics.registry.gauge(
                "kv_bytes_per_token",
                "device KV-cache bytes appended per generated token",
                unit="bytes").set(self._kv_bytes_per_token)
            if self.prefix_cache is not None:
                self.prefix_cache.attach_device_ledger(
                    self.device_ledger,
                    self._kv_bytes_per_token * cfg.block_size)
        # ---- fleet observability (timeline + postmortems) --------------
        # The timeline records registry/stall/ledger history; postmortems
        # freeze one correlated bundle on every alarm (flight-recorder
        # alarms via the callback below, KVPoolExhausted in step()) and on
        # demand. Standalone schedulers sample inline or via the sampler
        # thread (timeline_interval_s > 0); under a router the router's
        # own timeline also scrapes this registry fleet-wide.
        self.timeline = MetricsTimeline()
        self.timeline.add_source("serving", self.metrics.snapshot)
        self.timeline.add_source("stall", self.stall.snapshot)
        if self.device_ledger is not None:
            self.timeline.add_source("device", self.device_ledger.census)
        self.postmortems = PostmortemStore(max_bundles=cfg.postmortem_bundles)
        self.postmortems.add_context("flight_tail",
                                     lambda: self.flight.dump(last=32))
        self.postmortems.add_context(
            "flight_alarm", lambda: self.flight.last_alarm_dump)
        self.postmortems.add_context("requests",
                                     lambda: self.tracer.to_json()[-32:])
        self.postmortems.add_context("metrics", self.metrics.snapshot)
        self.postmortems.add_context("health", self.health)
        self.postmortems.add_context(
            "timeline_window", lambda: self.timeline.window(last_s=30.0))
        if self.device_ledger is not None:
            self.postmortems.add_context("device_memory",
                                         self.device_ledger.census)
        # ---- in-step profiling (named-region attribution) ---------------
        # ``capture_step_profile`` builds the StepProfiler lazily (it needs
        # compiled-program HLO, which only exists after the first step);
        # postmortem bundles attach the LATEST capture only (bounded).
        self.step_profiler: Optional[StepProfiler] = None
        self.postmortems.add_context(
            "step_profile",
            lambda: (self.step_profiler.last_summary
                     if self.step_profiler is not None else None))
        self.flight.set_alarm_callback(self._alarm_postmortem)
        if cfg.timeline_interval_s > 0:
            self.timeline.start(cfg.timeline_interval_s)

    def _alarm_postmortem(self, kind: str, reason: str, alarm: dict):
        """FlightRecorder alarm hook: one auto-captured bundle per alarm
        (TTFTBreachStorm / EvictionThrash / StallStorm all land here). The
        bundle carries the alarm WITHOUT its frozen step ring — the
        ``flight_alarm`` context already snapshots that."""
        self.postmortems.capture(
            kind, reason, alarm={k: alarm[k] for k in ("kind", "reason", "t")})

    # ---- admission -----------------------------------------------------

    def add_request(self, prompt_ids, max_new_tokens: Optional[int] = None,
                    eos_token_id: Optional[int] = None, priority: int = 0,
                    on_token=None, deadline_s: Optional[float] = None) -> int:
        """Enqueue one prompt. Raises ``ValueError`` for malformed requests
        (empty prompt, non-integer tokens, ``max_new_tokens < 1``, prompts
        that can never fit the window/pool), ``QueueFull`` past
        max_queue_size, and ``SchedulerOverloaded`` while draining or when
        the degradation ladder has reached ``reject``. ``deadline_s`` is a
        wall-clock budget from arrival: a request still unfinished past it
        is cancelled (reason ``deadline``) at the next step."""
        ids = np.asarray(prompt_ids).reshape(-1)
        if ids.dtype.kind not in "iu":
            raise ValueError(
                f"prompt_ids must be integer token ids, got dtype "
                f"{ids.dtype}")
        ids = ids.astype(np.int64)
        if ids.size == 0:
            raise ValueError("prompt must contain at least one token")
        mnt = (self.config.max_new_tokens
               if max_new_tokens is None else int(max_new_tokens))
        if mnt < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError("deadline_s must be positive (or None)")
        eos = (self.config.eos_token_id
               if eos_token_id is None else eos_token_id)
        if len(ids) > self.max_seq_len:
            raise ValueError(
                f"prompt is {len(ids)} tokens but the largest prefill "
                f"bucket is {self.max_seq_len} (max_seq_len)")
        total = len(ids) + mnt
        cap = self.allocator.num_blocks * self.config.block_size
        if total > self.max_seq_len or total > cap:
            raise ValueError(
                f"request needs {total} tokens but the window/pool caps at "
                f"{min(self.max_seq_len, cap)}")
        # admission mutates queue/rid state shared with whichever thread
        # drives step() — a router thread submits while replica drivers
        # decode, so the whole accept-or-reject decision runs under the
        # (reentrant) engine lock
        with self._elock:
            if self._draining:
                self.metrics.requests_rejected += 1
                raise SchedulerOverloaded(
                    "scheduler is draining; not accepting new requests")
            if (self._ladder is not None
                    and self._ladder.level >= LEVEL_REJECT):
                self.metrics.requests_rejected += 1
                raise SchedulerOverloaded(
                    f"overloaded: degradation ladder at "
                    f"{self._ladder.state!r} (kv_utilization="
                    f"{self.allocator.utilization():.2f}, "
                    f"queue_depth={len(self.queue)})")
            rid = self._next_rid
            self._next_rid += 1
            req = Request(request_id=rid, prompt_ids=ids,
                          max_new_tokens=mnt, eos_token_id=eos,
                          priority=priority, on_token=on_token,
                          deadline_s=deadline_s)
            try:
                self.queue.push(req)
            except Exception:
                self.metrics.requests_rejected += 1
                raise
            self.metrics.requests_received += 1
            # trace timeline anchored at the request's own arrival stamp so
            # phase durations and TTFT/E2E share one clock origin
            self.tracer.start(rid, t=req.arrival_t, prompt_tokens=len(ids),
                              priority=priority)
            return rid

    def _on_evicted_blocks(self, n: int):
        self._step_evicted += n

    # ---- internals -----------------------------------------------------

    def _live_tokens(self) -> int:
        return int(sum(self._pos[s] for s in range(len(self._slots))
                       if self._slots[s] is not None))

    def _caches(self, table: np.ndarray, pos: np.ndarray,
                wtable: Optional[np.ndarray] = None,
                wbase: Optional[np.ndarray] = None):
        """Fresh per-layer PagedCacheSlots over the shared pools. What the
        host built for this launch goes up ONCE, whatever the depth of the
        model: one block table and one position vector, shared by every
        layer's slot, and for a model with window layers the window
        class's table (``wtable``) and the position of its first column
        (``wbase``), shared by those. The compiled steps donate the pools
        and nothing else (``kv_cache.donate_pools``), so a shared tensor is
        an ordinary input on every backend. Each upload is a copy made
        here: the scheduler mutates its tables and positions in place
        right after a dispatch, and a long-lived host buffer may not cross
        the jax boundary while a dispatched step can still read it."""
        def up(a):
            return paddle.to_tensor(a.copy())

        pos_t = up(pos)
        kinds = [bool(g.window) for g in self._geometry]
        full = (up(table), pos_t) if False in kinds else None
        win = (up(wtable), pos_t, up(wbase)) if True in kinds else None
        return [PagedCacheSlot(kp, vp, *(win if w else full))
                for (kp, vp), w in zip(self._pools, kinds)]

    def _release_blocks(self, req: Request, slot: int = -1):
        """Return every block ``req`` holds, of both classes, and clear the
        window class's row of ``slot``."""
        self.allocator.free(req.blocks)
        req.blocks = []
        if self.window_allocator is not None:
            self.window_allocator.free(req.window_blocks)
            req.window_blocks = []
            if slot >= 0:
                self._wtable[slot] = -1
                self._wbase[slot] = 0

    def _window_span(self, pos: int) -> Tuple[int, int]:
        """``(first, last)`` page a window layer needs when the token at
        position ``pos`` is written and attends."""
        bs = self.config.block_size
        return max(0, pos - self._window + 1) // bs, pos // bs

    @holds_lock("_elock")
    def _roll_window(self, slot: int, req: Request):
        """Move ``slot``'s window-class row on to its dispatched position:
        pages wholly behind the window go back to the free list, and the
        page the next token lands in is allocated (``KVPoolExhausted``
        from here preempts, as from the full class)."""
        first, last = self._window_span(int(self._disp_pos[slot]))
        bs = self.config.block_size
        blocks = req.window_blocks
        base = int(self._wbase[slot]) // bs
        behind = min(first - base, len(blocks))
        if behind > 0:
            with RecordEvent("serving.window_release"):
                self.window_allocator.free(blocks[:behind])
                del blocks[:behind]
                self._window_released.inc(behind)
        base = max(base, first)
        changed = behind > 0
        while base + len(blocks) <= last:
            blocks += self.window_allocator.allocate(bs)
            changed = True
        if changed:
            self._wtable[slot] = -1
            self._wtable[slot, :len(blocks)] = blocks
            self._wbase[slot] = base * bs

    def _store_pools(self, caches):
        self._pools = [(c.k_pool, c.v_pool) for c in caches]

    def _cache_insert_on_release(self, req: Request, slot: int):
        """Donate a releasing sequence's cached KV to the radix tree (insert
        on retire AND preempt — a preempted request's own resume becomes a
        cache hit). Must run BEFORE ``allocator.free``: the tree increfs the
        blocks it adopts, so the free below only drops the request's pin."""
        if self.prefix_cache is None or not req.blocks:
            return
        pos = int(self._pos[slot])   # tokens whose K/V the blocks hold
        if pos <= 0:
            return
        seq = np.concatenate([np.asarray(req.prompt_ids, np.int64),
                              np.asarray(req.out_tokens, np.int64)])[:pos]
        try:
            inject("serving.prefix_insert")
            self.prefix_cache.insert(seq, req.blocks)
        except Exception as exc:
            # cache donation is best-effort: a transient fault just skips
            # the insert (the caller's free() still releases the blocks —
            # no leak, only a missed future hit). Fatal errors propagate.
            site = self._fault_site(exc, "serving.prefix_insert")
            if classify_error(exc) == "fatal":
                self.metrics.observe_fault(site, "fatal")
                raise
            self._note_fault(site)

    def _retire(self, slot: int, reason: str):
        req = self._slots[slot]
        req.finish(reason)
        self._cache_insert_on_release(req, slot)
        self._release_blocks(req, slot)
        req.slot = -1
        req.prefill_pos = -1
        self._slots[slot] = None
        self._table[slot] = -1
        self._pos[slot] = 0
        self._next_tok[slot] = 0
        self._disp_pos[slot] = 0
        self._disp_emitted[slot] = 0
        trace = self.tracer.get(req.request_id)
        if trace is not None:
            trace.note(finish_reason=reason,
                       generated_tokens=req.num_generated,
                       num_preemptions=req.num_preemptions)
        # close the trace at the request's finish stamp BEFORE judging SLO
        # — breach-cause attribution reads the completed phase timeline
        self.tracer.finish(req.request_id, t=req.finish_t)
        if reason in ("eos", "length"):
            # only natural completions count toward requests_finished /
            # goodput — a cancelled or failed request is not good tokens
            verdict = self.metrics.observe_finish(req, trace=trace)
            if self.metrics.ttft_slo_s is not None:
                self._alarms.observe_ttft(verdict["ttft_breach"],
                                          verdict["ttft_s"],
                                          self.metrics.ttft_slo_s)
        self._finished[req.request_id] = req.output()
        return req

    def _finalize_off_grid(self, req: Request, reason: str) -> Request:
        """Terminal bookkeeping for a request that holds NO slot and NO
        blocks (queued cancel/TTL, or a fault before packing)."""
        req.finish(reason)
        trace = self.tracer.get(req.request_id)
        if trace is not None:
            trace.note(finish_reason=reason,
                       generated_tokens=req.num_generated,
                       num_preemptions=req.num_preemptions)
        self.tracer.finish(req.request_id, t=req.finish_t)
        self._finished[req.request_id] = req.output()
        return req

    # ---- cancellation / deadlines -------------------------------------

    def cancel(self, request_id: int, cause: str = "user") -> RequestOutput:
        """Cancel one request wherever it lives. Queued: removed outright.
        Running: its KV is donated to the prefix cache (valid work), its
        blocks and slot are freed — concurrent requests' token streams are
        untouched (per-slot decode rows are independent). Already-terminal
        requests return their stored output (idempotent). The returned
        ``RequestOutput`` carries the tokens generated so far with
        ``finish_reason`` ``cancelled|deadline|queue_ttl``.

        At ``dispatch_depth > 0`` the in-flight pipeline drains first:
        tokens already dispatched commit before the cancel point, so a
        cancel between ``step()`` calls lands on exactly the state the
        synchronous engine would have — and a request that finishes
        naturally during the drain returns its stored output (idempotent)
        instead of being cancelled."""
        reason = "cancelled" if cause == "user" else cause
        with self._elock:
            if self._inflight:
                self._drain_all()
            if request_id in self._finished:
                return self._finished[request_id]
            queued = self.queue.remove(request_id)
            if queued is not None:
                self.metrics.observe_cancel(cause)
                return self._finalize_off_grid(queued, reason).output()
            for s, req in enumerate(self._slots):
                if req is not None and req.request_id == request_id:
                    self.metrics.observe_cancel(cause)
                    return self._retire(s, reason).output()
            raise KeyError(f"unknown request_id {request_id}")

    def start_drain(self):
        """Stop admitting new requests (``SchedulerOverloaded``); everything
        already queued or running finishes normally. ``health()`` reports
        ``draining`` until the engine empties."""
        self._draining = True

    @property
    def is_draining(self) -> bool:
        """True after ``start_drain()`` (or export): finishing existing
        work, admitting nothing new — routers must place elsewhere."""
        return self._draining

    def attach_driver(self, thread):
        """Register the thread driving ``step()`` so ``health()`` can report
        ``dead`` (non-200 /healthz) when it exits with work still pending —
        instead of a healthz that says ok while nothing decodes."""
        self._driver = thread

    def _sweep_expired(self) -> List[Request]:
        """Cancel requests past their deadline (queued OR running) and
        queued requests older than ``queue_ttl_s``. Runs at step start."""
        cfg = self.config
        now = _time.perf_counter()
        swept: List[Request] = []
        for req in list(self.queue._items):
            if req.past_deadline(now):
                self.cancel(req.request_id, cause="deadline")
                swept.append(req)
            elif (cfg.queue_ttl_s is not None
                    and now - req.arrival_t > cfg.queue_ttl_s):
                self.cancel(req.request_id, cause="queue_ttl")
                swept.append(req)
        for s in range(len(self._slots)):
            req = self._slots[s]
            if req is not None and req.past_deadline(now):
                self.cancel(req.request_id, cause="deadline")
                swept.append(req)
        return swept

    # ---- fault absorption ---------------------------------------------

    def _fault_site(self, exc: BaseException, default: str) -> str:
        return exc.site if isinstance(exc, InjectedFault) else default

    def _note_fault(self, site: str):
        self.metrics.observe_fault(site, "fired")
        self._step_faults[site] = self._step_faults.get(site, 0) + 1

    def _fault_budget_exhausted(self, req: Request) -> bool:
        req.consecutive_faults += 1
        return req.consecutive_faults >= self.config.max_step_faults

    def _preempt_victim(self, exclude_slot: int = -1) -> Optional[int]:
        """Pick the running sequence to evict: lowest priority, then the
        youngest (latest request id) — it has the least sunk compute."""
        best, best_key = None, None
        for s, req in enumerate(self._slots):
            if req is None or s == exclude_slot:
                continue
            key = (req.priority, -req.request_id)
            if best_key is None or key < best_key:
                best, best_key = s, key
        return best

    def _preempt(self, slot: int):
        req = self._slots[slot]
        with RecordEvent("serving.preempt"):
            self._cache_insert_on_release(req, slot)
            self._release_blocks(req, slot)
            req.slot = -1
            # a mid-prefill victim resumes via a clean chunked re-prefill;
            # its completed-chunk KV was just donated to the radix tree,
            # so the resume's prefix match recovers the frontier for free
            req.prefill_pos = -1
            req.num_preemptions += 1
            req.state = RequestState.PREEMPTED
            self._slots[slot] = None
            self._table[slot] = -1
            self._pos[slot] = 0
            self._next_tok[slot] = 0
            self._disp_pos[slot] = 0
            self._disp_emitted[slot] = 0
            # force=True: an evicted request must never be REJECTED by its
            # own admission control — it was already admitted once
            self.queue.push(req, force=True)
        self.metrics.preemptions += 1
        trace = self.tracer.get(req.request_id)
        if trace is not None:
            trace.transition(PHASE_PREEMPTED)
            trace.event("preempt", slot=slot,
                        generated_tokens=req.num_generated)

    @hot_path(reason="runs per decode iteration under block_accounting")
    @holds_lock("_elock")
    def _ensure_decode_capacity(self, slot: int, tokens: int = 1) -> bool:
        """Guarantee the slot can write ``tokens`` more (at its DISPATCHED
        position — capacity must cover in-flight speculation); preempt
        other sequences (or finally the slot itself) when the pool is dry.
        ``tokens`` > 1 is the speculative-verify case (the carry token
        plus k drafts write in one call), clamped to the block-table
        row's capacity — overflow writes drop in-kernel and only ever
        carry tokens the commit clamps away. False = the slot itself was
        evicted."""
        cap = self.config.max_blocks_per_seq * self.config.block_size
        req = self._slots[slot]
        while True:
            if req is None or self._slots[slot] is not req:
                return False             # drained away mid-assurance
            try:
                before = len(req.blocks)
                # extend() is idempotent for a given pos, so a fault here
                # (absorbed by the decode retry loop) re-runs cleanly
                inject("serving.block_alloc")
                add = max(1, min(int(tokens),
                                 cap - int(self._disp_pos[slot])))
                self.allocator.extend(req.blocks,
                                      int(self._disp_pos[slot]), add)
                for j in range(before, len(req.blocks)):
                    self._table[slot, j] = req.blocks[j]
                if self._window is not None:
                    self._roll_window(slot, req)
                return True
            except KVPoolExhausted:
                if self._inflight:
                    # async engine: committing the in-flight steps may
                    # retire slots and free blocks — drain and retry
                    # before evicting a live victim (preemption must act
                    # on committed state only)
                    self._drain_all()
                    req = self._slots[slot]
                    continue
                if not self.config.enable_preemption:
                    raise
                victim = self._preempt_victim(exclude_slot=slot)
                if victim is None:
                    self._preempt(slot)      # last resort: evict itself
                    return False
                self._preempt(victim)

    @hot_path(reason="admission host work delays every running decode")
    def _admit(self) -> List[Request]:
        """Fill free slots from the queue via prefill-then-pack.

        With prefix caching on, each prompt is first matched against the
        radix tree: hit blocks are pinned straight into the block-table row
        and only the uncached SUFFIX is prefilled (absolute position ids,
        cache pos = matched length — data, not shapes, so the same compiled
        prefill buckets serve hits and misses). A full-prompt hit keeps one
        token to recompute (the last prompt token produces the first sampled
        logit), which partially rewrites the final shared block — that block
        is forked copy-on-write before the write.

        Host-stall attribution: each admission's host time is split into
        ``radix_match`` (tree match + pin), ``block_accounting`` (alloc +
        COW + table row), ``sampling_sync`` (the blocking read of the first
        sampled token), ``streaming`` (emit + callback) and ``admission``
        (everything else: queue pop, request setup, packing, retire
        bookkeeping). Prefill device dispatch is excluded — it is compute,
        not host scheduling; it shows up as the request's ``prefill``
        sub-span instead. At ``dispatch_depth > 0`` the first-token sync
        is replaced by ``dispatch`` (carry splice + enqueue) and the token
        commits on the drain thread."""
        finished = []
        bs = self.config.block_size
        pc = _time.perf_counter
        while len(self.queue):
            it_t0 = pc()
            radix_s = block_s = sync_s = stream_s = prefill_s = 0.0
            slot = next((s for s, r in enumerate(self._slots) if r is None),
                        None)
            if slot is None:
                break
            nxt = self.queue.peek()
            if (self._ladder is not None
                    and self._ladder.level >= LEVEL_SHRINK
                    and self._pool_pressure()
                    >= self.config.shed_recover_occupancy
                    and nxt.num_preemptions == 0):
                # shed ladder rung 2: no FRESH admissions while the POOL is
                # the pressured resource. Preempted residents still resume —
                # their latency budget is spent and their eviction already
                # relieved the pool. The pressure guard matters twice over:
                # queue pressure alone must never gate admission (admitting
                # from the queue is the only way a queue drains), and
                # cache-only blocks must not count as pool pressure (gated
                # admission never allocates, and allocation is the only
                # eviction trigger) — either one livelocks.
                break
            ids = nxt.resume_ids
            P = len(ids)
            hit_blocks: List[int] = []
            matched = 0
            if self.prefix_cache is not None:
                t0 = pc()
                with RecordEvent("serving.prefix_match"):
                    hit_blocks = self.prefix_cache.match_and_pin(ids)
                matched = min(len(hit_blocks) * bs, P - 1)
                radix_s = pc() - t0
            # full-prompt hit ⇒ the last shared block gets partially
            # rewritten (the one recomputed token) ⇒ fork it first
            cow = matched < len(hit_blocks) * bs
            need_blocks = -(-P // bs) - len(hit_blocks) + (1 if cow else 0)
            t0 = pc()
            fresh: List[int] = []
            wfresh: List[int] = []
            with RecordEvent("serving.block_accounting"):
                try:
                    inject("serving.block_alloc")
                    fresh = (self.allocator.allocate(need_blocks * bs)
                             if need_blocks > 0 else [])
                    if self._window is not None:
                        # the pages the prompt's last window reaches
                        wfirst, wlast = self._window_span(P - 1)
                        wfresh = self.window_allocator.allocate(
                            (wlast - wfirst + 1) * bs)
                except KVPoolExhausted:
                    if fresh:                # the window class was dry
                        self.allocator.free(fresh)
                    if hit_blocks:
                        self.prefix_cache.unpin(hit_blocks)
                    break                    # running seqs keep precedence
                except Exception as exc:
                    # nothing allocated yet: drop the pins and triage. A
                    # transient fault leaves the request queued (retried
                    # next step) until its K-consecutive-fault budget runs
                    # out.
                    if hit_blocks:
                        self.prefix_cache.unpin(hit_blocks)
                    site = self._fault_site(exc, "serving.block_alloc")
                    if classify_error(exc) == "fatal":
                        self.metrics.observe_fault(site, "fatal")
                        raise
                    self._note_fault(site)
                    if self._fault_budget_exhausted(nxt):
                        self.queue.pop()
                        self.metrics.observe_fault(site, "request_failed")
                        self.metrics.requests_failed += 1
                        finished.append(
                            self._finalize_off_grid(nxt, "failed"))
                        continue
                    break
            block_s += pc() - t0
            req = self.queue.pop()
            trace = self.tracer.get(req.request_id)
            if trace is not None:
                trace.transition(PHASE_ADMIT)
                if req.num_preemptions:
                    trace.event("resumed",
                                preemptions=req.num_preemptions)
            t0 = pc()
            with RecordEvent("serving.block_accounting"):
                blocks = list(hit_blocks)
                if cow:
                    new_b = fresh.pop(0)
                    self._pools = copy_block_in_pools(
                        self._pools, blocks[-1], new_b)
                    self.allocator.decref(blocks[-1])  # drop pin on original
                    blocks[-1] = new_b
                blocks += fresh
                req.blocks = blocks
                req.slot = slot
                req.state = RequestState.RUNNING
                S = P - matched              # uncached suffix to prefill
                row = np.full((1, self.config.max_blocks_per_seq), -1,
                              np.int32)
                row[0, :len(blocks)] = blocks
                wrow = wbase = None
                if self._window is not None:
                    req.window_blocks = wfresh
                    wrow = np.full((1, self._window_blocks_per_seq), -1,
                                   np.int32)
                    wrow[0, :len(wfresh)] = wfresh
                    wbase = np.array([wfirst * bs], np.int32)
            block_s += pc() - t0
            if self._chunk_step is not None:
                # chunked admission: pack the slot MID-PREFILL (frontier =
                # the prefix-cache hit) and return — the chunk pump
                # advances it from the decode loop, bounded per step.
                # Until the final chunk samples the first token the slot
                # is excluded from decode dispatch and its table row is
                # masked, so no decode write can land inside an
                # incomplete prefill.
                self._slots[slot] = req
                self._table[slot] = row[0]
                self._pos[slot] = matched
                self._disp_pos[slot] = matched
                self._disp_emitted[slot] = req.num_generated
                self._next_tok[slot] = 0
                req.prefill_pos = matched
                if self.prefix_cache is not None:
                    self.prefix_cache.record_admission(matched, S)
                if trace is not None:
                    trace.note(cached_tokens=matched, prefilled_tokens=S,
                               chunk_size=self._chunk_size)
                    trace.subspan("prefix_match", radix_s)
                self.stall.record("radix_match", radix_s)
                self.stall.record("block_accounting", block_s)
                self.stall.record(
                    "admission", (pc() - it_t0) - radix_s - block_s)
                continue
            Pb = min(_bucket(S, self.config.prefill_bucket), self.max_seq_len)
            ids_np = np.zeros((1, Pb), np.int32)
            ids_np[0, :S] = ids[matched:]
            t0 = pc()
            try:
                inject("serving.prefill")
                with RecordEvent("serving.prefill"), paddle.no_grad():
                    with RecordEvent("serving.stage"):
                        args = (paddle.to_tensor(ids_np),
                                paddle.to_tensor(np.arange(
                                    matched, matched + Pb, dtype=np.int32)),
                                self._caches(
                                    row, np.array([matched], np.int32),
                                    wrow, wbase),
                                paddle.to_tensor(np.array([S - 1], np.int32)))
                    with RecordEvent("serving.launch"):
                        next_ids, stats, caches = self._step_fn(*args)
                        self._store_pools(caches)
            except Exception as exc:
                # the request is popped and holds blocks but is NOT packed
                # into the grid: release everything (free() drops fresh
                # blocks and decrefs cache pins alike) and either requeue
                # for a clean re-prefill or fail it past its budget.
                self._release_blocks(req)
                req.slot = -1
                site = self._fault_site(exc, "serving.prefill")
                if classify_error(exc) == "fatal":
                    self.metrics.observe_fault(site, "fatal")
                    raise
                self._note_fault(site)
                if self._fault_budget_exhausted(req):
                    self.metrics.observe_fault(site, "request_failed")
                    self.metrics.requests_failed += 1
                    finished.append(self._finalize_off_grid(req, "failed"))
                else:
                    self.queue.push(req, force=True)
                    if trace is not None:
                        trace.transition(PHASE_QUEUED)
                        trace.event("prefill_fault", site=site,
                                    consecutive=req.consecutive_faults)
                continue
            prefill_s = pc() - t0
            self.metrics.prefills += 1
            self.metrics.prefill_tokens += S
            if self.prefix_cache is not None:
                self.prefix_cache.record_admission(matched, S)
            # pack into the grid: the slot is live the moment its prefill
            # is in flight (committed token lands at sync/drain below)
            self._slots[slot] = req
            self._table[slot] = row[0]
            if self._window is not None:
                self._wtable[slot] = wrow[0]
                self._wbase[slot] = wbase[0]
            self._pos[slot] = P
            self._disp_pos[slot] = P
            self._disp_emitted[slot] = req.num_generated + 1
            self._next_tok[slot] = 0
            req.consecutive_faults = 0   # clean admission resets the budget
            if trace is not None:
                trace.note(cached_tokens=matched, prefilled_tokens=S)
                trace.subspan("prefix_match", radix_s)
                trace.subspan("prefill", prefill_s)
                trace.transition(PHASE_RUNNING)
            dispatch_s = 0.0
            if self.dispatch_depth:
                # dispatch-ahead: splice the on-device first token into
                # the decode carry and let the drain thread fetch it —
                # emit/EOS/length land at commit time (bounded staleness)
                t0 = pc()
                self._splice_admit(slot, next_ids)
                # admit stats are a [1]-batch prefill view — not tracked;
                # steady-state telemetry comes from the decode entries
                self._enqueue(_InFlight("admit", next_ids, [(slot, req)]))
                dispatch_s = pc() - t0
                self.stall.record("dispatch", dispatch_s)
                if trace is not None:
                    trace.subspan("dispatch", dispatch_s)
            else:
                # the ONE deliberate admission sync: the first sampled
                # token decides eos/packing — drained through the same
                # metered helper as the batch decode path
                arr, _stats_np, sync_s = self._fetch_tokens(next_ids)
                if trace is not None:
                    trace.subspan("sampling_sync", sync_s)
                with RecordEvent("serving.commit"):
                    tok = int(arr[0])
                    self._next_tok[slot] = tok
                    t0 = pc()
                    req.emit(tok)
                    stream_s = pc() - t0
                    self._events.append((req.request_id, tok))
                    self.metrics.generated_tokens += 1
                    if (req.eos_token_id is not None
                            and tok == req.eos_token_id):
                        finished.append(self._retire(slot, "eos"))
                    elif req.num_generated >= req.max_new_tokens:
                        finished.append(self._retire(slot, "length"))
            # attribute this admission's host time (device prefill excluded)
            self.stall.record("radix_match", radix_s)
            self.stall.record("block_accounting", block_s)
            self.stall.record("sampling_sync", sync_s)
            self.stall.record("streaming", stream_s)
            self.stall.record(
                "admission",
                (pc() - it_t0) - radix_s - block_s - sync_s - stream_s
                - prefill_s - dispatch_s)
        return finished

    @hot_path(reason="bounded per-step prefill work fused into the decode "
                     "loop — the chunk budget IS the TPOT protection")
    @holds_lock("_elock")
    def _prefill_chunks(self) -> List[Request]:
        """Advance mid-prefill slots by at most ``prefill_chunks_per_step``
        fixed-width ``[1, C]`` chunks (FCFS: lowest request id first, so
        one prefill finishes before the next starts). The chunk offset is
        data (cache ``pos`` + absolute position ids) — one compiled chunk
        program serves every offset. Non-final chunks discard their
        sampled id without a host sync; the final chunk's token follows
        the admission first-token path (sync fetch at depth 0, carry
        splice + drain commit at depth > 0) and the request transitions
        to RUNNING."""
        finished: List[Request] = []
        if self._chunk_step is None:
            return finished
        C = self._chunk_size
        budget = max(1, int(self.config.prefill_chunks_per_step))
        pc = _time.perf_counter
        while budget > 0:
            cand = [(r.request_id, s) for s, r in enumerate(self._slots)
                    if r is not None and r.is_prefilling]
            if not cand:
                return finished
            slot = min(cand)[1]
            req = self._slots[slot]
            trace = self.tracer.get(req.request_id)
            ids = req.resume_ids
            P = len(ids)
            off = int(req.prefill_pos)
            n = min(C, P - off)
            final = off + n >= P
            ids_np = np.zeros((1, C), np.int32)
            ids_np[0, :n] = ids[off:off + n]
            row = self._table[slot:slot + 1].copy()
            posv = np.array([off], np.int32)
            t0 = pc()
            try:
                inject("serving.prefill")
                with RecordEvent("serving.prefill"), paddle.no_grad():
                    with RecordEvent("serving.stage"):
                        args = (paddle.to_tensor(ids_np),
                                paddle.to_tensor(np.arange(
                                    off, off + C, dtype=np.int32)),
                                self._caches(row, posv),
                                paddle.to_tensor(np.array([n - 1], np.int32)))
                    with RecordEvent("serving.launch"):
                        next_ids, caches = self._chunk_step(*args)
                        self._store_pools(caches)
            except Exception as exc:
                site = self._fault_site(exc, "serving.prefill")
                if classify_error(exc) == "fatal":
                    self.metrics.observe_fault(site, "fatal")
                    raise
                self._note_fault(site)
                # release the slot for a clean re-prefill (or terminal
                # fail). Completed-chunk KV is donated to the radix tree
                # first, so the retry's prefix match can recover the
                # frontier instead of recomputing it.
                self._cache_insert_on_release(req, slot)
                self.allocator.free(req.blocks)
                req.blocks = []
                req.slot = -1
                req.prefill_pos = -1
                self._slots[slot] = None
                self._table[slot] = -1
                self._pos[slot] = 0
                self._next_tok[slot] = 0
                self._disp_pos[slot] = 0
                self._disp_emitted[slot] = 0
                if self._fault_budget_exhausted(req):
                    self.metrics.observe_fault(site, "request_failed")
                    self.metrics.requests_failed += 1
                    finished.append(self._finalize_off_grid(req, "failed"))
                elif not req.done:
                    self.queue.push(req, force=True)
                    if trace is not None:
                        trace.transition(PHASE_QUEUED)
                        trace.event("prefill_fault", site=site,
                                    consecutive=req.consecutive_faults)
                budget -= 1
                continue
            chunk_s = pc() - t0
            self.metrics.prefill_tokens += n
            self._step_chunked_tokens += n
            req.prefill_pos = off + n
            self._pos[slot] = off + n
            self._disp_pos[slot] = off + n
            if trace is not None:
                # per-chunk events keep TTFT attribution truthful when a
                # prefill spans several scheduler steps
                trace.event("prefill_chunk", offset=off, size=n)
                trace.subspan("prefill", chunk_s)
            budget -= 1
            if not final:
                continue
            # final chunk: the request leaves the prefilling state and its
            # sampled token is the first output — same contract as the
            # whole-prompt admission prefill
            req.prefill_pos = -1
            req.consecutive_faults = 0
            self.metrics.prefills += 1
            self._disp_emitted[slot] = req.num_generated + 1
            if trace is not None:
                trace.transition(PHASE_RUNNING)
            if self.dispatch_depth and self._spec_step is None:
                t0 = pc()
                self._splice_admit(slot, next_ids)
                self._enqueue(_InFlight("admit", next_ids, [(slot, req)]))
                dispatch_s = pc() - t0
                self.stall.record("dispatch", dispatch_s)
                if trace is not None:
                    trace.subspan("dispatch", dispatch_s)
            else:
                arr, _stats_np, sync_s = self._fetch_tokens(next_ids)
                if trace is not None:
                    trace.subspan("sampling_sync", sync_s)
                with RecordEvent("serving.commit"):
                    tok = int(arr[0])
                    self._next_tok[slot] = tok
                    t0 = pc()
                    req.emit(tok)
                    self.stall.record("streaming", pc() - t0)
                    self._events.append((req.request_id, tok))
                    self.metrics.generated_tokens += 1
                    if (req.eos_token_id is not None
                            and tok == req.eos_token_id):
                        finished.append(self._retire(slot, "eos"))
                    elif req.num_generated >= req.max_new_tokens:
                        finished.append(self._retire(slot, "length"))
        return finished

    @holds_lock("_elock")
    def _absorb_step_fault(self, exc: BaseException, running: List[int],
                           attempt: int) -> List[Request]:
        """Triage one decode-step fault. Fatal errors re-raise. Transient
        ones charge every running request's K-consecutive budget, retire
        the over-budget ones as ``failed`` (their slots simply drop out of
        the retry — the batch is not poisoned), back off, and let the
        caller retry. Returns the requests failed by this fault.

        The backoff is an ``_elock.wait``, not a ``time.sleep``: a
        Condition wait RELEASES the engine lock while sleeping, so
        ``add_request``/``cancel``/``shutdown`` proceed during a fault
        backoff instead of stalling behind it (and ``notify_all`` wakes
        the backoff early). Both callers re-read live state after the
        absorb, so interleaved mutation is safe."""
        site = self._fault_site(exc, "serving.decode_step")
        if classify_error(exc) == "fatal":
            self.metrics.observe_fault(site, "fatal")
            raise exc
        self._note_fault(site)
        failed: List[Request] = []
        for s in running:
            req = self._slots[s]
            if req is None:
                continue
            if self._fault_budget_exhausted(req):
                self.metrics.observe_fault(site, "request_failed")
                self.metrics.requests_failed += 1
                failed.append(self._retire(s, "failed"))
        backoff = self.config.retry_backoff_s
        if backoff > 0:
            self._elock.wait(min(backoff * (2 ** attempt), 1.0))
        return failed

    @hot_path(reason="the decode-loop iteration itself")
    @holds_lock("_elock")
    def _decode_once(self) -> List[Request]:
        """One SYNCHRONOUS fixed-shape decode iteration (depth 0): every
        running slot dispatches, the sampled tokens are fetched inline
        through the shared metered drain helper, and the step commits
        immediately.

        Stall attribution: the capacity loop (block extends + preemption
        table rewrites) is ``block_accounting``, the blocking token read is
        ``sampling_sync``, per-token emit/callbacks are ``streaming`` — the
        exact host seams ``dispatch_depth > 0`` overlaps.

        Fault contract: everything up to and including the blocking token
        read sits inside the retry envelope. The injection point fires
        BEFORE the dispatch consumes (donates) the pools, and the capacity
        extend is idempotent per position — so a retried step replays
        against identical state and surviving sequences stay
        token-identical to a fault-free run. A fault AFTER dispatch rolls
        the dispatched view back so the replay targets identical
        positions."""
        finished: List[Request] = []
        attempt = 0
        while True:
            pairs = self._live_pairs()
            if not pairs:
                return finished
            dispatched = False
            try:
                with self.stall.timed("block_accounting"):
                    for s, req in pairs:
                        if self._slots[s] is not req:
                            continue         # evicted by an earlier slot
                        self._ensure_decode_capacity(s)
                    # capacity assurance may have preempted ANY slot
                    pairs = self._live_pairs()
                if not pairs:
                    return finished
                next_ids, stats, _disp_s = self._dispatch_decode(pairs)
                dispatched = True
                arr, stats_np, _sync_s = self._fetch_tokens(next_ids,
                                                            stats=stats)
            except Exception as exc:
                if dispatched:
                    # tokens were lost after the dispatch advanced the
                    # dispatched view: roll it back so the retry replays
                    # the identical step
                    for s, _r in pairs:
                        self._disp_pos[s] -= 1
                        self._disp_emitted[s] -= 1
                    self._carry = None
                finished += self._absorb_step_fault(
                    exc, [s for s, _r in pairs], attempt)
                attempt += 1
                continue
            break
        self.metrics.decode_steps += 1
        if stats_np is not None:
            self._note_telemetry(stats_np)
        with RecordEvent("serving.commit"):
            finished += self._commit_decode(pairs, arr, metered=True)
        return finished

    # ---- speculative decoding (serving/spec/) --------------------------

    @hot_path(reason="the speculative decode iteration: one [S, 1+k] "
                     "verify call commits up to k+1 tokens per slot")
    @holds_lock("_elock")
    def _spec_decode_once(self) -> List[Request]:
        """One speculative decode iteration: host proposals (n-gram
        suffix match over each slot's committed context), ONE batched
        ``[S, 1+k]`` verify dispatch, one token fetch (greedy rows +
        in-program accept counts ride the same ``[S, k+2]`` read — zero
        extra host syncs), bulk commit of each slot's accepted prefix
        plus the model's bonus token.

        Speculation's accepted length is DATA the next step's positions
        depend on, so the verify path is synchronous at every
        ``dispatch_depth``: in-flight async work (admission first tokens)
        drains first, and the carry is dropped after commit — the token
        streams stay bit-identical to the plain engine at depth 0 and >0
        alike. Steps where no slot has a proposal fall back to the plain
        ``[S, 1]`` decode program (both programs are warmed and pinned)."""
        finished: List[Request] = []
        if self._inflight:
            self._drain_all()
        k = int(self.config.spec_k)
        S = self.config.max_num_seqs
        attempt = 0
        while True:
            pairs = self._live_pairs()
            if not pairs:
                return finished
            props = np.zeros((S, k), np.int32)
            plen = np.zeros(S, np.int32)
            with self.stall.timed("spec_propose"):
                for s, req in pairs:
                    p = self._proposer.propose(req.resume_ids, k)
                    if p is not None and len(p):
                        props[s, :len(p)] = p
                        plen[s] = len(p)
                        self._spec_proposed += len(p)
            if not plen.any():
                # nothing proposed anywhere: a k-wide verify would be
                # pure overhead — run the plain decode program instead
                out = finished + self._decode_once()
                self._carry = None
                return out
            try:
                with self.stall.timed("block_accounting"):
                    for s, req in pairs:
                        if self._slots[s] is not req:
                            continue
                        self._ensure_decode_capacity(s, tokens=k + 1)
                    pairs = self._live_pairs()
                if not pairs:
                    return finished
                out_dev = self._dispatch_spec(props)
                arr, _stats_np, _sync_s = self._fetch_tokens(out_dev)
            except Exception as exc:
                finished += self._absorb_step_fault(
                    exc, [s for s, _r in pairs], attempt)
                attempt += 1
                continue
            break
        self.metrics.decode_steps += 1
        self._spec_steps += 1
        with RecordEvent("serving.commit"):
            finished += self._commit_spec(pairs, arr, plen)
        # committed state is complete and exact — rebuild the next
        # dispatch's inputs from host state rather than the carry
        self._carry = None
        return finished

    @hot_path(reason="stages one [S, 1+k] verify step on device")
    @holds_lock("_elock")
    def _dispatch_spec(self, props: np.ndarray):
        """Dispatch ONE fixed-shape verification step: ids[:, 0] is each
        slot's committed carry token, ids[:, 1:] the (padded) drafts, at
        absolute positions ``disp_pos .. disp_pos+k`` (clamped to the
        window — tail positions past it belong to rejected drafts whose
        tokens the commit clamps away, and their KV writes drop
        in-kernel). Mid-prefill and frozen slots keep their masked table
        rows, so speculation never writes into them."""
        S, k = self.config.max_num_seqs, int(self.config.spec_k)
        inject("serving.decode_step")
        with RecordEvent("serving.decode_step"), paddle.no_grad():
            with RecordEvent("serving.stage"):
                ids = np.zeros((S, k + 1), np.int32)
                ids[:, 0] = self._next_tok
                ids[:, 1:] = props
                pos = (self._disp_pos[:, None]
                       + np.arange(k + 1, dtype=np.int32)[None, :])
                np.clip(pos, 0, self.max_seq_len - 1, out=pos)
                args = (paddle.to_tensor(ids),
                        paddle.to_tensor(pos.astype(np.int32)),
                        self._caches(self._disp_table(), self._disp_pos))
            with RecordEvent("serving.launch"):
                out, caches = self._spec_step(*args)
                self._store_pools(caches)
        return out

    @holds_lock("_elock")
    def _commit_spec(self, pairs, arr, plen) -> List[Request]:
        """Commit one verify step: ``arr`` is the fetched ``[S, k+2]``
        block (greedy tokens ``g_0..g_k``, then the device accept count).
        Each slot emits its accepted prefix plus the model's own next
        token — ``e = min(accept+1, proposal_len+1, remaining budget)``,
        truncated at EOS — so every emitted token is the model's argmax
        given the tokens before it: exactly the autoregressive stream.
        The committed and dispatched views advance together (the verify
        path is synchronous), and the last emitted token becomes the next
        step's carry token."""
        k = int(self.config.spec_k)
        pc = _time.perf_counter
        stream_s = 0.0
        done: List[Request] = []
        for s, req in pairs:
            if self._slots[s] is not req or req.done:
                continue                 # retired/cancelled: stale
            req.consecutive_faults = 0
            g = arr[s, :k + 1]
            accept = min(int(arr[s, k + 1]), int(plen[s]))
            self._spec_accepted += accept
            e = min(accept + 1, req.max_new_tokens - req.num_generated)
            emitted = 0
            retired = False
            for i in range(e):
                t = int(g[i])
                t0 = pc()
                req.emit(t)
                stream_s += pc() - t0
                self._events.append((req.request_id, t))
                self.metrics.generated_tokens += 1
                emitted = i + 1
                if req.eos_token_id is not None and t == req.eos_token_id:
                    retired = True
                    break
            self._spec_emitted += emitted
            self._pos[s] += emitted      # emitted-1 cached + 1 fed next
            self._disp_pos[s] = self._pos[s]
            self._next_tok[s] = int(g[emitted - 1])
            self._disp_emitted[s] = req.num_generated
            if retired:
                done.append(self._retire(s, "eos"))
            elif req.num_generated >= req.max_new_tokens:
                done.append(self._retire(s, "length"))
        self.stall.record("streaming", stream_s)
        return done

    def spec_stats(self) -> Optional[Dict[str, float]]:
        """Speculation accounting (None when ``spec_k`` is 0):
        verify-step count, proposed/accepted draft tokens, the accept
        rate, and mean emitted tokens per verify step. Overall
        tokens-per-decode-step (including no-proposal fallback steps) is
        ``metrics.generated_tokens / metrics.decode_steps``."""
        if self._spec_step is None:
            return None
        return {
            "verify_steps": self._spec_steps,
            "proposed_tokens": self._spec_proposed,
            "accepted_tokens": self._spec_accepted,
            "accept_rate": (self._spec_accepted / self._spec_proposed
                            if self._spec_proposed else 0.0),
            "emitted_tokens": self._spec_emitted,
            "tokens_per_verify_step": (self._spec_emitted / self._spec_steps
                                       if self._spec_steps else 0.0),
        }

    # ---- async engine (dispatch-ahead decode) --------------------------

    def _live_pairs(self) -> List[Tuple[int, Request]]:
        """Slots eligible for the next decode dispatch: occupied, not
        frozen (a frozen slot already has its full ``max_new_tokens``
        budget in flight — dispatching more would write past the block
        budget the request was admitted with), and not mid-prefill (a
        chunked admission's slot must not decode until its final chunk
        has sampled the first token)."""
        return [(s, r) for s, r in enumerate(self._slots)
                if r is not None and not r.is_prefilling
                and int(self._disp_emitted[s]) < r.max_new_tokens]

    def _disp_table(self, table: Optional[np.ndarray] = None) -> np.ndarray:
        """Block table (the full class's, or ``table``) for the next
        dispatch: frozen and mid-prefill slots get a masked (-1) row — the
        paged write kernel drops -1-table writes, so their speculative K/V
        is discarded instead of overrunning the row (or corrupting a
        half-built prefill)."""
        table = self._table if table is None else table
        frozen = [s for s, r in enumerate(self._slots)
                  if r is not None
                  and (r.is_prefilling
                       or int(self._disp_emitted[s]) >= r.max_new_tokens)]
        if not frozen:
            return table
        tbl = table.copy()
        tbl[frozen] = -1
        return tbl

    @holds_lock("_elock")
    def _decode_ids(self):
        """Token ids [S, 1] for the next decode dispatch: the device-
        resident carry when one exists (no host round-trip), else the
        committed host tokens."""
        S = self.config.max_num_seqs
        if self._carry is not None:
            return paddle.reshape(self._carry, [S, 1])
        return paddle.to_tensor(self._next_tok.reshape(S, 1)
                                .astype(np.int32))

    @hot_path(reason="stages one decode step on device without syncing it")
    @holds_lock("_elock")
    def _dispatch_decode(self, pairs):
        """Dispatch ONE fixed-shape decode step over the slot grid;
        returns ``(next_ids, stats, host_s)`` — the device-resident
        sampled ids, the in-program telemetry block (None when off), and
        the host-scheduling seconds spent around the compiled call
        (staging, table masking, carry/bookkeeping). The compiled-step
        invocation itself is excluded from ``host_s``: it is compute
        dispatch, not host scheduling — the same rule that keeps prefill
        out of the stall family. The dispatched view advances only after
        the dispatch succeeds (a faulted dispatch retries against
        identical state), and the injection point fires before the pools
        are donated — replay is token-identical."""
        S = self.config.max_num_seqs
        pc = _time.perf_counter
        t0 = pc()
        inject("serving.decode_step")
        with RecordEvent("serving.decode_step"), paddle.no_grad():
            with RecordEvent("serving.stage"):
                # _disp_pos is mutated in place right below: _caches
                # uploads a copy of it (the stale-transfer hazard async
                # dispatch exposes), and the step's [S, 1] position ids
                # are that one upload, reshaped on the device
                caches = self._caches(
                    self._disp_table(), self._disp_pos,
                    *((self._disp_table(self._wtable), self._wbase)
                      if self._window is not None else ()))
                args = (self._decode_ids(),
                        paddle.reshape(caches[0].pos, [S, 1]),
                        caches, self._zero_gather)
            with RecordEvent("serving.launch"):
                t_call = pc()
                next_ids, stats, caches = self._step_fn(*args)
                call_s = pc() - t_call
                self._store_pools(caches)
        for s, _req in pairs:
            self._disp_pos[s] += 1
            self._disp_emitted[s] += 1
        if self.dispatch_depth:
            self._carry = next_ids
        return next_ids, stats, (pc() - t0) - call_s

    @hot_path(reason="the engine's only blocking D2H read — every sampled-"
                     "token fetch (admission, batch decode, drain thread) "
                     "funnels through this one metered helper")
    def _fetch_tokens(self, next_ids, phase: str = "sampling_sync",
                      stats=None):
        """THE single metered token-readback site (the two pre-async call
        sites — admission first-token and batch decode — plus the drain
        thread all land here, so stall accounting cannot diverge between
        paths). ``phase="sampling_sync"`` meters critical-path stall;
        ``phase="drain"`` routes to the overlapped drain-wait counter.
        ``stats`` (the step's in-program telemetry block) rides the SAME
        blocking read — by the time the tokens are host-visible the step
        has completed, so the stats copy adds no extra device sync.
        Returns ``(tokens_np, stats_np_or_None, seconds_blocked)``."""
        t0 = _time.perf_counter()
        with self.stall.timed(phase):
            arr = np.asarray(next_ids.numpy())
            stats_np = (None if stats is None
                        else np.asarray(stats.numpy()))
        return arr, stats_np, _time.perf_counter() - t0

    @holds_lock("_elock")
    def _splice_admit(self, slot: int, next_ids):
        """Patch an admission prefill's on-device first token into the
        decode carry so the next dispatched step consumes it without a
        host round-trip (seeding the carry from committed host tokens if
        no step is in flight yet)."""
        S = self.config.max_num_seqs
        if self._carry is None:
            self._carry = paddle.to_tensor(self._next_tok.astype(np.int32))
        mask = np.zeros(S, bool)
        mask[slot] = True
        self._carry = splice_carry(self._carry, next_ids,
                                   paddle.to_tensor(mask))

    @holds_lock("_elock")
    def _enqueue(self, entry: _InFlight):
        self._inflight.append(entry)
        self._elock.notify_all()
        self._ensure_drain_thread()

    def _ensure_drain_thread(self):
        t = self._drain_thread
        if t is not None and t.is_alive():
            return
        t = threading.Thread(target=_drain_worker,
                             args=(weakref.ref(self),),
                             name="serving-drain", daemon=True)
        self._drain_thread = t
        t.start()

    def _next_drainable(self, timeout: float = 0.05):
        """(drain thread) the oldest in-flight entry, or None after a
        bounded wait — the worker re-checks scheduler liveness between
        waits so it can exit when the scheduler is dropped."""
        with self._elock:
            if not self._inflight:
                self._elock.wait(timeout)
            return self._inflight[0] if self._inflight else None

    @hot_path(reason="drain-thread commit: fetch off the critical path, "
                     "then host bookkeeping under the engine lock")
    def _drain_one(self, entry: _InFlight):
        """(drain thread) fetch one in-flight step's tokens — the device
        wait overlaps whatever the scheduler thread is doing — then commit
        them under the engine lock. A fetch/commit failure poisons the
        pipeline (``_drain_exc``) and surfaces on the scheduler thread at
        its next barrier."""
        try:
            arr, stats_np, _ = self._fetch_tokens(entry.next_ids,
                                                  phase="drain",
                                                  stats=entry.stats)
            exc: Optional[BaseException] = None
        except BaseException as e:        # noqa: BLE001 — must not die silently
            arr, stats_np, exc = None, None, e
        with self._elock:
            try:
                if exc is None:
                    if stats_np is not None:
                        self._note_telemetry(stats_np)
                    self._done_async += self._commit_entry(entry, arr)
                else:
                    self._drain_exc = exc
            except BaseException as e:    # noqa: BLE001
                self._drain_exc = e
            finally:
                if self._inflight and self._inflight[0] is entry:
                    self._inflight.popleft()
                self._elock.notify_all()

    @holds_lock("_elock")
    def _commit_entry(self, entry: _InFlight, arr) -> List[Request]:
        if entry.kind == "admit":
            slot, req = entry.slots[0]
            return self._commit_admit_token(slot, req, int(arr[0]))
        self.metrics.decode_steps += 1
        return self._commit_decode(entry.slots, arr, metered=False)

    @holds_lock("_elock")
    def _commit_admit_token(self, slot: int, req: Request,
                            tok: int) -> List[Request]:
        """Commit an admission's drained first token (depth > 0): emit,
        stamp, and retire on EOS/length — exactly what the synchronous
        path does inline."""
        done: List[Request] = []
        if self._slots[slot] is not req or req.done:
            return done                  # retired while in flight: stale
        self._next_tok[slot] = tok
        req.emit(tok)
        self._events.append((req.request_id, tok))
        self.metrics.generated_tokens += 1
        if req.eos_token_id is not None and tok == req.eos_token_id:
            done.append(self._retire(slot, "eos"))
        elif req.num_generated >= req.max_new_tokens:
            done.append(self._retire(slot, "length"))
        return done

    @holds_lock("_elock")
    def _commit_decode(self, pairs, step_np, metered: bool) -> List[Request]:
        """Commit one decode step's tokens: advance the COMMITTED view,
        emit, retire EOS/length. Tokens for a slot whose request was
        retired (or replaced) after this step was dispatched are stale
        speculation and are discarded — that identity check IS the
        bounded-staleness contract. ``metered`` folds emit time into the
        critical-path ``streaming`` stall (inline depth-0 commits only;
        drain-thread commits overlap decode and must not count)."""
        pc = _time.perf_counter
        stream_s = 0.0
        done: List[Request] = []
        for s, req in pairs:
            if self._slots[s] is not req or req.done:
                continue                 # retired/cancelled in flight
            req.consecutive_faults = 0   # a clean step resets budgets
            self._pos[s] += 1            # fed token is now cached
            t = int(step_np[s])
            self._next_tok[s] = t
            t0 = pc()
            req.emit(t)
            stream_s += pc() - t0
            self._events.append((req.request_id, t))
            self.metrics.generated_tokens += 1
            if req.eos_token_id is not None and t == req.eos_token_id:
                done.append(self._retire(s, "eos"))
            elif req.num_generated >= req.max_new_tokens:
                done.append(self._retire(s, "length"))
        if metered:
            self.stall.record("streaming", stream_s)
        return done

    @holds_lock("_elock")
    def _raise_drain_exc(self):
        """Surface a drain-thread failure on the scheduler thread."""
        if self._drain_exc is not None:
            exc = self._drain_exc
            self._drain_exc = None
            raise exc

    @holds_lock("_elock")
    def _drain_all(self):
        """Barrier: wait until every in-flight step has committed, then
        drop the device carry so the next dispatch rebuilds its inputs
        from committed host state. Runs before any action that must see
        (or mutate) committed-only state: preemption, cancellation and
        deadline sweeps, fault absorption, weight reload, shutdown."""
        while self._inflight and self._drain_exc is None:
            self._ensure_drain_thread()
            self._elock.wait(0.2)
        self._carry = None
        self._raise_drain_exc()

    @holds_lock("_elock")
    def _backpressure(self):
        """Bound the lookahead to ``dispatch_depth`` undrained steps.
        Together with the one-decode-dispatch-per-``step()`` cadence this
        is what makes a cancel between steps token-identical to depth 0:
        after k calls exactly k decode steps have been dispatched, and the
        cancel barrier commits all of them first."""
        while (len(self._inflight) > self.dispatch_depth
               and self._drain_exc is None):
            self._ensure_drain_thread()
            self._elock.wait(0.2)
        self._raise_drain_exc()

    @hot_path(reason="the async decode iteration: dispatch, never sync")
    @holds_lock("_elock")
    def _decode_dispatch_once(self) -> bool:
        """(depth > 0) dispatch one decode step over the live slots and
        enqueue it for the drain thread; never blocks on tokens. A
        dispatch fault drains the pipeline first (committing the clean
        in-flight steps and resetting fault budgets), charges budgets,
        and retries from committed host state — token-identical replay,
        the same contract as the synchronous envelope. Returns False when
        there was nothing to dispatch."""
        attempt = 0
        while True:
            pairs = self._live_pairs()
            if not pairs:
                return False
            try:
                with self.stall.timed("block_accounting"):
                    for s, req in pairs:
                        if self._slots[s] is not req:
                            continue     # evicted/drained by earlier slot
                        self._ensure_decode_capacity(s)
                    pairs = self._live_pairs()
                if not pairs:
                    return False
                next_ids, stats, disp_s = self._dispatch_decode(pairs)
            except Exception as exc:
                self._drain_all()
                self._done_async += self._absorb_step_fault(
                    exc, [s for s, _r in pairs], attempt)
                attempt += 1
                continue
            t0 = _time.perf_counter()
            self._enqueue(_InFlight("decode", next_ids, pairs, stats=stats))
            self.stall.record(
                "dispatch", disp_s + (_time.perf_counter() - t0))
            return True

    @holds_lock("_elock")
    def _collect_async_done(self) -> List[Request]:
        done, self._done_async = self._done_async, []
        return done

    def shutdown(self) -> Dict[str, int]:
        """Quiesce the engine — the crash-path contract the bench's
        partial-artifact writer relies on: drain every in-flight step (no
        orphaned device work), stop the drain thread, then cancel
        everything still queued or running so every KV block returns to
        the pool. Idempotent; returns drain/cancel counts."""
        self.timeline.stop()
        with self._elock:
            drained = len(self._inflight)
            try:
                self._drain_all()
            except BaseException:        # noqa: BLE001
                # a poisoned pipeline must still not leak: entries hold
                # only device token arrays, dropping them frees nothing
                # block-shaped — the cancels below release the KV
                self._inflight.clear()
                self._carry = None
            self._drain_stop = True
            self._elock.notify_all()
        cancelled = 0
        for req in list(self.queue._items):
            self.cancel(req.request_id, cause="user")
            cancelled += 1
        for s in range(len(self._slots)):
            if self._slots[s] is not None:
                self.cancel(self._slots[s].request_id, cause="user")
                cancelled += 1
        return {"drained_in_flight": drained, "cancelled": cancelled}

    # ---- replica failover (router drain/export hooks) ------------------

    def export_restartable(self) -> List[Dict[str, object]]:
        """Decommission this scheduler and return every accepted-but-
        unfinished request as a restartable spec — the router's
        token-identical failover source. Committed work is preserved: the
        in-flight pipeline drains first (the drain thread is independent of
        any dead driver thread, so already-dispatched steps still land),
        then each queued or running request is exported carrying its
        prompt, its COMMITTED generated prefix, and its ORIGINAL
        arrival/deadline budget. Replaying ``prompt + prefix`` on a
        survivor is the same pure-recompute path as preemption resume, so
        the continued stream is bit-identical to an uninterrupted run.
        Every KV block returns to the pool and the prefix cache is flushed:
        after export the pool is provably leak-free and the scheduler
        admits nothing new (``_draining``)."""
        specs: List[Dict[str, object]] = []
        with self._elock:
            try:
                self._drain_all()
            except BaseException:        # noqa: BLE001 — poisoned pipeline:
                # committed state up to the poison point is still exact;
                # dropping undrained entries loses only device-resident
                # speculation no caller ever observed
                self._inflight.clear()
                self._carry = None
            self._draining = True
            self._drain_stop = True
            self._elock.notify_all()
            export_t = _time.perf_counter()
            for req in list(self.queue._items):
                self.queue.remove(req.request_id)
                spec = self._export_spec(req)
                spec["trace"] = self.tracer.export_snapshot(
                    req.request_id, t=export_t)
                specs.append(spec)
            for s in range(len(self._slots)):
                req = self._slots[s]
                if req is None:
                    continue
                spec = self._export_spec(req)
                # the request's timeline travels with its spec: the
                # survivor's tracer continues it through an explicit
                # ``failover`` phase — one request, one timeline
                spec["trace"] = self.tracer.export_snapshot(
                    req.request_id, t=export_t)
                specs.append(spec)
                self._release_blocks(req, s)
                req.slot = -1
                self._slots[s] = None
                self._table[s] = -1
                self._pos[s] = 0
                self._next_tok[s] = 0
                self._disp_pos[s] = 0
                self._disp_emitted[s] = 0
        if self.prefix_cache is not None:
            self.prefix_cache.flush()
        return specs

    @staticmethod
    def _export_spec(req: Request) -> Dict[str, object]:
        return {
            "request_id": req.request_id,
            "prompt_ids": np.asarray(req.prompt_ids, np.int64).copy(),
            "out_tokens": list(req.out_tokens),
            "max_new_tokens": req.max_new_tokens,
            "eos_token_id": req.eos_token_id,
            "priority": req.priority,
            "arrival_t": req.arrival_t,
            "first_token_t": req.first_token_t,
            "deadline_s": req.deadline_s,
            "num_preemptions": req.num_preemptions,
            # chunk frontier at export time (-1 unless mid-prefill):
            # forensic context for the failover — the survivor's replay
            # re-prefills prompt+prefix from scratch either way, so the
            # continued stream stays token-identical
            "prefill_pos": req.prefill_pos,
        }

    def import_resumed(self, spec: Dict[str, object], on_token=None) -> int:
        """Adopt one exported spec (see ``export_restartable``): the
        request enters this scheduler's queue carrying its committed
        generated prefix (the next admission prefills
        ``prompt + prefix`` — the preemption-resume path, token-identical)
        and its ORIGINAL arrival clock, so ``deadline_s`` and queue-TTL
        keep measuring from first admission, not from the failover.
        Bypasses admission control (``force=True``): the request was
        already accepted once, a survivor must not re-reject it. Returns
        this scheduler's request id for it."""
        with self._elock:
            rid = self._next_rid
            self._next_rid += 1
            req = Request(
                request_id=rid,
                prompt_ids=np.asarray(spec["prompt_ids"], np.int64),
                max_new_tokens=int(spec["max_new_tokens"]),
                eos_token_id=spec.get("eos_token_id"),
                priority=int(spec.get("priority", 0)),
                on_token=on_token,
                deadline_s=spec.get("deadline_s"))
            req.out_tokens = list(spec.get("out_tokens", ()))
            req.arrival_t = float(spec["arrival_t"])
            req.first_token_t = spec.get("first_token_t")
            # resume-first queue placement + honest accounting: a failover
            # replay IS a recompute resume
            req.num_preemptions = int(spec.get("num_preemptions", 0)) + 1
            self.queue.push(req, force=True)
            self.metrics.requests_received += 1
            # continue the exported timeline (explicit ``failover`` phase
            # bridging export -> here) when the spec carries one; a fresh
            # trace otherwise (old-format spec, tracing off on the donor)
            self.tracer.resume(rid, spec.get("trace"), t=req.arrival_t
                               if spec.get("trace") is None else None,
                               prompt_tokens=len(req.prompt_ids),
                               priority=req.priority)
            return rid

    # ---- public loop ---------------------------------------------------

    def has_unfinished(self) -> bool:
        with self._elock:
            return (bool(len(self.queue))
                    or any(r is not None for r in self._slots)
                    or bool(self._inflight))

    @hot_path(reason="one scheduler iteration: admit + decode")
    def step(self) -> List[RequestOutput]:
        """One scheduler iteration: admit into free slots (prefill), then
        one decode step; returns outputs finishing this iteration. Each
        iteration also lands one flight-recorder record (occupancy, token
        split, preemptions, cache activity) and feeds the alarm monitors.

        At ``dispatch_depth > 0`` the decode step is DISPATCHED, not
        synced: the iteration ends at the backpressure gate (≤ depth
        undrained steps) and outputs whose final token drained this
        iteration are collected from the drain thread — a request can
        finish up to ``depth`` iterations after its last token was
        dispatched, never later than the next barrier."""
        with RecordEvent("serving.step"):
            was_training = self.model.training
            if self._eval_epoch != mode_epoch():
                # some Layer's training flag (or tree) changed since this
                # scheduler last put the model in eval mode: walk it again
                self.model.eval()
                self._eval_epoch = mode_epoch()
            t0 = _time.perf_counter()
            pre_prefill = self.metrics.prefill_tokens
            pre_gen = self.metrics.generated_tokens
            pre_preempt = self.metrics.preemptions
            pre_hit = (self.prefix_cache._hit_tokens
                       if self.prefix_cache is not None else 0)
            self._step_evicted = 0
            self._step_chunked_tokens = 0
            self._step_faults = {}
            with RecordEvent("serving.sweep"):
                done = self._sweep_expired()
                level = self._apply_degradation()
            try:
                with self._elock:
                    if self.dispatch_depth:
                        self._raise_drain_exc()
                    with RecordEvent("serving.admit"):
                        done += self._admit()
                        done += self._prefill_chunks()
                    if self._spec_step is not None:
                        # speculation's accepted length is data the next
                        # step's positions depend on: the verify path is
                        # synchronous at every depth (it drains in-flight
                        # work first)
                        done += self._spec_decode_once()
                    elif self.dispatch_depth == 0:
                        done += self._decode_once()
                    elif (not self._decode_dispatch_once()
                            and self._inflight):
                        # nothing dispatchable but steps still in flight
                        # (workload tail / every slot at its budget):
                        # drain so retires land and run() converges
                        self._drain_all()
                    else:
                        self._backpressure()
                    done += self._collect_async_done()
            except KVPoolExhausted as exc:
                # allocation failure surfaces WITH forensics: the full owner
                # census + the flight-recorder tail ride on the exception
                # (``exc.device_memory_census``) instead of a bare message,
                # and one correlated postmortem bundle freezes for later
                if self.device_ledger is not None:
                    self.device_ledger.attach_forensics(
                        exc, flight_tail=self.flight.dump(last=8))
                self.postmortems.capture("kv_pool_exhausted", str(exc))
                raise
            finally:
                if was_training:
                    self.model.train()
            with RecordEvent("serving.account"):
                # a request can retire twice in one iteration's view (e.g. its
                # final token drained during a sweep's cancel barrier AND was
                # collected from the drain thread) — report each once
                outs: List[RequestOutput] = []
                seen = set()
                for r in done:
                    if r.request_id not in seen:
                        seen.add(r.request_id)
                        outs.append(r.output())
                step_s = _time.perf_counter() - t0
                self.metrics.step_time.record(step_s)
                if self._watchdog is not None:
                    self._watchdog.observe(step_s)
                with self._elock:
                    in_flight = len(self._inflight)
                self.metrics.observe_gauges(
                    queue_depth=len(self.queue),
                    running=sum(r is not None for r in self._slots),
                    allocator=self.allocator, live_tokens=self._live_tokens(),
                    dispatch_depth=self.dispatch_depth,
                    in_flight_steps=in_flight)
                if self.window_allocator is not None:
                    used = self.window_allocator.num_used_blocks
                    self._window_used.set(used)
                    self.window_blocks_peak = max(self.window_blocks_peak,
                                                  used)
                record = dict(
                    running=sum(r is not None for r in self._slots),
                    queue_depth=len(self.queue),
                    free_blocks=self.allocator.num_free_blocks,
                    prefill_tokens=self.metrics.prefill_tokens - pre_prefill,
                    generated_tokens=self.metrics.generated_tokens - pre_gen,
                    preemptions=self.metrics.preemptions - pre_preempt,
                    cache_hit_tokens=((self.prefix_cache._hit_tokens
                                       if self.prefix_cache is not None else 0)
                                      - pre_hit),
                    evicted_blocks=self._step_evicted,
                    finished=len(outs))
                # engine fields land in the flight ring ONLY at depth > 0 —
                # synchronous-baseline dumps stay byte-stable
                if self.dispatch_depth:
                    record["dispatch_depth"] = self.dispatch_depth
                    record["in_flight_steps"] = in_flight
                # chunk-pump split lands ONLY when chunking is on (same rule)
                if self._chunk_step is not None:
                    record["chunked_tokens"] = self._step_chunked_tokens
                # armed/fired injection state and shed level land in the flight
                # ring ONLY when active — fault-free dumps stay byte-stable
                inj = get_injector()
                if inj.armed:
                    record["fault_plan"] = list(inj.armed_sites)
                if self._step_faults:
                    record["faults"] = sum(self._step_faults.values())
                    record["fault_sites"] = dict(self._step_faults)
                if level > LEVEL_OK:
                    record["degradation"] = level
                self.flight.record_step(**record)
                if self.prefix_cache is not None:
                    self._alarms.observe_evictions(self._step_evicted)
            return outs

    def _pool_pressure(self) -> float:
        """Pool pressure for the shed ladder: allocated blocks MINUS the
        prefix cache's reclaimable ones. A block whose only holder is the
        radix tree is freed on demand by the allocator's evict callback —
        a warm cache is not load. Counting it would hold the ladder up
        forever: admission gets gated, gated admission never allocates,
        and allocation is the only thing that evicts (livelock)."""
        used = self.allocator.num_used_blocks
        if self.prefix_cache is not None and used:
            used -= self.prefix_cache.reclaimable_blocks()
        return used / max(self.allocator.num_blocks, 1)

    def _apply_degradation(self) -> int:
        """Fold pool/queue pressure into the shed ladder; flush the prefix
        cache when first stepping onto the ladder. Returns the level."""
        if self._ladder is None:
            return LEVEL_OK
        cfg = self.config
        pressure = max(
            self._pool_pressure(),
            len(self.queue) / cfg.max_queue_size if cfg.max_queue_size
            else 0.0)
        old, new = self._ladder.observe(pressure)
        if (new > LEVEL_OK >= old and self.prefix_cache is not None):
            # rung 1 (crossed in any escalation): cached blocks are pure
            # opportunism — reclaim them before touching live requests
            self.prefix_cache.flush()
        self.metrics.degradation_level = new
        return new

    def run(self) -> Dict[int, RequestOutput]:
        """Drain: step until queue and slots are empty; outputs by rid."""
        while self.has_unfinished():
            self.step()
        return dict(self._finished)

    def stream(self):
        """Iterator face of streaming: yield ``(request_id, token)`` events
        in generation order while driving the scheduler until it drains."""
        while self._events:
            yield self._events.pop(0)
        while self.has_unfinished():
            self.step()
            while self._events:
                yield self._events.pop(0)

    def generate(self, prompts: Sequence, max_new_tokens=None,
                 eos_token_id=None) -> List[np.ndarray]:
        """Batch convenience mirroring ``DecodeEngine.generate``: returns
        prompt+completion per request, in submission order."""
        rids = [self.add_request(p, max_new_tokens=max_new_tokens,
                                 eos_token_id=eos_token_id)
                for p in prompts]
        outs = self.run()
        return [outs[r].token_ids for r in rids]

    def _step_fns(self):
        """Every compiled step this scheduler owns: the slot step, plus
        the chunk-prefill and spec-verify steps when enabled — recompile
        accounting and profiling cover all of them."""
        fns = [self._step_fn]
        if self._chunk_step is not None:
            fns.append(self._chunk_step)
        if self._spec_step is not None:
            fns.append(self._spec_step)
        return fns

    def num_programs(self):
        """Compiled-program count (recompile accounting for tests)."""
        return sum(f.num_programs() for f in self._step_fns())

    def prefix_cache_stats(self) -> Optional[Dict[str, object]]:
        """Hit/miss/eviction accounting of the prefix cache (None when
        ``enable_prefix_caching`` is off)."""
        if self.prefix_cache is None:
            return None
        return self.prefix_cache.stats()

    # ---- live introspection -------------------------------------------

    def health(self) -> Dict[str, object]:
        """Truthful health for ``/healthz``. Precedence: ``dead`` (an
        attached driver thread exited with work still pending) >
        ``draining`` > ``degraded`` (shed ladder engaged) > ``ok``."""
        state = "ok"
        if (self._driver is not None and not self._driver.is_alive()
                and self.has_unfinished()):
            state = "dead"
        elif self._draining:
            state = "draining"
        elif self._ladder is not None and self._ladder.level > LEVEL_OK:
            state = "degraded"
        return {
            "state": state,
            "degradation": (self._ladder.state if self._ladder is not None
                            else "ok"),
            "queue_depth": len(self.queue),
            "running": sum(r is not None for r in self._slots),
            "kv_utilization": round(self.allocator.utilization(), 4),
            "slow_steps": (self._watchdog.slow_steps
                           if self._watchdog is not None else 0),
            "stall_storms": (self._watchdog.storms
                             if self._watchdog is not None else 0),
        }

    def debug_state(self) -> Dict[str, object]:
        """The ``/debug/requests`` payload: live request table (running +
        queued), lifecycle traces, host-stall breakdown, SLO accounting,
        flight-recorder ring (+ frozen alarm dump), prefix-cache and
        compile stats. Host-side state only — reading it never syncs the
        device, so a scrape cannot stall a decode step."""
        now = _time.perf_counter()

        def _row(req, state, slot):
            return {
                "request_id": req.request_id, "state": state, "slot": slot,
                "priority": req.priority,
                "prompt_tokens": int(len(req.prompt_ids)),
                "generated_tokens": req.num_generated,
                "max_new_tokens": req.max_new_tokens,
                "num_preemptions": req.num_preemptions,
                "age_s": round(now - req.arrival_t, 6),
                "kv_blocks": len(req.blocks),
                "phase": (self.tracer.get(req.request_id).current_phase
                          if self.tracer.enabled
                          and self.tracer.get(req.request_id) is not None
                          else None),
            }

        rows = [_row(req, "RUNNING", s)
                for s, req in enumerate(self._slots) if req is not None]
        rows += [_row(req, req.state.name, -1) for req in self.queue._items]
        with self._elock:
            engine = {
                "dispatch_depth": self.dispatch_depth,
                "in_flight_steps": len(self._inflight),
                "drain_wait_seconds": round(
                    self.stall.drain_wait_seconds, 6),
            }
        return {
            "requests": rows,
            "engine": engine,
            "queue_depth": len(self.queue),
            "running": sum(r is not None for r in self._slots),
            "stall_seconds": self.stall.snapshot(),
            "slo": self.metrics.slo_snapshot(),
            "flight_recorder": self.flight.dump(),
            "flight_alarm": self.flight.last_alarm_dump,
            "traces": {
                "live": [t.to_dict() for t in self.tracer.live()],
                "completed": self.tracer.to_json(include_live=False)[-32:],
            },
            "prefix_cache": self.prefix_cache_stats(),
            "compile": self.compile_stats(),
            "health": self.health(),
            "fault_injection": get_injector().snapshot(),
            "timeline": self.timeline.snapshot(),
            "postmortems": self.postmortems.summary(),
        }

    def export_request_trace(self, path: str) -> str:
        """Write the request-lifecycle chrome trace (one track per request)
        — open in Perfetto / chrome://tracing next to a profiler export."""
        return self.tracer.export_chrome_trace(path)

    def start_endpoint(self, host: str = "127.0.0.1", port: int = 0):
        """Serve this scheduler's ``/metrics`` + ``/debug/requests`` over a
        background stdlib-http server; returns the started
        ``ObservabilityEndpoint`` (``.url``, ``.stop()``)."""
        from paddle_tpu.observability import ObservabilityEndpoint

        ep = ObservabilityEndpoint(host=host, port=port)
        ep.add_scheduler(self)
        ep.start()
        return ep

    # ---- weight hot-reload --------------------------------------------

    def reload_weights(self, source, step: Optional[int] = None,
                       verify="full") -> int:
        """Hot-reload model weights from a committed training checkpoint —
        the serving half of continuous training: a trainer commits through
        ``checkpoint.CheckpointManager``, the server picks the commit up
        between iterations without rebuilding the scheduler.

        ``source`` is a CheckpointManager or a checkpoint root path; the
        newest committed checkpoint (checksum-verified, torn commits are
        skipped) is loaded unless ``step`` pins one. Weight shapes must
        match — the compiled slot step is reused, so NO recompile happens.
        In-flight sequences keep their already-written KV blocks (their next
        tokens mix cache prefixes from the old weights; preempt or drain
        first for strict per-request consistency). The prefix cache is
        FLUSHED — cached KV from the old weights must never seed a
        new-weight decode. Returns the loaded step.
        """
        from paddle_tpu.checkpoint import CheckpointManager
        from paddle_tpu.profiler import RecordEvent, TracerEventType

        with self._elock:
            if self._inflight:
                # commit everything dispatched against the OLD weights
                # before the restore swaps parameters under the step
                self._drain_all()
        mgr = source if isinstance(source, CheckpointManager) \
            else CheckpointManager(str(source))
        try:
            # before restore touches the model: a fault here leaves the
            # old weights fully intact and the prefix cache valid
            inject("serving.weight_reload")
        except Exception as exc:
            self.metrics.observe_fault(
                self._fault_site(exc, "serving.weight_reload"), "fired")
            raise
        with RecordEvent("serving.reload_weights",
                         TracerEventType.UserDefined):
            res = mgr.restore(step=step, model=self.model, verify=verify,
                              restore_rng=False)
        if self.prefix_cache is not None:
            self.prefix_cache.flush()
        return res.step

    # ---- compile observability ----------------------------------------

    def mark_steady(self):
        """Declare warmup over: any further compile of this scheduler's
        step (prefill bucket or decode grid) is a steady-state recompile —
        the CompileTracker counts it and warns RecompileStorm loudly."""
        from paddle_tpu.observability import get_compile_tracker

        t = get_compile_tracker()
        for fn in self._step_fns():
            t.mark_steady(fn.tracker_name)

    def compile_stats(self) -> Dict[str, object]:
        """This scheduler's CompileTracker accounting: total compiles of
        its slot step and how many happened after ``mark_steady()`` — the
        zero-steady-state-recompile guarantee is pinned through this."""
        from paddle_tpu.observability import get_compile_tracker

        t = get_compile_tracker()
        names = [fn.tracker_name for fn in self._step_fns()]
        walks = [fn.state_walks for fn in self._step_fns()]
        return {
            "fn": names[0] if len(names) == 1 else names,
            "compiles": sum(t.compiles(n) for n in names),
            "steady_state_recompiles": sum(
                t.steady_state_recompiles(n) for n in names),
            # Layer-tree walks of each step program (as ``fn``): one after
            # each change of the model's structure, none in steady state
            "state_walks": walks[0] if len(walks) == 1 else walks,
        }

    # ---- device-side observability ------------------------------------

    def device_set(self) -> frozenset:
        """The devices this replica's state actually lives on — read off
        the KV pools' (and weights') committed shardings, so it is ground
        truth whether the scheduler is sharded or not (unsharded arrays
        report their single device). Used by ``ServingRouter`` to validate
        that replicas own disjoint chips."""
        devs: set = set()
        for kp, vp in self._pools:
            for t in (kp, vp):
                try:
                    devs.update(t._value.sharding.device_set)
                except AttributeError:
                    pass  # non-committed value (e.g. a stubbed pool)
        for p in self.model.parameters():
            try:
                devs.update(p._value.sharding.device_set)
                break  # all params live on one mesh; first is enough
            except AttributeError:
                pass  # uncommitted host value; keep looking
        return frozenset(devs)

    # ---- in-step profiling (named-region attribution) ------------------

    @holds_lock("_elock")
    def _note_telemetry(self, stats_np):
        """(commit path) fold one drained decode step's in-program
        telemetry block into the latest-value snapshot. Pure host
        bookkeeping on an already-fetched array."""
        prev = self._last_telemetry
        self._last_telemetry = {
            "active_slots": float(stats_np[0]),
            "occupancy": float(stats_np[0]) / max(self.config.max_num_seqs,
                                                  1),
            "mean_entropy": float(stats_np[1]),
            "mean_max_prob": float(stats_np[2]),
            "kv_blocks": float(stats_np[3]),
            "steps": (0 if prev is None else prev["steps"]) + 1,
        }
        # what the model adds to the block (``model.step_stats()``): the
        # step's value, and its sum over the steps for a mean
        for i, name in enumerate(self._step_fn.extra_stat_names):
            v = float(stats_np[4 + i])
            self._last_telemetry[name] = v
            self._last_telemetry[name + "_sum"] = v + (
                0.0 if prev is None else prev[name + "_sum"])

    def telemetry_snapshot(self) -> Optional[dict]:
        """Latest drained in-program telemetry block (None until the
        first decode step lands with ``enable_step_telemetry``)."""
        with self._elock:
            return (None if self._last_telemetry is None
                    else dict(self._last_telemetry))

    def drain_in_flight(self):
        """Public pipeline barrier: commit every in-flight step. The
        step-profiler runs this between traced steps so a capture at
        ``dispatch_depth > 0`` measures whole executed steps instead of
        cutting the trace mid-pipeline."""
        with self._elock:
            self._drain_all()

    def _profile_programs(self) -> List[dict]:
        """Program rows for ``attribute_trace``: every compiled program of
        this step (prefill buckets + decode), each with its HLO-derived
        instruction→region map. The decode program ([S, 1] token grid) is
        marked primary and leads the list — module-name collisions between
        prefill and decode executables resolve in its favor."""
        inv = get_program_inventory()
        want = f"i32[{self.config.max_num_seqs},1]"
        rows: List[dict] = []
        for fn in self._step_fns():
            for e in inv.entries(name_contains=fn.tracker_name):
                hlo = inv.hlo_text(e)
                if not hlo:
                    continue
                module, regions = parse_hlo_instruction_regions(hlo)
                row = {"name": e.name, "module": module, "regions": regions,
                       "nbytes": parse_hlo_instruction_bytes(hlo)}
                if fn is self._step_fn and want in e.signature:
                    an = inv.analyze(e)
                    if "flops" in an:
                        row["flops"] = an["flops"]
                        row["bytes_accessed"] = an["bytes_accessed"]
                    row["primary"] = True
                    rows.insert(0, row)
                else:
                    rows.append(row)
        return rows

    def capture_step_profile(self, steps: int = 8) -> dict:
        """On-demand in-step profile: trace ``steps`` scheduler steps
        under ``jax.profiler.trace`` and attribute device time to the
        named regions of each compiled program (region shares, per-region
        bytes estimates, the decode roofline decomposed by region).
        Expensive (device trace + parse) — bench/debug path only, never
        the hot loop. The summary is retained for ``/debug/stepprofile``
        and postmortem bundles."""
        if self.step_profiler is None:
            self.step_profiler = StepProfiler(
                self.step, self._profile_programs,
                barrier=self.drain_in_flight)
        return self.step_profiler.capture(steps=steps)

    def step_profile_state(self) -> Dict[str, object]:
        """Endpoint-facing snapshot: the latest capture + telemetry.
        NEVER touches the device (no trace, no sync) — safe to scrape."""
        return {
            "telemetry_enabled": bool(self.config.enable_step_telemetry),
            "telemetry": self.telemetry_snapshot(),
            "last_capture": (self.step_profiler.last_summary
                             if self.step_profiler is not None else None),
        }
