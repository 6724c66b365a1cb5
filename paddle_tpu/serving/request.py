"""Serving request lifecycle: admission config, per-request state, queue.

The deployment tier's request surface (reference: AnalysisPredictor +
Paddle Serving's request brokering) re-designed for iteration-level
scheduling: a ``Request`` lives through QUEUED → RUNNING → (PREEMPTED →
QUEUED →)* → FINISHED, carrying its generated prefix across preemptions so
a resume is a pure recompute (vLLM-style recompute preemption — freed KV
blocks are re-filled from ``prompt + generated`` on the next admission).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable, List, Optional

import numpy as np


class RequestState(Enum):
    QUEUED = 0
    RUNNING = 1
    PREEMPTED = 2
    FINISHED = 3
    CANCELLED = 4
    FAILED = 5


# finish reasons that are not a natural completion: the request was
# removed by policy (cancel/deadline/TTL) or retired after repeated
# faults. Everything else ("eos"/"length") counts toward goodput.
CANCEL_REASONS = ("cancelled", "deadline", "queue_ttl")
FAILED_REASON = "failed"


class QueueFull(RuntimeError):
    """Admission control: the wait queue is at max_queue_size."""


class SchedulerOverloaded(RuntimeError):
    """Load shedding: the degradation ladder reached ``reject`` (or the
    scheduler is draining) — the caller should back off or route away."""


@dataclass
class SchedulerConfig:
    """Knobs for the continuous-batching scheduler.

    ``max_num_seqs`` is the slot-grid width: the decode step is compiled
    ONCE for exactly this batch shape and every iteration runs it, so
    admissions/retirements never change the program. ``num_blocks`` sizes
    the paged KV pool (default: enough for every slot at ``max_seq_len``,
    i.e. preemption only under an explicitly tightened pool)."""

    max_num_seqs: int = 8
    max_queue_size: int = 256
    max_seq_len: int = 512
    block_size: int = 16
    num_blocks: Optional[int] = None
    max_new_tokens: int = 32          # per-request default cap
    eos_token_id: Optional[int] = None
    temperature: float = 0.0
    top_k: int = 0
    cache_dtype: str = "float32"
    enable_preemption: bool = True
    enable_prefix_caching: bool = False   # radix-tree KV reuse across requests
    prefill_bucket: int = 16          # smallest prefill width bucket
    # ---- async engine (dispatch-ahead decode). ``dispatch_depth`` N keeps
    # up to N device steps in flight before their sampled tokens are
    # synced: 0 is the fully synchronous metered baseline; >= 1 dispatches
    # step N+1 from the device-resident token carry while a background
    # drain thread fetches step N's tokens. Retire/EOS, preemption,
    # cancellation and fault retries are resolved at drain time — outputs
    # stay bit-identical to depth 0 (pinned in tests), only streaming
    # callbacks and finish notifications land up to N steps later.
    dispatch_depth: int = 0
    # ---- latency subsystem (serving/spec/): chunked prefill + speculative
    # decoding. ``prefill_chunk_size`` > 0 splits every admission prefill
    # into fixed-width [1, C] chunks run from the decode loop (at most
    # ``prefill_chunks_per_step`` per iteration) so long prompts stop
    # head-of-line-blocking in-flight decodes; the chunk offset is data,
    # not a shape — one compiled chunk program, zero steady-state
    # recompiles. ``spec_k`` > 0 turns each decode iteration into one
    # [S, 1+k] verification step over n-gram-proposed draft tokens with
    # in-program rejection sampling (tokens/step > 1 at any positive
    # accept rate). Both are greedy-only (temperature == 0, validated at
    # scheduler construction) and token-identical to the plain engine.
    prefill_chunk_size: int = 0       # 0 = whole-prompt prefill (off)
    prefill_chunks_per_step: int = 1  # chunk budget per scheduler step
    spec_k: int = 0                   # draft tokens per step; 0 = off
    spec_ngram_max: int = 3           # longest suffix n-gram matched
    spec_ngram_min: int = 1
    # ---- observability (request-lifecycle tracing, SLO, flight recorder).
    # Tracing is host-side bookkeeping only: the token stream is identical
    # on vs off (pinned in tests) and the overhead is held <5%.
    enable_request_tracing: bool = True
    trace_ring: int = 256             # completed RequestTraces retained
    flight_recorder_steps: int = 256  # per-step ring buffer depth
    ttft_slo_s: Optional[float] = None    # None = SLO accounting off
    tpot_slo_s: Optional[float] = None
    ttft_breach_streak: int = 4       # consecutive breaches -> alarm
    # Device-side observability: HBM ledger (owner-tagged device bytes,
    # OOM forensics) beside the program inventory. Host-side bookkeeping
    # only — tokens are bit-identical on vs off at every dispatch_depth
    # (pinned in tests).
    enable_device_observability: bool = True
    # In-program step telemetry: a tiny on-device stats block (slot
    # occupancy, sampled-token entropy/max-prob, kv blocks touched)
    # appended to the compiled step's outputs and fetched by the existing
    # token drain — zero extra steady-state host syncs, zero new compiled
    # programs, tokens bit-identical on vs off (pinned in tests).
    enable_step_telemetry: bool = True
    # Fleet observability: metrics time-series recorder + postmortem
    # bundles. ``timeline_interval_s`` > 0 spawns the background sampler
    # thread (role ``fleet-sample``); 0 leaves sampling to the owner
    # (router sampler, bench, or inline ``timeline.sample_once()``).
    timeline_interval_s: float = 0.0
    postmortem_bundles: int = 8       # correlated incident bundles retained
    # ---- resilience (fault retry, deadlines, shedding). The fault knobs
    # only matter when errors actually occur; the shed thresholds are
    # fractions of max(pool occupancy, queue fill).
    queue_ttl_s: Optional[float] = None   # evict QUEUED requests older
    max_step_faults: int = 3          # K consecutive faults -> "failed"
    retry_backoff_s: float = 0.0      # base backoff between step retries
    enable_degradation: bool = True   # shed ladder + watchdog on/off
    shed_flush_occupancy: float = 0.90
    shed_shrink_occupancy: float = 0.95
    shed_reject_occupancy: float = 0.98
    shed_recover_occupancy: float = 0.80
    shed_cooldown_steps: int = 4
    watchdog_factor: float = 8.0      # step > factor*EWMA counts slow
    watchdog_min_history: int = 16    # steps of EWMA warmup before arming
    watchdog_streak: int = 3          # consecutive slow steps -> StallStorm
    watchdog_abs_s: Optional[float] = None  # absolute per-step bound

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_seq_len // self.block_size)

    @property
    def total_blocks(self) -> int:
        if self.num_blocks is not None:
            return self.num_blocks
        return self.max_num_seqs * self.max_blocks_per_seq

    @classmethod
    def from_inference_config(cls, config, **overrides) -> "SchedulerConfig":
        """Bridge ``paddle.inference.Config`` deployment knobs into serving
        scheduler knobs (the APPLIED face of ``enable_memory_optim`` and
        ``enable_low_precision`` on the serving tier):

        - ``enable_memory_optim(x)``  → ``enable_preemption=x`` (paged-KV
          preemption IS the serving-tier memory optimization: graceful
          degradation instead of OOM when the block pool runs dry);
        - ``enable_low_precision(d)`` → ``cache_dtype=d`` (KV pool rests in
          the reduced precision — the dominant serving-memory consumer);
        - ``enable_prefix_caching(x)`` → ``enable_prefix_caching=x``
          (radix-tree KV reuse over the paged pool: shared prompt prefixes
          skip prefill entirely).
        """
        kw = {}
        flags = getattr(config, "_flags", {})
        if "memory_optim" in flags:
            kw["enable_preemption"] = bool(flags["memory_optim"])
        lp = flags.get("low_precision")
        if lp:
            kw["cache_dtype"] = lp
        if "prefix_caching" in flags:
            kw["enable_prefix_caching"] = bool(flags["prefix_caching"])
        kw.update(overrides)
        return cls(**kw)


@dataclass
class RequestOutput:
    """Final (or streaming-snapshot) result of one request."""

    request_id: int
    prompt_ids: np.ndarray            # [P] int64, the original prompt
    generated_ids: np.ndarray         # [G] int64, incl. the EOS if hit
    finish_reason: Optional[str]      # "eos"|"length"|"cancelled"|"deadline"
                                      # |"queue_ttl"|"failed"|None (running)
    ttft_s: Optional[float]           # time-to-first-token
    tpot_s: Optional[float]           # mean time-per-output-token (after 1st)
    num_preemptions: int

    @property
    def token_ids(self) -> np.ndarray:
        """prompt + completion (DecodeEngine.generate's return contract)."""
        return np.concatenate([self.prompt_ids, self.generated_ids])


@dataclass
class Request:
    """One in-flight generation request (host-side bookkeeping only)."""

    request_id: int
    prompt_ids: np.ndarray            # [P] int64/int32
    max_new_tokens: int
    eos_token_id: Optional[int]
    priority: int = 0                 # higher = more important
    on_token: Optional[Callable[[int, int], None]] = None  # (rid, token)
    state: RequestState = RequestState.QUEUED
    out_tokens: List[int] = field(default_factory=list)
    num_preemptions: int = 0
    arrival_t: float = field(default_factory=time.perf_counter)
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    finish_reason: Optional[str] = None
    blocks: List[int] = field(default_factory=list)   # live KV blocks
    # live blocks of the window class, oldest first (window layers only)
    window_blocks: List[int] = field(default_factory=list)
    slot: int = -1
    deadline_s: Optional[float] = None  # wall budget from arrival; None=∞
    consecutive_faults: int = 0       # step faults since last clean step
    # chunked-prefill frontier: tokens of ``resume_ids`` whose KV is
    # already written (prefix-cache hit + completed chunks). -1 = not
    # mid-prefill. Host data only — preemption resets it (resume is a
    # clean re-prefill, which may re-hit the donated chunk KV) and
    # ``export_restartable`` ships it as forensic context.
    prefill_pos: int = -1

    @property
    def is_prefilling(self) -> bool:
        """True while admitted but not fully prefilled (chunked admission):
        the slot holds blocks and a growing KV prefix but must not join a
        decode dispatch yet."""
        return self.prefill_pos >= 0

    @property
    def done(self) -> bool:
        return self.state in (RequestState.FINISHED, RequestState.CANCELLED,
                              RequestState.FAILED)

    def past_deadline(self, now: float) -> bool:
        return (self.deadline_s is not None
                and now - self.arrival_t > self.deadline_s)

    @property
    def resume_ids(self) -> np.ndarray:
        """Prompt for (re-)prefill: original prompt + generated prefix, so a
        preempted request recomputes its KV and continues token-for-token."""
        if not self.out_tokens:
            return np.asarray(self.prompt_ids, np.int64)
        return np.concatenate([np.asarray(self.prompt_ids, np.int64),
                               np.asarray(self.out_tokens, np.int64)])

    @property
    def num_generated(self) -> int:
        return len(self.out_tokens)

    def emit(self, token: int):
        """Record one generated token (streaming callback + TTFT stamp)."""
        now = time.perf_counter()
        if self.first_token_t is None:
            self.first_token_t = now
        self.out_tokens.append(int(token))
        if self.on_token is not None:
            self.on_token(self.request_id, int(token))

    def finish(self, reason: str):
        if reason in CANCEL_REASONS:
            self.state = RequestState.CANCELLED
        elif reason == FAILED_REASON:
            self.state = RequestState.FAILED
        else:
            self.state = RequestState.FINISHED
        self.finish_reason = reason
        self.finish_t = time.perf_counter()

    def output(self) -> RequestOutput:
        ttft = (self.first_token_t - self.arrival_t
                if self.first_token_t is not None else None)
        tpot = None
        if self.finish_t is not None and len(self.out_tokens) > 1:
            tpot = ((self.finish_t - self.first_token_t)
                    / (len(self.out_tokens) - 1))
        return RequestOutput(
            request_id=self.request_id,
            prompt_ids=np.asarray(self.prompt_ids, np.int64),
            generated_ids=np.asarray(self.out_tokens, np.int64),
            finish_reason=self.finish_reason,
            ttft_s=ttft, tpot_s=tpot,
            num_preemptions=self.num_preemptions)


class RequestQueue:
    """Bounded wait queue with priority ordering and resume-first placement.

    Pop order: highest ``priority`` first; within a priority class,
    preempted requests resume before fresh arrivals (they hold generated
    prefixes whose latency budget is already spent), then FIFO."""

    def __init__(self, max_size: int = 256):
        self.max_size = max_size
        self._items: List[Request] = []
        self._seq = 0

    def __len__(self) -> int:
        return len(self._items)

    def push(self, req: Request, force: bool = False):
        if not force and len(self._items) >= self.max_size:
            raise QueueFull(
                f"wait queue full ({self.max_size}); rejecting request "
                f"{req.request_id}")
        req.state = RequestState.QUEUED
        self._seq += 1
        self._items.append(req)
        self._items.sort(key=lambda r: (-r.priority,
                                        0 if r.num_preemptions else 1,
                                        r.arrival_t))

    def peek(self) -> Optional[Request]:
        return self._items[0] if self._items else None

    def pop(self) -> Request:
        return self._items.pop(0)

    def remove(self, request_id: int) -> Optional[Request]:
        """Pull one request out of the queue by id (cancel / TTL sweep)."""
        for i, r in enumerate(self._items):
            if r.request_id == request_id:
                return self._items.pop(i)
        return None
