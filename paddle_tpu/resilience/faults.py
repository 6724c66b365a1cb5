"""Deterministic fault injection: seeded plans over named injection sites.

The serving/checkpoint stack is threaded with runtime-inert ``inject(site)``
hooks at the places real deployments actually fail (device step dispatch,
prefill, block allocation, checkpoint shard/manifest/rename I/O, weight
reload, prefix-cache insert). With no plan armed a hook is one global
``None`` check, like the runtime-inert observability annotations. With a
plan armed, the hook raises ``InjectedFault`` exactly where a crash/device
error would surface, so every recovery path in the scheduler and the
checkpoint commit protocol is testable deterministically — no subprocess
kills, no timing races.

``FaultPlan`` is seeded: per-site probability draws come from one
``random.Random(seed)``, and ``at=(n, ...)`` fires on exact hit counts, so
a chaos test replays bit-identically. Armed/fired sites are tracked by the
process-wide ``FaultInjector`` (``snapshot()``), and the scheduler folds
fired sites into its flight-recorder ring — the last-N-iterations picture
includes which faults were live.

``classify_error`` is the transient-vs-fatal triage the retry machinery
uses: injected faults carry their own kind; programming errors
(ValueError/TypeError/...) and pool exhaustion are fatal (propagate,
never retry); so is anything raised while a program compiles or executes
for the first time (a retry fails the same way); I/O errors and
device-runtime errors whose status can clear at run time are transient.

Stdlib-only on purpose: checkpoint writers and the serving hot loop both
import this module, and an injection hook must never pull jax.
"""

from __future__ import annotations

import random
import threading
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Tuple

from paddle_tpu.observability.annotations import guarded_by

__all__ = [
    "FaultInjector",
    "FaultPlan",
    "FaultRule",
    "InjectedFault",
    "SITES",
    "arm",
    "classify_error",
    "disarm",
    "fault_plan",
    "get_injector",
    "inject",
]

# Named injection points wired through the stack. A plan may arm any
# subset; arming an unknown site is an error (typos must not silently
# inject nothing).
SITES = (
    "serving.decode_step",    # before the compiled decode dispatch
    "serving.prefill",        # before an admission's prefill dispatch
    "serving.block_alloc",    # before KV block allocate/extend
    "serving.prefix_insert",  # before donating KV to the radix tree
    "serving.weight_reload",  # before a hot weight reload restores
    "ckpt.shard_write",       # per shard file inside the checkpoint writer
    "ckpt.manifest_write",    # before MANIFEST.json is written
    "ckpt.rename",            # before the atomic tmp -> final rename
    "router.route",           # before a routing decision places a request
    "replica.step",           # per replica-driver scheduler iteration
    "replica.healthcheck",    # per supervisor health probe of one replica
)


class InjectedFault(RuntimeError):
    """Raised by an armed ``FaultPlan`` at an injection site.

    ``kind`` drives ``classify_error``: "transient" faults are retried by
    the scheduler's bounded-retry machinery, "fatal" ones propagate."""

    def __init__(self, site: str, hit: int, kind: str = "transient"):
        self.site = site
        self.hit = int(hit)
        self.kind = kind
        super().__init__(f"injected {kind} fault at {site!r} (hit {hit})")


class FaultRule:
    """When one site fires: per-hit probability and/or exact hit counts.

    ``times`` caps total fires (None = unlimited); ``kind`` is carried on
    the raised ``InjectedFault``."""

    __slots__ = ("prob", "at", "times", "kind")

    def __init__(self, prob: float = 0.0, at: Tuple[int, ...] = (),
                 times: Optional[int] = None, kind: str = "transient"):
        self.prob = float(prob)
        self.at = tuple(int(n) for n in (at or ()))
        self.times = None if times is None else int(times)
        self.kind = kind

    def to_dict(self) -> Dict[str, object]:
        return {"prob": self.prob, "at": list(self.at), "times": self.times,
                "kind": self.kind}


class FaultPlan:
    """A seeded set of per-site fault rules. Deterministic: probability
    draws consume one ``random.Random(seed)`` in hit order, ``at=`` rules
    fire on exact 1-based hit counts — the same plan against the same
    workload fires at the same instants, every run."""

    def __init__(self, seed: int = 0):
        self.seed = int(seed)
        self._rng = random.Random(self.seed)
        self.rules: Dict[str, FaultRule] = {}

    def on(self, site: str, prob: float = 0.0, at=None,
           times: Optional[int] = None,
           kind: str = "transient") -> "FaultPlan":
        """Arm ``site``; chainable. ``at`` may be an int or a sequence of
        1-based hit counts."""
        if site not in SITES:
            raise ValueError(f"unknown injection site {site!r} "
                             f"(known: {', '.join(SITES)})")
        if isinstance(at, int):
            at = (at,)
        self.rules[site] = FaultRule(prob=prob, at=at or (), times=times,
                                     kind=kind)
        return self

    @property
    def sites(self) -> Tuple[str, ...]:
        return tuple(sorted(self.rules))

    def should_fire(self, site: str, hit: int, fired_so_far: int) -> bool:
        rule = self.rules.get(site)
        if rule is None:
            return False
        if rule.times is not None and fired_so_far >= rule.times:
            return False
        if hit in rule.at:
            return True
        return rule.prob > 0.0 and self._rng.random() < rule.prob

    def kind(self, site: str) -> str:
        rule = self.rules.get(site)
        return rule.kind if rule is not None else "transient"

    def to_dict(self) -> Dict[str, object]:
        return {"seed": self.seed,
                "rules": {s: r.to_dict() for s, r in self.rules.items()}}


class FaultInjector:
    """Process-wide injection state: the armed plan + hit/fire accounting.

    Thread contract: the serving loop and checkpoint writer threads both
    call ``check()`` while a test arms/disarms —
    counters, the event ring, and listeners are touched under ``_lock``.
    The disarmed fast path reads ``_plan`` without the lock: it is a
    single reference read, and the worst race is one extra armed/disarmed
    check — never a torn counter."""

    _hits: guarded_by("_lock")
    _fires: guarded_by("_lock")
    _events: guarded_by("_lock")
    _listeners: guarded_by("_lock")

    def __init__(self, max_events: int = 256):
        self._plan: Optional[FaultPlan] = None
        self._lock = threading.Lock()
        self._hits: Dict[str, int] = {}
        self._fires: Dict[str, int] = {}
        self._events: deque = deque(maxlen=int(max_events))
        self._listeners: List[Callable[[str, int], None]] = []

    # ------------------------------------------------------------ arming
    def arm(self, plan: FaultPlan) -> FaultPlan:
        """Install ``plan`` and reset hit/fire accounting."""
        if not isinstance(plan, FaultPlan):
            raise TypeError(f"arm() takes a FaultPlan, got {type(plan)}")
        with self._lock:
            self._hits = {}
            self._fires = {}
            self._events.clear()
        self._plan = plan
        return plan

    def disarm(self) -> None:
        self._plan = None

    @property
    def armed(self) -> bool:
        return self._plan is not None

    @property
    def armed_sites(self) -> Tuple[str, ...]:
        plan = self._plan
        return plan.sites if plan is not None else ()

    # ------------------------------------------------------------ firing
    def check(self, site: str) -> None:
        """Count one hit at ``site``; raise if the armed plan says fire."""
        plan = self._plan
        if plan is None:
            return
        with self._lock:
            hit = self._hits.get(site, 0) + 1
            self._hits[site] = hit
            fired_so_far = self._fires.get(site, 0)
            fire = plan.should_fire(site, hit, fired_so_far)
            if fire:
                self._fires[site] = fired_so_far + 1
                self._events.append({"site": site, "hit": hit,
                                     "fire": fired_so_far + 1})
            listeners = list(self._listeners) if fire else ()
        if not fire:
            return
        for cb in listeners:
            cb(site, hit)
        raise InjectedFault(site, hit, kind=plan.kind(site))

    def add_listener(self, cb: Callable[[str, int], None]) -> None:
        """``cb(site, hit)`` runs on every fire, before the raise."""
        with self._lock:
            self._listeners.append(cb)

    def remove_listener(self, cb) -> None:
        with self._lock:
            if cb in self._listeners:
                self._listeners.remove(cb)

    # --------------------------------------------------------- reading
    def snapshot(self) -> Dict[str, object]:
        plan = self._plan
        with self._lock:
            hits = dict(self._hits)
            fires = dict(self._fires)
            events = list(self._events)
        return {
            "armed": plan is not None,
            "plan": plan.to_dict() if plan is not None else None,
            "hits": hits,
            "fires": fires,
            "events": events,
        }


_INJECTOR = FaultInjector()


def get_injector() -> FaultInjector:
    return _INJECTOR


def inject(site: str) -> None:
    """The injection hook. Runtime-inert when no plan is armed: one global
    reference read + ``None`` check. Armed, it may raise
    ``InjectedFault``."""
    if _INJECTOR._plan is None:
        return
    _INJECTOR.check(site)


def arm(plan: FaultPlan) -> FaultPlan:
    return _INJECTOR.arm(plan)


def disarm() -> None:
    _INJECTOR.disarm()


@contextmanager
def fault_plan(plan: FaultPlan):
    """``with fault_plan(FaultPlan(seed=0).on(...)):`` — arm for a scope,
    always disarm on exit (a leaked armed plan would poison later tests)."""
    arm(plan)
    try:
        yield plan
    finally:
        disarm()


# ---------------------------------------------------------------------------
# transient vs fatal triage

# exception type names that are never retried: programming errors and
# capacity conditions with their own handling (preemption, admission
# control). Matched by name so this module stays import-light.
_FATAL_NAMES = frozenset({
    "ValueError", "TypeError", "KeyError", "IndexError", "AttributeError",
    "AssertionError", "NotImplementedError", "ZeroDivisionError",
    "KVPoolExhausted", "QueueFull", "SchedulerOverloaded",
})

# status codes and transport messages that can clear on a retry. The device
# runtime raises ``jax.errors.JaxRuntimeError`` with the XLA status code
# leading the message; any other status (INTERNAL, INVALID_ARGUMENT, a
# Mosaic compile failure, ...) is fatal.
_TRANSIENT_MARKERS = ("RESOURCE_EXHAUSTED", "UNAVAILABLE",
                      "DEADLINE_EXCEEDED", "ABORTED", "socket closed",
                      "connection reset")


def classify_error(exc: BaseException) -> str:
    """``"transient"`` (bounded retry) or ``"fatal"`` (propagate).

    ``exc.program_start`` is set by the jit entry (``StaticFunction``) on
    anything raised from a call that traced — the program was compiling or
    executing for the first time, so a compile failure or an out-of-memory
    there is deterministic and fatal, whatever its status code.

    Unknown errors default to fatal — a retry loop that eats exceptions it
    does not understand is exactly the swallowed-exception anti-pattern
    ``graft_lint``'s ``swallowed-exception`` rule exists to reject."""
    if isinstance(exc, InjectedFault):
        return "transient" if exc.kind == "transient" else "fatal"
    if getattr(exc, "program_start", False):
        return "fatal"
    if type(exc).__name__ in _FATAL_NAMES:
        return "fatal"
    if isinstance(exc, OSError):
        return "transient"                # I/O flake: retryable
    if any(m in str(exc) for m in _TRANSIENT_MARKERS):
        return "transient"
    return "fatal"
