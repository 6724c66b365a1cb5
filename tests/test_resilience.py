"""Serving resilience (paddle_tpu/resilience/ + scheduler hardening).

Chaos oracle: every run under a seeded ``FaultPlan`` must end with every
request in a terminal state (done/cancelled/failed/rejected), zero leaked
KV blocks, and — for requests that complete normally — token streams
bit-identical to the fault-free run (injection happens BEFORE dispatch
donates the cache, and ``allocator.extend`` is idempotent per position,
so a retried step rewrites identical KV). Plus: the degradation ladder's
ordered shed + hysteresis, the step-latency watchdog's StallStorm, the
truthful ``/healthz`` (ok -> degraded -> ok, and a dead driver thread
answering 503 instead of hanging), request validation, and a seeded
open load under faults and cancellations.
"""

import threading
import time
import urllib.error
import urllib.request
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.resilience import (
    DegradationLadder,
    FaultInjector,
    FaultPlan,
    InjectedFault,
    LEVEL_OK,
    LEVEL_REJECT,
    LEVEL_SHRINK,
    StallStorm,
    StepWatchdog,
    arm,
    classify_error,
    disarm,
    fault_plan,
    get_injector,
    inject,
)
from paddle_tpu.serving import (
    ContinuousBatchingScheduler,
    SchedulerConfig,
    SchedulerOverloaded,
)


@pytest.fixture(autouse=True, scope="module")
def _no_aot_replay():
    """XLA:CPU AOT replay corrupts these decode programs' NUMERICS (wrong
    generated tokens) even when the persistent cache was written by the
    SAME jax build in the same session — the NOTES-r7 'stale cache' flake
    was this, and version-stamping the dir (utils/compile_cache.py) cannot
    catch a same-version unsound replay. Serving tests therefore compile
    fresh; the rest of the suite keeps the persistent-cache speedup."""
    import jax

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    return GPTForCausalLM(gpt_tiny(num_layers=2))


def _sched(model, **over):
    kw = dict(max_num_seqs=2, max_seq_len=64, block_size=8)
    kw.update(over)
    return ContinuousBatchingScheduler(model, SchedulerConfig(**kw))


def _prompts(n=4, seed=0, lo=4, hi=10):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1000, int(rng.integers(lo, hi + 1)))
            for _ in range(n)]


def _drain(sched, guard=3000):
    while sched.has_unfinished():
        sched.step()
        guard -= 1
        assert guard > 0, "scheduler did not drain"
    return dict(sched._finished)


def _assert_pool_clean(sched):
    if sched.prefix_cache is not None:
        sched.prefix_cache.flush()
    assert sched.allocator.num_used_blocks == 0, (
        f"block leak: {sched.allocator.num_used_blocks} blocks still held "
        f"after drain")


# ------------------------------------------------------- fault plan units

def test_fault_plan_fires_at_exact_hits():
    inj = FaultInjector()
    inj.arm(FaultPlan(seed=0).on("serving.decode_step", at=(2, 4)))
    fired = []
    for i in range(1, 6):
        try:
            inj.check("serving.decode_step")
            fired.append(False)
        except InjectedFault as e:
            fired.append(True)
            assert e.site == "serving.decode_step" and e.hit == i
    assert fired == [False, True, False, True, False]
    snap = inj.snapshot()
    assert snap["hits"]["serving.decode_step"] == 5
    assert snap["fires"]["serving.decode_step"] == 2


def test_fault_plan_probability_is_seed_deterministic():
    def pattern(seed):
        inj = FaultInjector()
        inj.arm(FaultPlan(seed=seed).on("serving.decode_step", prob=0.5))
        out = []
        for _ in range(32):
            try:
                inj.check("serving.decode_step")
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out

    assert pattern(3) == pattern(3)          # same seed, same firing order
    assert pattern(3) != pattern(4)
    assert 0 < sum(pattern(3)) < 32


def test_fault_plan_times_caps_total_fires():
    inj = FaultInjector()
    inj.arm(FaultPlan(seed=0).on("serving.decode_step", prob=1.0, times=2))
    fires = 0
    for _ in range(10):
        try:
            inj.check("serving.decode_step")
        except InjectedFault:
            fires += 1
    assert fires == 2


def test_unknown_site_rejected():
    with pytest.raises(ValueError, match="unknown injection site"):
        FaultPlan(seed=0).on("serving.nope", prob=1.0)


def test_disarmed_inject_is_inert():
    assert not get_injector().armed
    for _ in range(3):
        inject("serving.decode_step")        # must not raise or count
    assert get_injector().snapshot()["armed"] is False


def test_classify_error():
    assert classify_error(InjectedFault("s", 1, kind="fatal")) == "fatal"
    assert classify_error(InjectedFault("s", 1)) == "transient"
    assert classify_error(ValueError("bad")) == "fatal"
    assert classify_error(OSError("io")) == "transient"


def test_classify_error_device_runtime_errors():
    """The installed runtime raises jax.errors.JaxRuntimeError with the XLA
    status code leading the message. A status that can clear at run time is
    retried; any other is fatal; and ANYTHING raised while a program
    compiles or runs for the first time is fatal — a retry fails the same
    way, and it used to end as quietly failed requests."""
    import jax

    Err = jax.errors.JaxRuntimeError
    oom = "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting " \
          "to allocate 4.00G. That was not possible. There are 3.75G free."
    assert classify_error(Err(oom)) == "transient"
    assert classify_error(Err("UNAVAILABLE: device busy")) == "transient"
    assert classify_error(
        Err("INTERNAL: Mosaic failed to compile TPU kernel")) == "fatal"
    assert classify_error(Err("INVALID_ARGUMENT: bad shape")) == "fatal"
    first = Err(oom)
    first.program_start = True
    assert classify_error(first) == "fatal"
    # an eager allocation failure surfaces as ValueError on the chip
    # (seen in PR 21): fatal by name, whatever it says
    assert classify_error(ValueError(oom)) == "fatal"


def test_static_function_marks_errors_from_a_programs_first_run():
    """StaticFunction sets ``program_start`` on what a TRACING call raises
    (compile failure, out-of-memory on the first execution) and on nothing
    a cached program raises later."""
    import jax

    from paddle_tpu.jit.api import StaticFunction

    def body(x):               # the body runs at trace time only
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error")

    sf = StaticFunction(body, name="test.program_start")
    x = paddle.to_tensor(np.ones((4,), np.float32))
    with pytest.raises(jax.errors.JaxRuntimeError) as ei:
        sf(x)
    assert ei.value.program_start is True
    assert classify_error(ei.value) == "fatal"

    # a program that compiled: its later calls do not trace, so whatever
    # they raise carries no mark and is triaged by its status alone
    ok = StaticFunction(lambda x: x * 2, name="test.program_cached")
    np.testing.assert_array_equal(np.asarray(ok(x).numpy()), 2.0)
    traces = ok._traces
    ok(x)
    assert ok._traces == traces == 1


def test_first_run_failure_is_fatal_not_a_quietly_failed_request(model,
                                                                 monkeypatch):
    """End to end through the scheduler: an out-of-memory from the prefill
    program's FIRST execution propagates out of ``run()`` instead of being
    retried ``max_step_faults`` times and retired as ``failed``."""
    import jax

    sched = _sched(model)
    sf = sched._step_fn._sf
    real = sf._run_impl

    def oom_while_tracing(*a, **k):
        sf._traces += 1                      # what a cache miss does
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")

    monkeypatch.setattr(sf, "_run_impl", oom_while_tracing)
    sched.add_request(_prompts(1)[0], max_new_tokens=3)
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        sched.run()
    assert sched.metrics.requests_failed == 0
    assert any(k for k in sched.metrics.faults_snapshot())  # noted as fatal

    # the SAME status from a program that has run before stays retryable
    monkeypatch.setattr(sf, "_run_impl", real)
    sched2 = _sched(model)
    sched2.add_request(_prompts(1)[0], max_new_tokens=3)
    _drain(sched2)                           # programs compiled and proven
    sf2 = sched2._step_fn._sf
    real2, fired = sf2._run_impl, []

    def oom_once(*a, **k):
        if not fired:
            fired.append(1)
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: Error allocating device buffer")
        return real2(*a, **k)

    monkeypatch.setattr(sf2, "_run_impl", oom_once)
    rid = sched2.add_request(_prompts(1)[0], max_new_tokens=3)
    outs = _drain(sched2)
    assert fired and outs[rid].finish_reason in ("length", "eos")


# --------------------------------- per-site recovery with token identity

@pytest.mark.parametrize("site,rule", [
    ("serving.decode_step", dict(at=(2, 5))),
    ("serving.prefill", dict(at=1)),
    ("serving.block_alloc", dict(at=(1, 3))),
])
def test_transient_fault_recovers_token_identical(model, site, rule):
    prompts = _prompts(4)
    base_sched = _sched(model)
    base_rids = [base_sched.add_request(p, max_new_tokens=5)
                 for p in prompts]
    base = _drain(base_sched)

    sched = _sched(model)
    rids = [sched.add_request(p, max_new_tokens=5) for p in prompts]
    with fault_plan(FaultPlan(seed=0).on(site, **rule)):
        outs = _drain(sched)
        assert get_injector().snapshot()["fires"].get(site, 0) >= 1
    for r0, r1 in zip(base_rids, rids):
        assert outs[r1].finish_reason in ("length", "eos")
        np.testing.assert_array_equal(base[r0].token_ids,
                                      outs[r1].token_ids)
    _assert_pool_clean(sched)
    assert any("fired" in k and site in k
               for k in sched.metrics.faults_snapshot())


def test_prefix_insert_fault_is_best_effort(model):
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 1000, 16)
    prompts = [np.concatenate([shared, rng.integers(0, 1000, 4)])
               for _ in range(3)]
    base_sched = _sched(model, enable_prefix_caching=True)
    base_rids = [base_sched.add_request(p, max_new_tokens=4)
                 for p in prompts]
    base = _drain(base_sched)

    sched = _sched(model, enable_prefix_caching=True)
    rids = [sched.add_request(p, max_new_tokens=4) for p in prompts]
    with fault_plan(FaultPlan(seed=0).on("serving.prefix_insert",
                                         prob=1.0)):
        outs = _drain(sched)
    # inserts were skipped, not fatal: generation identical, nothing leaks
    for r0, r1 in zip(base_rids, rids):
        np.testing.assert_array_equal(base[r0].token_ids,
                                      outs[r1].token_ids)
    _assert_pool_clean(sched)


def test_weight_reload_fault_leaves_weights_intact(model, tmp_path):
    from paddle_tpu.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, model=model)
    prompt = _prompts(1)[0]

    base_sched = _sched(model)
    r0 = base_sched.add_request(prompt, max_new_tokens=5)
    base = _drain(base_sched)

    sched = _sched(model)
    with fault_plan(FaultPlan(seed=0).on("serving.weight_reload", at=1)):
        with pytest.raises(InjectedFault):
            sched.reload_weights(mgr)
    # the fault fired before restore touched the model: serving continues
    # on the old weights, token-identical
    r1 = sched.add_request(prompt, max_new_tokens=5)
    outs = _drain(sched)
    np.testing.assert_array_equal(base[r0].token_ids, outs[r1].token_ids)
    assert any("serving.weight_reload" in k
               for k in sched.metrics.faults_snapshot())


def test_fault_budget_exhaustion_fails_request(model):
    sched = _sched(model, max_step_faults=3)
    rid = sched.add_request(_prompts(1)[0], max_new_tokens=5)
    with fault_plan(FaultPlan(seed=0).on("serving.decode_step", prob=1.0)):
        outs = _drain(sched)
    assert outs[rid].finish_reason == "failed"
    assert sched.metrics.requests_failed == 1
    assert any("request_failed" in k
               for k in sched.metrics.faults_snapshot())
    _assert_pool_clean(sched)


def test_all_sites_chaos_peers_identical_and_zero_leak(model):
    prompts = _prompts(6, seed=2)
    base_sched = _sched(model, enable_prefix_caching=True)
    base_rids = [base_sched.add_request(p, max_new_tokens=5)
                 for p in prompts]
    base = _drain(base_sched)

    plan = FaultPlan(seed=1)
    for site in ("serving.decode_step", "serving.prefill",
                 "serving.block_alloc", "serving.prefix_insert"):
        plan.on(site, prob=0.2)
    sched = _sched(model, enable_prefix_caching=True, max_step_faults=2)
    rids = [sched.add_request(p, max_new_tokens=5) for p in prompts]
    with fault_plan(plan):
        outs = _drain(sched)
    assert len(outs) == len(prompts)         # no fault may leak a request
    for r0, r1 in zip(base_rids, rids):
        assert outs[r1].finish_reason in ("length", "eos", "failed")
        if outs[r1].finish_reason != "failed":
            # peers that survived the storm are bit-identical
            np.testing.assert_array_equal(base[r0].token_ids,
                                          outs[r1].token_ids)
    _assert_pool_clean(sched)


# --------------------------------------------- cancellation and deadlines

def test_cancel_queued_running_idempotent_unknown(model):
    sched = _sched(model, max_num_seqs=1)
    p1, p2 = _prompts(2)
    r1 = sched.add_request(p1, max_new_tokens=8)
    r2 = sched.add_request(p2, max_new_tokens=8)
    sched.step()                             # r1 running, r2 queued
    out2 = sched.cancel(r2)                  # queued: freed off-grid
    assert out2.finish_reason == "cancelled"
    assert len(out2.generated_ids) == 0
    out1 = sched.cancel(r1)                  # running: slot + blocks freed
    assert out1.finish_reason == "cancelled"
    assert len(out1.generated_ids) >= 1
    assert sched.cancel(r1).finish_reason == "cancelled"   # idempotent
    with pytest.raises(KeyError):
        sched.cancel(10 ** 9)
    assert not sched.has_unfinished()
    _assert_pool_clean(sched)
    assert sched.metrics.cancelled_snapshot() == {'cause="user"': 2.0}


def test_deadline_cancels_with_reason_deadline(model):
    sched = _sched(model, max_num_seqs=1)
    r1 = sched.add_request(_prompts(1)[0], max_new_tokens=50,
                           deadline_s=1e-6)
    outs = _drain(sched)
    assert outs[r1].finish_reason == "deadline"
    assert any('cause="deadline"' in k
               for k in sched.metrics.cancelled_snapshot())
    _assert_pool_clean(sched)


def test_queue_ttl_evicts_stale_queued_only(model):
    sched = _sched(model, max_num_seqs=1, queue_ttl_s=0.05)
    p1, p2 = _prompts(2)
    r1 = sched.add_request(p1, max_new_tokens=4)
    r2 = sched.add_request(p2, max_new_tokens=4)
    sched.step()                             # r1 admitted before the TTL
    time.sleep(0.1)
    outs = _drain(sched)
    assert outs[r1].finish_reason in ("length", "eos")   # running: immune
    assert outs[r2].finish_reason == "queue_ttl"
    assert any('cause="queue_ttl"' in k
               for k in sched.metrics.cancelled_snapshot())
    _assert_pool_clean(sched)


# ------------------------------------------- degradation ladder + watchdog

def test_ladder_escalates_immediately_deescalates_with_hysteresis():
    lad = DegradationLadder(flush_at=0.5, shrink_at=0.7, reject_at=0.9,
                            recover_at=0.3, cooldown_steps=2)
    assert lad.observe(0.95) == (0, 3)       # spike: straight to reject
    assert lad.state == "reject"
    assert lad.observe(0.1) == (3, 3)        # calm 1: holds (hysteresis)
    assert lad.observe(0.1) == (3, 2)        # calm 2: one rung down
    assert lad.observe(0.4) == (2, 2)        # not calm enough: resets
    assert lad.observe(0.1) == (2, 2)
    assert lad.observe(0.1) == (2, 1)
    assert lad.observe(0.1)[1] == 1
    assert lad.observe(0.1) == (1, 0)
    assert lad.state == "ok" and lad.transitions == 4
    with pytest.raises(ValueError, match="thresholds"):
        DegradationLadder(flush_at=0.5, shrink_at=0.4)


def test_step_watchdog_fires_stall_storm_once_per_streak():
    wd = StepWatchdog(factor=3.0, min_history=4, streak=2)
    for _ in range(8):
        assert not wd.observe(0.01)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert wd.observe(1.0)
        assert wd.observe(1.0)               # streak of 2 -> one storm
        assert wd.observe(0.01) is False     # recovery resets the run
    storms = [x for x in w if isinstance(x.message, StallStorm)]
    assert len(storms) == 1
    assert wd.storms == 1 and wd.slow_steps == 2
    # slow samples were not folded into the EWMA
    assert wd.ewma == pytest.approx(0.01, rel=0.01)


def test_degradation_engages_under_queue_pressure_and_recovers(model):
    sched = _sched(model, max_num_seqs=1, max_queue_size=4,
                   shed_flush_occupancy=0.5, shed_shrink_occupancy=0.9,
                   shed_reject_occupancy=0.95, shed_recover_occupancy=0.3,
                   shed_cooldown_steps=1)
    for p in _prompts(4, seed=3):
        sched.add_request(p, max_new_tokens=3)
    sched.step()                             # queue 3/4 = 0.75 -> degraded
    assert sched.health()["state"] == "degraded"
    assert sched.metrics.snapshot()["degradation_level"] >= 1
    _drain(sched)
    for _ in range(4):                       # calm steps de-escalate
        sched.step()
    assert sched.health()["state"] == "ok"
    assert sched.metrics.snapshot()["degradation_level"] == 0
    assert sched._ladder.transitions >= 2


def test_warm_prefix_cache_is_not_pool_pressure(model):
    # A pool full of evictable cached blocks must neither hold the shed
    # ladder up nor gate admission: the tree's blocks are reclaimed by the
    # very allocate() call an admission makes, so they are not load. Before
    # the _pool_pressure() fix this livelocked — gated admission never
    # allocates, and allocation is the only eviction trigger.
    sched = _sched(model, enable_prefix_caching=True, num_blocks=12,
                   shed_flush_occupancy=0.6, shed_shrink_occupancy=0.7,
                   shed_reject_occupancy=0.99, shed_recover_occupancy=0.3,
                   shed_cooldown_steps=1)
    for p in _prompts(6, seed=11, lo=12, hi=17):
        sched.add_request(p, max_new_tokens=3)
    _drain(sched)                           # retires warm the radix tree
    assert sched.prefix_cache.reclaimable_blocks() > 0
    raw = sched.allocator.utilization()
    live = sched._pool_pressure()
    assert live < 0.3 <= raw, (live, raw)   # warm cache, no live load
    sched._ladder.observe(0.8)              # pressure spike -> SHRINK
    assert sched._ladder.level >= LEVEL_SHRINK
    for p in _prompts(4, seed=12, lo=12, hi=17):
        sched.add_request(p, max_new_tokens=3)
    outs = _drain(sched)                    # hung here before the fix
    assert len(outs) == 10
    for _ in range(4):                      # calm steps de-escalate
        sched.step()
    assert sched.health()["state"] == "ok"
    _assert_pool_clean(sched)


def test_overload_rejection_at_reject_level_and_while_draining(model):
    sched = _sched(model)
    sched._ladder.observe(1.0)               # pressure spike -> reject
    assert sched._ladder.level == LEVEL_REJECT
    with pytest.raises(SchedulerOverloaded, match="overloaded"):
        sched.add_request(_prompts(1)[0], max_new_tokens=3)
    while sched._ladder.level > LEVEL_OK:
        sched._ladder.observe(0.0)
    sched.start_drain()
    with pytest.raises(SchedulerOverloaded, match="draining"):
        sched.add_request(_prompts(1)[0], max_new_tokens=3)
    assert sched.metrics.snapshot()["requests_rejected"] == 2
    assert sched.health()["state"] == "draining"


# ------------------------------------------------- /healthz truthfulness

def test_healthz_flips_ok_degraded_ok_and_dead_driver_is_503(model):
    sched = _sched(model, shed_cooldown_steps=1)
    ep = sched.start_endpoint()
    try:
        def healthz():
            return urllib.request.urlopen(ep.url + "/healthz",
                                          timeout=10).read()

        assert healthz() == b"ok"
        sched._ladder.observe(1.0)
        assert healthz() == b"degraded"      # degraded is alive: still 200
        for _ in range(6):
            sched._ladder.observe(0.0)
        assert healthz() == b"ok"

        # a dead scheduler thread with work pending must answer 503, not
        # hang the probe or lie "ok"
        sched.add_request(_prompts(1)[0], max_new_tokens=3)
        t = threading.Thread(target=lambda: None)
        t.start()
        t.join()
        sched.attach_driver(t)
        with pytest.raises(urllib.error.HTTPError) as ei:
            healthz()
        assert ei.value.code == 503
        assert ei.value.read() == b"dead"
    finally:
        ep.stop()
    _drain(sched)                            # leave the module-scoped pool


# --------------------------------------------------- add_request validation

def test_add_request_validation(model):
    sched = _sched(model)
    with pytest.raises(ValueError, match="at least one token"):
        sched.add_request(np.array([], dtype=np.int64), max_new_tokens=3)
    with pytest.raises(ValueError, match="integer token ids"):
        sched.add_request(np.array([1.0, 2.0]), max_new_tokens=3)
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.add_request(np.array([1, 2]), max_new_tokens=0)
    with pytest.raises(ValueError, match="largest prefill bucket"):
        sched.add_request(np.arange(200), max_new_tokens=3)
    with pytest.raises(ValueError, match="deadline_s"):
        sched.add_request(np.array([1, 2]), max_new_tokens=3,
                          deadline_s=0.0)
    assert not sched.has_unfinished()
    assert sched.metrics.snapshot()["requests_received"] == 0


# ------------------------------------------------- an open load under chaos

def _chaos_load(sched, n, *, seed=0, rate=1.0, new_tokens=(3, 5),
                cancel_rate=0.0, plan=None, window=(0, 10 ** 9)):
    """``n`` seeded requests arriving over scheduler iterations, ``plan``
    armed for the iterations in ``window``, a seeded share of the requests
    cancelled a few iterations after they arrive. Returns the outputs by
    request id in arrival order, the number refused at admission and the
    tokens streamed in each iteration."""
    rng = np.random.default_rng(seed)
    arrive_at = np.cumsum(rng.exponential(1.0 / rate, n))
    prompts = [rng.integers(0, 1000, int(k)) for k in rng.integers(4, 11, n)]
    budgets = rng.integers(new_tokens[0], new_tokens[1] + 1, n)
    crng = np.random.default_rng(seed + 1)
    will_cancel = crng.random(n) < cancel_rate
    cancel_delay = crng.integers(1, 5, n)
    streamed = [0]

    def on_token(rid, tok):
        streamed[0] += 1

    rids, cancels, per_it = [], [], []
    offered = rejected = it = 0
    try:
        while offered < n or sched.has_unfinished():
            if plan is not None:
                if it == window[0]:
                    arm(plan)
                elif it == window[1]:
                    disarm()
            while offered < n and arrive_at[offered] <= it:
                try:
                    rid = sched.add_request(
                        prompts[offered],
                        max_new_tokens=int(budgets[offered]),
                        on_token=on_token)
                    rids.append(rid)
                    if will_cancel[offered]:
                        cancels.append((it + int(cancel_delay[offered]), rid))
                except SchedulerOverloaded:
                    rejected += 1
                offered += 1
            for due, rid in list(cancels):
                if due <= it:
                    sched.cancel(rid)        # idempotent once finished
                    cancels.remove((due, rid))
            streamed[0] = 0
            sched.step()
            per_it.append(streamed[0])
            it += 1
            assert it < 3000, "chaos load did not drain"
    finally:
        disarm()
    return {r: sched._finished[r] for r in rids}, rejected, per_it


def _busy_median(per_it):
    busy = sorted(t for t in per_it if t > 0)
    return busy[len(busy) // 2]


def test_chaos_load_census_and_zero_leak(model):
    """Faults at every serving site and cancellations over one seeded
    load: every request offered ends in a terminal state, the survivors
    hold their exact count, the pool is whole and the injector disarmed."""
    plan = FaultPlan(seed=0)
    for site in ("serving.decode_step", "serving.prefill",
                 "serving.block_alloc"):
        plan.on(site, prob=0.3)
    sched = _sched(model, max_step_faults=2)
    outs, rejected, _ = _chaos_load(sched, 5, cancel_rate=0.3, plan=plan)
    census = {}
    for o in outs.values():
        census[o.finish_reason] = census.get(o.finish_reason, 0) + 1
    assert set(census) <= {"length", "eos", "cancelled", "failed"}
    assert sum(census.values()) + rejected == 5
    assert sum(sched.metrics.faults_snapshot().values()) >= 1
    _assert_pool_clean(sched)
    assert not get_injector().armed


def test_fault_window_tokens_identical_and_step_yield_recovers(model):
    """Decode-step faults over iterations 4..11 only: retries absorb every
    one (token streams identical to the fault-free load), and once the
    window closes an iteration yields as many tokens as before it."""
    kw = dict(seed=0, rate=0.8, new_tokens=(6, 10))
    base, _, base_per_it = _chaos_load(_sched(model), 12, **kw)

    sched = _sched(model, max_step_faults=6)
    plan = FaultPlan(seed=0).on("serving.decode_step", prob=0.3)
    outs, rejected, per_it = _chaos_load(sched, 12, plan=plan,
                                         window=(4, 12), **kw)
    assert rejected == 0
    assert any("serving.decode_step" in k
               for k in sched.metrics.faults_snapshot())
    for (_, a), (_, b) in zip(sorted(base.items()), sorted(outs.items())):
        assert b.finish_reason == "length"
        np.testing.assert_array_equal(a.token_ids, b.token_ids)
    assert _busy_median(per_it[12:]) == _busy_median(base_per_it)
    _assert_pool_clean(sched)


def test_goodput_does_not_rise_with_the_fault_rate(model):
    """One seeded load at fault rates 0 / 0.1 / 0.25 / 0.4 on the three
    serving sites: all of it completes without faults, and a higher rate
    never completes more requests than a lower one."""
    done = []
    for rate in (0.0, 0.1, 0.25, 0.4):
        plan = None
        if rate:
            plan = FaultPlan(seed=0)
            for site in ("serving.decode_step", "serving.prefill",
                         "serving.block_alloc"):
                plan.on(site, prob=rate)
        sched = _sched(model, max_step_faults=3)
        outs, rejected, _ = _chaos_load(sched, 12, rate=0.8,
                                        new_tokens=(6, 10), plan=plan)
        assert len(outs) + rejected == 12
        done.append(sum(o.finish_reason == "length" for o in outs.values()))
        _assert_pool_clean(sched)
    assert done[0] == 12
    assert done == sorted(done, reverse=True), done
