"""Fault-tolerant multi-replica serving: router, supervisor, failover.

The failover identity oracle: greedy per-request token streams are
independent of batching, placement, and timing, so a request replayed
from its committed view on a survivor must produce a stream bit-identical
to a single-replica run — the same standard PR 8/10 pinned for retry and
async dispatch. Pinned here across a replica kill mid-decode, plus: zero
block leaks after supervisor reap, the circuit-breaker open→half_open→
closed lifecycle, deadlines measured from FIRST admission across
failover, affinity-vs-health routing precedence, zero-downtime rolling
weight reload, the three router fault sites, and ``shutdown()`` with
requests live on every replica.
"""

import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.resilience import (FaultPlan, InjectedFault, fault_plan,
                                   get_injector)
from paddle_tpu.serving import (
    CircuitBreaker,
    ContinuousBatchingScheduler,
    SchedulerConfig,
    SchedulerOverloaded,
    ServingRouter,
)


@pytest.fixture(autouse=True, scope="module")
def _no_aot_replay():
    """XLA:CPU AOT replay corrupts these decode programs' NUMERICS (see
    test_serving_sched.py for the history) — serving tests compile fresh."""
    import jax

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    return GPTForCausalLM(gpt_tiny(num_layers=1))


def _factory(model, **over):
    kw = dict(max_num_seqs=2, max_seq_len=64, block_size=8)
    kw.update(over)

    def factory():
        return ContinuousBatchingScheduler(model, SchedulerConfig(**kw))

    return factory


def _router(model, n=3, **over):
    sched_over = over.pop("sched", {})
    kw = dict(cooldown_s=0.05, affinity_tokens=8)
    kw.update(over)
    return ServingRouter(_factory(model, **sched_over), num_replicas=n,
                         **kw)


def _prompts(n, seed=0, lo=4, hi=12):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1000, int(k))
            for k in rng.integers(lo, hi, n)]


def _oracle(model, prompts, max_new, **over):
    """Single-replica reference streams, rid-indexed in submit order."""
    sched = _factory(model, **over)()
    rids = [sched.add_request(p, max_new_tokens=max_new) for p in prompts]
    guard = 3000
    while sched.has_unfinished():
        sched.step()
        guard -= 1
        assert guard > 0
    outs = dict(sched._finished)
    sched.shutdown()
    return [outs[r].token_ids for r in rids]


def _pools_clean(router):
    for rep in router.replicas:
        sched = rep.sched
        if sched.prefix_cache is not None:
            sched.prefix_cache.flush()
        assert sched.allocator.num_used_blocks == 0, (
            f"replica {rep.replica_id} leaked "
            f"{sched.allocator.num_used_blocks} blocks")


# ------------------------------------------------------- the chaos drill

def test_replica_kill_mid_decode_token_identical_no_leaks(model):
    """The drill: kill a replica mid-decode; every in-flight request
    completes on survivors bit-identical to the single-replica oracle,
    the dead replica's pool drains to zero after reap, and its breaker
    opens then re-closes after cooldown."""
    prompts = _prompts(8, seed=1)
    refs = _oracle(model, prompts, 6)

    router = _router(model, n=3)
    rids = [router.submit(p, max_new_tokens=6) for p in prompts]
    for _ in range(3):
        router.step()

    dead_sched = router.replicas[0].sched        # the incarnation we kill
    router.crash_replica(0)
    router.step()                                # supervisor reaps here

    # (b) zero leaks on the dead incarnation's pool after reap: export
    # freed every block and flushed its prefix cache
    assert dead_sched.allocator.num_used_blocks == 0
    assert router.replicas[0].sched is not dead_sched   # restarted fresh
    assert router.replicas[0].generation == 1

    # (c) breaker opened on reap...
    br = router.supervisor.breakers[0]
    assert br.state() == "open"
    assert not router.supervisor.routable(router.replicas[0])

    guard = 3000
    while router.has_unfinished():
        router.step()
        guard -= 1
        assert guard > 0, "router did not drain after the kill"
    results = {rid: router.get_finished(rid) for rid in rids}

    # (a) token identity vs the single-replica oracle, every request
    assert sorted(results) == sorted(rids)
    for rid, ref in zip(rids, refs):
        assert results[rid].finish_reason in ("eos", "length")
        np.testing.assert_array_equal(results[rid].token_ids, ref)
    dbg = router.debug_state()
    assert dbg["router"]["failovers"] == 1
    assert dbg["router"]["requests_failed_over"] >= 1
    assert dbg["supervisor"]["restarts"] == 1

    # (c) ...and re-closes after cooldown: a clean probe from half_open
    time.sleep(0.06)
    assert br.state() == "half_open"
    router.supervisor.probe_all()
    assert br.state() == "closed"
    assert router.supervisor.routable(router.replicas[0])

    router.shutdown()
    _pools_clean(router)


def test_failover_streams_each_token_exactly_once(model):
    """The streaming contract survives failover: on_token fires once per
    generated token, never replaying the committed prefix to the client."""
    prompts = _prompts(4, seed=3)
    counts = {}

    router = _router(model, n=2)
    rids = [router.submit(p, max_new_tokens=6,
                          on_token=lambda rid, tok:
                          counts.__setitem__(rid, counts.get(rid, 0) + 1))
            for p in prompts]
    for _ in range(2):
        router.step()
    router.crash_replica(0)
    results = router.run()
    for rid in rids:
        assert counts.get(rid, 0) == len(results[rid].generated_ids)
    router.shutdown()
    _pools_clean(router)


# ------------------------------------- deadlines measured from admission

def test_deadline_breach_spans_replica_kill(model):
    """A re-queued request must NOT get a fresh deadline budget: the
    original arrival timestamp rides through failover, so a budget that
    would survive if re-measured from the re-queue still breaches."""
    prompt = _prompts(1, seed=5, lo=6, hi=7)[0]
    router = _router(model, n=2)
    # budget 0.3s; we burn ~0.2s before the kill and ~0.2s after it: a
    # fresh budget at re-queue would leave 0.1s of slack, the original
    # clock is 0.1s overdrawn
    rid = router.submit(prompt, max_new_tokens=50, deadline_s=0.3)
    router.step()
    time.sleep(0.2)
    router.crash_replica(0)
    router.step()                                # reap + failover
    assert router.debug_state()["router"]["requests_failed_over"] == 1
    time.sleep(0.2)
    results = router.run()
    assert results[rid].finish_reason == "deadline"
    router.shutdown()
    _pools_clean(router)


# ------------------------------------------------ routing + affinity

def test_affinity_pins_prefix_to_one_replica(model):
    """Requests sharing >= affinity_tokens of prompt land on the replica
    whose radix tree holds the prefix."""
    rng = np.random.default_rng(9)
    shared = rng.integers(0, 1000, 8)
    prompts = [np.concatenate([shared, rng.integers(0, 1000, 4)])
               for _ in range(4)]
    router = _router(model, n=3, sched=dict(enable_prefix_caching=True))
    rids = [router.submit(p, max_new_tokens=3) for p in prompts]
    with router._lock:
        homes = {router._records[r].replica_id for r in rids}
    assert len(homes) == 1, f"shared prefix scattered over {homes}"
    router.run()
    # the bound replica's radix tree served the repeats
    home = homes.pop()
    assert router.replicas[home].sched.prefix_cache.stats()["hit_rate"] > 0
    router.shutdown()
    _pools_clean(router)


def test_health_gate_outranks_affinity(model):
    """A draining/reloading replica loses its affinity traffic: health is
    checked before the prefix binding, never after."""
    rng = np.random.default_rng(10)
    shared = rng.integers(0, 1000, 8)

    def prompt():
        return np.concatenate([shared, rng.integers(0, 1000, 4)])

    router = _router(model, n=2)
    r0 = router.submit(prompt(), max_new_tokens=3)
    with router._lock:
        home = router._records[r0].replica_id
    router.replicas[home].begin_reload()         # out of the routing set
    r1 = router.submit(prompt(), max_new_tokens=3)
    with router._lock:
        moved = router._records[r1].replica_id
    assert moved != home
    router.replicas[home].end_reload()
    router.run()
    router.shutdown()
    _pools_clean(router)


def test_no_routable_replica_rejects(model):
    router = _router(model, n=2)
    for rep in router.replicas:
        rep.begin_reload()
    with pytest.raises(SchedulerOverloaded, match="no routable replica"):
        router.submit(_prompts(1)[0], max_new_tokens=3)
    assert router.metrics.requests_rejected == 1
    router.shutdown()


def test_round_robin_spreads_load(model):
    router = _router(model, n=3, policy="round_robin")
    rids = [router.submit(p, max_new_tokens=3)
            for p in _prompts(6, seed=11)]
    with router._lock:
        homes = [router._records[r].replica_id for r in rids]
    assert set(homes) == {0, 1, 2}
    router.run()
    router.shutdown()
    _pools_clean(router)


# ------------------------------------------------ rolling weight reload

def test_rolling_reload_zero_downtime_token_identical(model, tmp_path):
    """Reload every replica behind live traffic: requests in flight during
    the rollout all finish, streams stay bit-identical (same weights), and
    every replica reports the loaded step."""
    from paddle_tpu.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, model=model)

    prompts = _prompts(6, seed=13)
    refs = _oracle(model, prompts, 5)
    router = _router(model, n=2)
    rids = [router.submit(p, max_new_tokens=5) for p in prompts]
    router.step()
    loaded = router.rolling_reload(mgr)
    assert loaded == [3, 3]
    results = router.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(results[rid].token_ids, ref)
    assert router.health()["state"] == "ok"
    router.shutdown()
    _pools_clean(router)


# ------------------------------------------------ router fault sites

def test_route_site_transient_is_absorbed(model):
    router = _router(model, n=2)
    with fault_plan(FaultPlan(seed=0).on("router.route", prob=1.0)):
        rid = router.submit(_prompts(1)[0], max_new_tokens=3)
    results = router.run()
    assert results[rid].finish_reason in ("eos", "length")
    assert router.metrics.faults_snapshot() == {
        'outcome="fired",site="router.route"': 1.0}
    router.shutdown()
    _pools_clean(router)


def test_route_site_fatal_propagates(model):
    router = _router(model, n=2)
    with fault_plan(FaultPlan(seed=0).on("router.route", at=1,
                                         kind="fatal")):
        with pytest.raises(InjectedFault):
            router.submit(_prompts(1)[0], max_new_tokens=3)
    assert any("fatal" in k for k in router.metrics.faults_snapshot())
    assert not router.has_unfinished()
    router.shutdown()


def test_replica_step_transient_skips_iteration(model):
    prompts = _prompts(4, seed=15)
    refs = _oracle(model, prompts, 5)
    router = _router(model, n=2)
    rids = [router.submit(p, max_new_tokens=5) for p in prompts]
    with fault_plan(FaultPlan(seed=2).on("replica.step", prob=0.3)):
        results = router.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(results[rid].token_ids, ref)
    assert sum(r.health()["transient_faults"]
               for r in router.replicas) >= 1
    router.shutdown()
    _pools_clean(router)


def test_replica_step_fatal_kills_and_fails_over(model):
    prompts = _prompts(4, seed=16)
    refs = _oracle(model, prompts, 5)
    router = _router(model, n=2)
    rids = [router.submit(p, max_new_tokens=5) for p in prompts]
    with fault_plan(FaultPlan(seed=0).on("replica.step", at=2,
                                         kind="fatal")):
        results = router.run()
    assert router.debug_state()["router"]["failovers"] == 1
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(results[rid].token_ids, ref)
    router.shutdown()
    _pools_clean(router)


def test_healthcheck_site_trips_breaker_at_threshold(model):
    router = _router(model, n=2, probe_fail_threshold=2, cooldown_s=30.0)
    br = router.supervisor.breakers[0]
    plan = FaultPlan(seed=0)
    plan.on("replica.healthcheck", prob=1.0)
    with fault_plan(plan):
        rep = router.replicas[0]
        h = router.supervisor.probe(rep)
        assert h["state"] == "unknown"
        assert br.state() == "closed"            # 1 failure < threshold 2
        router.supervisor.probe(rep)
    assert br.state() == "open"
    assert not router.supervisor.routable(rep)
    assert any("replica.healthcheck" in k
               for k in router.metrics.faults_snapshot())
    router.shutdown()


def test_disarmed_inject_untouched_by_new_sites():
    """The new sites ride the same disarmed fast path: one None check,
    no per-site state while nothing is armed."""
    inj = get_injector()
    assert not inj.armed
    from paddle_tpu.resilience import inject

    before = inj.snapshot()["hits"]
    for site in ("router.route", "replica.step", "replica.healthcheck"):
        inject(site)                             # must be a no-op
    assert inj.snapshot()["hits"] == before      # nothing recorded


# ------------------------------------------------ breaker + export units

def test_circuit_breaker_lifecycle_fake_clock():
    now = [0.0]
    cb = CircuitBreaker(cooldown_s=10.0, probe_fail_threshold=3,
                        clock=lambda: now[0])
    assert cb.state() == "closed" and cb.allows()
    cb.record_probe(False); cb.record_probe(False)
    assert cb.state() == "closed"                # below threshold
    cb.record_probe(False)
    assert cb.state() == "open" and not cb.allows()
    now[0] = 5.0
    cb.record_probe(True)                        # cooldown not elapsed
    assert cb.state() == "open"
    now[0] = 10.0
    assert cb.state() == "half_open" and cb.allows()
    cb.record_probe(False)                       # half_open trial failed
    assert cb.state() == "open"
    now[0] = 20.0
    assert cb.state() == "half_open"
    cb.record_probe(True)
    assert cb.state() == "closed"
    assert cb.trips == 2


def test_export_import_resumes_token_identical(model):
    """The scheduler-level failover hooks: export drains the committed
    view and frees every block; import replays as a recompute resume with
    the ORIGINAL arrival clock and an honest preemption count."""
    prompts = _prompts(3, seed=20)
    refs = _oracle(model, prompts, 6)

    src = _factory(model)()
    rids = [src.add_request(p, max_new_tokens=6) for p in prompts]
    for _ in range(3):
        src.step()
    specs = src.export_restartable()
    assert src.is_draining
    assert src.allocator.num_used_blocks == 0
    assert {s["request_id"] for s in specs} == set(rids)
    by_rid = {s["request_id"]: s for s in specs}
    mid = sum(len(by_rid[r]["out_tokens"]) for r in rids)
    assert mid >= 1, "export before any decode committed nothing"

    dst = _factory(model)()
    new_rids = [dst.import_resumed(by_rid[r]) for r in rids]
    guard = 2000
    while dst.has_unfinished():
        dst.step()
        guard -= 1
        assert guard > 0
    outs = dict(dst._finished)
    for old, new, ref in zip(rids, new_rids, refs):
        np.testing.assert_array_equal(outs[new].token_ids, ref)
        assert outs[new].num_preemptions >= 1   # failover IS a resume
    dst.shutdown()
    src.shutdown()


# ------------------------------------ shutdown with work on every replica

@pytest.mark.parametrize("depth", [0, 2])
def test_shutdown_with_live_requests_frees_every_replica_pool(model, depth):
    """``shutdown()`` while every replica still holds queued and running
    requests (and, at depth 2, dispatched steps): all of them are
    cancelled, every replica's pool is whole, no engine has work left."""
    router = _router(model, n=2, sched={"dispatch_depth": depth})
    for p in _prompts(6, seed=5):
        router.submit(p, max_new_tokens=30)
    for _ in range(2):
        router.step()
    assert all(rep.sched.has_unfinished() for rep in router.replicas)
    counts = router.shutdown()
    assert counts["cancelled"] == 6
    assert not any(rep.sched.has_unfinished() for rep in router.replicas)
    _pools_clean(router)


def test_affinity_hit_rate_at_least_round_robin_on_prefix_groups(model):
    """Three prompt families, each request drawn into one at random (a
    cyclic draw would line up with round-robin placement): placement by
    affinity serves at least as many prompt tokens from the replicas'
    radix trees as round-robin does over the same requests."""
    rng = np.random.default_rng(0)
    shared = [rng.integers(0, 1000, 16) for _ in range(3)]
    prompts = [np.concatenate([shared[int(g)], rng.integers(0, 1000, 5)])
               for g in rng.integers(0, 3, 12)]

    def hit_tokens(policy):
        router = _router(model, n=3, policy=policy,
                         sched=dict(enable_prefix_caching=True))
        for p in prompts:
            router.submit(p, max_new_tokens=3)
            router.step()
        router.run()
        hits = sum(rep.sched.prefix_cache.stats()["hit_tokens"]
                   for rep in router.replicas)
        router.shutdown()
        _pools_clean(router)
        return hits

    affinity = hit_tokens("affinity")
    assert affinity > 0
    assert affinity >= hit_tokens("round_robin")


# ------------------------------------------- fleet journey kill drill

@pytest.mark.parametrize("depth", [0, 2])
def test_kill_drill_single_journey_track_token_identical(model, depth):
    """The fleet-observability drill: kill a replica mid-decode with
    journey tracing + the metrics sampler enabled. Every failed-over
    request must render as EXACTLY ONE fleet-trace track carrying an
    explicit ``failover`` phase plus router reap/replay spans, its phase
    durations must still sum to E2E (the gapless invariant survives the
    replica hop), and the tokens must stay bit-identical to the
    single-replica oracle at dispatch_depth 0 and 2."""
    prompts = _prompts(6, seed=11)
    max_new = 6
    refs = _oracle(model, prompts, max_new, dispatch_depth=depth)

    router = _router(model, n=3, sched={"dispatch_depth": depth},
                     timeline_interval_s=0.005)
    rids = [router.submit(p, max_new_tokens=max_new) for p in prompts]
    router.timeline.sample_once()        # deterministic inline samples
    for _ in range(3):
        router.step()
        router.timeline.sample_once()

    router.crash_replica(0)
    router.step()                        # supervisor reaps + fails over
    router.timeline.sample_once()

    guard = 3000
    while router.has_unfinished():
        router.step()
        guard -= 1
        assert guard > 0, "router did not drain after the kill"
    results = {rid: router.get_finished(rid) for rid in rids}

    # token identity with the full observability stack on
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(results[rid].token_ids, ref)

    dbg = router.debug_state()
    assert dbg["router"]["failovers"] == 1
    moved = dbg["router"]["requests_failed_over"]
    assert moved >= 1

    # one journey per request; the moved ones carry the replica hop
    journeys = {j.router_rid: j for j in router.fleet.journeys()}
    assert sorted(journeys) == sorted(rids)
    hopped = [j for j in journeys.values() if j.failovers > 0]
    assert len(hopped) == moved

    trace = router.export_fleet_trace()
    ev = trace["traceEvents"]
    # one track for every request, hopped or not
    tracks = [e["tid"] for e in ev
              if e.get("ph") == "M" and e.get("name") == "thread_name"]
    assert sorted(tracks) == sorted(rids)
    for j in hopped:
        tid = j.router_rid
        # exactly ONE track for the failed-over request
        tracks = [e for e in ev if e.get("ph") == "M"
                  and e.get("name") == "thread_name"
                  and e.get("tid") == tid]
        assert len(tracks) == 1
        names = {e["name"] for e in ev
                 if e.get("ph") == "X" and e.get("tid") == tid}
        # the explicit failover span links the replica segments, and the
        # router-side spans frame it on the same single track
        assert "req.failover" in names
        assert {"router.route", "router.reap", "router.replay"} <= names

        # gapless across the hop: phase durations sum to E2E on the
        # survivor's resumed trace, which holds the WHOLE timeline
        seg = j.segments[-1]
        rep = router.replicas[seg["replica_id"]]
        tr = rep.sched.tracer.get(seg["replica_rid"])
        assert tr is not None and tr.finish_t is not None
        total = sum(tr.phase_durations().values())
        assert total == pytest.approx(tr.e2e_s(), abs=1e-6)
        assert tr.phase_count("failover") == 1

    # the sampler actually ran (inline + background thread) and recorded
    # queryable per-replica history; the breaker-open incident captured
    # one correlated postmortem bundle
    assert router.timeline.samples_taken >= 4
    assert any(m.startswith("replica0.") or m.startswith("router.")
               for m in router.timeline.metric_names())
    assert router.postmortems.captures >= 1
    kinds = [b["kind"] for b in router.postmortems.bundles()]
    assert "breaker_open" in kinds
    bundle = [b for b in router.postmortems.bundles()
              if b["kind"] == "breaker_open"][-1]
    assert "journeys" in bundle and "timeline_window" in bundle
    assert "router" in bundle
    # an alarm raised by a replica's own flight recorder lands in the
    # fleet's store through the wired callback: on a survivor, and on the
    # incarnation that replaced the dead one (re-bound after the restart)
    for rep, kind in ((router.replicas[-1], "ttft_breach_storm"),
                      (router.replicas[0], "eviction_thrash")):
        before = router.postmortems.captures
        rep.sched.flight.alarm(kind, "raised by the test")
        assert router.postmortems.captures == before + 1
        assert router.postmortems.last()["kind"] == kind

    router.shutdown()
    assert not router.timeline.snapshot()["sampler_alive"]
    _pools_clean(router)
