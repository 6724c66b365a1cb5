"""Sharded multi-chip serving (paddle_tpu/serving/sharded/).

The contract under test: one serving replica spanning a tp mesh must be
OBSERVATIONALLY IDENTICAL to the single-device engine — token streams
bit-identical to the unsharded oracle at every dispatch_depth, through
forced preemption, prefix-cache eviction, and router kill-drill failover
— while the KV pool's bytes actually split ~1/tp per chip (pinned
against the per-device ledger census) and the one-compiled-decode-
program / zero-steady-state-recompile invariant holds at any tp.

Runs on the emulated CPU mesh (conftest forces
--xla_force_host_platform_device_count=8), so tp=2 and 2x-tp=2 router
fleets all fit. Every scheduler builds a FRESH identically-seeded model:
sharding COMMITS the model's parameters to its replica's mesh, so a
model object must never be shared across differently-placed schedulers.
"""

import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (
    ContinuousBatchingScheduler,
    SchedulerConfig,
    ServingRouter,
)
from paddle_tpu.serving.sharded import (
    DeviceGroupPlan,
    TensorParallelSharding,
)


@pytest.fixture(autouse=True, scope="module")
def _no_aot_replay():
    """XLA:CPU AOT replay corrupts decode-program numerics (see
    test_serving_async.py) — serving tests compile fresh."""
    import jax

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _model():
    paddle.seed(7)
    return GPTForCausalLM(gpt_tiny(num_layers=2))


def _sched(depth=0, tp=None, plan="exact", **over):
    kw = dict(max_num_seqs=2, max_seq_len=64, block_size=8,
              dispatch_depth=depth)
    kw.update(over)
    sharding = TensorParallelSharding(tp=tp, plan=plan) if tp else None
    return ContinuousBatchingScheduler(_model(), SchedulerConfig(**kw),
                                       sharding=sharding)


def _prompts(n, seed=0, lo=4, hi=13):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1000, int(k)) for k in rng.integers(lo, hi, n)]


def _pool_clean(sched):
    if sched.prefix_cache is not None:
        sched.prefix_cache.flush()
    assert sched.allocator.num_used_blocks == 0, (
        f"block leak: {sched.allocator.num_used_blocks} still held")


# ------------------------------------------------------- identity oracle

def test_sharded_matches_unsharded_oracle_every_depth():
    """tp in {1, 2} x dispatch_depth in {0, 2}: token streams bit-
    identical to the single-device engine AND the per-request eager
    greedy decode."""
    prompts = _prompts(4)
    oracle = _sched()
    refs = oracle.generate(prompts, max_new_tokens=5)
    oracle.shutdown()
    eager_model = _model()
    for p, ref in zip(prompts, refs):
        eag = eager_model.generate(
            paddle.to_tensor(p[None, :].astype(np.int64)),
            max_new_tokens=5, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(eag.numpy())[0], ref)
    for tp in (1, 2):
        for depth in (0, 2):
            sched = _sched(depth=depth, tp=tp)
            outs = sched.generate(prompts, max_new_tokens=5)
            for o, ref in zip(outs, refs):
                np.testing.assert_array_equal(o, ref)
            sched.shutdown()
            _pool_clean(sched)


def test_sharded_preemption_resume_identical():
    """Pool sized so sequences preempt: the recompute-resume cycle on a
    head-sharded pool must not change a token vs the unsharded engine."""
    prompts = _prompts(2, seed=1, lo=9, hi=11)
    ref = None
    for tp in (None, 2):
        for depth in (0, 2):
            sched = _sched(depth=depth, tp=tp, block_size=4, num_blocks=6)
            outs = sched.generate(prompts, max_new_tokens=8)
            assert sched.metrics.snapshot()["preemptions"] >= 1
            if ref is None:
                ref = outs
            else:
                for a, b in zip(ref, outs):
                    np.testing.assert_array_equal(a, b)
            sched.shutdown()
            _pool_clean(sched)


def test_sharded_prefix_cache_eviction_identical():
    """Prefix caching + continuous LRU eviction over the sharded pool
    (COW block copies are eager ops on head-sharded arrays): identical
    streams with the cache on and off, at tp 1 and 2."""
    prompts = _prompts(6, seed=3, lo=9, hi=20)
    ref = None
    for tp in (None, 1, 2):
        sched = _sched(tp=tp, enable_prefix_caching=True, num_blocks=8)
        outs = sched.generate(prompts, max_new_tokens=5)
        assert sched.prefix_cache_stats()["evicted_blocks"] > 0
        if ref is None:
            ref = outs
        else:
            for a, b in zip(ref, outs):
                np.testing.assert_array_equal(a, b)
        sched.shutdown()
        _pool_clean(sched)
    plain = _sched(tp=2)
    outs = plain.generate(prompts, max_new_tokens=5)
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a, b)
    plain.shutdown()
    _pool_clean(plain)


# ------------------------------------------------- compiled-program pins

def test_zero_steady_state_recompiles_sharded():
    """The tentpole invariant survives the mesh: after mark_steady a
    second workload through the tp=2 engine compiles NOTHING, at sync
    and dispatch-ahead depths."""
    for depth in (0, 2):
        sched = _sched(depth=depth, tp=2, max_num_seqs=3)
        sched.generate(_prompts(4, seed=7), max_new_tokens=4)
        stats = sched.compile_stats()
        assert stats["compiles"] == sched.num_programs()
        sched.mark_steady()
        sched.generate(_prompts(5, seed=8), max_new_tokens=4)
        stats = sched.compile_stats()
        assert stats["steady_state_recompiles"] == 0
        sched.shutdown()
        _pool_clean(sched)


def test_bad_sharding_configs_rejected():
    import jax

    with pytest.raises(ValueError, match="plan"):
        TensorParallelSharding(tp=2, plan="nope")
    with pytest.raises(ValueError, match="num_heads"):
        _sched(tp=3)  # gpt_tiny has 4 heads; 4 % 3 != 0
    with pytest.raises(ValueError, match="devices"):
        TensorParallelSharding(tp=len(jax.devices()) + 1)
    with pytest.raises(ValueError):
        DeviceGroupPlan(tp=len(jax.devices()), replicas=2)


# --------------------------------------------------- per-device accounting

def test_per_device_ledger_census_matches_ground_truth():
    """The sharded KV split is falsifiable: per-chip census within 5% of
    bytes computed from the arrays' actual shardings, KV ~1/tp per chip,
    and the {owner,device} gauge series exported."""
    from paddle_tpu.observability.device_memory import (
        tree_device_nbytes,
        tree_nbytes,
    )

    sched = _sched(tp=2)
    rep = sched.device_ledger.census_report()
    kv = rep["owners"]["kv_pool"]
    pool_total = tree_nbytes(sched._pools)
    truth = tree_device_nbytes(sched._pools)
    assert set(kv["devices"]) == set(truth)
    assert len(truth) == 2
    for dev, nb in truth.items():
        # exact halves from the head shard
        assert nb * 2 == pool_total
        assert kv["devices"][dev] == nb
    # whole-replica per-chip census >= 95% of ground truth (weights+pool)
    w_truth = tree_device_nbytes([p for p in sched.model.parameters()])
    for dev in truth:
        ground = truth[dev] + w_truth[dev]
        assert rep["per_device"][dev] >= 0.95 * ground
    snap = sched.metrics.registry.snapshot()
    for dev in truth:
        key = (f'serving_device_memory_bytes{{device="{dev}",'
               f'owner="kv_pool"}}')
        assert snap[key] == truth[dev]
    sched.shutdown()
    _pool_clean(sched)


def test_device_observability_carries_per_chip_memory():
    sched = _sched(tp=2)
    per_dev = sched.device_ledger.census_report()["per_device"]
    assert len(per_dev) == 2
    assert all(v > 0 for v in per_dev.values())
    sched.shutdown()
    _pool_clean(sched)


# ------------------------------------------------- router: disjoint fleets

def _make_replica(sh):
    return ContinuousBatchingScheduler(
        _model(), SchedulerConfig(max_num_seqs=2, max_seq_len=64,
                                  block_size=8),
        sharding=sh)


def test_router_kill_drill_sharded_survivors():
    """Kill a tp=2 replica mid-decode: every request completes on the
    OTHER tp=2 replica (disjoint chips) bit-identical to the single-
    device oracle, and the restarted replica comes back on its own
    device group."""
    prompts = _prompts(6, seed=4)
    oracle = _sched()
    orids = [oracle.add_request(p, max_new_tokens=6) for p in prompts]
    guard = 3000
    while oracle.has_unfinished():
        oracle.step()
        guard -= 1
        assert guard > 0
    refs = [oracle._finished[r].token_ids for r in orids]
    oracle.shutdown()

    plan = DeviceGroupPlan(tp=2, replicas=2)
    router = ServingRouter(plan.replica_factories(_make_replica),
                           cooldown_s=0.05, device_ownership="error")
    groups = [frozenset(rep.sched.device_set()) for rep in router.replicas]
    assert not groups[0] & groups[1]
    rids = [router.submit(p, max_new_tokens=6) for p in prompts]
    for _ in range(3):
        router.step()
    router.crash_replica(0)
    outs = {}
    guard = 3000
    while len(outs) < len(rids):
        for o in router.step():
            outs[o.request_id] = o
        guard -= 1
        assert guard > 0
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(outs[rid].token_ids, ref)
    assert router.replicas[0].generation == 1
    # the restart went through replica 0's own factory -> same chips
    assert frozenset(router.replicas[0].sched.device_set()) == groups[0]
    router.shutdown()


def test_router_device_ownership_validation():
    """Overlapping replica device sets: error mode rejects, warn mode
    warns once per process, disjoint fleets stay silent."""
    import paddle_tpu.serving.router.router as router_mod

    def colocated():
        return ContinuousBatchingScheduler(
            _model(), SchedulerConfig(max_num_seqs=2, max_seq_len=64,
                                      block_size=8))

    with pytest.raises(ValueError, match="share devices"):
        ServingRouter(colocated, num_replicas=2, device_ownership="error")
    old = router_mod._OWNERSHIP_WARNED
    router_mod._OWNERSHIP_WARNED = False
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            r1 = ServingRouter(colocated, num_replicas=2)
            r2 = ServingRouter(colocated, num_replicas=2)
        hits = [w for w in caught if issubclass(w.category, RuntimeWarning)
                and "share devices" in str(w.message)]
        assert len(hits) == 1  # once per process, not per router
        r1.shutdown()
        r2.shutdown()
    finally:
        router_mod._OWNERSHIP_WARNED = old
    # disjoint sharded fleet passes the strict gate silently
    plan = DeviceGroupPlan(tp=1, replicas=2)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        router = ServingRouter(plan.replica_factories(_make_replica),
                               device_ownership="error")
    router.shutdown()


def test_router_factory_sequence_validation():
    def f():
        return None

    with pytest.raises(ValueError, match="factories"):
        ServingRouter([f, f, f], num_replicas=4)
    with pytest.raises(ValueError, match="callable"):
        ServingRouter([])
