"""Async zero-bubble serving engine (scheduler dispatch_depth > 0).

Identity oracle: at every ``dispatch_depth`` the engine must produce
token streams bit-identical to the synchronous (depth-0) engine and to
the per-request eager decode — dispatch-ahead only moves WHEN the host
observes a step's tokens, never which tokens the step computes. Pinned
here under plain load, forced preemption, prefix-cache eviction
pressure, mid-flight cancel/deadline, and injected transient faults.
Plus: the one-compiled-decode-program / zero-steady-state-recompile
invariant at depth > 0, the engine block in ``debug_state()`` and the
flight ring, and shutdown's drain-everything contract.
"""

import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.resilience import FaultPlan, fault_plan, get_injector
from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

DEPTHS = (0, 1, 2)


@pytest.fixture(autouse=True, scope="module")
def _no_aot_replay():
    """XLA:CPU AOT replay corrupts these decode programs' NUMERICS (wrong
    generated tokens) even when the persistent cache was written by the
    SAME jax build in the same session — serving tests compile fresh (see
    test_serving_sched.py for the full history)."""
    import jax

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    return GPTForCausalLM(gpt_tiny(num_layers=2))


def _eager_oracle(model, prompt, max_new):
    out = model.generate(paddle.to_tensor(prompt[None, :].astype(np.int64)),
                         max_new_tokens=max_new, temperature=0.0)
    return np.asarray(out.numpy())[0]


def _sched(model, depth, **over):
    kw = dict(max_num_seqs=2, max_seq_len=64, block_size=8,
              dispatch_depth=depth)
    kw.update(over)
    return ContinuousBatchingScheduler(model, SchedulerConfig(**kw))


def _drain(sched, guard=3000):
    while sched.has_unfinished():
        sched.step()
        guard -= 1
        assert guard > 0, "scheduler did not drain"
    return dict(sched._finished)


def _pool_clean(sched):
    if sched.prefix_cache is not None:
        sched.prefix_cache.flush()
    assert sched.allocator.num_used_blocks == 0, (
        f"block leak: {sched.allocator.num_used_blocks} still held")


# ------------------------------------------------------- identity oracle

def test_depths_match_eager_ragged(model):
    """6 ragged requests through 3 slots at every depth == per-request
    eager greedy, token for token."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 1000, int(n))
               for n in rng.integers(4, 14, 6)]
    refs = [_eager_oracle(model, p, 5) for p in prompts]
    for d in DEPTHS:
        sched = _sched(model, d, max_num_seqs=3)
        outs = sched.generate(prompts, max_new_tokens=5)
        for p, o, ref in zip(prompts, outs, refs):
            np.testing.assert_array_equal(o, ref)
        sched.shutdown()
        _pool_clean(sched)


def test_depths_identical_under_forced_preemption(model):
    """Pool sized so both sequences admit but cannot both finish: the
    preempt/resume cycle must commute with dispatch-ahead (the drain
    barrier before preemption makes the resume see committed state)."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 1000, 10), rng.integers(0, 1000, 9)]
    ref = None
    for d in DEPTHS:
        sched = _sched(model, d, block_size=4, num_blocks=6)
        outs = sched.generate(prompts, max_new_tokens=8)
        assert sched.metrics.snapshot()["preemptions"] >= 1
        if ref is None:
            ref = outs
        else:
            for a, b in zip(ref, outs):
                np.testing.assert_array_equal(a, b)
        sched.shutdown()
        _pool_clean(sched)


def test_depths_identical_under_prefix_cache_eviction(model):
    """Prefix cache on with a pool far below the retired-KV footprint:
    continuous LRU eviction while steps are in flight must not change a
    single token vs the synchronous engine."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 1000, int(n))
               for n in rng.integers(9, 20, 8)]
    ref = None
    for d in DEPTHS:
        sched = _sched(model, d, enable_prefix_caching=True, num_blocks=8)
        outs = sched.generate(prompts, max_new_tokens=5)
        assert sched.prefix_cache_stats()["evicted_blocks"] > 0
        if ref is None:
            ref = outs
        else:
            for a, b in zip(ref, outs):
                np.testing.assert_array_equal(a, b)
        sched.shutdown()
        _pool_clean(sched)


@pytest.mark.parametrize("depth", [1, 2])
def test_streamed_tokens_in_order_under_churn(model, depth):
    """More requests than slots and short outputs, so rows retire and
    admit while steps are in flight and the drain thread does the
    streaming: each request's callbacks carry its tokens once, in order,
    and they are the synchronous engine's tokens."""
    rng = np.random.default_rng(10)
    prompts = [rng.integers(0, 1000, int(n))
               for n in rng.integers(4, 14, 10)]

    def run(d):
        streamed = {}
        sched = _sched(model, d, max_num_seqs=3)
        rids = [sched.add_request(
                    p, max_new_tokens=4 + i % 3,
                    on_token=lambda r, t: streamed.setdefault(r, []).append(t))
                for i, p in enumerate(prompts)]
        outs = _drain(sched)
        sched.shutdown()
        _pool_clean(sched)
        for r in rids:
            assert streamed[r] == list(outs[r].generated_ids)
        return [streamed[r] for r in rids]

    assert run(depth) == run(0)


# ------------------------------------------------- mid-flight lifecycle

def test_cancel_mid_flight_exact_parity(model):
    """A cancel between step() calls must land on exactly the state the
    synchronous engine would have: the in-flight pipeline drains first,
    so the cancelled request's tokens-so-far AND every survivor's full
    stream are depth-invariant."""
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 1000, 8), rng.integers(0, 1000, 6),
               rng.integers(0, 1000, 7)]
    results = {}
    for d in DEPTHS:
        sched = _sched(model, d)
        rids = [sched.add_request(p, max_new_tokens=10) for p in prompts]
        for _ in range(3):
            sched.step()
        cancelled = sched.cancel(rids[0])
        assert cancelled.finish_reason == "cancelled"
        outs = _drain(sched)
        sched.shutdown()
        _pool_clean(sched)
        results[d] = (list(cancelled.generated_ids),
                      {r: list(outs[r].token_ids) for r in rids[1:]})
    assert results[1] == results[0]
    assert results[2] == results[0]


def test_deadline_mid_flight(model):
    rng = np.random.default_rng(5)
    sched = _sched(model, 2, max_num_seqs=1)
    rid = sched.add_request(rng.integers(0, 1000, 6), max_new_tokens=50,
                            deadline_s=1e-6)
    outs = _drain(sched)
    assert outs[rid].finish_reason == "deadline"
    sched.shutdown()
    _pool_clean(sched)


def test_transient_faults_at_depth_token_identical(model):
    """Injected decode-step faults with two steps in flight: the retry
    path drains the pipeline, replays, and every surviving stream stays
    bit-identical to the fault-free synchronous run."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 1000, int(n))
               for n in rng.integers(4, 10, 4)]
    base_sched = _sched(model, 0)
    base_rids = [base_sched.add_request(p, max_new_tokens=5)
                 for p in prompts]
    base = _drain(base_sched)
    base_sched.shutdown()

    sched = _sched(model, 2)
    rids = [sched.add_request(p, max_new_tokens=5) for p in prompts]
    with fault_plan(FaultPlan(seed=0).on("serving.decode_step",
                                         at=(2, 5))):
        outs = _drain(sched)
        assert get_injector().snapshot()["fires"].get(
            "serving.decode_step", 0) >= 1
    for r0, r1 in zip(base_rids, rids):
        assert outs[r1].finish_reason in ("length", "eos")
        np.testing.assert_array_equal(base[r0].token_ids,
                                      outs[r1].token_ids)
    sched.shutdown()
    _pool_clean(sched)


# ----------------------------------------- invariants + introspection

def test_zero_steady_state_recompiles_at_depth(model):
    """The tentpole invariant: dispatch-ahead must reuse the ONE compiled
    decode program — a second workload after mark_steady() compiles
    nothing at any depth."""
    rng = np.random.default_rng(7)
    for d in (1, 2):
        sched = _sched(model, d, max_num_seqs=3)
        sched.generate([rng.integers(0, 1000, int(n))
                        for n in rng.integers(4, 14, 5)], max_new_tokens=4)
        stats = sched.compile_stats()
        assert stats["compiles"] == sched.num_programs() == 2
        sched.mark_steady()
        sched.generate([rng.integers(0, 1000, int(n))
                        for n in rng.integers(4, 14, 6)], max_new_tokens=4)
        stats = sched.compile_stats()
        assert stats["steady_state_recompiles"] == 0
        assert stats["compiles"] == 2
        sched.shutdown()


def test_debug_state_and_flight_expose_engine(model):
    rng = np.random.default_rng(8)
    sched = _sched(model, 2)
    sched.add_request(rng.integers(0, 1000, 6), max_new_tokens=8)
    for _ in range(3):
        sched.step()
    dbg = sched.debug_state()
    assert dbg["engine"]["dispatch_depth"] == 2
    assert 0 <= dbg["engine"]["in_flight_steps"] <= 2
    assert dbg["engine"]["drain_wait_seconds"] >= 0
    _drain(sched)
    # decode-step rows in the flight ring carry the engine fields at
    # depth > 0 (and ONLY then — depth-0 dumps stay byte-stable)
    rows = [r for r in sched.flight.dump() if "dispatch_depth" in r]
    assert rows and all(r["dispatch_depth"] == 2 for r in rows)
    assert all("in_flight_steps" in r for r in rows)
    sched.shutdown()

    sync = _sched(model, 0)
    sync.add_request(rng.integers(0, 1000, 6), max_new_tokens=4)
    _drain(sync)
    assert sync.debug_state()["engine"]["in_flight_steps"] == 0
    assert all("dispatch_depth" not in r for r in sync.flight.dump())


def test_shutdown_drains_in_flight_and_frees(model, monkeypatch):
    """``shutdown()`` with a step dispatched and not yet drained. The test
    holds the drain thread at its fetch until ``shutdown()`` has counted
    the pipeline, so the step is in flight whatever the host is doing."""
    rng = np.random.default_rng(9)
    sched = _sched(model, 2)
    for _ in range(3):
        sched.add_request(rng.integers(0, 1000, 8), max_new_tokens=20)
    for _ in range(3):
        sched.step()
    with sched._elock:                   # let the pipeline run dry first:
        sched._drain_all()               # the next step must not back up

    gate = threading.Event()
    fetch, drain_all = sched._fetch_tokens, sched._drain_all

    def held_fetch(next_ids, phase="sampling_sync", stats=None):
        if phase == "drain":
            assert gate.wait(60), "shutdown() never reached its barrier"
        return fetch(next_ids, phase=phase, stats=stats)

    def counted_then_drain():
        gate.set()                       # shutdown() has read the count
        return drain_all()

    monkeypatch.setattr(sched, "_fetch_tokens", held_fetch)
    monkeypatch.setattr(sched, "_drain_all", counted_then_drain)
    sched.step()                         # one decode step, held undrained
    with sched._elock:
        in_flight = len(sched._inflight)
    assert in_flight == 1
    counts = sched.shutdown()
    assert counts["drained_in_flight"] == in_flight
    assert counts["cancelled"] == 3
    assert not sched.has_unfinished()
    _pool_clean(sched)
    # idempotent: nothing left to drain or cancel
    again = sched.shutdown()
    assert again == {"drained_in_flight": 0, "cancelled": 0}
