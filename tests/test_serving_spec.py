"""Chunked prefill + speculative decoding (paddle_tpu/serving/spec/).

The contract under test: both features are pure LATENCY-SHAPE changes —
token streams bit-identical to the depth-0 unchunked autoregressive
oracle through every composition (dispatch depth, tensor parallelism,
forced preemption mid-prefill, prefix-cache eviction, router failover
with an in-flight chunk frontier) — while the engine keeps its
zero-steady-state-recompile invariant over the enlarged program set
(decode grid + chunk program + verify grid).

Runs on the emulated CPU mesh (conftest forces
--xla_force_host_platform_device_count=8). Repetitive prompts are the
n-gram proposer's favorable regime — the spec legs exercise REAL accepts,
not just the fallback path.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (
    ContinuousBatchingScheduler,
    SchedulerConfig,
    ServingRouter,
)
from paddle_tpu.serving.sharded import DeviceGroupPlan, TensorParallelSharding
from paddle_tpu.serving.spec import NgramProposer, Proposer


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


def _sched(depth=0, tp=None, chunk=0, k=0, **over):
    kw = dict(max_num_seqs=2, max_seq_len=64, block_size=8,
              dispatch_depth=depth, prefill_chunk_size=chunk, spec_k=k)
    kw.update(over)
    sharding = TensorParallelSharding(tp=tp) if tp else None
    return ContinuousBatchingScheduler(_model(), SchedulerConfig(**kw),
                                       sharding=sharding)


def _prompts(n, seed=0):
    """Half repetitive (real n-gram accepts), half random (fallback +
    low-accept verify) — the identity oracle must hold over both."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        if i % 2 == 0:
            pat = rng.integers(2, 40, 6)
            out.append(np.concatenate([pat, pat]))
        else:
            out.append(rng.integers(0, 1000, int(rng.integers(5, 13))))
    return out


def _pool_clean(sched):
    if sched.prefix_cache is not None:
        sched.prefix_cache.flush()
    assert sched.allocator.num_used_blocks == 0, (
        f"block leak: {sched.allocator.num_used_blocks} still held")


# ------------------------------------------------------ proposer (host)

def test_ngram_proposer_longest_recent_suffix():
    p = NgramProposer(max_n=3, min_n=1)
    assert isinstance(p, Proposer)
    # suffix (7, 8) occurred earlier; the follower run is proposed
    ctx = np.array([7, 8, 9, 1, 7, 8])
    np.testing.assert_array_equal(p.propose(ctx, 3), [9, 1, 7])
    # most RECENT earlier occurrence wins over the first one
    ctx = np.array([5, 1, 5, 2, 5])
    np.testing.assert_array_equal(p.propose(ctx, 1), [2])
    # proposal clamped to what actually follows the match
    np.testing.assert_array_equal(p.propose(np.array([3, 4, 3]), 5), [4, 3])


def test_ngram_proposer_declines_and_validates():
    p = NgramProposer(max_n=3, min_n=1)
    assert p.propose(np.array([1, 2, 3, 4]), 4) is None   # no repeats
    assert p.propose(np.array([5]), 2) is None            # too short
    assert p.propose(np.array([1, 2, 1, 3]), 0) is None   # k < 1
    with pytest.raises(ValueError, match="min_n"):
        NgramProposer(max_n=1, min_n=2)
    with pytest.raises(ValueError, match="min_n"):
        NgramProposer(max_n=2, min_n=0)


def test_greedy_only_gate():
    for kw in (dict(chunk=16), dict(k=3)):
        with pytest.raises(ValueError, match="greedy"):
            _sched(temperature=0.7, **kw)


# ------------------------------------------------------- identity oracle

def test_chunked_and_spec_match_oracle_every_depth_and_tp():
    """feature in {chunked, spec, both} x depth {0, 2}, plus both at
    tp=2: token streams bit-identical to the depth-0 unchunked oracle."""
    prompts = _prompts(4)
    oracle = _sched()
    refs = oracle.generate(prompts, max_new_tokens=6)
    oracle.shutdown()
    cases = [dict(chunk=8), dict(k=3), dict(chunk=8, k=3)]
    for case in cases:
        for depth in (0, 2):
            sched = _sched(depth=depth, **case)
            outs = sched.generate(prompts, max_new_tokens=6)
            for o, ref in zip(outs, refs):
                np.testing.assert_array_equal(
                    o, ref, err_msg=f"{case} depth={depth}")
            sched.shutdown()
            _pool_clean(sched)
    for tp in (1, 2):
        sched = _sched(tp=tp, chunk=8, k=3)
        outs = sched.generate(prompts, max_new_tokens=6)
        for o, ref in zip(outs, refs):
            np.testing.assert_array_equal(o, ref, err_msg=f"tp={tp}")
        sched.shutdown()
        _pool_clean(sched)


def test_spec_eos_and_budget_identical():
    """Early EOS inside an accepted run and a tight max_new budget must
    truncate the spec emit exactly like the autoregressive engine."""
    prompts = _prompts(2)
    base = _sched()
    refs = base.generate(prompts, max_new_tokens=6)
    base.shutdown()
    # an eos the oracle actually emits mid-stream -> real early stop
    eos = int(refs[0][len(prompts[0]) + 2])
    ref_eos = None
    for kw in (dict(), dict(chunk=8, k=4)):
        sched = _sched(**kw)
        outs = sched.generate(prompts, max_new_tokens=6, eos_token_id=eos)
        if ref_eos is None:
            ref_eos = outs
            assert any(len(o) < len(r) for o, r in zip(outs, refs)), (
                "chosen eos did not actually stop any stream early")
        else:
            for o, r in zip(outs, ref_eos):
                np.testing.assert_array_equal(o, r)
        sched.shutdown()
        _pool_clean(sched)
    # budget tighter than the draft depth: never emit past max_new
    sched = _sched(k=4)
    outs = sched.generate(prompts, max_new_tokens=2)
    for o, p, r in zip(outs, prompts, refs):
        assert len(o) == len(p) + 2
        np.testing.assert_array_equal(o, r[:len(o)])
    sched.shutdown()
    _pool_clean(sched)


def test_preemption_mid_prefill_identical():
    """Pool sized so the chunked engine preempts while long prompts are
    still mid-prefill: the frontier is dropped, blocks freed, and the
    recompute-resume stays token-identical to the unchunked engine."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 1000, 10) for _ in range(2)]
    ref, preempted = None, 0
    for chunk, k in ((0, 0), (4, 0), (4, 3)):
        sched = _sched(chunk=chunk, k=k, block_size=4, num_blocks=6)
        outs = sched.generate(prompts, max_new_tokens=8)
        if chunk:
            preempted += sched.metrics.snapshot()["preemptions"]
        if ref is None:
            ref = outs
        else:
            for a, b in zip(ref, outs):
                np.testing.assert_array_equal(a, b)
        sched.shutdown()
        _pool_clean(sched)
    assert preempted >= 1, "pool never forced a preemption under chunking"


def test_prefix_cache_eviction_chunked_identical():
    """Identity must survive prefix caching with continuous LRU eviction
    while chunking + speculation are on."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 1000, int(k))
               for k in rng.integers(9, 20, 6)]
    ref = None
    for kw in (dict(), dict(chunk=8, k=3)):
        sched = _sched(enable_prefix_caching=True, num_blocks=8, **kw)
        outs = sched.generate(prompts, max_new_tokens=5)
        assert sched.prefix_cache_stats()["evicted_blocks"] > 0
        if ref is None:
            ref = outs
        else:
            for a, b in zip(ref, outs):
                np.testing.assert_array_equal(a, b)
        sched.shutdown()
        _pool_clean(sched)


def test_chunked_prefill_skips_cached_prefix():
    """A repeat prompt's cached prefix is NOT re-chunked: the chunk
    frontier starts at the radix match, so the second admission prefills
    strictly fewer tokens — token streams identical both times."""
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 1000, 40)
    sched = _sched(chunk=8, k=3, enable_prefix_caching=True)
    out1 = sched.generate([prompt], max_new_tokens=4)[0]
    first = sched.metrics.snapshot()["prefill_tokens"]
    out2 = sched.generate([prompt], max_new_tokens=4)[0]
    second = sched.metrics.snapshot()["prefill_tokens"] - first
    np.testing.assert_array_equal(out1, out2)
    assert sched.prefix_cache_stats()["hit_tokens"] > 0
    assert 0 < second < first, (
        f"cached prefix was re-chunked: {second} vs {first} prefilled")
    sched.shutdown()
    _pool_clean(sched)


# -------------------------------------------- failover: chunk frontier

def test_export_restartable_mid_prefill_frontier():
    """Export while a request is mid-chunked-prefill: the spec carries
    the chunk frontier, the pool is leak-free, and replaying on a fresh
    engine is token-identical."""
    rng = np.random.default_rng(5)
    long_prompt = rng.integers(0, 1000, 40)
    oracle = _sched(max_seq_len=64)
    ref = oracle.generate([long_prompt], max_new_tokens=5)[0]
    oracle.shutdown()

    src = _sched(chunk=8, k=3, max_seq_len=64)
    rid = src.add_request(long_prompt, max_new_tokens=5)
    src.step()                      # admission packs the slot mid-prefill
    specs = src.export_restartable()
    assert src.allocator.num_used_blocks == 0
    [spec] = specs
    assert spec["request_id"] == rid
    assert spec["prefill_pos"] >= 0, (
        "exported mid-prefill request must carry its chunk frontier")
    assert spec["prefill_pos"] < len(long_prompt)

    dst = _sched(chunk=8, k=3, max_seq_len=64)
    new_rid = dst.import_resumed(spec)
    guard = 2000
    while dst.has_unfinished():
        dst.step()
        guard -= 1
        assert guard > 0
    np.testing.assert_array_equal(dst._finished[new_rid].token_ids, ref)
    dst.shutdown()
    _pool_clean(dst)
    src.shutdown()


def test_router_kill_drill_with_chunk_frontier():
    """Crash a replica while a long prompt's chunk frontier is in flight:
    every request completes on the survivor bit-identical to the
    oracle."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 1000, 40)] + _prompts(3, seed=6)
    oracle = _sched()
    orids = [oracle.add_request(p, max_new_tokens=5) for p in prompts]
    guard = 3000
    while oracle.has_unfinished():
        oracle.step()
        guard -= 1
        assert guard > 0
    refs = [oracle._finished[r].token_ids for r in orids]
    oracle.shutdown()

    def make_replica(sh):
        return ContinuousBatchingScheduler(
            _model(), SchedulerConfig(max_num_seqs=2, max_seq_len=64,
                                      block_size=8, prefill_chunk_size=8,
                                      spec_k=3),
            sharding=sh)

    plan = DeviceGroupPlan(tp=1, replicas=2)
    router = ServingRouter(plan.replica_factories(make_replica),
                           cooldown_s=0.05, device_ownership="error")
    rids = [router.submit(p, max_new_tokens=5) for p in prompts]
    router.step()                   # admissions land; frontiers open
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
    router.shutdown()


# ---------------------------------------- what the two features are for

@pytest.mark.parametrize("k", [2, 4])
def test_speculation_finishes_in_fewer_device_steps(k):
    """Prompts that repeat a pattern three times, where the n-gram
    proposer has something to propose: drafts are accepted, a verify step
    yields more than one token, and the same tokens come out of fewer
    device steps than one-token decode needs."""
    rng = np.random.default_rng(0)
    prompts = [np.tile(rng.integers(2, 40, 6), 3) for _ in range(3)]

    def run(spec_k):
        sched = _sched(k=spec_k)
        outs = sched.generate(prompts, max_new_tokens=24)
        steps = sched.metrics.snapshot()["decode_steps"]
        stats = sched.spec_stats()
        sched.shutdown()
        _pool_clean(sched)
        return outs, steps, stats

    ref, base_steps, _ = run(0)
    outs, steps, st = run(k)
    for o, r in zip(outs, ref):
        np.testing.assert_array_equal(o, r)
    assert st["accepted_tokens"] > 0
    assert st["tokens_per_verify_step"] > 1.0
    assert steps < base_steps


@pytest.fixture(scope="module")
def storm_reference():
    return _prefill_storm()


def _prefill_storm(chunk=0, k=0):
    """Two rows decode 32 tokens each; once they are under way two
    96-token prompts arrive for the one free slot. Returns every
    request's tokens in arrival order, the flight-recorder rows of the
    iterations in which both decoding rows were live after the long
    prompts arrived, and the steady-state recompile count."""
    rng = np.random.default_rng(0)
    pat = rng.integers(2, 40, 8)
    decoders = [np.concatenate([pat, pat]) for _ in range(2)]
    storms = [rng.integers(0, 1000, 96) for _ in range(2)]
    sched = _sched(chunk=chunk, k=k, max_num_seqs=3, max_seq_len=128)
    # every program shape once before steady state, one request at a time:
    # a random context alone takes the no-proposal [S, 1] decode program, a
    # repetitive one the verify grid
    sched.generate([rng.integers(0, 1000, 96)], max_new_tokens=4)
    sched.generate([np.concatenate([pat, pat])], max_new_tokens=6)
    sched.mark_steady()

    rids = [sched.add_request(p, max_new_tokens=32) for p in decoders]
    for _ in range(5):
        sched.step()
    rids += [sched.add_request(p, max_new_tokens=4) for p in storms]
    rows = []
    guard = 2000
    while sched.has_unfinished():
        both_live = all(r not in sched._finished for r in rids[:2])
        sched.step()
        if both_live and all(r not in sched._finished for r in rids[:2]):
            rows.append(sched.flight.dump(last=1)[0])
        guard -= 1
        assert guard > 0
    recompiles = sched.compile_stats()["steady_state_recompiles"]
    tokens = [list(sched._finished[r].token_ids) for r in rids]
    chunk_size = sched._chunk_size if chunk else None
    sched.shutdown()
    _pool_clean(sched)
    return tokens, rows, recompiles, chunk_size


@pytest.mark.parametrize("k", [0, 3], ids=["chunked", "chunked+spec"])
def test_prefill_storm_is_taken_a_chunk_an_iteration(storm_reference, k):
    """Whole-prompt prefill takes a 96-token prompt in one iteration; the
    chunk pump never takes more than one chunk, every iteration still
    yields a token for each decoding row, the tokens are the same and
    nothing compiles in steady state."""
    ref_tokens, ref_rows, _, _ = storm_reference
    assert max(r["prefill_tokens"] for r in ref_rows) == 96
    tokens, rows, recompiles, chunk_size = _prefill_storm(chunk=16, k=k)
    assert tokens == ref_tokens
    assert recompiles == 0
    assert sum(r["chunked_tokens"] for r in rows) > 0
    assert max(r["prefill_tokens"] for r in rows) <= chunk_size
    assert min(r["generated_tokens"] for r in rows) >= 2


# ------------------------------------------------- compiled-program pins

def test_zero_steady_state_recompiles_both_features():
    """With chunking AND speculation on, the program set is exactly
    {decode grid, chunk program, verify grid} (+ admission prefill of the
    warmup) — and after mark_steady a second workload compiles NOTHING,
    at sync and dispatch-ahead depths."""
    from paddle_tpu.observability.program_inventory import (
        get_program_inventory,
    )

    for depth in (0, 2):
        sched = _sched(depth=depth, chunk=8, k=3)
        sched.generate(_prompts(4, seed=7), max_new_tokens=6)
        stats = sched.compile_stats()
        assert stats["compiles"] == sched.num_programs()
        # ProgramInventory pins the enlarged program set: the [S,1]
        # decode grid plus the chunk and verify programs are all live
        inv = get_program_inventory()
        S = sched.config.max_num_seqs
        assert any(f"i32[{S},1]" in e.signature
                   for e in inv.entries(
                       name_contains=sched._step_fn.tracker_name))
        assert list(inv.entries(
            name_contains=sched._chunk_step.tracker_name))
        assert any(f"i32[{S},4]" in e.signature     # [S, 1+k], k=3
                   for e in inv.entries(
                       name_contains=sched._spec_step.tracker_name))
        sched.mark_steady()
        sched.generate(_prompts(5, seed=8), max_new_tokens=6)
        stats = sched.compile_stats()
        assert stats["steady_state_recompiles"] == 0, stats
        sched.shutdown()
        _pool_clean(sched)


# ------------------------------------------------------- observability

def test_tracer_chunk_events_and_flight_chunked_tokens():
    """Satellite contract: per-chunk ``prefill_chunk`` events (offset +
    size) on the request timeline, and the flight recorder's per-step
    ``chunked_tokens`` field."""
    rng = np.random.default_rng(9)
    long_prompt = rng.integers(0, 1000, 40)
    sched = _sched(chunk=8)
    rid = sched.add_request(long_prompt, max_new_tokens=3)
    guard = 2000
    while sched.has_unfinished():
        sched.step()
        guard -= 1
        assert guard > 0
    C = sched._chunk_size              # chunk=8 buckets up to 16
    tr = sched.tracer.get(rid).to_dict()
    chunks = [e for e in tr["events"] if e["name"] == "prefill_chunk"]
    assert len(chunks) == -(-40 // C)
    offs = [c["offset"] for c in chunks]
    assert offs == sorted(offs) and offs[0] == 0
    assert sum(c["size"] for c in chunks) == 40
    assert all(0 < c["size"] <= C for c in chunks)
    steps = sched.flight.dump()
    assert all("chunked_tokens" in r for r in steps)
    assert sum(r["chunked_tokens"] for r in steps) == 40
    sched.shutdown()
    _pool_clean(sched)


def test_spec_stats_and_stall_phase():
    """spec_stats reports the accept accounting; the host-side proposal
    walk is attributed to the new ``spec_propose`` stall phase."""
    from paddle_tpu.observability.serving_stall import STALL_PHASES

    assert "spec_propose" in STALL_PHASES
    sched = _sched(k=3)
    assert sched.spec_stats() is None or sched.spec_stats()["verify_steps"] == 0
    sched.generate(_prompts(4, seed=11), max_new_tokens=8)
    st = sched.spec_stats()
    assert st["verify_steps"] > 0
    assert st["proposed_tokens"] >= st["accepted_tokens"] >= 0
    assert 0.0 <= st["accept_rate"] <= 1.0
    assert st["tokens_per_verify_step"] >= 1.0
    assert st["emitted_tokens"] >= st["verify_steps"]
    assert sched.stall.snapshot()["spec_propose"] > 0
    sched.shutdown()
    _pool_clean(sched)
    # chunk/spec off: the feature surface reports absent, not zero
    plain = _sched()
    assert plain.spec_stats() is None
    assert "chunked_tokens" not in (plain.flight.dump() or [{}])[0]
    plain.shutdown()
