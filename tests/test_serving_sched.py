"""Continuous-batching serving scheduler (paddle_tpu/serving/).

Correctness oracle: per-request EAGER generate() (models/generation.py's
concat-cache loop, itself verified cached==full-context) — the scheduler's
iteration-level batching over the paged slot grid must be token-identical
under greedy decoding, including under forced preemption (tiny block pool)
and EOS early-exit. Plus: zero steady-state recompiles across admissions,
allocator hardening, admission control, metrics/streaming/profiler spans,
the inference-Config bridge, and a seeded open load's counts.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.models.kv_cache import BlockAllocator, KVPoolExhausted
from paddle_tpu.serving import (
    ContinuousBatchingScheduler,
    QueueFull,
    Request,
    RequestQueue,
    SchedulerConfig,
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


def _eager_oracle(model, prompt, max_new):
    out = model.generate(paddle.to_tensor(prompt[None, :].astype(np.int64)),
                         max_new_tokens=max_new, temperature=0.0)
    return np.asarray(out.numpy())[0]


# ---------------------------------------------------------------- allocator

def test_block_allocator_alloc_free_reuse_cycles():
    a = BlockAllocator(num_blocks=6, block_size=4)
    assert a.num_free_blocks == 6 and a.num_used_blocks == 0
    b1 = a.allocate(9)            # 3 blocks
    assert len(b1) == 3 and a.num_used_blocks == 3
    assert a.utilization() == pytest.approx(0.5)
    # 9 live tokens in 12 slots of capacity -> 25% tail slack
    assert a.fragmentation(live_tokens=9) == pytest.approx(0.25)
    a.extend(b1, cur_tokens=9, add_tokens=4)   # grow to 13 -> 4 blocks
    assert len(b1) == 4
    a.free(b1)
    assert a.num_free_blocks == 6 and a.num_used_blocks == 0
    # freed blocks are reusable
    b2 = a.allocate(24)
    assert sorted(b2) == list(range(6))
    with pytest.raises(KVPoolExhausted):
        a.allocate(1)
    a.free(b2)


def test_block_allocator_double_free_raises():
    a = BlockAllocator(num_blocks=4, block_size=4)
    blocks = a.allocate(8)
    a.free(blocks)
    with pytest.raises(RuntimeError, match="double free"):
        a.free(blocks)
    with pytest.raises(RuntimeError, match="double free"):
        a.free([99])              # never owned


# -------------------------------------------------------------- queue/admit

def test_queue_admission_control_and_priority():
    q = RequestQueue(max_size=2)
    r = [Request(request_id=i, prompt_ids=np.array([1]), max_new_tokens=4,
                 eos_token_id=None, priority=p)
         for i, p in [(0, 0), (1, 5), (2, 0)]]
    q.push(r[0])
    q.push(r[1])
    with pytest.raises(QueueFull):
        q.push(r[2])
    q.push(r[2], force=True)      # preemption path bypasses the cap
    assert q.pop().request_id == 1   # highest priority first
    assert q.pop().request_id == 0   # then FIFO
    assert q.pop().request_id == 2


def test_infeasible_request_rejected(model):
    cfg = SchedulerConfig(max_num_seqs=2, max_seq_len=32, block_size=8,
                          num_blocks=2)  # pool caps at 16 tokens
    sched = ContinuousBatchingScheduler(model, cfg)
    with pytest.raises(ValueError):
        sched.add_request(np.arange(12), max_new_tokens=8)  # 20 > 16
    with pytest.raises(ValueError):
        sched.add_request(np.arange(30), max_new_tokens=8)  # > window


# ------------------------------------------------------ oracle equivalence

def test_scheduler_matches_eager_ragged8(model):
    """8 ragged requests through a 3-slot grid == per-request eager greedy,
    token for token (continuous batching must not change any sequence)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 1000, int(n))
               for n in rng.integers(4, 14, 8)]
    sched = ContinuousBatchingScheduler(
        model, SchedulerConfig(max_num_seqs=3, max_seq_len=64, block_size=8,
                               max_new_tokens=5))
    outs = sched.generate(prompts, max_new_tokens=5)
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, _eager_oracle(model, p, 5))
    m = sched.metrics.snapshot()
    assert m["requests_finished"] == 8
    assert m["generated_tokens"] == 40
    assert m["free_blocks"] == m["total_blocks"]  # all KV returned


def test_scheduler_preemption_resume_matches_eager(model):
    """KV pool sized so both sequences admit but cannot both finish: the
    younger one is preempted mid-decode, resumed via recompute, and still
    matches its uninterrupted eager decode exactly."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 1000, 10), rng.integers(0, 1000, 9)]
    cfg = SchedulerConfig(max_num_seqs=2, max_seq_len=64, block_size=4,
                          num_blocks=6, max_new_tokens=8)
    sched = ContinuousBatchingScheduler(model, cfg)
    outs = sched.generate(prompts, max_new_tokens=8)
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, _eager_oracle(model, p, 8))
    m = sched.metrics.snapshot()
    assert m["preemptions"] >= 1, "pool was sized to force a preemption"
    assert m["prefills"] >= 3      # 2 admissions + >=1 resume recompute
    assert m["free_blocks"] == m["total_blocks"]


def test_scheduler_eos_trims(model):
    # seed 1's greedy stream has distinct tokens mid-stream (needed below);
    # fully-degenerate streams (tiny model fixed points) can't test trimming
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 1000, 8)
    base = ContinuousBatchingScheduler(
        model, SchedulerConfig(max_num_seqs=2, max_seq_len=64, block_size=8,
                               max_new_tokens=6)).generate([prompt])[0]
    gen = base[len(prompt):]
    # "eos" = the first mid-stream token NOT seen earlier in the stream, so
    # the run must stop exactly there (a repeated token would stop sooner)
    k = next(i for i in range(1, len(gen)) if gen[i] not in gen[:i])
    eos = int(gen[k])
    sched = ContinuousBatchingScheduler(
        model, SchedulerConfig(max_num_seqs=2, max_seq_len=64, block_size=8,
                               max_new_tokens=6))
    rid = sched.add_request(prompt, eos_token_id=eos)
    out = sched.run()[rid]
    assert out.finish_reason == "eos"
    assert out.generated_ids[-1] == eos
    assert len(out.generated_ids) == k + 1
    np.testing.assert_array_equal(out.token_ids, base[:len(prompt) + k + 1])


def test_no_recompile_across_admissions(model):
    """Steady state must be zero recompiles: later admissions (same prompt
    buckets) and a whole second workload reuse the same jit programs —
    pinned through the process-wide CompileTracker (the observability
    surface every layer reports compiles into), with the program-cache
    count kept as a cross-check."""
    rng = np.random.default_rng(3)
    sched = ContinuousBatchingScheduler(
        model, SchedulerConfig(max_num_seqs=3, max_seq_len=64, block_size=8,
                               max_new_tokens=4))
    sched.generate([rng.integers(0, 1000, int(n))
                    for n in rng.integers(4, 14, 5)], max_new_tokens=4)
    programs = sched.num_programs()
    stats = sched.compile_stats()
    # warmup compiled exactly the tracked programs: one prefill bucket
    # (<=16) + one decode step = exactly two compiles of the slot step
    assert stats["compiles"] == programs == 2
    sched.mark_steady()        # further compiles are RecompileStorm warnings
    sched.generate([rng.integers(0, 1000, int(n))
                    for n in rng.integers(4, 14, 6)], max_new_tokens=4)
    stats = sched.compile_stats()
    assert stats["steady_state_recompiles"] == 0
    assert stats["compiles"] == 2
    assert sched.num_programs() == programs


# -------------------------------------------- streaming / metrics / spans

def test_streaming_callbacks_and_latency_metrics(model):
    rng = np.random.default_rng(4)
    got = []
    sched = ContinuousBatchingScheduler(
        model, SchedulerConfig(max_num_seqs=2, max_seq_len=64, block_size=8))
    rid = sched.add_request(rng.integers(0, 1000, 6), max_new_tokens=4,
                            on_token=lambda r, t: got.append((r, t)))
    out = sched.run()[rid]
    assert [t for _, t in got] == list(out.generated_ids)
    assert all(r == rid for r, _ in got)
    assert out.ttft_s is not None and out.ttft_s > 0
    assert out.tpot_s is not None and out.tpot_s > 0
    snap = sched.metrics.snapshot()
    assert snap["ttft_s"]["count"] == 1 and snap["tpot_s"]["count"] == 1


def test_stream_iterator_yields_all_tokens(model):
    rng = np.random.default_rng(7)
    sched = ContinuousBatchingScheduler(
        model, SchedulerConfig(max_num_seqs=2, max_seq_len=64, block_size=8))
    rids = [sched.add_request(rng.integers(0, 1000, 6), max_new_tokens=3)
            for _ in range(3)]
    events = list(sched.stream())
    outs = {rid: sched._finished[rid] for rid in rids}
    for rid in rids:
        toks = [t for r, t in events if r == rid]
        assert toks == list(outs[rid].generated_ids)


def test_profiler_records_serving_spans(model):
    from paddle_tpu.profiler import Profiler

    rng = np.random.default_rng(5)
    sched = ContinuousBatchingScheduler(
        model, SchedulerConfig(max_num_seqs=2, max_seq_len=64, block_size=8))
    prof = Profiler(timer_only=False)
    prof.start()
    sched.generate([rng.integers(0, 1000, 6)], max_new_tokens=3)
    prof.stop()
    report = prof.summary()
    assert "serving spans" in report
    assert "serving.prefill" in report
    assert "serving.decode_step" in report


# ------------------------------------------------- inference Config bridge

def test_inference_config_bridges_to_scheduler_config():
    from paddle_tpu.inference import Config

    cfg = Config()
    cfg.enable_memory_optim(False)
    cfg.enable_low_precision("bfloat16")
    sc = cfg.to_scheduler_config(max_num_seqs=4)
    assert sc.enable_preemption is False     # memory_optim wired through
    assert sc.cache_dtype == "bfloat16"      # precision knob wired through
    assert sc.max_num_seqs == 4              # overrides win

    sc2 = Config().to_scheduler_config()
    assert sc2.enable_preemption is True     # untouched default
    assert sc2.cache_dtype == "float32"


# ------------------------------------------------------ generation helpers

def test_trim_at_eos_helper():
    from paddle_tpu.models.generation import trim_at_eos

    p, g = np.array([1, 2]), np.array([3, 9, 4, 9])
    np.testing.assert_array_equal(trim_at_eos(p, g, 9), [1, 2, 3, 9])
    np.testing.assert_array_equal(trim_at_eos(p, g, None), [1, 2, 3, 9, 4, 9])
    np.testing.assert_array_equal(trim_at_eos(p, g, 7), [1, 2, 3, 9, 4, 9])


def test_eager_generate_streams_tokens(model):
    rng = np.random.default_rng(6)
    ids = rng.integers(0, 1000, (2, 5))
    steps = []
    out = model.generate(paddle.to_tensor(ids.astype(np.int64)),
                         max_new_tokens=3, temperature=0.0,
                         on_token=lambda t: steps.append(t))
    out_np = np.asarray(out.numpy())
    assert len(steps) == 3
    np.testing.assert_array_equal(np.stack(steps, 1), out_np[:, 5:])


# ------------------------------------------------------- seeded open load

def test_seeded_load_counts_streams_and_prometheus_round_trip(model):
    """A seeded load arriving over scheduler iterations (Poisson gaps,
    mixed prompt and output lengths): every request finishes with its
    exact count, every token is streamed once, the counters agree with
    the outputs and survive the Prometheus text round trip, and a second
    load after ``mark_steady()`` compiles nothing."""
    from paddle_tpu.observability import parse_prometheus_text

    sched = ContinuousBatchingScheduler(
        model, SchedulerConfig(max_num_seqs=2, max_seq_len=64, block_size=8))

    def drive(seed, n=6, rate=1.0):
        rng = np.random.default_rng(seed)
        arrive_at = np.cumsum(rng.exponential(1.0 / rate, n))
        prompts = [rng.integers(0, 1000, int(k))
                   for k in rng.integers(4, 11, n)]
        budgets = [int(k) for k in rng.integers(3, 7, n)]
        streamed, rids = {}, []

        def on_token(rid, tok):
            streamed[rid] = streamed.get(rid, 0) + 1

        it = 0
        while len(rids) < n or sched.has_unfinished():
            while len(rids) < n and arrive_at[len(rids)] <= it:
                rids.append(sched.add_request(
                    prompts[len(rids)], max_new_tokens=budgets[len(rids)],
                    on_token=on_token))
            sched.step()
            it += 1
            assert it < 1000, "load did not drain"
        outs = [sched._finished[r] for r in rids]
        for out, budget in zip(outs, budgets):
            assert out.finish_reason == "length"
            assert len(out.generated_ids) == budget
            assert streamed[out.request_id] == budget
        return sum(budgets)

    generated = drive(seed=0)
    m = sched.metrics.snapshot()
    assert m["requests_finished"] == 6
    assert m["generated_tokens"] == generated
    assert m["ttft_s"]["count"] == 6
    assert 0.0 <= m["kv_utilization"] <= 1.0
    assert m["free_blocks"] == m["total_blocks"]
    assert sched.num_programs() <= 3     # prefill buckets 8/16 + decode
    prom = parse_prometheus_text(sched.metrics.prometheus_text())
    assert prom["serving_generated_tokens"]["value"] == generated
    assert prom["serving_ttft_seconds"]["count"] == 6

    programs = sched.num_programs()
    sched.mark_steady()
    drive(seed=0)                        # the same buckets again
    assert sched.compile_stats()["steady_state_recompiles"] == 0
    assert sched.num_programs() == programs


# ------------------------------------------- fault-backoff lock regression

def test_fault_backoff_releases_engine_lock(model):
    """FIXED by this PR (found by graft_lint's blocking-under-lock rule):
    ``_absorb_step_fault`` backed off with ``time.sleep`` while holding
    ``_elock``, so every ``add_request``/``cancel``/``shutdown`` stalled
    behind a fault backoff. The backoff is now ``_elock.wait`` — a
    Condition wait releases the engine lock while sleeping and wakes
    early on ``notify_all``."""
    import threading
    import time

    from paddle_tpu.resilience.faults import InjectedFault

    sched = ContinuousBatchingScheduler(
        model, SchedulerConfig(max_num_seqs=2, max_seq_len=32, block_size=8,
                               retry_backoff_s=5.0))
    got_lock = threading.Event()
    release_times = []

    def contender():
        with sched._elock:
            got_lock.set()
            release_times.append(time.perf_counter())
            sched._elock.notify_all()   # wake the backoff early

    t = threading.Thread(target=contender, daemon=True)
    exc = InjectedFault("serving.decode_step", 1, kind="transient")
    t0 = time.perf_counter()
    with sched._elock:
        t.start()
        failed = sched._absorb_step_fault(exc, running=[], attempt=0)
        absorbed_at = time.perf_counter()
    t.join(timeout=10)
    assert failed == []
    # the contender acquired the lock DURING the backoff (with the old
    # sleep-under-lock it could not run until after absorb returned), and
    # its notify_all cut the 1 s capped wait short
    assert got_lock.is_set()
    assert release_times and release_times[0] <= absorbed_at
    assert absorbed_at - t0 < 0.9, (
        f"backoff held the engine lock for {absorbed_at - t0:.2f}s")


# ------------------------------------------- the branches the chip takes

def _donation_case(kind):
    """``(model, prompts, scheduler sizes, new tokens, the feature sets)``
    of one donation run: GPT with every feature, or a toy MiMo-V2 (window
    and full layers: both block classes, with window rolls and page
    releases inside the run), whose one-class features refuse."""
    rng = np.random.default_rng(3)
    if kind == "window":
        from paddle_tpu.models.mimo_v2 import MiMoV2ForCausalLM, mimo_v2_tiny

        paddle.seed(0)
        model = MiMoV2ForCausalLM(mimo_v2_tiny(experts_held=(4, 8)))
        model.eval()
        prompts = [rng.integers(0, 256, n) for n in (5, 19, 11, 26, 9)]
        sizes = dict(max_num_seqs=3, max_seq_len=64, block_size=4,
                     cache_dtype="float32")
        return model, prompts, sizes, 14, ({}, dict(dispatch_depth=2))
    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny(num_layers=1))   # six schedulers: small
    shared = rng.integers(0, 1000, 16)            # a cacheable prefix
    pattern = rng.integers(0, 1000, 5)            # n-gram proposals fire
    prompts = [np.concatenate([shared, rng.integers(0, 1000, 5)]),
               np.concatenate([pattern, pattern, pattern]),
               np.concatenate([shared, rng.integers(0, 1000, 9)]),
               rng.integers(0, 1000, 40),         # several chunks
               np.concatenate([shared, rng.integers(0, 1000, 5)])]
    sizes = dict(max_num_seqs=3, max_seq_len=128, block_size=8)
    return model, prompts, sizes, 6, (
        {}, dict(dispatch_depth=2), dict(enable_prefix_caching=True),
        dict(prefill_chunk_size=16), dict(spec_k=3))


@pytest.mark.parametrize("kind", ["gpt", "window"])
def test_donation_forced_on_is_token_identical(monkeypatch, kind):
    """``_donate`` is False on XLA:CPU, so the executables that donate
    their KV pools (decode, admission prefill, chunk, verify) are what
    every TPU run takes and what no CPU test used to. Force donation on —
    plain, dispatch-ahead, prefix cache, chunked prefill, speculation —
    and require the undonated plain run's tokens (every one of those
    features is token-identical to it by contract). What is staged is the
    same either way: one table and one position tensor for all layers."""
    from paddle_tpu.serving import scheduler as sched_mod

    model, prompts, sizes, new, feature_sets = _donation_case(kind)

    def run(donate, **over):
        monkeypatch.setattr(sched_mod, "_backend_donates", lambda: donate)
        sched = ContinuousBatchingScheduler(model, SchedulerConfig(
            **sizes, **over))
        assert sched._donate is donate
        outs = sched.generate(prompts, max_new_tokens=new)
        assert sched.metrics.requests_failed == 0
        assert not sched.metrics.faults_snapshot()
        if kind == "window":
            # rows rolled past the window and gave pages back in the run
            assert sched._window_released.value > 0
            assert sched.window_allocator.num_used_blocks == 0
        sched.shutdown()
        return [[int(t) for t in o] for o in outs]

    undonated = run(False)
    for over in feature_sets:
        assert run(True, **over) == undonated, over
