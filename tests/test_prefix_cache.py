"""Automatic prefix caching (paddle_tpu/serving/prefix_cache/).

Correctness bar: scheduler outputs are TOKEN-IDENTICAL with the cache on
vs off — including under forced eviction and preempt-resume — against the
same per-request eager `generate()` oracle the r6 preemption tests pinned.
Plus: the refcount protocol (shared blocks never freed under a sharer),
copy-on-write on full-prompt hits, LRU leaf eviction under pool pressure,
zero steady-state recompiles with the cache enabled, the inference-Config
bridge, weight-hot-swap flush, and the counts at a given prefix share.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.models.kv_cache import KVPoolExhausted
from paddle_tpu.serving import (
    ContinuousBatchingScheduler,
    SchedulerConfig,
)
from paddle_tpu.serving.prefix_cache import (
    PrefixCache,
    RadixTree,
    RefCountingBlockAllocator,
)


@pytest.fixture(autouse=True, scope="module")
def _no_aot_replay():
    """Same guard as test_serving_sched: XLA:CPU AOT replay corrupts decode
    program numerics; serving tests compile fresh."""
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


# ----------------------------------------------- ref-counting allocator

def test_refcount_allocator_basics_and_stats():
    a = RefCountingBlockAllocator(num_blocks=6, block_size=4)
    b = a.allocate(9)                       # 3 blocks, ref 1 each
    assert all(a.ref_count(x) == 1 for x in b)
    assert not a.is_shared(b[0])
    a.incref(b[0])
    assert a.ref_count(b[0]) == 2 and a.is_shared(b[0])
    # occupancy/fragmentation stats keep working under sharing: a shared
    # block still counts ONCE toward occupancy
    assert a.num_used_blocks == 3 and a.num_free_blocks == 3
    assert a.utilization() == pytest.approx(0.5)
    assert a.fragmentation(live_tokens=9) == pytest.approx(0.25)
    # free() is one holder's decref: the shared block survives the first
    a.free(b)
    assert a.num_used_blocks == 1 and a.ref_count(b[0]) == 1
    a.decref(b[0])
    assert a.num_free_blocks == 6 and a.num_used_blocks == 0
    with pytest.raises(RuntimeError, match="double free"):
        a.decref(b[0])
    with pytest.raises(RuntimeError, match="not allocated"):
        a.incref(b[0])


def test_refcount_allocator_eviction_callback_reclaims():
    a = RefCountingBlockAllocator(num_blocks=4, block_size=4)
    held = a.allocate(16)                   # pool fully allocated
    cached = list(held[:2])                 # the "tree" adopts two...
    for b in cached:
        a.incref(b)
    a.free(held)                            # ...and the request retires
    assert a.num_used_blocks == 2           # cached survive, others free

    def evict(n):
        # release up to n cached entries (the PrefixCache protocol)
        k = min(n, len(cached))
        for _ in range(k):
            a.decref(cached.pop())
        return k

    a.set_evict_cb(evict)
    running = a.allocate(8)                 # uses the 2 free, no eviction
    assert len(running) == 2 and len(cached) == 2
    got = a.allocate(8)                     # pool dry -> evicts both cached
    assert len(got) == 2 and not cached
    with pytest.raises(KVPoolExhausted):
        a.allocate(4)                       # nothing evictable remains


# --------------------------------------------------------- radix tree

def test_radix_tree_block_granularity_match_insert():
    t = RadixTree(block_size=4)
    toks = list(range(10))                  # 2 full blocks + partial tail
    adopted = t.insert(toks, [7, 8])
    assert adopted == [7, 8] and len(t) == 2
    # full match is block-aligned; partial third block is never cached
    assert t.match(toks) == [7, 8]
    assert t.match(toks[:6]) == [7]         # only the first block matches
    assert t.match([99] + toks[1:]) == []   # divergence inside block 0
    # dedup: re-inserting the same chunks adopts nothing
    assert t.insert(toks, [1, 2]) == []
    # divergent second block forks a sibling, first block still shared
    other = toks[:4] + [77, 77, 77, 77]
    assert t.insert(other, [3, 4]) == [4]
    assert t.match(other) == [7, 4]


def test_radix_tree_lru_leaf_eviction_and_flush():
    t = RadixTree(block_size=2)
    t.insert([1, 2, 3, 4], [10, 11])        # chain: 10 -> 11
    t.insert([5, 6], [12])                  # leaf: 12
    t.match([1, 2, 3, 4])                   # chain is now most recent
    # LRU leaf is 12; inner node 10 must not be evicted before leaf 11
    assert t.evict_lru(1) == [12]
    assert t.evict_lru(2) == [11, 10]       # leaves-first, chain unwinds
    assert len(t) == 0
    t.insert([1, 2], [9])
    assert sorted(t.flush()) == [9] and len(t) == 0


def test_prefix_cache_pin_protocol_and_eviction_preference():
    a = RefCountingBlockAllocator(num_blocks=4, block_size=2)
    pc = PrefixCache(a, block_size=2)
    b1 = a.allocate(4)                      # request 1's two blocks
    pc.insert([1, 2, 3, 4], b1)             # tree adopts (ref 2)
    a.free(b1)                              # request exits (ref 1: tree)
    assert a.num_used_blocks == 2
    pinned = pc.match_and_pin([1, 2, 3, 4])
    assert pinned == b1 and all(a.ref_count(x) == 2 for x in b1)
    got = a.allocate(4)                     # the 2 free blocks, no eviction
    assert len(got) == 2
    # pressure with only PINNED cache entries left: the tree unwinds (the
    # pinner becomes sole owner) but the blocks are NOT freed under it —
    # the pool is genuinely exhausted
    with pytest.raises(KVPoolExhausted):
        a.allocate(2)
    assert pc.stats()["evicted_blocks"] == 2
    assert all(a.ref_count(x) == 1 for x in pinned)   # pin survived
    assert pc.stats()["cached_blocks"] == 0
    pc.unpin(pinned)                        # last holder -> truly free now
    assert a.num_free_blocks == 2


# ------------------------------------------ scheduler: token identity

def _mk(model, enable, **kw):
    cfg = dict(max_num_seqs=2, max_seq_len=64, block_size=8,
               enable_prefix_caching=enable)
    cfg.update(kw)
    return ContinuousBatchingScheduler(model, SchedulerConfig(**cfg))


def test_shared_prefix_workload_token_identical_and_hits(model):
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 1000, 24)
    prompts = [np.concatenate([shared, rng.integers(0, 1000, int(n))])
               for n in rng.integers(4, 10, 6)]
    off = _mk(model, False).generate(prompts, max_new_tokens=5)
    sched = _mk(model, True)
    on = sched.generate(prompts, max_new_tokens=5)
    for p, a, b in zip(prompts, off, on):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(b, _eager_oracle(model, p, 5))
    st = sched.prefix_cache_stats()
    assert st["hit_tokens"] > 0, "shared 24-token prefix must hit"
    assert st["cached_blocks"] > 0
    # hit tokens were NOT prefilled: the miss counter is the prefill work
    assert sched.metrics.prefill_tokens == st["miss_tokens"]
    # registry face: counters + hit-rate gauge exported per scheduler
    prom = sched.metrics.prometheus_text()
    assert "serving_prefix_cache_hit_tokens_total" in prom
    assert "serving_prefix_cache_hit_rate" in prom


def test_full_prompt_hit_copy_on_write_token_identical(model):
    """An exactly-repeated prompt (length = block multiple) is a FULL hit:
    one token is kept to recompute, which partially rewrites the final
    shared block — it must be forked copy-on-write, and every later
    identical request must still decode identically (a corrupted shared
    block would diverge request 3+)."""
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 1000, 16)      # 2 exact blocks of 8
    ref = _eager_oracle(model, prompt, 6)
    sched = _mk(model, True)
    for _ in range(3):                      # sequential: each later one hits
        out = sched.generate([prompt], max_new_tokens=6)[0]
        np.testing.assert_array_equal(out, ref)
    st = sched.prefix_cache_stats()
    # requests 2 and 3 each matched P-1 = 15 tokens (the CoW cap)
    assert st["hit_tokens"] >= 30


def test_forced_eviction_cycles_token_identical(model):
    """Pool far smaller than the retired-KV footprint: the tree must evict
    LRU blocks continuously, and every output must still match eager."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 1000, int(n))
               for n in rng.integers(9, 20, 8)]
    sched = _mk(model, True, num_blocks=8, max_num_seqs=2)  # 64-token pool
    outs = sched.generate(prompts, max_new_tokens=5)
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, _eager_oracle(model, p, 5))
    st = sched.prefix_cache_stats()
    assert st["evicted_blocks"] > 0, "pool was sized to force eviction"
    # no leak: flushing the tree returns the whole pool
    sched.prefix_cache.flush()
    assert sched.allocator.num_free_blocks == sched.allocator.num_blocks


def test_preempt_resume_with_cache_forced_eviction_drill(model):
    """The r6 preemption oracle with the cache ON: the pool is sized so
    both sequences admit but cannot both finish — the younger is
    preempted (donating its KV to the tree), cached blocks are evicted
    under continued decode pressure while it waits, and its resume (which
    may partially hit its own donated blocks) stays token-identical."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 1000, 10), rng.integers(0, 1000, 9)]
    sched = _mk(model, True, block_size=4, num_blocks=6, max_num_seqs=2)
    outs = sched.generate(prompts, max_new_tokens=8)
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, _eager_oracle(model, p, 8))
    m = sched.metrics.snapshot()
    st = sched.prefix_cache_stats()
    assert m["preemptions"] >= 1, "pool was sized to force a preemption"
    assert st["evicted_blocks"] >= 1, "resume under pressure must evict"


def test_zero_steady_state_recompiles_with_cache(model):
    """Hit blocks are block-table DATA, not program shapes: after warmup
    covers the suffix buckets, a whole second workload (hits, CoW forks,
    evictions included) must not compile anything new."""
    rng = np.random.default_rng(4)
    shared = rng.integers(0, 1000, 16)

    def workload(seed):
        r = np.random.default_rng(seed)
        return [np.concatenate([shared, r.integers(0, 1000, 8)])
                for _ in range(4)]

    sched = _mk(model, True)
    sched.generate(workload(10), max_new_tokens=4)
    # repeat one prompt exactly -> the CoW path is inside warmup too
    sched.generate(workload(10)[:1], max_new_tokens=4)
    programs = sched.num_programs()
    sched.mark_steady()
    sched.generate(workload(11), max_new_tokens=4)
    sched.generate(workload(11)[:1], max_new_tokens=4)
    stats = sched.compile_stats()
    assert stats["steady_state_recompiles"] == 0
    assert sched.num_programs() == programs


# ------------------------------------------------- integration faces

def test_inference_config_bridges_prefix_caching():
    from paddle_tpu.inference import Config

    cfg = Config()
    cfg.enable_prefix_caching()
    sc = cfg.to_scheduler_config()
    assert sc.enable_prefix_caching is True
    assert Config().to_scheduler_config().enable_prefix_caching is False
    cfg2 = Config()
    cfg2.enable_prefix_caching(False)
    assert cfg2.to_scheduler_config().enable_prefix_caching is False


def test_reload_weights_flushes_prefix_cache(model, tmp_path):
    """Weight hot-swap invalidates every cached block: stale-weight KV
    must never seed a new-weight decode."""
    from paddle_tpu.checkpoint import CheckpointManager

    rng = np.random.default_rng(5)
    sched = _mk(model, True)
    prompt = rng.integers(0, 1000, 12)
    sched.generate([prompt], max_new_tokens=4)
    assert sched.prefix_cache_stats()["cached_blocks"] > 0
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(1, model=model)
    step = sched.reload_weights(mgr)
    assert step == 1
    assert sched.prefix_cache_stats()["cached_blocks"] == 0
    # same weights were reloaded -> decode still matches eager
    out = sched.generate([prompt], max_new_tokens=4)[0]
    np.testing.assert_array_equal(out, _eager_oracle(model, prompt, 4))


def test_prefix_match_span_recorded(model):
    from paddle_tpu.profiler import Profiler

    rng = np.random.default_rng(6)
    sched = _mk(model, True)
    prof = Profiler(timer_only=False)
    prof.start()
    sched.generate([rng.integers(0, 1000, 10)], max_new_tokens=3)
    prof.stop()
    assert "serving.prefix_match" in prof.summary()


# -------------------------------------------------- satellite: pallas

def test_pallas_package_exports_and_manifest():
    """ops/pallas re-exports entry points + KERNELS manifest, while the
    module attributes (which carry routing state like _FLASH_ENABLED)
    stay importable as modules."""
    import types

    from paddle_tpu.ops import pallas

    assert isinstance(pallas.flash_attention, types.ModuleType)
    assert isinstance(pallas.fused_adamw, types.ModuleType)
    assert isinstance(pallas.fused_rms_norm, types.ModuleType)
    assert callable(pallas.scaled_dot_product_attention)
    assert callable(pallas.fused_adamw_flat)
    assert callable(pallas.rms_norm_routed)
    assert set(pallas.KERNELS) == {"flash_attention", "fused_adamw",
                                   "fused_rms_norm"}
    for k, spec in pallas.KERNELS.items():
        assert callable(spec["entry"]), k
        assert spec["gate"] is None or callable(spec["gate"]), k
        assert spec["module"].startswith("paddle_tpu.ops.pallas."), k


# ------------------------------------------ shared-system-prompt shares

@pytest.mark.parametrize("share", [0.0, 0.9])
def test_prefix_share_counts_and_token_identity(model, share):
    """Every prompt is ``shared_prefix + unique_tail``, the prefix ``share``
    of its 96 tokens. The cache's counts follow from the share alone: no
    hit without a shared prefix; with one, every request after the first
    skips the prefix's whole blocks, and those are exactly the prompt
    tokens the cache-off run prefilled and this one did not. Tokens are
    identical either way."""
    n, plen, bs = 6, 96, 16
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 1000, int(round(share * plen)))
    prompts = [np.concatenate([shared,
                               rng.integers(0, 1000, plen - len(shared))])
               for _ in range(n)]

    def run(enable):
        sched = _mk(model, enable, block_size=bs, max_seq_len=128)
        # the first request alone, so the tree holds the prefix when the
        # others arrive (a server that has been up for a while)
        outs = sched.generate(prompts[:1], max_new_tokens=4)
        outs += sched.generate(prompts[1:], max_new_tokens=4)
        return sched, outs

    off_sched, off = run(False)
    on_sched, on = run(True)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)
    assert off_sched.prefix_cache_stats() is None
    st = on_sched.prefix_cache_stats()
    hit = (n - 1) * (len(shared) // bs) * bs
    assert st["hit_tokens"] == hit
    assert st["miss_tokens"] == n * plen - hit
    assert st["cached_blocks"] > 0        # retired prompts stay in the tree
    assert (off_sched.metrics.prefill_tokens
            - on_sched.metrics.prefill_tokens) == hit
