"""MiMo-V2 (window + full attention layers with their own KV geometry, a
dropless expert layer that holds a share) against its plain reference
(``models/mimo_v2_reference.py``), on the CPU at a small size in float32:
the eager forward, prefill + decode through a paged cache with two block
classes, the same through the continuous-batching scheduler (preemption
included), each mechanism shown to matter, the expert-share sum, no drops
under a forced router, the window class's page accounting, and the features
that refuse a windowed model."""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import kv_cache
from paddle_tpu.models import mimo_v2_reference as ref
from paddle_tpu.models.kv_cache import (
    BlockAllocator, PagedCacheSlot, window_blocks_per_seq)
from paddle_tpu.models.mimo_v2 import (
    MiMoV2Config,
    MiMoV2ForCausalLM,
    mimo_v2_tiny,
)
from paddle_tpu.nn.moe import DroplessMoE
from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

# XLA:CPU replays of cached executables have given wrong decode numerics
# (tests/conftest.py): every serving test module compiles fresh
jax.config.update("jax_enable_compilation_cache", False)

BS = 4          # block size: the window (8) spans 2 pages, a row holds 3
TOL = 1e-4      # of the logit scale, float32 against float32


def _model(seed=0, **kw):
    paddle.seed(seed)
    model = MiMoV2ForCausalLM(mimo_v2_tiny(**kw))
    model.eval()
    return model


def _ids(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _want(model, ids, **kw):
    return np.asarray(ref.logits(ref.weights_of(model), ids,
                                 model.config.to_dict(), **kw))


def _close(got, want, tol=TOL):
    scale = np.abs(want).max()
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * scale, (
        np.abs(got - want).max() / scale)


@pytest.fixture(scope="module")
def model():
    return _model(experts_held=(4, 8))


def _eager(model, ids):
    with paddle.no_grad():
        return model(paddle.to_tensor(ids[None])).numpy()[0]


# ---- (a), (d): the eager forward, and each mechanism matters ---------------

def test_eager_forward_matches_reference(model):
    ids = _ids(37)
    _close(_eager(model, ids), _want(model, ids))


def test_config_round_trips_through_public_names(model):
    d = model.config.to_dict()
    assert d["num_hidden_layers"] == 4 and d["num_attention_heads"] == 8
    assert MiMoV2Config.from_public(d) == model.config
    assert MiMoV2Config(num_layers=7).hybrid_layer_pattern == (
        0, 1, 1, 1, 1, 0, 1)


@pytest.mark.parametrize("mechanism", [
    "sink", "window", "value_scale", "partial_rope", "rope_bases",
    "correction_bias"])
def test_reference_without_one_mechanism_disagrees(model, mechanism):
    ids = _ids(37)
    want = _want(model, ids)
    off = _want(model, ids, without=(mechanism,))
    got = _eager(model, ids)
    assert np.abs(got - off).max() > 5 * TOL * np.abs(want).max()


# ---- (e), (f): the expert layer's share, and no drops ----------------------

def _moe(held=None, seed=3):
    paddle.seed(seed)
    return DroplessMoE(32, 16, 16, 4, experts_held=held)


def test_shares_add_up_to_the_uncut_layer():
    whole = _moe()
    x = paddle.to_tensor(np.random.default_rng(1).normal(
        size=(2, 9, 32)).astype(np.float32))
    want = whole(x).numpy()
    total = np.zeros_like(want)
    for first in range(0, 16, 4):
        share = _moe((first, 4))
        share.router.set_value(whole.router)
        share.e_score_correction_bias.set_value(
            whole.e_score_correction_bias)
        share.w_in.set_value(whole.w_in[first:first + 4])
        share.w_out.set_value(whole.w_out[first:first + 4])
        total += share(x).numpy()
        pairs, _ = share.last_stats.numpy()
        assert 0 <= pairs <= 18 * 4
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert np.abs(want).max() > 1e-4


def test_uncut_layer_matches_reference_experts():
    moe = _moe((2, 5))
    x = np.random.default_rng(2).normal(size=(11, 32)).astype(np.float32)
    w = {"post_attention_layernorm.weight": np.ones(32, np.float32),
         "mlp.router": moe.router._value,
         "mlp.e_score_correction_bias": moe.e_score_correction_bias._value,
         "mlp.w_in": moe.w_in._value, "mlp.w_out": moe.w_out._value}
    cfg = {"layernorm_epsilon": 0.0, "num_experts_per_tok": 4,
           "n_routed_experts": 16, "experts_held": (2, 5)}
    normed = x / np.sqrt((x * x).mean(-1, keepdims=True))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._experts(x, w, cfg, frozenset())) - x
    np.testing.assert_allclose(moe(paddle.to_tensor(normed)).numpy(), want,
                               atol=1e-5)


def test_no_token_dropped_when_every_token_goes_to_one_expert():
    moe = _moe((4, 4))
    bias = np.full(16, -10.0, np.float32)
    bias[[5, 0, 1, 2]] = 10.0        # every token: expert 5 (held) + 3 not
    moe.e_score_correction_bias.set_value(paddle.to_tensor(bias))
    x = np.random.default_rng(4).normal(size=(40, 32)).astype(np.float32)
    out = moe(paddle.to_tensor(x)).numpy()
    pairs, load_max = moe.last_stats.numpy()
    assert pairs == 40 and load_max == 40
    scores = 1 / (1 + np.exp(-(x @ np.asarray(moe.router._value))))
    weight = scores[:, 5] / scores[:, [5, 0, 1, 2]].sum(-1)
    w_in, w_out = (np.asarray(moe.w_in._value[1]),
                   np.asarray(moe.w_out._value[1]))
    gate, up = (x @ w_in)[:, :16], (x @ w_in)[:, 16:]
    want = weight[:, None] * ((gate / (1 + np.exp(-gate)) * up) @ w_out)
    np.testing.assert_allclose(out, want, atol=1e-5)
    assert (np.abs(out).max(-1) > 0).all()       # every token has its part


def test_experts_held_must_be_a_share():
    with pytest.raises(ValueError, match="experts_held"):
        _moe((12, 8))


# ---- (b): prefill + decode through a paged cache with two block classes ----

def _paged_caches(model, pools, n_full, pos, base_page):
    """One sequence: the full class's table names pages 0 .., the window
    class's the pages from ``base_page`` on (its pool holds 3 to a row)."""
    wb = window_blocks_per_seq(model.config.sliding_window, BS)
    slots = []
    for (kp, vp), g in zip(pools, model.cache_geometry()):
        if g.window:
            table = np.full((1, wb), -1, np.int32)
            # page j of the row lives in pool block j % (wb + 1)
            for c in range(wb):
                table[0, c] = (base_page + c) % (wb + 1)
            slots.append(PagedCacheSlot(
                kp, vp, paddle.to_tensor(table),
                paddle.to_tensor(np.array([pos], np.int32)),
                paddle.to_tensor(np.array([base_page * BS], np.int32))))
        else:
            slots.append(PagedCacheSlot(
                kp, vp, paddle.to_tensor(np.arange(n_full,
                                                   dtype=np.int32)[None]),
                paddle.to_tensor(np.array([pos], np.int32))))
    return slots


@pytest.mark.parametrize("prompt", [5, 13, 22])
def test_prefill_then_decode_through_two_block_classes(model, prompt):
    cfg = model.config
    steps, n_full = 9, 10
    wb = window_blocks_per_seq(cfg.sliding_window, BS)
    pools = [tuple(paddle.zeros(shape, dtype="float32")
                   for shape in kv_cache.pool_shapes(
                       g, wb + 1 if g.window else n_full, BS))
             for g in model.cache_geometry()]
    assert pools[1][0].shape == [wb + 1, BS, 4 * 24]      # folded, window
    assert pools[0][1].shape == [n_full, BS, 2 * 16]      # V narrower than K
    ids = list(_ids(prompt + steps, seed=prompt))
    got, at, feed = [], 0, ids[:prompt]
    with paddle.no_grad():
        for step in range(steps + 1):
            first = max(0, at + len(feed) - cfg.sliding_window) // BS
            caches = _paged_caches(model, pools, n_full, at, first)
            out, caches = model(
                paddle.to_tensor(np.asarray(feed, np.int32)[None]),
                paddle.to_tensor(np.arange(at, at + len(feed),
                                           dtype=np.int32)), caches)
            pools = [(c.k_pool, c.v_pool) for c in caches]
            got.append(out.numpy()[0, -1])
            at += len(feed)
            feed = ids[at:at + 1]
    assert kv_cache._last_path == "xla"
    _close(np.stack(got), _want(model, np.asarray(ids[:at]),
                                last=steps + 1))


def test_decode_engine_builds_its_caches_from_the_geometry(model):
    from paddle_tpu.models.serving import DecodeEngine

    ids = _ids(13, seed=5)
    want = list(ids)
    for _ in range(4):
        want.append(int(_want(model, np.asarray(want), last=1)[0].argmax()))
    for paged in (False, True):
        eng = DecodeEngine(model, max_seq_len=32, use_paged=paged,
                           block_size=BS)
        out = eng.generate(ids[None], max_new_tokens=4)[0]
        assert list(out) == want, paged


# ---- (c), (g): through the scheduler ---------------------------------------

def _greedy(model, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(_want(model, np.asarray(seq), last=1)[0].argmax()))
    return seq[len(prompt):]


def test_scheduler_serves_unequal_requests_with_one_decode_program(model):
    sched = ContinuousBatchingScheduler(model, SchedulerConfig(
        max_num_seqs=3, max_seq_len=64, block_size=BS,
        cache_dtype="float32"))
    prompts = [_ids(n, seed=n) for n in (5, 19, 11, 26, 9)]
    new = [7, 12, 9, 5, 14]
    rids = [sched.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts, new)]
    sched.run()
    buckets = sched.num_programs()
    sched.mark_steady()
    again = sched.add_request(prompts[1], max_new_tokens=new[1])
    outs = sched.run() if False else None
    while sched.has_unfinished():
        sched.step()
    assert sched.num_programs() == buckets
    assert sched.compile_stats()["steady_state_recompiles"] == 0
    for rid, p, n in zip(rids + [again], prompts + [prompts[1]],
                         new + [new[1]]):
        assert list(map(int, sched._finished[rid].generated_ids)) == _greedy(
            model, p, n)
    assert sched.allocator.num_used_blocks == 0
    assert sched.window_allocator.num_used_blocks == 0
    snap = sched.telemetry_snapshot()
    assert snap["moe_pairs_held"] >= 0 and snap["moe_load_max_sum"] > 0


def test_preempted_request_resumes_token_identical(model):
    # 12 full-class blocks: two rows of 17 + 14 tokens outgrow them
    sched = ContinuousBatchingScheduler(model, SchedulerConfig(
        max_num_seqs=2, max_seq_len=64, block_size=BS, num_blocks=12,
        cache_dtype="float32"))
    prompts = [_ids(17, seed=1), _ids(14, seed=2)]
    rids = [sched.add_request(p, max_new_tokens=14) for p in prompts]
    sched.run()
    assert sched.metrics.preemptions >= 1
    for rid, p in zip(rids, prompts):
        assert list(map(int, sched._finished[rid].generated_ids)) == _greedy(
            model, p, 14)
    assert sched.allocator.num_used_blocks == 0
    assert sched.window_allocator.num_used_blocks == 0


def test_window_class_exhaustion_preempts_too(model):
    wb = window_blocks_per_seq(model.config.sliding_window, BS)
    sched = ContinuousBatchingScheduler(model, SchedulerConfig(
        max_num_seqs=2, max_seq_len=64, block_size=BS,
        cache_dtype="float32"))
    # the scheduler sizes the class for every slot at once (it never runs
    # dry); a free list of one row and a page shows that it would preempt
    sched.window_allocator = BlockAllocator(wb + 1, BS)
    prompts = [_ids(6, seed=7), _ids(3, seed=8)]
    rids = [sched.add_request(p, max_new_tokens=12) for p in prompts]
    sched.run()
    assert sched.metrics.preemptions >= 1
    for rid, p in zip(rids, prompts):
        assert list(map(int, sched._finished[rid].generated_ids)) == _greedy(
            model, p, 12)
    assert sched.window_allocator.num_used_blocks == 0


def test_window_row_never_holds_more_than_its_pages(model):
    w = model.config.sliding_window
    wb = window_blocks_per_seq(w, BS)
    sched = ContinuousBatchingScheduler(model, SchedulerConfig(
        max_num_seqs=2, max_seq_len=64, block_size=BS,
        cache_dtype="float32"))
    assert sched._pools[1][0].shape[0] == 2 * wb        # not max_seq_len's
    assert sched._pools[0][0].shape[0] == 2 * (64 // BS)
    sched.add_request(_ids(5, seed=9), max_new_tokens=4 * w)
    sched.add_request(_ids(11, seed=10), max_new_tokens=3)
    held = []
    while sched.has_unfinished():
        sched.step()
        held += [len(r.window_blocks) for r in sched._slots if r is not None]
        for s, r in enumerate(sched._slots):
            if r is not None:
                assert (sched._wtable[s] >= 0).sum() == len(r.window_blocks)
    assert max(held) == wb
    assert sched.window_blocks_peak <= 2 * wb
    released = sched._window_released.value
    assert released >= (5 + 4 * w - w) // BS - 1
    alloc = sched.window_allocator
    assert alloc.num_used_blocks == 0
    assert sorted(alloc._free) == list(range(alloc.num_blocks))


# ---- (i): features that assume one class of blocks refuse ------------------

@pytest.fixture(scope="module")
def latent_model():
    from paddle_tpu.models.joyai_flash import (
        JoyAIFlashForCausalLM, joyai_flash_tiny)

    paddle.seed(0)
    return JoyAIFlashForCausalLM(joyai_flash_tiny())


@pytest.mark.parametrize("family, why", [
    ("model", "sliding-window layers"), ("latent_model", "latent-cache")])
@pytest.mark.parametrize("kw, feature", [
    (dict(enable_prefix_caching=True), "prefix caching"),
    (dict(spec_k=2), "speculative decoding"),
    (dict(prefill_chunk_size=16), "chunked prefill"),
])
def test_one_class_features_refuse_a_windowed_model(request, family, why, kw,
                                                    feature):
    """... and a model whose layers cache latent rows (a chunk of several
    tokens does not read the rows cached before it)."""
    with pytest.raises(ValueError, match=f"{feature}.*{why}"):
        ContinuousBatchingScheduler(
            request.getfixturevalue(family), SchedulerConfig(
                max_num_seqs=2, max_seq_len=64, block_size=BS,
                cache_dtype="float32", **kw))


@pytest.mark.parametrize("family", ["model", "latent_model"])
def test_sharded_step_refuses_a_windowed_model(request, family):
    with pytest.raises(ValueError, match="sharded step"):
        ContinuousBatchingScheduler(
            request.getfixturevalue(family), SchedulerConfig(
                max_num_seqs=2, max_seq_len=64, block_size=BS,
                cache_dtype="float32"), sharding=object())


def test_gpt_and_llama_answer_with_one_class_of_layers():
    from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM,
                                   gpt_tiny, llama_tiny)

    for model in (GPTForCausalLM(gpt_tiny(num_layers=2)),
                  LlamaForCausalLM(llama_tiny())):
        cfg = model.config
        geometry = kv_cache.cache_geometry(model)
        assert len(set(geometry)) == 1 and len(geometry) == cfg.num_layers
        g = geometry[0]
        assert (g.k_dim == g.v_dim == cfg.hidden_size // cfg.num_heads
                and g.window is None and not g.fold_heads)
        assert g.kv_heads == (getattr(cfg, "num_key_value_heads", None)
                              or cfg.num_heads)


def test_reference_follows_given_routing_up_to_a_tie_only(model):
    """The benchmark's check hands the reference the served router's
    choices. It follows one where no expert left out scores more than the
    margin above one held (a choice rounded across a tie is not charged as
    an error of the logits) and keeps its own everywhere else; the report
    says how many rows differ and how many lie beyond the margin."""
    ids = _ids(29, seed=3)
    got = _eager(model, ids)
    moe = [l.mlp for l in model.model.layers
           if isinstance(l.mlp, DroplessMoE)]
    chosen = [np.asarray(m.last_experts.numpy()) for m in moe]
    assert chosen[0].shape == (29, 4)

    def follow(choices, margin):
        routing = {"follow": choices, "margin": margin, "own": [],
                   "report": []}
        return _want(model, ids, routing=routing), routing

    same, routing = follow(chosen, 1e-6)
    _close(got, same)
    for own, c in zip(routing["own"], chosen):
        assert (np.sort(np.asarray(own), -1) == np.sort(c, -1)).all()
    for r in routing["report"]:
        assert r["differs"] == r["beyond"] == 0.0 and r["gap_max"] < 0
        assert r["differs_without_bias"] > 0.2
        assert r["beyond_without_bias"] > 0.2
    # a choice that is not the router's: beyond a small margin the reference
    # keeps its own (the logits stay, the report counts the rows) ...
    swapped = [np.where(c == c[:, :1], (c + 1) % 16, c) for c in chosen]
    kept, routing = follow(swapped, 1e-6)
    _close(got, kept)
    assert all(r["differs"] > 0.5 and r["beyond"] > 0.5
               for r in routing["report"])
    # ... and under a margin as wide as the scores it follows, and the
    # logits show it
    off, routing = follow(swapped, 10.0)
    assert all(r["differs"] > 0.5 and r["beyond"] == 0.0
               for r in routing["report"])
    assert np.abs(off - got).max() > 5 * TOL * np.abs(got).max()
    # without choices to follow it only tells its own
    routing = {"own": [], "report": []}
    _close(got, _want(model, ids, routing=routing))
    assert len(routing["own"]) == 3 and routing["report"] == []


@pytest.mark.parametrize("held_choices", [1, 2], ids=["few", "all"])
def test_pair_buffer_overflow_takes_the_whole_buffer(held_choices):
    """The products run over a buffer of 4 x the expected share of the
    pairs (128 tokens x 4 choices x 2 / 32 held = 32 pairs: 128 rows); a
    step whose routing overflows it runs over all T * k rows. Every token
    chooses ``held_choices`` of the two experts held here."""
    paddle.seed(3)
    moe = DroplessMoE(32, 16, 32, 4, experts_held=(4, 2))
    bias = np.full(32, -10.0, np.float32)
    chosen = [4, 5][:held_choices] + [0, 1, 2][:4 - held_choices]
    bias[chosen] = 10.0
    moe.e_score_correction_bias.set_value(paddle.to_tensor(bias))
    x = np.random.default_rng(6).normal(size=(128, 32)).astype(np.float32)
    out = moe(paddle.to_tensor(x)).numpy()
    pairs, load = moe.last_stats.numpy()
    assert pairs == 128 * held_choices and load == 128
    scores = 1 / (1 + np.exp(-(x @ np.asarray(moe.router._value))))
    want = np.zeros_like(x)
    for e in chosen[:held_choices]:
        w_in = np.asarray(moe.w_in._value[e - 4])
        gate, up = (x @ w_in)[:, :16], (x @ w_in)[:, 16:]
        want += (scores[:, e] / scores[:, chosen].sum(-1))[:, None] * (
            (gate / (1 + np.exp(-gate)) * up) @ np.asarray(
                moe.w_out._value[e - 4]))
    np.testing.assert_allclose(out, want, atol=2e-5)


@pytest.mark.parametrize("window, sink, base", [
    (None, False, 0), (None, True, 0), (8, True, 0), (8, True, 16)],
    ids=["full", "full_sink", "window_sink", "window_sink_base"])
def test_wide_attention_in_blocks_equals_the_whole(monkeypatch, window, sink,
                                                   base):
    """Over ``_SCORE_BYTES`` of scores the queries go in chunks and the keys
    in blocks with the running-max softmax, only as far as a query can
    see: the same numbers as all scores at once."""
    import jax.numpy as jnp

    rng = np.random.default_rng(12)
    B, s, H, kvh, D, Dv, L = 2, 32, 8, 2, 24, 16, 64
    q = jnp.asarray(rng.standard_normal((B, s, H, D)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((B, L, kvh, D)), jnp.float32)
    values = jnp.asarray(rng.standard_normal((B, L, kvh, Dv)), jnp.float32)
    pos = jnp.asarray([3 + base, 17 + base], jnp.int32)
    sk = jnp.asarray(rng.standard_normal(H), jnp.float32) if sink else None
    bs = None if base == 0 else jnp.full((B,), base, jnp.int32)
    whole = kv_cache._masked_attention(q, keys, values, pos, window, sk, bs)
    monkeypatch.setattr(kv_cache, "_SCORE_BYTES", B * H * 8 * L * 4)
    blocks = kv_cache._masked_attention(q, keys, values, pos, window, sk, bs)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                               atol=2e-6)
