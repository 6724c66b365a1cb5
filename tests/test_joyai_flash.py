"""JoyAI-LLM-Flash (latent attention: one cached row a token for all heads,
expanded in prefill and absorbed in decode; a dropless expert layer that
holds a share, beside a shared expert) against its plain reference
(``models/joyai_flash_reference.py``), on the CPU at a small size in float32:
the eager forward, prefill + decode through the paged latent cache and
through ``DecodeEngine``'s static one, absorbed == expanded, each mechanism
shown to matter, the expert-share sum with the shared expert counted once,
the scaling factor's default, the other families' pools and donation masks,
and the same through the continuous-batching scheduler (preemption
included)."""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import joyai_flash_reference as ref
from paddle_tpu.models import kv_cache
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.models.joyai_flash import (
    JoyAIFlashAttention,
    JoyAIFlashConfig,
    JoyAIFlashForCausalLM,
    joyai_flash_tiny,
)
from paddle_tpu.models.kv_cache import PagedCacheSlot
from paddle_tpu.models.mimo_v2 import MiMoV2ForCausalLM, mimo_v2_tiny
from paddle_tpu.nn.moe import DroplessMoE
from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

# XLA:CPU replays of cached executables have given wrong decode numerics
# (tests/conftest.py): every serving test module compiles fresh
jax.config.update("jax_enable_compilation_cache", False)

BS = 4          # block size: a 13-token prompt crosses three page boundaries
TOL = 1e-4      # of the logit scale, float32 against float32


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
    paddle.seed(0)
    model = JoyAIFlashForCausalLM(joyai_flash_tiny(experts_held=(4, 8)))
    model.eval()
    return model


def _eager(model, ids):
    with paddle.no_grad():
        return model(paddle.to_tensor(ids[None])).numpy()[0]


# ---- the eager forward, the config, and each mechanism matters ------------

def test_eager_forward_matches_reference(model):
    ids = _ids(37)
    _close(_eager(model, ids), _want(model, ids))


def test_config_round_trips_through_public_names(model):
    d = model.config.to_dict()
    assert d["num_hidden_layers"] == 3 and d["num_attention_heads"] == 4
    assert JoyAIFlashConfig.from_public(d) == model.config
    public = dict(d, model_type="joyai_llm_flash", head_dim=64, n_group=1)
    assert JoyAIFlashConfig.from_public(public) == model.config
    with pytest.raises(ValueError, match="rope_scaling"):
        JoyAIFlashConfig.from_public(dict(d, rope_scaling={"type": "yarn"}))


@pytest.mark.parametrize("mechanism", [
    "rope_score", "k_rotation", "shared_expert", "routed_scaling",
    "correction_bias", "softmax_scale"])
def test_reference_without_one_mechanism_disagrees(model, mechanism):
    ids = _ids(37)
    want = _want(model, ids)
    off = _want(model, ids, without=(mechanism,))
    got = _eager(model, ids)
    assert np.abs(got - off).max() > 5 * TOL * np.abs(want).max()


def test_reference_with_an_8_bit_latent_row_disagrees(model):
    ids = _ids(37)
    want = _want(model, ids)
    off = _want(model, ids, kv_dtype=jax.numpy.float8_e4m3fn)
    assert np.abs(want - off).max() > 5 * TOL * np.abs(want).max()


# ---- absorbed == expanded over one set of weights --------------------------

def test_absorbed_decode_equals_expanded_prefill():
    """The attention layer alone: a chunk at once (expanded K and V of its
    own rows) against the same tokens one at a time through a static latent
    cache (every step absorbed, the first included)."""
    paddle.seed(3)
    cfg = joyai_flash_tiny()
    attn = JoyAIFlashAttention(cfg)
    n = 11
    hidden = paddle.to_tensor(np.random.default_rng(3).normal(
        size=(2, n, cfg.hidden_size)).astype(np.float32))
    geom = [kv_cache.LayerCacheGeometry(1, cfg.latent_dim, cfg.kv_lora_rank,
                                        latent=True)]
    with paddle.no_grad():
        whole, _ = attn(hidden, paddle.to_tensor(np.arange(n, dtype=np.int32)))
        (slot,) = kv_cache.make_static_cache(1, 2, 16, 1, 0, "float32", geom)
        assert slot.v is None and slot.k.shape == [2, 16, 128]
        steps = []
        for t in range(n):
            out, slot = attn(hidden[:, t:t + 1],
                             paddle.to_tensor(np.full((2, 1), t, np.int32)),
                             slot)
            steps.append(out.numpy()[:, 0])
    np.testing.assert_allclose(np.stack(steps, 1), whole.numpy(), atol=2e-6)
    assert np.abs(whole.numpy()).max() > 1e-3
    # the rows' padding lanes stay zero: the decode kernel contracts them
    assert not np.asarray(slot.k.numpy())[:, :, cfg.latent_dim:].any()


# ---- prefill + decode through the paged latent cache -----------------------

def test_prefill_then_decode_through_the_paged_latent_cache(model):
    """Three rows of different lengths: each prefilled alone (expanded,
    across page boundaries, through a shuffled table), then 22 decode steps
    of all three at once (absorbed), against the reference's full forward
    over each row's tokens."""
    prompts, steps, max_blocks = (5, 13, 22), 22, 12
    geometry = model.cache_geometry()
    n_blocks = 3 * max_blocks
    pools = [kv_cache.zero_pools(g, n_blocks, BS, "float32")
             for g in geometry]
    assert pools[0][0].shape == [n_blocks, BS, 128] and pools[0][1] is None
    table = np.random.default_rng(9).permutation(n_blocks).astype(
        np.int32).reshape(3, max_blocks)
    seqs = [list(_ids(p + steps, seed=p)) for p in prompts]

    def launch(ids, pos_ids, rows, pos):
        nonlocal pools
        caches = [PagedCacheSlot(kp, vp, paddle.to_tensor(table[rows]),
                                 paddle.to_tensor(np.asarray(pos, np.int32)))
                  for kp, vp in pools]
        out, caches = model(paddle.to_tensor(np.asarray(ids, np.int32)),
                            paddle.to_tensor(np.asarray(pos_ids, np.int32)),
                            caches)
        pools = [(c.k_pool, c.v_pool) for c in caches]
        return out.numpy()

    got = [[] for _ in prompts]
    with paddle.no_grad():
        for r, (p, seq) in enumerate(zip(prompts, seqs)):
            got[r].append(launch([seq[:p]], np.arange(p), [r], [0])[0, -1])
        at = np.asarray(prompts)
        for step in range(steps):
            out = launch([[seq[a]] for seq, a in zip(seqs, at)], at[:, None],
                         [0, 1, 2], at)
            for r in range(3):
                got[r].append(out[r, 0])
            at = at + 1
    assert kv_cache._last_path == "xla"
    for r, (p, seq) in enumerate(zip(prompts, seqs)):
        _close(np.stack(got[r]), _want(model, np.asarray(seq[:p + steps]),
                                       last=steps + 1))


def _is_greedy(model, prompt, generated) -> bool:
    """Whether ``generated`` is what the reference would have produced one
    arg-max at a time: every token is the arg-max of the reference's logits
    over all before it (one full forward; by induction the same thing)."""
    generated = list(map(int, generated))
    seq = np.concatenate([prompt, generated[:-1]]).astype(np.int32)
    want = _want(model, seq, last=len(generated)).argmax(-1)
    return generated == list(map(int, want))


@pytest.mark.parametrize("paged", [False, True], ids=["static", "paged"])
def test_decode_engine_builds_its_caches_from_the_geometry(model, paged):
    from paddle_tpu.models.serving import DecodeEngine

    ids = _ids(13, seed=5)
    eng = DecodeEngine(model, max_seq_len=64, use_paged=paged, block_size=BS)
    out = eng.generate(ids[None], max_new_tokens=21)[0]
    assert list(out[:13]) == list(ids) and len(out) == 13 + 21
    assert _is_greedy(model, ids, out[13:])


# ---- the expert layer: shares, the shared expert, the scaling factor -------

def _moe(held=None, seed=3, **kw):
    paddle.seed(seed)
    return DroplessMoE(32, 16, 16, 4, experts_held=held, **kw)


def test_sixteen_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """Each of the 16 chips of a deployment computes its one expert's part
    and the shared expert alike: the parts summed, with the shared expert
    counted once, are the reference's whole layer."""
    kw = dict(routed_scaling_factor=2.5, shared_width=16)
    whole = _moe(**kw)
    x = np.random.default_rng(1).normal(size=(2, 9, 32)).astype(np.float32)
    w = {"post_attention_layernorm.weight": np.ones(32, np.float32),
         **{"mlp." + k: v._value for k, v in whole.state_dict().items()}}
    cfg = {"rms_norm_eps": 0.0, "num_experts_per_tok": 4,
           "n_routed_experts": 16, "routed_scaling_factor": 2.5,
           "n_shared_experts": 1}
    normed = x / np.sqrt((x * x).mean(-1, keepdims=True))
    flat = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._experts(flat, w, cfg, frozenset())) - flat
        shared = want - (np.asarray(ref._experts(
            flat, w, cfg, frozenset({"shared_expert"}))) - flat)
    total = np.zeros_like(want)
    for first in range(16):
        share = _moe((first, 1), **kw)
        for name in ("router", "e_score_correction_bias", "shared_gate",
                     "shared_up", "shared_down"):
            getattr(share, name).set_value(getattr(whole, name))
        share.w_in.set_value(whole.w_in[first:first + 1])
        share.w_out.set_value(whole.w_out[first:first + 1])
        total += share(paddle.to_tensor(normed)).numpy().reshape(-1, 32)
    # every share added the shared expert: count it once
    np.testing.assert_allclose(total - 15 * shared, want, atol=1e-5)
    np.testing.assert_allclose(
        whole(paddle.to_tensor(normed)).numpy().reshape(-1, 32), want,
        atol=1e-5)
    assert np.abs(shared).max() > 1e-4 and np.abs(want - shared).max() > 1e-4


def test_scaling_factor_and_shared_expert_default_to_the_layer_as_it_was():
    x = paddle.to_tensor(np.random.default_rng(2).normal(
        size=(7, 32)).astype(np.float32))
    plain, stated, scaled = (_moe((2, 5)),
                             _moe((2, 5), routed_scaling_factor=1.0),
                             _moe((2, 5), routed_scaling_factor=2.5))
    assert set(plain.state_dict()) == {"router", "e_score_correction_bias",
                                       "w_in", "w_out"}
    base = plain(x).numpy()
    assert (stated(x).numpy() == base).all()           # bit-identical
    np.testing.assert_allclose(scaled(x).numpy(), 2.5 * base, rtol=1e-5,
                               atol=1e-8)


# ---- the other families' pools, donation and paths are what they were ------

@pytest.mark.parametrize("family", ["gpt", "mimo", "joyai"])
def test_pool_shapes_and_donation_masks_by_family(family):
    make = {"gpt": lambda: GPTForCausalLM(gpt_tiny()),
            "mimo": lambda: MiMoV2ForCausalLM(mimo_v2_tiny()),
            "joyai": lambda: JoyAIFlashForCausalLM(joyai_flash_tiny())}
    model = make[family]()
    geometry = kv_cache.cache_geometry(model)
    shapes = [kv_cache.pool_shapes(g, 6, BS) for g in geometry]
    cfg = model.config
    if family == "gpt":
        d = cfg.hidden_size // cfg.num_heads
        assert shapes == [([6, BS, cfg.num_heads, d],) * 2] * cfg.num_layers
    elif family == "mimo":
        assert shapes[0] == ([6, BS, 2 * 24], [6, BS, 2 * 16])
        assert shapes[1] == ([6, BS, 4 * 24], [6, BS, 4 * 16])
    else:
        assert shapes == [([6, BS, 128], None)] * cfg.num_layers
    assert all(g.latent == (family == "joyai") for g in geometry)
    caches = [PagedCacheSlot(*kv_cache.zero_pools(g, 6, BS, "float32"),
                             "table", "pos") for g in geometry]
    mask = kv_cache.donate_pools("ids", "pos_ids", caches, "gather")
    assert mask[:2] == (False, False) and mask[3:] == (False,)
    assert all(tuple(m) == (True, True, False, False, False)
               for m in mask[2])
    # what is donated: both pools of a K/V layer, the one pool of a latent
    donated = jax.tree.leaves(jax.tree.map(
        lambda m, c: c if m else None, mask[2], caches,
        is_leaf=lambda x: x is None or isinstance(x, (bool, str))))
    assert len(donated) == len(geometry) * (1 if family == "joyai" else 2)
    back = kv_cache.pools_only(caches)
    assert all(b.block_table is None and b.pos is None for b in back)
    assert all((b.v_pool is None) == (family == "joyai") for b in back)


# ---- through the scheduler ---------------------------------------------------

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
    sched.run()
    assert sched.num_programs() == buckets
    assert sched.compile_stats()["steady_state_recompiles"] == 0
    for rid, p, n in zip(rids, prompts, new):
        got = sched._finished[rid].generated_ids
        assert len(got) == n and _is_greedy(model, p, got)
    assert list(sched._finished[again].generated_ids) == list(
        sched._finished[rids[1]].generated_ids)
    assert sched.allocator.num_used_blocks == 0
    assert sched.window_allocator is None     # the latent rows: full class
    snap = sched.telemetry_snapshot()
    assert snap["moe_pairs_held"] >= 0 and snap["moe_load_max_sum"] > 0
    # 3 layers x 128 lanes (the 40-wide row padded to a lane tile) x 4 B
    assert sched.metrics.registry.get("kv_bytes_per_token").value == 1536


def test_preempted_request_resumes_token_identical(model):
    # 12 blocks: two rows of 17 + 14 tokens outgrow them
    sched = ContinuousBatchingScheduler(model, SchedulerConfig(
        max_num_seqs=2, max_seq_len=64, block_size=BS, num_blocks=12,
        cache_dtype="float32"))
    prompts = [_ids(17, seed=1), _ids(14, seed=2)]
    rids = [sched.add_request(p, max_new_tokens=14) for p in prompts]
    sched.run()
    assert sched.metrics.preemptions >= 1
    for rid, p in zip(rids, prompts):
        got = sched._finished[rid].generated_ids
        assert len(got) == 14 and _is_greedy(model, p, got)
    assert sched.allocator.num_used_blocks == 0
