"""GPT decoder-only LM — the flagship hybrid-parallel model (the reference's
Fleet GPT-3 config: BASELINE.md #4, SURVEY §3.5 call stack).

TPU-native design:
- TP via fleet mp_layers (VocabParallelEmbedding / Column/RowParallelLinear):
  full logical weights + NamedSharding constraints; GSPMD inserts the
  all-gather / reduce-scatter that Megatron hand-writes
  (reference: python/paddle/distributed/fleet/layers/mpu/mp_layers.py).
- Attention runs through the flash-attention entry (Pallas kernel on TPU,
  fused-XLA fallback elsewhere; reference:
  python/paddle/nn/functional/flash_attention.py:147).
- Long context: sequence activations can carry a "sep" mesh-axis shard
  (reference's segment-parallel axis, fleet/base/topology.py:68); with
  causal flash attention the sep axis shards the KV loop over ICI.
- bf16-friendly: params live in fp32 (master weights in the optimizer),
  activations cast by amp.auto_cast outside.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed.fleet import topology as topo
from paddle_tpu.distributed.fleet.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    _constrain,
)
from paddle_tpu.models import kv_cache
from paddle_tpu.nn import initializer as I
from paddle_tpu.observability.step_profile import region
from paddle_tpu.nn.param_attr import ParamAttr
from paddle_tpu.ops.pallas.flash_attention import scaled_dot_product_attention

try:  # P only needed when a hybrid mesh is live
    from jax.sharding import PartitionSpec as P
except Exception:  # pragma: no cover
    P = None


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 0  # 0 -> 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    use_flash_attention: bool = True
    tie_word_embeddings: bool = True
    # sequence-parallel: constrain seq dim of activations over the sep axis
    sequence_parallel: bool = False
    # long-context: exact ring attention over the sep axis (KV blocks rotate
    # on the ICI ring; O(S/N) memory per chip) instead of letting GSPMD
    # all-gather the sharded KV
    use_ring_attention: bool = False
    # alternative sep strategy: Ulysses all-to-all (heads reshard over sep,
    # full-sequence flash per head group; needs num_heads % sep == 0)
    use_ulysses_attention: bool = False
    # activation recompute per decoder layer (reference: fleet recompute /
    # recompute_granularity): None/"" = off, "full" = drop everything,
    # any jax.checkpoint_policies name (e.g. "dots_saveable") = selective
    recompute: str | None = None

    def __post_init__(self):
        if not self.intermediate_size:
            self.intermediate_size = 4 * self.hidden_size


def gpt_tiny(**kw) -> "GPTConfig":
    """Small config for tests / compile checks."""
    cfg = dict(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
               max_position_embeddings=256)
    cfg.update(kw)
    return GPTConfig(**cfg)


def gpt3_1p3b(**kw) -> "GPTConfig":
    """GPT-3 1.3B — the Fleet hybrid-parallel benchmark config."""
    cfg = dict(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
               max_position_embeddings=2048)
    cfg.update(kw)
    return GPTConfig(**cfg)


def _attention(q, k, v, cfg, dropout_p=0.0, training=True):
    """Route to a sequence-parallel attention strategy when configured and
    a sep>1 mesh is live: ring (KV rotation, O(S/N) memory) or ulysses
    (all-to-all head resharding, full-S flash per head group)."""
    hcg = topo.get_hybrid_communicate_group()
    sep_live = hcg is not None and hcg.get_sep_parallel_world_size() > 1
    if sep_live and getattr(cfg, "use_ulysses_attention", False):
        from paddle_tpu.ops.ulysses_attention import ulysses_flash_attention

        return ulysses_flash_attention(q, k, v, causal=True,
                                       dropout=dropout_p, training=training)
    if sep_live and getattr(cfg, "use_ring_attention", False):
        from paddle_tpu.ops.ring_attention import ring_flash_attention

        return ring_flash_attention(q, k, v, dropout=dropout_p,
                                    causal=True, mesh=hcg.get_mesh(),
                                    training=training)
    return scaled_dot_product_attention(
        q, k, v, is_causal=True, dropout_p=dropout_p, training=training)


def _seq_constrain(x, cfg: GPTConfig):
    """Shard the sequence dim over the sep axis (segment parallel)."""
    if not cfg.sequence_parallel or P is None:
        return x
    hcg = topo.get_hybrid_communicate_group()
    if hcg is None or hcg.get_sep_parallel_world_size() <= 1:
        return x
    return _constrain(x, P("dp", "sep", *([None] * (x.ndim - 2))))


class GPTEmbeddings(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.word_embeddings = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range)),
        )
        self.position_embeddings = nn.Embedding(
            cfg.max_position_embeddings, cfg.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range)),
        )
        self.dropout = nn.Dropout(cfg.hidden_dropout)
        self._cfg = cfg

    def forward(self, input_ids, position_ids=None):
        if position_ids is None:
            seq_len = input_ids.shape[-1]
            if seq_len > self._cfg.max_position_embeddings:
                raise ValueError(
                    f"sequence length {seq_len} exceeds "
                    f"max_position_embeddings {self._cfg.max_position_embeddings}"
                )
            position_ids = paddle.arange(0, seq_len, dtype="int32")
        h = self.word_embeddings(input_ids) + self.position_embeddings(position_ids)
        return self.dropout(_seq_constrain(h, self._cfg))


class GPTAttention(nn.Layer):
    """Fused-QKV self attention; heads sharded over mp via the qkv column
    shard, contracted back by the row-parallel output projection."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.qkv_proj = ColumnParallelLinear(
            cfg.hidden_size, 3 * cfg.hidden_size, gather_output=False)
        self.out_proj = RowParallelLinear(
            cfg.hidden_size, cfg.hidden_size, input_is_parallel=True)
        self.attn_dropout_p = cfg.attention_dropout
        self._cfg = cfg

    def forward(self, hidden, cache=None):
        b, s, h = hidden.shape
        qkv = self.qkv_proj(hidden)  # [b, s, 3h] (mp-sharded last dim)
        qkv = paddle.reshape(qkv, [b, s, self.num_heads, 3 * self.head_dim])
        q, k, v = paddle.split(qkv, 3, axis=-1)  # [b, s, nh, hd] each
        if isinstance(cache, (kv_cache.StaticCacheSlot, kv_cache.PagedCacheSlot)):
            # serving path: static-shape cache write + length-masked attention
            # (one compiled program for every decode step)
            out, new_cache = kv_cache.cache_update_attend(q, k, v, cache)
            out = paddle.reshape(out, [b, s, h])
            return self.out_proj(out), new_cache
        new_cache = None
        if cache is not None:
            # incremental decode: prepend cached K/V; causality against the
            # full prefix comes from the unequal-length causal mask
            ck, cv = cache
            if ck is not None:
                k = paddle.concat([ck, k], axis=1)
                v = paddle.concat([cv, v], axis=1)
            new_cache = (k, v)
        out = _attention(q, k, v, self._cfg, self.attn_dropout_p, self.training)
        out = paddle.reshape(out, [b, s, h])
        out = self.out_proj(out)
        if cache is not None:
            return out, new_cache
        return out


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.fc_in = ColumnParallelLinear(
            cfg.hidden_size, cfg.intermediate_size, gather_output=False)
        self.fc_out = RowParallelLinear(
            cfg.intermediate_size, cfg.hidden_size, input_is_parallel=True)

    def forward(self, x):
        return self.fc_out(F.gelu(self.fc_in(x), approximate=True))


class GPTDecoderLayer(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.attn = GPTAttention(cfg)
        self.ln_2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.mlp = GPTMLP(cfg)
        self.dropout = nn.Dropout(cfg.hidden_dropout)
        self._cfg = cfg

    def forward(self, x, cache=None):
        # step_profile regions: ln/residual ride their sublayer's region
        # so the in-step attribution covers (nearly) every op the layer
        # emits — kv_gather nests inside attention and wins the leaf share
        with region("attention"):
            a = self.attn(self.ln_1(x), cache)
            new_cache = None
            if cache is not None:
                a, new_cache = a
            x = x + self.dropout(a)
        with region("mlp"):
            x = x + self.dropout(self.mlp(self.ln_2(x)))
            x = _seq_constrain(x, self._cfg)
        return (x, new_cache) if cache is not None else x


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.config = cfg
        self.embeddings = GPTEmbeddings(cfg)
        self.h = nn.LayerList([GPTDecoderLayer(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def forward(self, input_ids, position_ids=None, caches=None):
        with region("embed"):
            h = self.embeddings(input_ids, position_ids)
        new_caches = [] if caches is not None else None
        remat = self.config.recompute if (self.config.recompute
                                          and self.training
                                          and caches is None) else None
        for i, blk in enumerate(self.h):
            if caches is not None:
                h, nc = blk(h, caches[i])
                new_caches.append(nc)
            elif remat:
                from paddle_tpu.distributed.fleet.utils.recompute import (
                    recompute,
                )

                h = recompute(blk, h,
                              policy=None if remat == "full" else remat)
            else:
                h = blk(h)
        with region("logits"):
            h = self.ln_f(h)
        return (h, new_caches) if caches is not None else h


class GPTForCausalLM(nn.Layer):
    """LM head ties to the (vocab-sharded) embedding: logits stay mp-sharded
    into the parallel cross entropy (mp_layers.py:742 pattern)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(cfg)
        self.config = cfg
        if not cfg.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                cfg.hidden_size, cfg.vocab_size, has_bias=False,
                gather_output=False)

    def cache_geometry(self):
        """One class of layers: every layer caches the same KV heads and
        one width for K and V (``kv_cache.uniform_cache_geometry``)."""
        return kv_cache.uniform_cache_geometry(self.config)

    def forward(self, input_ids, position_ids=None, caches=None):
        if caches is not None:
            h, new_caches = self.gpt(input_ids, position_ids, caches)
        else:
            h = self.gpt(input_ids, position_ids)
        with region("logits"):
            if self.config.tie_word_embeddings:
                w = self.gpt.embeddings.word_embeddings.weight  # [V, H] mp-sharded on V
                logits = paddle.matmul(h, w, transpose_y=True)
            else:
                logits = self.lm_head(h)
        if caches is not None:
            return logits, new_caches
        return logits

    def loss_fused(self, input_ids, labels, position_ids=None,
                   num_chunks=8, ignore_index=-100):
        """Memory-efficient training loss: lm-head matmul + softmax-CE fused
        through the vocab-chunked online-logsumexp kernel — the [T, V]
        logits tensor (2.4 GB at bench shape) never materializes
        (incubate/nn/functional/fused_linear_ce.py). Tied-embedding models
        only (the chunked weight IS the embedding matrix)."""
        from paddle_tpu.core.dispatch import apply
        from paddle_tpu.incubate.nn.functional.fused_linear_ce import (
            fused_linear_cross_entropy,
        )

        assert self.config.tie_word_embeddings, "fused loss needs tied head"
        h = self.gpt(input_ids, position_ids)
        w = self.gpt.embeddings.word_embeddings.weight

        def f(hv, wv, lv):
            T = hv.shape[0] * hv.shape[1]
            return fused_linear_cross_entropy(
                hv.reshape(T, hv.shape[-1]), wv, lv.reshape(T),
                num_chunks, ignore_index)

        return apply("fused_linear_cross_entropy", f, h, w, labels)

    def hybrid_parallel_plan(self, mp_size, pp_axis="pp", mp_axis="mp"):
        """Stacked-parameter plan for the one-program dp x mp x pp Engine
        route (auto_parallel/hybrid.py; reference: static Engine +
        parallelizer_v2 composing all axes in one program)."""
        from paddle_tpu.distributed.auto_parallel.hybrid import GPTHybridPlan

        return GPTHybridPlan(self, mp_size, pp_axis, mp_axis)

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=0, eos_token_id=None, seed=None, on_token=None):
        from paddle_tpu.models.generation import greedy_or_sample

        return greedy_or_sample(self, input_ids, self.config.num_layers,
                                max_new_tokens, temperature, top_k,
                                eos_token_id, seed, on_token=on_token)


class GPTPretrainingCriterion(nn.Layer):
    """Next-token cross entropy over (possibly vocab-sharded) logits. GSPMD
    keeps the vocab shard through log-softmax; no explicit parallel CE
    needed.

    Fused formulation: logsumexp runs with f32 accumulators directly on the
    (bf16) logits, so the [tokens, vocab] f32 logits array the naive
    cast-then-CE materializes (~1.6 GB at GPT-2-small batch 8k tokens) never
    exists — XLA fuses the reductions into the logits matmul epilogue
    (+5% step throughput on chip)."""

    def __init__(self, cfg: GPTConfig | None = None):
        super().__init__()

    def forward(self, logits, labels, ignore_index: int = -100):
        from paddle_tpu.core.dispatch import apply

        def f(lg, lb):
            import jax
            import jax.numpy as jnp

            v = lg.shape[-1]
            lg2 = lg.reshape(-1, v)
            lb2 = lb.reshape(-1).astype(jnp.int32)
            valid = lb2 != ignore_index
            lb_safe = jnp.where(valid, lb2, 0)
            m = jax.lax.stop_gradient(jnp.max(lg2, axis=-1, keepdims=True))
            # subtract AFTER the f32 cast so the shift itself is exact
            shifted = lg2.astype(jnp.float32) - m.astype(jnp.float32)
            lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
            picked = jnp.take_along_axis(
                shifted, lb_safe[:, None], axis=-1)[:, 0]
            per_tok = jnp.where(valid, lse - picked, 0.0)
            return jnp.sum(per_tok) / jnp.maximum(
                jnp.sum(valid.astype(jnp.float32)), 1.0)

        return apply("softmax_cross_entropy_fused", f, logits, labels)
