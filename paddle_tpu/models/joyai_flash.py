"""JoyAI-LLM-Flash decoder LM (``model_type`` ``joyai_llm_flash``, 48B-A2.7B;
https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json):
multi-head latent attention in every layer, a dense first layer, then sparse
experts beside one shared expert. DeepSeek-V3's block at other sizes.

Layer ``l`` on ``x [S, H]``, as the public config gives it (readings the
config does not settle are marked *assumed*):

- ``h = RMSNorm(x)``; ``c_q = RMSNorm(h W_qa)`` (``q_lora_rank``); ``q = c_q
  W_qb -> [S, heads, nope + rope]`` = ``(q_nope | q_rope)``. ``(c_kv | k_r) =
  h W_kva`` (``kv_lora_rank + rope``); ``c_kv = RMSNorm(c_kv)``. RoPE (base
  ``rope_theta``, ``rope_scaling`` null) on ``q_rope`` and on ``k_r``, which
  all heads share; interleaved pairs, as ``rope_interleave`` true says
  (*assumed*: dimensions ``2i, 2i + 1`` turn together; the scores are the
  same under any pairing applied to q and k alike).
- **What a layer caches is one row a token, ``(c_kv | RoPE(k_r))``**
  (``kv_lora_rank + rope`` wide), whatever the number of heads.
- Expanded form (a chunk of ``s > 1`` tokens, and the eager forward): ``(k_nope
  | v) = c_kv W_kvb -> [S, heads, nope + v]``; ``k = (k_nope | k_r)``; scores
  ``q . k / sqrt(nope + rope)``, causal softmax, ``. v``.
- Absorbed form (one token a row against the cache): ``q_lat[h] = q_nope[h]
  W_kvb,k[h]^T`` (``kv_lora_rank``); score ``(q_lat[h] . c_kv + q_rope[h] .
  k_r) / sqrt(nope + rope)``; ``o_lat[h] = softmax . c_kv``; ``o[h] =
  o_lat[h] W_kvb,v[h]``. The same mathematics: the K and V up-projections
  are moved from the cached positions onto the one query. Which form runs
  is decided from what the code can observe, ``s == 1`` with a cache.
- ``x <- x + concat_h(o[h]) W_o``.
- ``h2 = RMSNorm(x)``; in the first ``first_k_dense_replace`` layers a dense
  SwiGLU MLP of ``intermediate_size``, else ``nn/moe.py``'s dropless expert
  layer: sigmoid scores, top ``num_experts_per_tok`` of score + correction
  bias (``noaux_tc``; ``n_group`` 1, no group limit), weights normalised over
  the chosen times ``routed_scaling_factor``, over the experts this model
  holds (``experts_held``), plus ``n_shared_experts`` shared expert(s) as one
  SwiGLU of ``n_shared_experts * moe_intermediate_size`` over every token.
- final RMSNorm, untied output head.

``W_qb`` and ``W_kvb`` are stored as their parts (``q_b_nope_proj`` /
``q_b_rope_proj``; ``k_b_proj [heads, nope, kv_lora_rank]`` / ``v_b_proj
[heads, kv_lora_rank, v]``), so that neither form slices a weight in a step.

Left out: the multi-token-prediction block (``num_nextn_predict_layers`` 1).
It is no part of the next-token logits.

The serving contract is ``GPTForCausalLM``'s: ``model(ids, position_ids,
caches) -> (logits, new_caches)``. ``cache_geometry()`` tells the scheduler
and ``DecodeEngine`` that every layer caches one latent row a token.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, fields
from typing import Optional, Tuple

import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.core.dispatch import apply
from paddle_tpu.incubate.nn import functional as IF
from paddle_tpu.models import kv_cache
from paddle_tpu.models.mimo_v2 import MiMoV2MLP, _in_dtype, _linear, _normal
from paddle_tpu.nn import moe
from paddle_tpu.nn.moe import DroplessMoE
from paddle_tpu.observability.step_profile import region


@dataclass
class JoyAIFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_layers: int = 40
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    intermediate_size: int = 7168
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    # (first, count) of the routed experts this model holds; None = all
    experts_held: Optional[Tuple[int, int]] = None
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # parameters are created in this type
    dtype: str = "float32"

    def __post_init__(self):
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)

    # the public config's key for each field that is named otherwise here
    _PUBLIC = {"num_layers": "num_hidden_layers",
               "num_heads": "num_attention_heads"}
    # public keys this model has one reading of: another value is refused
    _ONLY = {"rope_scaling": None, "rope_interleave": True, "n_group": 1,
             "topk_group": 1, "topk_method": "noaux_tc",
             "scoring_func": "sigmoid", "norm_topk_prob": True,
             "moe_layer_freq": 1, "hidden_act": "silu",
             "attention_bias": False, "tie_word_embeddings": False}

    def to_dict(self) -> dict:
        """The fields under the public config's own key names."""
        return {self._PUBLIC.get(f.name, f.name): getattr(self, f.name)
                for f in fields(self)}

    @classmethod
    def from_public(cls, public: dict, **overrides) -> "JoyAIFlashConfig":
        """From a dict under the public config's key names (keys this
        class has no field for are passed over, but for those that would
        change the layer's equations: ``_ONLY``)."""
        for key, only in cls._ONLY.items():
            if key in public and public[key] != only:
                raise ValueError(f"{key} = {public[key]!r}: this model "
                                 f"implements {only!r} only")
        kw = {f.name: public[cls._PUBLIC.get(f.name, f.name)]
              for f in fields(cls)
              if cls._PUBLIC.get(f.name, f.name) in public}
        kw.update(overrides)
        return cls(**kw)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Width of the row a layer caches for one token."""
        return self.kv_lora_rank + self.qk_rope_head_dim


def joyai_flash_tiny(**kw) -> JoyAIFlashConfig:
    """A CPU-test size with every mechanism of the family: latent attention
    in each layer, one dense layer, then experts beside a shared one."""
    cfg = dict(vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
               q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
               moe_intermediate_size=32, n_routed_experts=16,
               num_experts_per_tok=4, max_position_embeddings=512)
    cfg.update(kw)
    return JoyAIFlashConfig(**cfg)


def _norm(cfg, width):
    return _in_dtype(cfg, nn.RMSNorm(width, cfg.rms_norm_eps))


def _absorb_raw(q_nope, k_b):
    """``q_lat [B,s,N,C]``: the K up-projection of head ``n`` folded into
    its query, ``q_nope [B,s,N,d] x k_b [N,d,C]``."""
    with region("mla_absorb"):
        return jnp.einsum("bsnd,ndc->bsnc", q_nope, k_b)


def _unabsorb_raw(o_lat, v_b):
    """``o [B,s,N,v]``: the V up-projection on each head's weighted sum of
    latent rows, ``o_lat [B,s,N,C] x v_b [N,C,v]``."""
    with region("mla_absorb"):
        return jnp.einsum("bsnc,ncv->bsnv", o_lat, v_b)


def _expand_raw(c_kv, k_r, k_b, v_b):
    """``(k [B,s,N,d+r], v [B,s,N,v])`` of a chunk's own latent rows:
    ``c_kv [B,s,C]`` through both up-projections, and the rotary key
    ``k_r [B,s,1,r]`` that all heads share beside each head's ``k_nope``."""
    with region("mla_expand"):
        k_nope = jnp.einsum("bsc,ndc->bsnd", c_kv, k_b)
        v = jnp.einsum("bsc,ncv->bsnv", c_kv, v_b)
        k_r = jnp.broadcast_to(k_r, k_nope.shape[:3] + k_r.shape[3:])
        return jnp.concatenate([k_nope, k_r], axis=-1), v


class JoyAIFlashAttention(nn.Layer):
    def __init__(self, cfg: JoyAIFlashConfig):
        super().__init__()
        self.num_heads = n = cfg.num_heads
        self.nope, self.rope, self.vd = (cfg.qk_nope_head_dim,
                                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        self.latent = cfg.kv_lora_rank
        self.rope_base = float(cfg.rope_theta)
        self.scale = 1.0 / math.sqrt(cfg.qk_head_dim)
        h = cfg.hidden_size
        self.q_a_proj = _linear(cfg, h, cfg.q_lora_rank)
        self.q_a_layernorm = _norm(cfg, cfg.q_lora_rank)
        self.q_b_nope_proj = _linear(cfg, cfg.q_lora_rank, n * self.nope)
        self.q_b_rope_proj = _linear(cfg, cfg.q_lora_rank, n * self.rope)
        self.kv_a_proj_with_mqa = _linear(cfg, h, cfg.latent_dim)
        self.kv_a_layernorm = _norm(cfg, self.latent)
        dtype = None if cfg.dtype == "float32" else cfg.dtype
        self.k_b_proj = self.create_parameter(
            [n, self.nope, self.latent], attr=_normal(cfg), dtype=dtype)
        self.v_b_proj = self.create_parameter(
            [n, self.latent, self.vd], attr=_normal(cfg), dtype=dtype)
        self.o_proj = _linear(cfg, n * self.vd, h)

    def forward(self, hidden, position_ids=None, cache=None):
        b, s, _ = hidden.shape
        n = self.num_heads
        c_q = self.q_a_layernorm(self.q_a_proj(hidden))
        q_nope = self.q_b_nope_proj(c_q).reshape([b, s, n, self.nope])
        q_rope = self.q_b_rope_proj(c_q).reshape([b, s, n, self.rope])
        kv = self.kv_a_proj_with_mqa(hidden)
        c_kv = self.kv_a_layernorm(kv[:, :, :self.latent])
        k_r = kv[:, :, self.latent:].reshape([b, s, 1, self.rope])
        q_rope, k_r, _ = IF.fused_rotary_position_embedding(
            q_rope, k_r, position_ids=position_ids,
            use_neox_rotary_style=False, rotary_emb_base=self.rope_base)
        # what the layer caches of these tokens
        rows = paddle.concat([c_kv, k_r.reshape([b, s, self.rope])], axis=-1)
        if cache is not None and s == 1:
            q_lat = apply("mla_absorb", _absorb_raw, q_nope, self.k_b_proj)
            o_lat, new_cache = kv_cache.latent_cache_update_attend(
                paddle.concat([q_lat, q_rope], axis=-1), rows, cache,
                v_dim=self.latent, scale=self.scale)
            out = apply("mla_unabsorb", _unabsorb_raw, o_lat, self.v_b_proj)
        else:
            new_cache = (None if cache is None
                         else kv_cache.latent_cache_write(rows, cache))
            k, v = apply("mla_expand", _expand_raw, c_kv, k_r, self.k_b_proj,
                         self.v_b_proj)
            out = kv_cache.causal_attention(
                paddle.concat([q_nope, q_rope], axis=-1), k, v)
        return self.o_proj(out.reshape([b, s, n * self.vd])), new_cache


class JoyAIFlashDecoderLayer(nn.Layer):
    def __init__(self, cfg: JoyAIFlashConfig, layer: int):
        super().__init__()
        self.input_layernorm = _norm(cfg, cfg.hidden_size)
        self.self_attn = JoyAIFlashAttention(cfg)
        self.post_attention_layernorm = _norm(cfg, cfg.hidden_size)
        if layer >= cfg.first_k_dense_replace:
            self.mlp = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                experts_held=cfg.experts_held,
                initializer_range=cfg.initializer_range, dtype=cfg.dtype,
                routed_scaling_factor=cfg.routed_scaling_factor,
                shared_width=cfg.n_shared_experts * cfg.moe_intermediate_size)
        else:
            self.mlp = MiMoV2MLP(cfg)      # the same dense SwiGLU

    def forward(self, x, position_ids=None, cache=None):
        a, new_cache = self.self_attn(self.input_layernorm(x), position_ids,
                                      cache)
        x = x + a
        return x + self.mlp(self.post_attention_layernorm(x)), new_cache


class JoyAIFlashModel(nn.Layer):
    def __init__(self, cfg: JoyAIFlashConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = _in_dtype(cfg, nn.Embedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=_normal(cfg)))
        self.layers = nn.LayerList(
            [JoyAIFlashDecoderLayer(cfg, i) for i in range(cfg.num_layers)])
        self.norm = _norm(cfg, cfg.hidden_size)

    def forward(self, input_ids, position_ids=None, caches=None):
        h = self.embed_tokens(input_ids)
        new_caches = []
        for i, layer in enumerate(self.layers):
            h, nc = layer(h, position_ids,
                          None if caches is None else caches[i])
            new_caches.append(nc)
        return self.norm(h), new_caches


class JoyAIFlashForCausalLM(nn.Layer):
    def __init__(self, cfg: JoyAIFlashConfig):
        super().__init__()
        self.config = cfg
        self.model = JoyAIFlashModel(cfg)
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids, position_ids=None, caches=None):
        h, new_caches = self.model(input_ids, position_ids, caches)
        logits = self.lm_head(h)
        return (logits, new_caches) if caches is not None else logits

    def cache_geometry(self):
        """Every layer caches one latent row a token that all heads read:
        ``kv_lora_rank + qk_rope_head_dim`` wide, its first ``kv_lora_rank``
        lanes the position's value."""
        cfg = self.config
        return [kv_cache.LayerCacheGeometry(
            1, cfg.latent_dim, cfg.kv_lora_rank, latent=True)
            for _ in range(cfg.num_layers)]

    def step_stats(self):
        """``(names, traced f32 values)`` of the last forward, for the
        compiled serving step's telemetry block: pairs routed to held
        experts and the largest held expert's load, each a mean over the
        expert layers."""
        return moe.step_stats([l.mlp for l in self.model.layers
                               if isinstance(l.mlp, DroplessMoE)])
