"""MiMo-V2 decoder LM (``model_type`` ``mimo_v2``; the MiMo-V2-Flash family,
https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json): window
and full attention layers with their own KV geometry, and sparse experts.

Layer ``l`` on ``x [S, H]``, as the public config gives it (readings the
config does not settle are marked *assumed*):

- ``h = RMSNorm(x)``; ``q = h Wq -> [S, heads, qk]``, ``k = h Wk -> [S, KVH,
  qk]``, ``v = h Wv -> [S, KVH, vd]``, no bias. ``KVH`` is
  ``num_key_value_heads`` where ``hybrid_layer_pattern[l] == 0`` (full
  attention) and ``swa_num_key_value_heads`` where it is 1 (sliding window).
- RoPE on the first ``int(qk * partial_rotary_factor)`` dimensions of q and
  k, the rest unrotated; base ``rope_theta`` in full layers,
  ``swa_rope_theta`` in window layers; rotate-half pairing (*assumed*).
- ``v <- attention_value_scale * v`` (*assumed*: applied to the value states
  before the weighted sum, so the cache holds scaled values).
- scores ``q_i k_j / sqrt(qk)`` within a group of ``heads / KVH`` query
  heads a KV head; ``j`` visible iff ``j <= i`` and, in a window layer,
  ``i - j < sliding_window``. Window layers have a learned sink logit a head
  (``add_swa_attention_sink_bias``) that joins the softmax's denominator and
  carries no value; full layers have none.
- ``x <- x + concat_h(sum_j p_ij v_j) Wo``.
- ``h2 = RMSNorm(x)``; where ``moe_layer_freq[l] == 0`` a dense SwiGLU MLP of
  ``intermediate_size``, else the dropless expert layer of ``nn/moe.py``
  (sigmoid scores, top ``num_experts_per_tok`` of score + correction bias,
  weights normalised over the chosen, ``routed_scaling_factor`` null = 1, no
  shared expert) over the experts this model holds (``experts_held``).
- final RMSNorm, untied output head.

Left out: the 3 multi-token-prediction layers and the vision and audio
encoders the family describes (not in this config; text only), and
``attention_chunk_size`` (*assumed* to name an implementation's blocking,
not a mask).

The serving contract is ``GPTForCausalLM``'s: ``model(ids, position_ids,
caches) -> (logits, new_caches)``. ``cache_geometry()`` tells the scheduler
and ``DecodeEngine`` what each layer caches.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Optional, Tuple

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.incubate.nn import functional as IF
from paddle_tpu.models import kv_cache
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn import moe
from paddle_tpu.nn.moe import DroplessMoE
from paddle_tpu.nn.param_attr import ParamAttr


@dataclass
class MiMoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    num_layers: int = 48
    num_heads: int = 64
    head_dim: int = 192
    v_head_dim: int = 128
    num_key_value_heads: int = 4
    swa_num_key_value_heads: int = 8
    # 0 = full attention, 1 = sliding window; one entry a layer
    hybrid_layer_pattern: Tuple[int, ...] = ()
    sliding_window: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    intermediate_size: int = 16384
    # 0 = dense MLP, 1 = expert layer; one entry a layer
    moe_layer_freq: Tuple[int, ...] = ()
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    # (first, count) of the experts this model holds; None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    max_position_embeddings: int = 1048576
    layernorm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    # parameters are created in this type (at the published widths a
    # float32 copy of them does not fit one chip)
    dtype: str = "float32"

    def __post_init__(self):
        n = self.num_layers
        period = (0, 1, 1, 1, 1, 1)
        if not self.hybrid_layer_pattern:
            # as published: full, window x 4, then full + window x 5 repeated
            self.hybrid_layer_pattern = tuple(
                ([0, 1, 1, 1, 1] + list(period) * n)[:n])
        if not self.moe_layer_freq:
            self.moe_layer_freq = tuple([0] + [1] * (n - 1))
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            got = tuple(int(v) for v in getattr(self, name))[:n]
            if len(got) != n:
                raise ValueError(f"{name} has {len(got)} entries for "
                                 f"{n} layers")
            setattr(self, name, got)
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)

    # the public config's key for each field that is named otherwise here
    _PUBLIC = {"num_layers": "num_hidden_layers",
               "num_heads": "num_attention_heads"}

    def to_dict(self) -> dict:
        """The fields under the public config's own key names."""
        return {self._PUBLIC.get(f.name, f.name): getattr(self, f.name)
                for f in fields(self)}

    @classmethod
    def from_public(cls, public: dict, **overrides) -> "MiMoV2Config":
        """From a dict under the public config's key names (keys this
        class has no field for are passed over)."""
        kw = {f.name: public[cls._PUBLIC.get(f.name, f.name)]
              for f in fields(cls)
              if cls._PUBLIC.get(f.name, f.name) in public}
        kw.update(overrides)
        return cls(**kw)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def is_window(self, layer: int) -> bool:
        return self.hybrid_layer_pattern[layer] == 1

    def kv_heads(self, layer: int) -> int:
        return (self.swa_num_key_value_heads if self.is_window(layer)
                else self.num_key_value_heads)


def mimo_v2_tiny(**kw) -> MiMoV2Config:
    """A CPU-test size with every mechanism of the family: one dense layer,
    then a period of window and full layers over experts; window 8, so that
    short prompts pass it."""
    cfg = dict(vocab_size=256, hidden_size=64, num_layers=4, num_heads=8,
               head_dim=24, v_head_dim=16, num_key_value_heads=2,
               swa_num_key_value_heads=4, hybrid_layer_pattern=(0, 1, 1, 0),
               sliding_window=8, intermediate_size=128,
               moe_intermediate_size=32, n_routed_experts=16,
               num_experts_per_tok=4, max_position_embeddings=512)
    cfg.update(kw)
    return MiMoV2Config(**cfg)


def _normal(cfg):
    return ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))


def _in_dtype(cfg, layer):
    """``layer`` with its parameters in the configuration's type."""
    return layer if cfg.dtype == "float32" else layer.to(dtype=cfg.dtype)


def _linear(cfg, n_in, n_out):
    return _in_dtype(cfg, nn.Linear(n_in, n_out, weight_attr=_normal(cfg),
                                    bias_attr=False))


def _norm(cfg):
    return _in_dtype(cfg, nn.RMSNorm(cfg.hidden_size, cfg.layernorm_epsilon))


def _partial_rope(q, k, position_ids, rotary_dim: int, base: float):
    """Rotate the first ``rotary_dim`` dimensions of ``q`` and ``k``
    ``[B, S, N, D]`` for the positions ``position_ids`` (``[S]`` or
    ``[B, S]``; None: 0 .. S-1), rotate-half pairing; the rest passes
    through."""
    q_rot, k_rot, _ = IF.fused_rotary_position_embedding(
        q[:, :, :, :rotary_dim], k[:, :, :, :rotary_dim],
        position_ids=position_ids, rotary_emb_base=base)
    return (paddle.concat([q_rot, q[:, :, :, rotary_dim:]], axis=-1),
            paddle.concat([k_rot, k[:, :, :, rotary_dim:]], axis=-1))


class MiMoV2Attention(nn.Layer):
    def __init__(self, cfg: MiMoV2Config, layer: int):
        super().__init__()
        self.num_heads, self.kv_heads = cfg.num_heads, cfg.kv_heads(layer)
        self.qk, self.vd = cfg.head_dim, cfg.v_head_dim
        self.window = cfg.sliding_window if cfg.is_window(layer) else None
        self.rope_base = (cfg.swa_rope_theta if self.window
                          else cfg.rope_theta)
        self.rotary_dim = cfg.rotary_dim
        self.value_scale = cfg.attention_value_scale
        h = cfg.hidden_size
        self.q_proj = _linear(cfg, h, self.num_heads * self.qk)
        self.k_proj = _linear(cfg, h, self.kv_heads * self.qk)
        self.v_proj = _linear(cfg, h, self.kv_heads * self.vd)
        self.o_proj = _linear(cfg, self.num_heads * self.vd, h)
        has_sink = (cfg.add_swa_attention_sink_bias if self.window
                    else cfg.add_full_attention_sink_bias)
        # seeded like a weight (a zero sink would hide a missing one)
        self.sink = (self.create_parameter([self.num_heads],
                                           attr=_normal(cfg), dtype="float32")
                     if has_sink else None)

    def forward(self, hidden, position_ids=None, cache=None):
        b, s, _ = hidden.shape
        q = self.q_proj(hidden).reshape([b, s, self.num_heads, self.qk])
        k = self.k_proj(hidden).reshape([b, s, self.kv_heads, self.qk])
        v = self.v_proj(hidden).reshape([b, s, self.kv_heads, self.vd])
        q, k = _partial_rope(q, k, position_ids, self.rotary_dim,
                             self.rope_base)
        v = v * self.value_scale
        if cache is not None:
            out, new_cache = kv_cache.cache_update_attend(
                q, k, v, cache, window=self.window, sink=self.sink)
        else:
            out, new_cache = kv_cache.causal_attention(
                q, k, v, window=self.window, sink=self.sink), None
        out = self.o_proj(out.reshape([b, s, self.num_heads * self.vd]))
        return out, new_cache


class MiMoV2MLP(nn.Layer):
    def __init__(self, cfg: MiMoV2Config):
        super().__init__()
        self.gate_proj = _linear(cfg, cfg.hidden_size, cfg.intermediate_size)
        self.up_proj = _linear(cfg, cfg.hidden_size, cfg.intermediate_size)
        self.down_proj = _linear(cfg, cfg.intermediate_size, cfg.hidden_size)

    def forward(self, x):
        return self.down_proj(IF.swiglu(self.gate_proj(x), self.up_proj(x)))


class MiMoV2DecoderLayer(nn.Layer):
    def __init__(self, cfg: MiMoV2Config, layer: int):
        super().__init__()
        self.input_layernorm = _norm(cfg)
        self.self_attn = MiMoV2Attention(cfg, layer)
        self.post_attention_layernorm = _norm(cfg)
        if cfg.moe_layer_freq[layer]:
            self.mlp = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                experts_held=cfg.experts_held,
                initializer_range=cfg.initializer_range, dtype=cfg.dtype)
        else:
            self.mlp = MiMoV2MLP(cfg)

    def forward(self, x, position_ids=None, cache=None):
        a, new_cache = self.self_attn(self.input_layernorm(x), position_ids,
                                      cache)
        x = x + a
        return x + self.mlp(self.post_attention_layernorm(x)), new_cache


class MiMoV2Model(nn.Layer):
    def __init__(self, cfg: MiMoV2Config):
        super().__init__()
        self.config = cfg
        self.embed_tokens = _in_dtype(cfg, nn.Embedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=_normal(cfg)))
        self.layers = nn.LayerList(
            [MiMoV2DecoderLayer(cfg, i) for i in range(cfg.num_layers)])
        self.norm = _norm(cfg)

    def forward(self, input_ids, position_ids=None, caches=None):
        h = self.embed_tokens(input_ids)
        new_caches = []
        for i, layer in enumerate(self.layers):
            h, nc = layer(h, position_ids,
                          None if caches is None else caches[i])
            new_caches.append(nc)
        return self.norm(h), new_caches


class MiMoV2ForCausalLM(nn.Layer):
    def __init__(self, cfg: MiMoV2Config):
        super().__init__()
        self.config = cfg
        self.model = MiMoV2Model(cfg)
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids, position_ids=None, caches=None):
        h, new_caches = self.model(input_ids, position_ids, caches)
        logits = self.lm_head(h)
        return (logits, new_caches) if caches is not None else logits

    def cache_geometry(self):
        """What each layer caches: window layers have their own KV heads
        and need the last ``sliding_window`` positions only. The KV heads do
        not fill a sublane tile, so the pools fold them into the row."""
        cfg = self.config
        return [kv_cache.LayerCacheGeometry(
            cfg.kv_heads(i), cfg.head_dim, cfg.v_head_dim,
            cfg.sliding_window if cfg.is_window(i) else None, True)
            for i in range(cfg.num_layers)]

    def step_stats(self):
        """``(names, traced f32 values)`` of the last forward, for the
        compiled serving step's telemetry block: pairs routed to held
        experts and the largest held expert's load, each a mean over the
        expert layers."""
        return moe.step_stats([l.mlp for l in self.model.layers
                               if isinstance(l.mlp, DroplessMoE)])

