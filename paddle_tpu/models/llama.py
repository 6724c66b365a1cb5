"""LLaMA decoder LM — second flagship (the reference's auto-parallel test
fixture semi_auto_llama.py / BASELINE.md #5 PaddleNLP LLaMA-2 pretrain).

RMSNorm + RoPE + SwiGLU + grouped-query attention, TP-sharded via the fleet
mp layers, flash attention through the Pallas kernel, optional sep-axis
sequence sharding for long context (same scheme as models/gpt.py)."""

from __future__ import annotations

from dataclasses import dataclass

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed.fleet.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from paddle_tpu.incubate.nn import functional as IF
from paddle_tpu.models import kv_cache
from paddle_tpu.models.gpt import (
    GPTPretrainingCriterion,
    _attention,
    _seq_constrain,
)
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.param_attr import ParamAttr
from paddle_tpu.ops.pallas.flash_attention import scaled_dot_product_attention


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_key_value_heads: int = 0  # 0 -> MHA (== num_heads)
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_base: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    sequence_parallel: bool = False
    use_ring_attention: bool = False

    def __post_init__(self):
        if not self.num_key_value_heads:
            self.num_key_value_heads = self.num_heads

    # gpt._seq_constrain reads this field name
    @property
    def hidden_dropout(self):
        return 0.0


def llama_tiny(**kw) -> LlamaConfig:
    cfg = dict(vocab_size=1024, hidden_size=128, intermediate_size=352,
               num_layers=2, num_heads=4, num_key_value_heads=2,
               max_position_embeddings=256)
    cfg.update(kw)
    return LlamaConfig(**cfg)


def llama2_7b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama2_13b(**kw) -> LlamaConfig:
    cfg = dict(hidden_size=5120, intermediate_size=13824, num_layers=40,
               num_heads=40)
    cfg.update(kw)
    return LlamaConfig(**cfg)


# nn.RMSNorm already implements the float32-upcast rsqrt normalization
LlamaRMSNorm = nn.RMSNorm


class LlamaAttention(nn.Layer):
    """GQA attention; q heads sharded over mp via column-parallel projection,
    kv heads repeated up to q heads post-RoPE."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.rope_base = cfg.rope_base
        q_size = cfg.num_heads * self.head_dim
        kv_size = cfg.num_key_value_heads * self.head_dim
        self.q_proj = ColumnParallelLinear(cfg.hidden_size, q_size,
                                           has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(cfg.hidden_size, kv_size,
                                           has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(cfg.hidden_size, kv_size,
                                           has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(q_size, cfg.hidden_size, has_bias=False,
                                        input_is_parallel=True)
        self._cfg = cfg

    def forward(self, hidden, position_ids=None, cache=None):
        b, s, _ = hidden.shape
        q = paddle.reshape(self.q_proj(hidden), [b, s, self.num_heads,
                                                 self.head_dim])
        k = paddle.reshape(self.k_proj(hidden), [b, s, self.num_kv_heads,
                                                 self.head_dim])
        v = paddle.reshape(self.v_proj(hidden), [b, s, self.num_kv_heads,
                                                 self.head_dim])
        q, k, _ = IF.fused_rotary_position_embedding(
            q, k, position_ids=position_ids, rotary_emb_base=self.rope_base)
        if isinstance(cache, (kv_cache.StaticCacheSlot, kv_cache.PagedCacheSlot)):
            # serving path: cache holds KV heads; GQA repeat happens inside
            # the masked-attention op
            out, new_cache = kv_cache.cache_update_attend(q, k, v, cache)
            out = paddle.reshape(out, [b, s, self.num_heads * self.head_dim])
            return self.o_proj(out), new_cache
        new_cache = None
        if cache is not None:
            # cached K/V are already rotated for their absolute positions
            ck, cv = cache
            if ck is not None:
                k = paddle.concat([ck, k], axis=1)
                v = paddle.concat([cv, v], axis=1)
            new_cache = (k, v)
        if self.num_kv_heads != self.num_heads:
            rep = self.num_heads // self.num_kv_heads
            k = paddle.repeat_interleave(k, rep, axis=2)
            v = paddle.repeat_interleave(v, rep, axis=2)
        out = _attention(q, k, v, self._cfg)
        out = paddle.reshape(out, [b, s, self.num_heads * self.head_dim])
        out = self.o_proj(out)
        if cache is not None:
            return out, new_cache
        return out


class LlamaMLP(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.gate_proj = ColumnParallelLinear(
            cfg.hidden_size, cfg.intermediate_size, has_bias=False,
            gather_output=False)
        self.up_proj = ColumnParallelLinear(
            cfg.hidden_size, cfg.intermediate_size, has_bias=False,
            gather_output=False)
        self.down_proj = RowParallelLinear(
            cfg.intermediate_size, cfg.hidden_size, has_bias=False,
            input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(IF.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = LlamaRMSNorm(cfg.hidden_size,
                                                     cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)
        self._cfg = cfg

    def forward(self, x, position_ids=None, cache=None):
        a = self.self_attn(self.input_layernorm(x), position_ids, cache)
        new_cache = None
        if cache is not None:
            a, new_cache = a
        x = x + a
        x = x + self.mlp(self.post_attention_layernorm(x))
        x = _seq_constrain(x, self._cfg)
        return (x, new_cache) if cache is not None else x


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range)),
        )
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(cfg) for _ in range(cfg.num_layers)])
        self.norm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, caches=None):
        if input_ids.shape[-1] > self.config.max_position_embeddings:
            raise ValueError(
                f"sequence length {input_ids.shape[-1]} exceeds "
                f"max_position_embeddings {self.config.max_position_embeddings}")
        h = _seq_constrain(self.embed_tokens(input_ids), self.config)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if caches is not None:
                h, nc = layer(h, position_ids, caches[i])
                new_caches.append(nc)
            else:
                h = layer(h, position_ids)
        h = self.norm(h)
        return (h, new_caches) if caches is not None else h


class LlamaForCausalLM(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.llama = LlamaModel(cfg)
        self.config = cfg
        if cfg.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(
                cfg.hidden_size, cfg.vocab_size, has_bias=False,
                gather_output=False)

    def cache_geometry(self):
        """One class of layers: every layer caches the same KV heads and
        one width for K and V (``kv_cache.uniform_cache_geometry``)."""
        return kv_cache.uniform_cache_geometry(self.config)

    def forward(self, input_ids, position_ids=None, caches=None):
        if caches is not None:
            h, new_caches = self.llama(input_ids, position_ids, caches)
        else:
            h = self.llama(input_ids, position_ids)
        if self.lm_head is None:
            w = self.llama.embed_tokens.weight
            logits = paddle.matmul(h, w, transpose_y=True)
        else:
            logits = self.lm_head(h)
        if caches is not None:
            return logits, new_caches
        return logits

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=0, eos_token_id=None, seed=None, on_token=None):
        from paddle_tpu.models.generation import greedy_or_sample

        return greedy_or_sample(self, input_ids, self.config.num_layers,
                                max_new_tokens, temperature, top_k,
                                eos_token_id, seed, on_token=on_token)

    def hybrid_parallel_plan(self, mp_size, pp_axis="pp", mp_axis="mp"):
        """One-program dp x mp x pp Engine route (BASELINE.md config #5:
        LLaMA-2 pretrain under auto_parallel; reference
        test/auto_parallel/semi_auto_llama.py)."""
        from paddle_tpu.distributed.auto_parallel.hybrid import (
            LlamaHybridPlan,
        )

        return LlamaHybridPlan(self, mp_size, pp_axis, mp_axis)


LlamaPretrainingCriterion = GPTPretrainingCriterion
