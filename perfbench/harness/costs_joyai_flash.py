"""Operations and bytes the JoyAI-LLM-Flash configuration's decode step and
its new kernel need, from the configuration file's shapes alone (public
config key names). Kept with the benchmark so that a PR that claims a gain
cannot move them.

A decode step's weights are bound by bytes (at 128 rows every product is far
under the ridge point). The latent decode kernel is not so by nature: a
cached position costs ``2 * heads * (row + kv_lora_rank)`` operations against
``row`` elements read, 60 FLOP/B in bfloat16 at 32 heads, a quarter of a
v5e's ridge (197e12 / 819e9 = 240): its least time is still its bytes', and
its roofline is counted in bytes, but only a kernel that keeps the MXU at a
quarter of its peak reaches it.
"""

from __future__ import annotations

# the expert layer is the one MiMo-V2's configuration has (the same keys):
# an expert's parameters, the held experts a step's rows reach under a
# uniform router, and their weights' bytes
from perfbench.harness.costs_mimo_v2 import (  # noqa: F401
    expert_layer_bytes,
    expert_params,
    experts_touched,
)


def latent_row_bytes(cfg: dict, cache_bytes: int) -> int:
    """Bytes of one position's latent row in one layer as published:
    ``(c_kv | RoPE(k_r))``, whatever padding an implementation allocates."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * cache_bytes


def latent_flops_per_position(cfg: dict) -> int:
    """Operations the absorbed form needs for one cached position in one
    layer: every head's score over the row and its weighted sum over the
    row's first ``kv_lora_rank`` lanes."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return 2 * cfg["num_attention_heads"] * (row + cfg["kv_lora_rank"])


def attention_params(cfg: dict) -> int:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q, kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (h * q + q * heads * (nope + rope) + h * (kv + rope)
            + kv * heads * (nope + v) + heads * v * h)


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def latent_bytes(cfg: dict, live_tokens: float, cache_bytes: int) -> float:
    """Least bytes of one layer's latent decode attention: every live
    position's published row, once."""
    return live_tokens * latent_row_bytes(cfg, cache_bytes)


def decode_step_min_bytes(cfg: dict, weight_bytes: int, cache_bytes: int,
                          rows: float, live_tokens: float) -> float:
    """Bytes an ideal decode step of ``rows`` occupied slots holding
    ``live_tokens`` positions must read from HBM: attention, router, shared
    expert, dense MLP and output head weights once, the weights of the held
    experts a step could touch, and every layer's live latent rows.
    Embedding rows, norms, writes and activations are small beside them."""
    h = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    moe = expert_layers(cfg)
    shared = cfg["n_shared_experts"] * expert_params(cfg)
    weights = (cfg["vocab_size"] * h                         # output head
               + cfg["num_hidden_layers"] * attention_params(cfg)
               + dense * 3 * h * cfg["intermediate_size"]
               + moe * (h * cfg["n_routed_experts"] + shared))
    return (weights * weight_bytes
            + moe * expert_layer_bytes(cfg, rows, weight_bytes)
            + cfg["num_hidden_layers"]
            * latent_bytes(cfg, live_tokens, cache_bytes))
