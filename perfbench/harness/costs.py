"""Operations and bytes an algorithm needs, from shapes alone. Kept with
the benchmark so that a PR that claims a gain cannot move them."""

from __future__ import annotations


def param_count(cfg: dict) -> int:
    """Parameters of the GPT decoder in the configuration file ``cfg``
    (tied output head counted once)."""
    h, L = cfg["hidden_size"], cfg["num_layers"]
    ffn = cfg.get("intermediate_size") or 4 * h
    per_layer = (2 * h + 2 * h                 # two LayerNorms
                 + h * 3 * h + 3 * h           # fused QKV
                 + h * h + h                   # attention output
                 + h * ffn + ffn + ffn * h + h)
    return (cfg["vocab_size"] * h + cfg["max_position_embeddings"] * h
            + L * per_layer + 2 * h)


def train_model_flops_per_token(cfg: dict) -> float:
    """6 N: forward and backward matmul FLOPs a token requires (Kaplan et
    al. 2020), attention's quadratic part and recomputation not counted —
    the convention of ``bench.py::bench_gpt3_1p3b``."""
    return 6.0 * param_count(cfg)


def kv_bytes_per_token(cfg: dict, cache_bytes: int) -> int:
    return cfg["num_layers"] * 2 * cfg["hidden_size"] * cache_bytes


def decode_step_min_bytes(cfg: dict, weight_bytes: int, cache_bytes: int,
                          live_tokens: int) -> float:
    """Bytes an ideal decode step must read from HBM: every weight once
    (the position table excepted: one row to a slot) and the live K and V of
    the occupied slots. Writes and activations are small beside them."""
    weights = (param_count(cfg)
               - cfg["max_position_embeddings"] * cfg["hidden_size"])
    return (weights * weight_bytes
            + live_tokens * kv_bytes_per_token(cfg, cache_bytes))


def flash_flops(batch: int, heads: int, seq: int, head_dim: int,
                causal: bool = True) -> dict:
    """Matmul FLOPs of one flash-attention call by pass: forward has two
    ``S x S x D`` products a head (QK^T, PV), backward five (recomputed
    QK^T, dV, dP, dQ, dK); a causal mask needs half of each."""
    one = 2.0 * batch * heads * seq * seq * head_dim * (0.5 if causal else 1)
    return {"fwd": 2 * one, "bwd": 5 * one}
