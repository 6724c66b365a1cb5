"""Operations and bytes the MiMo-V2 configuration's decode step and its new
kernels need, from the configuration file's shapes alone (public config key
names). Kept with the benchmark so that a PR that claims a gain cannot move
them.

A decode step is bound by bytes: at 128 rows every product is far under the
ridge point, so the least time is the least bytes over the HBM bandwidth.
"""

from __future__ import annotations


def _layers(cfg: dict):
    n = cfg["num_hidden_layers"]
    return [(cfg["hybrid_layer_pattern"][i] == 1, cfg["moe_layer_freq"][i] == 1)
            for i in range(n)]


def kv_heads(cfg: dict, window_layer: bool) -> int:
    return (cfg["swa_num_key_value_heads"] if window_layer
            else cfg["num_key_value_heads"])


def kv_row_bytes(cfg: dict, window_layer: bool, cache_bytes: int) -> int:
    """Bytes of one position's K and V in one layer."""
    return (kv_heads(cfg, window_layer)
            * (cfg["head_dim"] + cfg["v_head_dim"]) * cache_bytes)


def attention_params(cfg: dict, window_layer: bool) -> int:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kvh = kv_heads(cfg, window_layer)
    return (h * heads * cfg["head_dim"] + h * kvh * cfg["head_dim"]
            + h * kvh * cfg["v_head_dim"] + heads * cfg["v_head_dim"] * h)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def experts_touched(cfg: dict, rows: float) -> float:
    """Held experts that at least one of ``rows`` tokens chooses, in
    expectation under a uniform router: each token picks
    ``num_experts_per_tok`` of ``n_routed_experts``."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["n_routed_experts"]
    return cfg["experts_held"] * (1.0 - miss ** max(rows, 0.0))


def expert_layer_bytes(cfg: dict, rows: float, weight_bytes: int) -> float:
    """Least bytes of one expert layer's two grouped products at ``rows``
    tokens: the weights of the held experts a token reaches, once."""
    return experts_touched(cfg, rows) * expert_params(cfg) * weight_bytes


def window_layer_kv_bytes(cfg: dict, rows: float, mean_len: float,
                          cache_bytes: int) -> float:
    """Least K/V bytes of one window layer's decode attention: each row's
    last ``sliding_window`` positions (fewer while the row is shorter)."""
    return (rows * min(mean_len, cfg["sliding_window"])
            * kv_row_bytes(cfg, True, cache_bytes))


def decode_step_min_bytes(cfg: dict, weight_bytes: int, cache_bytes: int,
                          rows: float, live_tokens: float) -> float:
    """Bytes an ideal decode step of ``rows`` occupied slots holding
    ``live_tokens`` positions must read from HBM: attention, router, dense
    MLP and output head weights once, the weights of the experts a step
    could touch, the full layers' live K/V and the window layers' K/V up to
    the window. Embedding rows, norms, writes and activations are small
    beside them."""
    h = cfg["hidden_size"]
    mean_len = live_tokens / rows if rows else 0.0
    total = cfg["vocab_size"] * h * weight_bytes             # output head
    for window_layer, moe_layer in _layers(cfg):
        total += attention_params(cfg, window_layer) * weight_bytes
        if moe_layer:
            total += h * cfg["n_routed_experts"] * weight_bytes   # router
            total += expert_layer_bytes(cfg, rows, weight_bytes)
        else:
            total += 3 * h * cfg["intermediate_size"] * weight_bytes
        if window_layer:
            total += window_layer_kv_bytes(cfg, rows, mean_len, cache_bytes)
        else:
            total += live_tokens * kv_row_bytes(cfg, False, cache_bytes)
    return total


def count_layers(cfg: dict, window: bool = None, moe: bool = None) -> int:
    return sum((window is None or w == window) and (moe is None or m == moe)
               for w, m in _layers(cfg))
