"""The benchmark's own copy of the plain reference of the MiMo-V2 decoder
(the program keeps its copy, ``paddle_tpu/models/mimo_v2_reference.py``, for
its tests; the benchmark imports nothing from it): float32
``jax.numpy`` under ``default_matmul_precision("highest")``, no cache, no
kernels, no batching, one sequence, one layer upcast at a time (one expert
at a time inside an expert layer).

It follows the public config's layer equations as ``models/mimo_v2.py``'s
docstring writes them down, with the same readings *assumed*: rotate-half
RoPE pairing on the first ``int(head_dim * partial_rotary_factor)``
dimensions, the value scale applied to the value states, the sink as one
more column of the softmax that carries no value, ``attention_chunk_size``
not a mask. Given the same ``experts_held`` it leaves out what the absent
experts would have added, as the model does, and the vocabulary is whatever
the weights hold.

``cfg`` is a dict under the public config's own key names
(``MiMoV2Config.to_dict()`` gives one). It reads the model's weights by
their ``state_dict`` names in whatever type they are stored. ``without``
switches single mechanisms off, for the tests that show each one matters:
``sink``, ``window``, ``value_scale``, ``partial_rope``, ``rope_bases``,
``correction_bias``; ``kv_dtype`` rounds K and V to the type a cache of
lower precision would hold them in (the reading that sets the limits'
upper side). ``routing = {"follow": [one [S, k] array of expert ids an
expert layer, in order] or None, "margin": m, "own": [], "report": []}``
appends this router's own choice of every expert layer to ``own`` and, given
choices to follow, follows one in the rows where it is this router's own
choice up to a tie: no expert it leaves out scores (score + bias, by this
router) more than ``m`` above one it holds. The served path in bfloat16
rounds a few choices across such a tie, which is no error, and the
comparison of logits should not charge it as one; in every other row this
router keeps its own choice, so that a wrong choice shows in the logits too.
``report`` gets, a layer, the share of rows that differ from this router's
own set, the share ``beyond`` the margin, the largest gap a followed or
refused row had to bridge, and what a router that ignored the correction
bias would read on the same scores.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, rotary_dim: int, base: float):
    """x [S, N, D] at positions 0 .. S-1: rotate-half over the first
    ``rotary_dim`` dimensions."""
    s = x.shape[0]
    inv = 1.0 / (base ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                          / rotary_dim))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]       # [S,1,r]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    return jnp.concatenate([rot * jnp.cos(ang) + turned * jnp.sin(ang), rest],
                           axis=-1)


def _attention(x, w, cfg: dict, layer: int, without: frozenset,
               kv_dtype=None):
    s = x.shape[0]
    window_layer = cfg["hybrid_layer_pattern"][layer] == 1
    heads, qk, vd = (cfg["num_attention_heads"], cfg["head_dim"],
                     cfg["v_head_dim"])
    kvh = (cfg["swa_num_key_value_heads"] if window_layer
           else cfg["num_key_value_heads"])
    h = _rms_norm(x, _f32(w["input_layernorm.weight"]),
                  cfg["layernorm_epsilon"])
    q = (h @ _f32(w["self_attn.q_proj.weight"])).reshape(s, heads, qk)
    k = (h @ _f32(w["self_attn.k_proj.weight"])).reshape(s, kvh, qk)
    v = (h @ _f32(w["self_attn.v_proj.weight"])).reshape(s, kvh, vd)
    rotary = (qk if "partial_rope" in without
              else int(qk * cfg["partial_rotary_factor"]))
    base = (cfg["swa_rope_theta"]
            if window_layer and "rope_bases" not in without
            else cfg["rope_theta"])
    q, k = _rope(q, rotary, base), _rope(k, rotary, base)
    if "value_scale" not in without:
        v = v * cfg["attention_value_scale"]
    if kv_dtype is not None:        # what a cache of that type would hold
        k = k.astype(kv_dtype).astype(jnp.float32)
        v = v.astype(kv_dtype).astype(jnp.float32)
    g = heads // kvh
    scores = jnp.einsum("qkgd,lkd->kgql", q.reshape(s, kvh, g, qk),
                        k) / math.sqrt(qk)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    visible = j <= i
    if window_layer and "window" not in without:
        visible &= i - j < cfg["sliding_window"]
    scores = jnp.where(visible, scores, -jnp.inf)
    has_sink = (cfg["add_swa_attention_sink_bias"] if window_layer
                else cfg["add_full_attention_sink_bias"])
    if has_sink and "sink" not in without:
        sink = jnp.broadcast_to(
            _f32(w["self_attn.sink"]).reshape(kvh, g, 1, 1), (kvh, g, s, 1))
        probs = jax.nn.softmax(jnp.concatenate([scores, sink], axis=-1),
                               axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("kgql,lkd->qkgd", probs, v).reshape(s, heads * vd)
    return x + out @ _f32(w["self_attn.o_proj.weight"])


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _same_set(a, b):
    """Rows of ``a, b [S, k]`` that are the same set."""
    return jnp.all(jnp.sort(a, -1) == jnp.sort(b, -1), -1)


def _gap(choose, held):
    """How far each row's set ``held [S, k]`` is from the top k of
    ``choose [S, E]``: the largest score left out less the smallest held
    (negative where ``held`` is the top k)."""
    inside = jnp.any(held[..., None] == jnp.arange(choose.shape[-1]), axis=-2)
    return (jnp.max(jnp.where(inside, -jnp.inf, choose), -1)
            - jnp.min(jnp.where(inside, choose, jnp.inf), -1))


def _experts(x, w, cfg: dict, without: frozenset, routing=None):
    h = _rms_norm(x, _f32(w["post_attention_layernorm.weight"]),
                  cfg["layernorm_epsilon"])
    scores = jax.nn.sigmoid(h @ _f32(w["mlp.router"]))            # [S, E]
    choose = scores
    if "correction_bias" not in without:
        choose = scores + _f32(w["mlp.e_score_correction_bias"])
    k = cfg["num_experts_per_tok"]
    _, chosen = jax.lax.top_k(choose, k)                          # [S, k]
    if routing is not None:
        routing["own"].append(chosen)
    if routing is not None and routing.get("follow") is not None:
        own, margin = chosen, routing["margin"]
        given = jnp.asarray(routing["follow"][len(routing["report"])],
                            jnp.int32)
        gap = _gap(choose, given)
        chosen = jnp.where((gap <= margin)[:, None], given, own)
        _, unbiased = jax.lax.top_k(scores, k)
        routing["report"].append({
            "differs": float(jnp.mean(~_same_set(given, own))),
            "beyond": float(jnp.mean(gap > margin)),
            "gap_max": float(jnp.max(gap)),
            "differs_without_bias": float(jnp.mean(~_same_set(unbiased,
                                                              own))),
            "beyond_without_bias": float(jnp.mean(
                _gap(choose, unbiased) > margin))})
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / picked.sum(axis=-1, keepdims=True)
    first, count = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    width = w["mlp.w_out"].shape[1]
    out = jnp.zeros_like(x)
    for e in range(count):
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        w_in = _f32(w["mlp.w_in"][e])
        out = out + w_e[:, None] * _swiglu(
            h, w_in[:, :width], w_in[:, width:], _f32(w["mlp.w_out"][e]))
    return x + out


def _dense(x, w, cfg: dict):
    h = _rms_norm(x, _f32(w["post_attention_layernorm.weight"]),
                  cfg["layernorm_epsilon"])
    return x + _swiglu(h, _f32(w["mlp.gate_proj.weight"]),
                       _f32(w["mlp.up_proj.weight"]),
                       _f32(w["mlp.down_proj.weight"]))


def hidden(weights: dict, ids, cfg: dict, without=(), kv_dtype=None,
           routing=None):
    """Final hidden states ``[S, H]`` (before the last RMSNorm) of one
    sequence of token ids ``[S]``."""
    without = frozenset(without)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision(HIGHEST):
        x = _f32(weights["model.embed_tokens.weight"][ids])
        for i in range(cfg["num_hidden_layers"]):
            prefix = f"model.layers.{i}."
            w = {k[len(prefix):]: v for k, v in weights.items()
                 if k.startswith(prefix)}
            x = _attention(x, w, cfg, i, without, kv_dtype)
            x = (_experts(x, w, cfg, without, routing)
                 if cfg["moe_layer_freq"][i]
                 else _dense(x, w, cfg))
    return x


def logits(weights: dict, ids, cfg: dict, last: int = 0, without=(),
           kv_dtype=None, routing=None):
    """Float32 logits ``[S, V]`` of one sequence, or of its ``last``
    positions only."""
    x = hidden(weights, ids, cfg, without, kv_dtype, routing)
    with jax.default_matmul_precision(HIGHEST):
        x = _rms_norm(x[-last:] if last else x,
                      _f32(weights["model.norm.weight"]),
                      cfg["layernorm_epsilon"])
        return x @ _f32(weights["lm_head.weight"])


def weights_of(model) -> dict:
    """The model's own arrays by ``state_dict`` name (no copy)."""
    return {k: v._value for k, v in model.state_dict().items()}
