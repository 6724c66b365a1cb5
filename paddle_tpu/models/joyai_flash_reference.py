"""Plain reference of the JoyAI-LLM-Flash decoder (``models/joyai_flash.py``):
float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, the
expanded form of latent attention only, no cache, no kernels, no batching,
one sequence, one layer upcast at a time (one expert at a time inside an
expert layer).

It follows the public config's layer equations as ``models/joyai_flash.py``'s
docstring writes them down, with the same reading *assumed*: RoPE on
interleaved pairs. Given the same ``experts_held`` it leaves out what the
absent routed experts would have added, as the model does; the shared expert
is whole; the vocabulary is whatever the weights hold.

``cfg`` is a dict under the public config's own key names
(``JoyAIFlashConfig.to_dict()`` gives one). It reads the model's weights by
their ``state_dict`` names in whatever type they are stored. ``without``
switches single mechanisms off, for the tests and controls that show each
one matters: ``rope_score`` (the rotary part of the score left out),
``k_rotation`` (``k_r`` cached unrotated), ``shared_expert``,
``routed_scaling`` (factor 1.0), ``correction_bias``, ``softmax_scale``
(``1 / sqrt(qk_nope_head_dim)``); ``kv_dtype`` rounds the latent row ``(c_kv
| RoPE(k_r))`` to the type a cache of lower precision would hold it in (the
reading that sets the limits' upper side). ``routing = {"follow": [one [S,
k] array of expert ids an expert layer, in order] or None, "margin": m,
"own": [], "report": []}`` appends this router's own choice of every expert
layer to ``own`` and, given choices to follow, follows one in the rows where
it is this router's own choice up to a tie: no expert it leaves out scores
(score + bias, by this router) more than ``m`` above one it holds. The
served path in bfloat16 rounds a few choices across such a tie, which is no
error, and the comparison of logits should not charge it as one; in every
other row this router keeps its own choice, so that a wrong choice shows in
the logits too. ``report`` gets, a layer, the share of rows that differ from
this router's own set, the share ``beyond`` the margin, the largest gap a
followed or refused row had to bridge, and what a router that ignored the
correction bias would read on the same scores.
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


def _rope(x, base: float):
    """x [S, N, r] at positions 0 .. S-1: dimensions 2i and 2i + 1 turn
    together by the angle ``pos / base ** (2i / r)``."""
    s, _, r = x.shape
    inv = 1.0 / (base ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :])[:, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _attention(x, w, cfg: dict, without: frozenset, kv_dtype=None):
    s = x.shape[0]
    heads, nope, rope = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    h = _rms_norm(x, _f32(w["input_layernorm.weight"]), eps)
    c_q = _rms_norm(h @ _f32(w["self_attn.q_a_proj.weight"]),
                    _f32(w["self_attn.q_a_layernorm.weight"]), eps)
    q_nope = (c_q @ _f32(w["self_attn.q_b_nope_proj.weight"])).reshape(
        s, heads, nope)
    q_rope = _rope((c_q @ _f32(w["self_attn.q_b_rope_proj.weight"])).reshape(
        s, heads, rope), cfg["rope_theta"])
    kv = h @ _f32(w["self_attn.kv_a_proj_with_mqa.weight"])
    c_kv = _rms_norm(kv[:, :rank], _f32(w["self_attn.kv_a_layernorm.weight"]),
                     eps)
    k_r = kv[:, rank:].reshape(s, 1, rope)
    if "k_rotation" not in without:
        k_r = _rope(k_r, cfg["rope_theta"])
    if kv_dtype is not None:        # what a cache of that type would hold
        c_kv = c_kv.astype(kv_dtype).astype(jnp.float32)
        k_r = k_r.astype(kv_dtype).astype(jnp.float32)
    k_nope = jnp.einsum("sc,ndc->snd", c_kv, _f32(w["self_attn.k_b_proj"]))
    v = jnp.einsum("sc,ncv->snv", c_kv, _f32(w["self_attn.v_b_proj"]))
    scores = jnp.einsum("qnd,lnd->nql", q_nope, k_nope)
    if "rope_score" not in without:
        scores = scores + jnp.einsum("qnr,lr->nql", q_rope, k_r[:, 0])
    scores = scores / math.sqrt(nope if "softmax_scale" in without
                                else nope + rope)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("nql,lnv->qnv", probs, v).reshape(s, -1)
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
                  cfg["rms_norm_eps"])
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
    if "routed_scaling" not in without:
        weights = weights * cfg["routed_scaling_factor"]
    first, count = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    width = w["mlp.w_out"].shape[1]
    out = jnp.zeros_like(x)
    for e in range(count):
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        w_in = _f32(w["mlp.w_in"][e])
        out = out + w_e[:, None] * _swiglu(
            h, w_in[:, :width], w_in[:, width:], _f32(w["mlp.w_out"][e]))
    if cfg["n_shared_experts"] and "shared_expert" not in without:
        out = out + _swiglu(h, _f32(w["mlp.shared_gate"]),
                            _f32(w["mlp.shared_up"]),
                            _f32(w["mlp.shared_down"]))
    return x + out


def _dense(x, w, cfg: dict):
    h = _rms_norm(x, _f32(w["post_attention_layernorm.weight"]),
                  cfg["rms_norm_eps"])
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
            x = _attention(x, w, cfg, without, kv_dtype)
            x = (_dense(x, w, cfg) if i < cfg["first_k_dense_replace"]
                 else _experts(x, w, cfg, without, routing))
    return x


def logits(weights: dict, ids, cfg: dict, last: int = 0, without=(),
           kv_dtype=None, routing=None):
    """Float32 logits ``[S, V]`` of one sequence, or of its ``last``
    positions only."""
    x = hidden(weights, ids, cfg, without, kv_dtype, routing)
    with jax.default_matmul_precision(HIGHEST):
        x = _rms_norm(x[-last:] if last else x,
                      _f32(weights["model.norm.weight"]),
                      cfg["rms_norm_eps"])
        return x @ _f32(weights["lm_head.weight"])


def weights_of(model) -> dict:
    """The model's own arrays by ``state_dict`` name (no copy)."""
    return {k: v._value for k, v in model.state_dict().items()}
