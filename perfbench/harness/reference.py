"""Plain reference of the GPT decoder the cells run: float32 ``jax.numpy``,
``default_matmul_precision("highest")``, no cache, no kernels, no batching.

It follows GPT-2/GPT-3 (Radford et al. 2019; Brown et al. 2020): learned
position embeddings, pre-LayerNorm blocks, fused QKV with bias, causal
softmax attention scaled by 1/sqrt(head size), a 4x MLP with the tanh GELU,
a final LayerNorm and an output head tied to the token embedding. One
departure, taken from the program so that the same weights mean the same
function: the fused QKV output is laid out per head as [q | k | v]
(``models/gpt.py::GPTAttention``), not as three contiguous thirds.

It reads the model's own weights by their ``state_dict`` names, in whatever
type they are stored, and upcasts one layer at a time, so that at 1.3B it
fits beside a full KV pool.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_LAYER_KEYS = ("ln_1.weight", "ln_1.bias", "attn.qkv_proj.weight",
               "attn.qkv_proj.bias", "attn.out_proj.weight",
               "attn.out_proj.bias", "ln_2.weight", "ln_2.bias",
               "mlp.fc_in.weight", "mlp.fc_in.bias", "mlp.fc_out.weight",
               "mlp.fc_out.bias")


def _f32(x):
    return x.astype(jnp.float32)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


@functools.partial(jax.jit, static_argnames=("num_heads", "eps"))
def _block(x, p, num_heads: int, eps: float):
    (ln1w, ln1b, wqkv, bqkv, wo, bo, ln2w, ln2b,
     wfi, bfi, wfo, bfo) = (_f32(a) for a in p)
    s, h = x.shape
    hd = h // num_heads
    with jax.default_matmul_precision("highest"):
        qkv = _layer_norm(x, ln1w, ln1b, eps) @ wqkv + bqkv
        q, k, v = jnp.split(qkv.reshape(s, num_heads, 3 * hd), 3, axis=-1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), jnp.bool_))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h)
        x = x + attn @ wo + bo
        y = _layer_norm(x, ln2w, ln2b, eps) @ wfi + bfi
        return x + jax.nn.gelu(y, approximate=True) @ wfo + bfo


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, lnw, lnb, wte, eps: float):
    with jax.default_matmul_precision("highest"):
        return _layer_norm(x, _f32(lnw), _f32(lnb), eps) @ _f32(wte).T


def hidden(weights: dict, ids, num_layers: int, num_heads: int,
           eps: float):
    """Final hidden states ``[S, H]`` (before the last LayerNorm) of one
    sequence of token ids ``[S]``. ``weights`` maps the model's
    ``state_dict`` names to arrays."""
    ids = jnp.asarray(ids, jnp.int32)
    x = (_f32(weights["gpt.embeddings.word_embeddings.weight"][ids])
         + _f32(weights["gpt.embeddings.position_embeddings.weight"]
                [: ids.shape[0]]))
    for i in range(num_layers):
        x = _block(x, tuple(weights[f"gpt.h.{i}.{k}"] for k in _LAYER_KEYS),
                   num_heads=num_heads, eps=eps)
    return x


def logits(weights: dict, ids, num_layers: int, num_heads: int, eps: float,
           last: int = 0):
    """Float32 logits ``[S, V]`` of one sequence, or of its ``last``
    positions only."""
    x = hidden(weights, ids, num_layers, num_heads, eps)
    return _head(x[-last:] if last else x, weights["gpt.ln_f.weight"],
                 weights["gpt.ln_f.bias"],
                 weights["gpt.embeddings.word_embeddings.weight"], eps=eps)


def next_token_loss(weights: dict, ids, labels, num_layers: int,
                    num_heads: int, eps: float, chunk: int = 256) -> float:
    """Mean cross-entropy of ``labels`` ``[B, S]`` under the logits of
    ``ids`` ``[B, S]``: one sequence at a time, and the output head over
    ``chunk`` positions at a time, so that beside a train step's state it
    does not raise the process's peak memory."""
    total, count = 0.0, 0
    for row, lab in zip(ids, labels):
        x = hidden(weights, row, num_layers, num_heads, eps)
        for at in range(0, x.shape[0], chunk):
            lg = _head(x[at:at + chunk], weights["gpt.ln_f.weight"],
                       weights["gpt.ln_f.bias"],
                       weights["gpt.embeddings.word_embeddings.weight"],
                       eps=eps)
            picked = jnp.take_along_axis(
                lg, jnp.asarray(lab[at:at + chunk], jnp.int32)[:, None],
                axis=-1)[:, 0]
            total += float(jnp.sum(jax.nn.logsumexp(lg, axis=-1) - picked))
            count += lg.shape[0]
    return total / count


def weights_of(model) -> dict:
    """The model's own arrays by ``state_dict`` name (no copy)."""
    return {k: v._value for k, v in model.state_dict().items()}
