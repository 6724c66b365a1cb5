"""Flash attention (parity: phi/kernels/gpu/flash_attn_kernel.cu +
python/paddle/nn/functional/flash_attention.py:147).

TPU-native: a Pallas fused kernel (written against the MXU/VMEM model) on a
TPU at block-divisible shapes; an XLA-fused jnp formulation (one fused
computation, also the test reference) wherever the gate does not select the
kernel. The gate decides once, from platform and shape: a kernel that was
selected and fails raises. Layout is paddle's
[batch, seqlen, num_heads, head_dim].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from paddle_tpu.core.dispatch import apply
from paddle_tpu.framework import random as rng
from paddle_tpu.tensor import Tensor


# toggled by FLAGS_use_flash_attention (framework/flags.py)
_FLASH_ENABLED = True

# evidence trail: "pallas" | "splash" | "xla" | "xla-traced-cu" — set on
# every trace so tests/bench can assert which path the gate selected
_last_path = None
_warned_traced_cu = False
_interpret = False   # tests force the splash kernel through the interpreter


def _dropout(x, p, training):
    """Inverted dropout (shared by every attention path)."""
    if p <= 0.0 or not training:
        return x
    keep = jax.random.bernoulli(rng.next_key(), 1.0 - p, x.shape)
    return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)


def _use_pallas(q_shape, head_dim) -> bool:
    if not _FLASH_ENABLED:
        return False
    from paddle_tpu.device import is_tpu

    if not is_tpu():
        return False
    # block-divisibility: seq multiples of 128, head_dim multiple of 128 not
    # required (we pad head_dim inside the kernel wrapper if needed)
    b, s, h, d = q_shape
    return s % 128 == 0 and d in (64, 128, 256)


def _attention_reference(q, k, v, bias, causal, scale):
    """XLA-fused reference attention. q,k,v: [B, S, H, D]."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def flash_attention_fwd(q, k, v, bias=None, causal=False, scale=None):
    """Raw jax-level flash attention entry (arrays in, array out)."""
    global _last_path
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if _use_pallas(q.shape, q.shape[-1]):
        from paddle_tpu.ops.pallas import flash_attention_tpu as ker

        _last_path = "pallas"
        return ker.flash_attention(q, k, v, bias=bias, causal=causal,
                                   scale=scale)
    _last_path = "xla"
    return _attention_reference(q, k, v, bias, causal, scale)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None):
    """Tensor-level API used by nn.functional (paddle signature)."""
    scale = 1.0 / math.sqrt(query.shape[-1])

    def f(q, k, v, *rest):
        bias = rest[0] if rest else None
        if bias is not None and bias.dtype == jnp.bool_:
            bias = jnp.where(bias, 0.0, -jnp.inf).astype(jnp.float32)
        out = flash_attention_fwd(q, k, v, bias=bias, causal=is_causal, scale=scale)
        return _dropout(out, dropout_p, training)

    args = [query, key, value]
    if attn_mask is not None:
        args.append(attn_mask)
    return apply("scaled_dot_product_attention", f, *args)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    out = scaled_dot_product_attention(
        query, key, value, attn_mask=None, dropout_p=dropout, is_causal=causal,
        training=training,
    )
    if return_softmax:
        return out, None
    return out, None


def _use_splash_varlen(tq, tk, d) -> bool:
    """Gate for the Pallas SPLASH kernel on the varlen path: a TPU,
    self-attention packing (tq == tk), block-divisible total length,
    MXU-friendly head dim."""
    if not _FLASH_ENABLED:
        return False
    from paddle_tpu.device import is_tpu

    return (is_tpu() and tq == tk and tq % 128 == 0
            and d in (64, 128, 256))


def _splash_varlen(q, k, v, seg_q, seg_k, causal, scale):
    """Segment-masked packed attention via the Pallas splash kernel
    (block-sparse: fully-masked blocks are never computed — the real
    upgrade over the dense [T, T] mask). q/k/v: [T, H, D]."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sk,
        splash_attention_mask as _sm,
    )

    T, H, D = q.shape
    mask_cls = _sm.CausalMask if causal else _sm.FullMask
    mask = _sm.MultiHeadMask([mask_cls((T, T)) for _ in range(H)])
    kernel = _sk.make_splash_mha_single_device(mask, interpret=_interpret)
    seg = _sk.SegmentIds(q=seg_q.astype(jnp.int32),
                         kv=seg_k.astype(jnp.int32))
    # splash computes softmax(q @ k^T) with segment/causal masking and NO
    # internal scale knob on this entry: fold the scale into q
    qh = jnp.swapaxes(q, 0, 1).astype(jnp.float32) * scale
    kh = jnp.swapaxes(k, 0, 1).astype(jnp.float32)
    vh = jnp.swapaxes(v, 0, 1).astype(jnp.float32)
    out = kernel(qh, kh, vh, segment_ids=seg)  # [H, T, D]
    return jnp.swapaxes(out, 0, 1).astype(q.dtype)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q=None, max_seqlen_k=None, scale=None,
                        dropout=0.0, causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen (packed) attention (parity:
    python/paddle/nn/functional/flash_attention.py:455 flash_attn_unpadded,
    kernel phi/kernels/gpu/flash_attn_kernel.cu varlen path).

    ``query/key/value``: [total_tokens, num_heads, head_dim] — sequences
    packed back-to-back; ``cu_seqlens_*``: [batch+1] int32 cumulative
    lengths. Attention is segment-masked so tokens only attend within
    their own sequence. On a TPU with self-attention packing the Pallas
    SPLASH kernel runs it block-sparsely (masked blocks skipped); elsewhere
    the gate selects an XLA-fused dense-mask formulation (also the decode
    path, whose causal convention aligns unequal q/k packings to sequence
    ends)."""
    if scale is None:
        scale = 1.0 / math.sqrt(query.shape[-1])
    # splash needs PROVABLY identical q/k packings (its CausalMask is
    # absolute-position; the end-aligned decode convention is dense-only).
    # Concrete cu tensors compare by value host-side (tiny arrays); traced
    # ones fall back to object identity.
    same_packing = cu_seqlens_q is cu_seqlens_k
    traced_cu = False
    if not same_packing:
        try:
            import numpy as _np

            a = cu_seqlens_q._value if isinstance(cu_seqlens_q, Tensor) \
                else cu_seqlens_q
            b = cu_seqlens_k._value if isinstance(cu_seqlens_k, Tensor) \
                else cu_seqlens_k
            if not (isinstance(a, jax.core.Tracer)
                    or isinstance(b, jax.core.Tracer)):
                same_packing = (a.shape == b.shape
                                and bool(_np.array_equal(_np.asarray(a),
                                                         _np.asarray(b))))
            else:
                traced_cu = True
        except Exception:
            same_packing = False

    def f(q, k, v, cu_q, cu_k):
        tq = q.shape[0]
        tk = k.shape[0]
        # segment id per token: index of the sequence it belongs to
        seg_q = jnp.searchsorted(cu_q, jnp.arange(tq), side="right") - 1
        seg_k = jnp.searchsorted(cu_k, jnp.arange(tk), side="right") - 1
        global _last_path
        splash_eligible = (_use_splash_varlen(tq, tk, q.shape[-1])
                           and not (dropout > 0.0 and training))
        if splash_eligible and same_packing:
            # same_packing: splash's CausalMask is absolute-position; the
            # end-aligned decode convention (cu_q != cu_k) must use the
            # dense path. dropout: attention-dropout applies to the PROBS,
            # which splash never materializes — train-with-dropout keeps
            # the dense formulation for exact reference semantics.
            _last_path = "splash"
            return _splash_varlen(q, k, v, seg_q, seg_k, causal, scale)
        logits = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            # positions aligned to sequence ENDS so unequal q/k packings
            # (decode: 1 query vs L cached keys) mask correctly — the
            # reference kernel's causal convention for varlen
            pos_q = jnp.arange(tq) - cu_q[seg_q]
            pos_k = jnp.arange(tk) - cu_k[seg_k]
            # k-length and q-length of each QUERY's segment: query i may see
            # keys with pos_k <= pos_q[i] + (len_k - len_q)
            len_q = cu_q[seg_q + 1] - cu_q[seg_q]
            len_k = cu_k[seg_q + 1] - cu_k[seg_q]
            shift = (len_k - len_q)[:, None]
            mask = mask & (pos_k[None, :] <= pos_q[:, None] + shift)
        logits = jnp.where(mask[None, :, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        # fully-masked rows (padding) produce NaN from softmax(-inf): zero
        probs = jnp.where(mask[None, :, :], probs, 0.0)
        probs = _dropout(probs, dropout, training)
        out = jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype), v)
        if traced_cu and splash_eligible:
            # splash was skipped because traced cu_seqlens couldn't be
            # PROVEN equal — make that observable (benches watch
            # _last_path; the notice fires once). Note the
            # packings may be GENUINELY different (then dense is the only
            # correct path) — we can't tell under tracing, so the advice
            # is conditional.
            _last_path = "xla-traced-cu"
            global _warned_traced_cu
            if not _warned_traced_cu:
                import warnings

                _warned_traced_cu = True
                warnings.warn(
                    "splash varlen kernel skipped: cu_seqlens are traced so "
                    "equal packing could not be proven. IF your q/k packings "
                    "are identical, pass the same object (or concrete "
                    "arrays) for cu_seqlens_q/k to enable the kernel; if "
                    "they differ, the dense path is the correct one and "
                    "this notice is expected.")
        else:
            _last_path = "xla"
        return out

    out = apply("flash_attn_unpadded", f, query, key, value,
                cu_seqlens_q, cu_seqlens_k)
    # second element is the softmax placeholder (not materialized, as in the
    # reference when return_softmax=False; fused path never exposes it)
    return out, None
