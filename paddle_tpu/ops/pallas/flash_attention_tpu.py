"""TPU Pallas flash-attention kernel entry (parity:
phi/kernels/gpu/flash_attn_kernel.cu — fwd+bwd fused attention).

Dispatches to the Pallas MHA kernel family (block-tiled online-softmax
attention with a custom VJP, i.e. the flash algorithm scheduled for
MXU/VMEM). Layout at this boundary is paddle's [batch, seq, heads, head_dim];
the kernel runs [batch, heads, seq, head_dim].

Block sizes: block_q 1024 / block_k 512, each clamped to the largest block
that divides the sequence — larger q blocks amortize the KV loop while k
stays within VMEM at head_dim 64-256. They compile and match the XLA
reference on a v5e at batch 2 x seq 1024 x 16 heads x 128
(``chip_smoke.py``, PR 21); their speed against other block sizes on this
round's chip: not measured. The caps stay overridable for sweeps
(``PADDLE_TPU_FLASH_FWD_BLOCKS`` / ``PADDLE_TPU_FLASH_BWD_BLOCKS``).
"""

from __future__ import annotations

import os

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.flash_attention import (
    BlockSizes,
    flash_attention as _mha,
)


def _largest_dividing_block(n: int, cap: int) -> int:
    for b in (2048, 1024, 512, 256, 128):
        if b <= cap and n % b == 0:
            return b
    return min(n, cap)


# backward (dkv/dq) block caps, set apart from the forward ones
_BWD_CAPS = None


def _bwd_caps():
    global _BWD_CAPS
    if _BWD_CAPS is None:
        env = os.environ.get("PADDLE_TPU_FLASH_BWD_BLOCKS", "")
        _BWD_CAPS = (1024, 512, 1024, 512)  # q_dkv, k_dkv, q_dq, k_dq
        if env:
            try:
                parts = [int(x) for x in env.split(",")]
                if len(parts) != 4 or any(p <= 0 for p in parts):
                    raise ValueError(env)
                _BWD_CAPS = tuple(parts)
            except ValueError:
                import warnings

                warnings.warn(
                    "PADDLE_TPU_FLASH_BWD_BLOCKS must be 4 positive ints "
                    f"'q_dkv,k_dkv,q_dq,k_dq'; got {env!r} — using defaults")
    return _BWD_CAPS


_FWD_CAPS = None


def _fwd_caps():
    global _FWD_CAPS
    if _FWD_CAPS is None:
        env = os.environ.get("PADDLE_TPU_FLASH_FWD_BLOCKS", "")
        _FWD_CAPS = (1024, 512)
        if env:
            try:
                parts = [int(x) for x in env.split(",")]
                if len(parts) != 2 or any(p <= 0 for p in parts):
                    raise ValueError(env)
                _FWD_CAPS = tuple(parts)
            except ValueError:
                import warnings

                warnings.warn(
                    "PADDLE_TPU_FLASH_FWD_BLOCKS must be 2 positive ints "
                    f"'q,k'; got {env!r} — using defaults")
    return _FWD_CAPS


def _block_sizes(sq: int, sk: int) -> BlockSizes:
    # largest dividing block ≤ cap: seq 1536 gets 512, not a failing 1024
    cq, ck = _fwd_caps()
    bq = _largest_dividing_block(sq, cq)
    bk = _largest_dividing_block(sk, ck)
    cq_dkv, ck_dkv, cq_dq, ck_dq = _bwd_caps()
    bq_dkv = _largest_dividing_block(sq, cq_dkv)
    bk_dkv = _largest_dividing_block(sk, ck_dkv)
    bq_dq = _largest_dividing_block(sq, cq_dq)
    bk_dq = _largest_dividing_block(sk, ck_dq)
    return BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq_dkv, block_k_major_dkv=bk_dkv,
        block_k_dkv=bk_dkv, block_q_dkv=bq_dkv,
        block_k_major_dq=bk_dq, block_k_dq=bk_dq, block_q_dq=bq_dq,
    )


def flash_attention(q, k, v, bias=None, causal=False, scale=1.0):
    """q, k, v: [B, S, H, D] -> out [B, S, H, D]."""
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    ab = None
    if bias is not None:
        # the kernel computes (qk + ab) * sm_scale; our contract is
        # qk * scale + bias, so pre-divide the bias by scale
        b_, h_, sq_, sk_ = (qt.shape[0], qt.shape[1], qt.shape[2], kt.shape[2])
        ab = jnp.broadcast_to(
            bias.astype(jnp.float32) / float(scale), (b_, h_, sq_, sk_))
    out = _mha(
        qt, kt, vt, ab=ab, causal=causal, sm_scale=float(scale),
        block_sizes=_block_sizes(qt.shape[2], kt.shape[2]),
    )
    return jnp.transpose(out, (0, 2, 1, 3))
