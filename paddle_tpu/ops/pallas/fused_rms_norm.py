"""Hand-written Pallas TPU kernel: fused RMSNorm forward + backward.

Reference capability: phi/kernels/fusion/gpu/fused_rms_norm kernels (the
rms_norm fwd/grad pair paddle ships as one fused GPU kernel each way).

Original kernel, not a wrapper: rows stream HBM -> VMEM in (block_rows, D)
tiles; the forward computes the fp32 row rstd on the VPU and writes
out = x * rstd * w in one pass, saving rstd (one scalar per row) as the
backward residual. The backward recomputes nothing from HBM but x, g:

    xhat = x * rstd
    dw   = sum_rows g * xhat                      (per-block partials)
    dx   = rstd * w * g - xhat * rstd/D * sum_d(g * w * x)

Both directions are memory-bound single passes (read 2N, write N + D),
which is the floor — the win over the unfused chain is not FLOPs but
avoiding the extra HBM round-trips XLA sometimes leaves between the
variance reduction and the scale application at large D.

Callers gate with ``use_fused_rms_norm()``; off a TPU the gate selects the
XLA composition ``rms_ref``. The Pallas interpreter (``interpret=True``) is
something a test asks for. A kernel that was selected and fails raises.

VMEM: one (block_rows, D) fp32 tile is held to 1 MiB (``_block_rows``), so
the backward's three streamed tiles, double-buffered, plus its fp32
temporaries stay under the 16 MiB scoped-VMEM default at every D the gate
admits (D = 8192 runs 32-row blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_TILE_ELEMS = 256 * 1024   # fp32 elements per (block_rows, D) tile: 1 MiB
_last_path = None          # "pallas" | "xla" — evidence hook (flash pattern)
_interpret = False         # tests force interpret mode through the router


def _block_rows(d: int) -> int:
    """Rows per block for width ``d``: the 1 MiB tile budget, as a multiple
    of 16 (the bf16 sublane tile; also a multiple of fp32's 8)."""
    return max(16, min(256, _TILE_ELEMS // d // 16 * 16))


def rms_ref(x, w, eps):
    """The plain XLA RMSNorm composition — what the gate selects off-chip
    and the reference tests compare the kernel against (fp32 accumulation,
    scale in input dtype)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    out = (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return out * w if w is not None else out


def rms_norm_routed(x, w, eps):
    """Raw-array RMSNorm over the last axis: Pallas kernel on a TPU at the
    widths the gate admits (observable via ``_last_path``), XLA composition
    otherwise. THE entry every framework layer should use —
    nn.functional.rms_norm, incubate.fused_rms_norm and the models all
    route here."""
    global _last_path
    d = x.shape[-1]
    if w is not None and use_fused_rms_norm(d):
        _last_path = "pallas"
        out = rms_norm_pallas(x.reshape(-1, d), w, eps, None, _interpret)
        return out.reshape(x.shape)
    _last_path = "xla"
    return rms_ref(x, w, eps)


def use_fused_rms_norm(d: int) -> bool:
    from paddle_tpu.device import is_tpu

    # lane-aligned width, and narrow enough that the 16-row minimum block
    # stays inside the tile budget
    return is_tpu() and d % 128 == 0 and d <= 8192


def _fwd_kernel(eps, x_ref, w_ref, o_ref, rstd_ref):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    rstd_ref[:] = rstd
    o_ref[:] = (x * rstd).astype(x_ref.dtype) * w_ref[:]


def _bwd_kernel(x_ref, w_ref, g_ref, rstd_ref, dx_ref, dwp_ref):
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]            # [rows, 1] fp32
    xhat = x * rstd
    gw = g * w
    # dvar path: mean over features of gw * xhat
    c = jnp.mean(gw * xhat, axis=1, keepdims=True)
    dx = rstd * (gw - xhat * c)
    dx_ref[:] = dx.astype(x_ref.dtype)
    # per-row-block partial dw as one (8, D) fp32 tile: rows fold onto the
    # 8 sublanes (a pure VPU add, no cross-sublane reduce) and the caller
    # sums blocks and sublanes. A (1, D) partial would be a block whose
    # second-to-last dim is neither a multiple of 8 nor the full extent.
    rows, d = x.shape
    dwp_ref[:] = jnp.sum((g * xhat).reshape(rows // 8, 8, d), axis=0)


def _pad_rows(a, block_rows):
    n = a.shape[0]
    pad = (-n) % block_rows
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a, n


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def rms_norm_pallas(x2d, w, eps=1e-6, block_rows=None, interpret=False):
    """RMSNorm over the last axis of a 2-D [N, D] input; weight [D].
    ``block_rows=None`` sizes the row block from D (``_block_rows``); an
    explicit value must be a multiple of 8."""
    out, _ = _fwd(x2d, w, eps, block_rows, interpret)
    return out


def _fwd(x2d, w, eps, block_rows, interpret):
    n, d = x2d.shape
    block_rows = block_rows or _block_rows(d)
    xp, n_orig = _pad_rows(x2d, block_rows)
    grid = (xp.shape[0] // block_rows,)
    out, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x2d.dtype),
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp, w.reshape(1, d))
    return out[:n_orig], rstd


def _rms_fwd(x2d, w, eps, block_rows, interpret):
    out, rstd = _fwd(x2d, w, eps, block_rows, interpret)
    return out, (x2d, w, rstd)


def _rms_bwd(eps, block_rows, interpret, res, g):
    x2d, w, rstd = res
    n, d = x2d.shape
    block_rows = block_rows or _block_rows(d)
    if block_rows % 8:
        raise ValueError(f"block_rows must be a multiple of 8: {block_rows}")
    xp, n_orig = _pad_rows(x2d, block_rows)
    gp, _ = _pad_rows(g, block_rows)
    nblocks = xp.shape[0] // block_rows
    dx, dw_part = pl.pallas_call(
        _bwd_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((None, 8, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x2d.dtype),
            jax.ShapeDtypeStruct((nblocks, 8, d), jnp.float32),
        ],
        interpret=interpret,
    )(xp, w.reshape(1, d), gp, rstd)
    dw = jnp.sum(dw_part, axis=(0, 1)).astype(w.dtype)
    return dx[:n_orig], dw


rms_norm_pallas.defvjp(_rms_fwd, _rms_bwd)
