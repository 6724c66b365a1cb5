"""Hand-written Pallas TPU kernel: fused AdamW over the flat parameter space.

Reference capability: the multi-tensor fused optimizer kernels
(paddle/phi/kernels/fusion/gpu/distributed_fused_lamb_init_kernel.cu and the
multi_tensor adam path) — one kernel pass updates every parameter instead of
one launch per parameter.

This is an original kernel (not a wrapper around a stock library op): the
flat fp32 buffers (param, grad, m, v, per-element weight-decay) stream
HBM -> VMEM in (block_rows, 128) tiles; each grid step performs the whole
AdamW update on the VPU and writes param/m/v back through input/output
aliasing (true in-place, zero extra HBM traffic). The op is memory-bound:
one fused pass reads 5N and writes 3N floats — the theoretical floor.

The kernel compiles for a TPU only. The Pallas interpreter is something a
test asks for — ``interpret=True`` on the call, or this module's
``_interpret`` switch for the ``FusedAdamW`` optimizer paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_DEFAULT_BLOCK_ROWS = 512  # 512*128 fp32 = 256 KiB per buffer in VMEM
_interpret = False         # tests force interpret mode for FusedAdamW


def use_fused_adamw() -> bool:
    from paddle_tpu.device import is_tpu

    return is_tpu()


def _adamw_kernel(beta1, beta2, eps,
                  lr_ref,
                  p_ref, g_ref, m_ref, v_ref, wd_ref, b1p_ref, b2p_ref,
                  op_ref, om_ref, ov_ref, ob1_ref, ob2_ref):
    lr = lr_ref[0]
    g = g_ref[:]
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    # PER-ELEMENT pow accumulators (phi input convention): params that join
    # the grad-bearing set later restart their own bias-correction chain
    b1p = b1p_ref[:]
    b2p = b2p_ref[:]
    m_hat = m / (1.0 - b1p)
    v_hat = v / (1.0 - b2p)
    p = p_ref[:]
    p = p * (1.0 - lr * wd_ref[:])  # decoupled decay, per-element coeff
    op_ref[:] = p - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    om_ref[:] = m
    ov_ref[:] = v
    ob1_ref[:] = b1p * beta1
    ob2_ref[:] = b2p * beta2


@functools.partial(
    jax.jit,
    static_argnames=("beta1", "beta2", "eps", "block_rows", "interpret"))
def fused_adamw_flat(p, g, m, v, wd, lr, b1pow, b2pow, *,
                     beta1=0.9, beta2=0.999, eps=1e-8,
                     block_rows=_DEFAULT_BLOCK_ROWS, interpret=False):
    """One AdamW step over flat fp32 buffers.

    p/g/m/v/wd: [N] float32 (N padded to a multiple of 8*128 by the caller —
    see pad_flat). lr: scalar. b1pow/b2pow: [N] per-element incoming pow
    accumulators (beta-initialized at each element's step 1) — per-element
    so late-joining params restart their own bias-correction chain.
    Returns (p', m', v', b1pow', b2pow').
    """
    n = p.shape[0]
    assert n % (8 * _LANES) == 0, n
    rows = n // _LANES
    br = min(block_rows, max(rows, 8))
    # pad rows up to a block multiple — NEVER shrink the block (a small
    # fallback block explodes the grid length: 124M params at br=8 is a
    # 121k-step grid and a ~1000x slowdown)
    rows_p = ((rows + br - 1) // br) * br
    grid = (rows_p // br,)

    shape2d = (rows_p, _LANES)

    def as2d(a):
        a = a.reshape(rows, _LANES)
        if rows_p != rows:
            # zero padding is safe even for the pow chains: 1/(1-0) = 1 and
            # padded outputs are discarded by unpad()
            a = jnp.pad(a, ((0, rows_p - rows), (0, 0)))
        return a

    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1)
    b1pow = jnp.broadcast_to(jnp.asarray(b1pow, jnp.float32), (n,))
    b2pow = jnp.broadcast_to(jnp.asarray(b2pow, jnp.float32), (n,))

    kernel = functools.partial(_adamw_kernel, float(beta1), float(beta2),
                               float(eps))
    row_spec = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec(
        memory_space=None if interpret else pltpu.SMEM)

    out_p, out_m, out_v, out_b1, out_b2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[scalar_spec,
                  row_spec, row_spec, row_spec, row_spec, row_spec,
                  row_spec, row_spec],
        out_specs=[row_spec] * 5,
        out_shape=[jax.ShapeDtypeStruct(shape2d, jnp.float32)] * 5,
        # p->p', m->m', v->v', b1p->b1p', b2p->b2p'
        input_output_aliases={1: 0, 3: 1, 4: 2, 6: 3, 7: 4},
        interpret=interpret,
    )(lr_arr, as2d(p), as2d(g), as2d(m), as2d(v), as2d(wd),
      as2d(b1pow), as2d(b2pow))
    unpad = lambda a: a.reshape(rows_p * _LANES)[:n]
    return (unpad(out_p), unpad(out_m), unpad(out_v),
            unpad(out_b1), unpad(out_b2))


def pad_flat(arrs, pad_multiple=8 * _LANES):
    """Concat a list of arrays into one padded flat fp32 buffer; returns
    (flat, sizes, total_padded)."""
    flats = [jnp.ravel(a).astype(jnp.float32) for a in arrs]
    sizes = [f.shape[0] for f in flats]
    total = sum(sizes)
    padded = total + ((-total) % pad_multiple)
    flat = jnp.concatenate(flats + [jnp.zeros(padded - total, jnp.float32)]) \
        if flats else jnp.zeros(padded, jnp.float32)
    return flat, sizes, padded


