"""Paged decode attention for pools with the KV heads folded into the row:
one query token a row against ``k_pool [NB, bs, KVH * Dk]`` and ``v_pool
[NB, bs, KVH * Dv]`` (``kv_cache.LayerCacheGeometry.fold_heads``).

The sibling of ``paged_attention.py`` for grouped-query models whose few KV
heads do not fill a sublane tile (4 or 8 in bfloat16) and whose K and V rows
differ in width (192 / 128): with the heads side by side in the row, a page
is ``[bs, KVH * D]``, whole tiles whatever KVH is, and the walk over a row's
block table, the double-buffered copies of ``pages_per_group`` pages a loop
step and the float32 running-max softmax are that kernel's. What differs:

- GQA by a block-diagonal query, never by repeating or slicing K. Query head
  ``h`` reads KV head ``h // (H / KVH)``: its row of ``Q [H, KVH * Dk]``
  holds ``q_h`` in that KV head's lanes and zeros elsewhere, so one MXU call
  ``Q x K_page^T`` gives every head its own scores, and ``P x V_page``
  ``[H, KVH * Dv]`` holds each head's result in its KV head's lanes (a
  128-aligned block, selected at the row's end). ``Q`` is made in the
  kernel, ``q [H, Dk] x E [Dk, KVH * Dk]`` with ``E`` a tiled identity
  (exact: one product a sum) times a 0/1 mask, so no lane of K is ever
  sliced at a boundary that is not a multiple of 128. The zeros cost KVH x
  the useful arithmetic, hidden under the copies as in the sibling.
- a window: a row reads only the pages that hold its last ``window``
  positions, and ``base[b]`` is the position of its table's first column
  (a window layer's table holds only those pages; ``kv_cache``).
- a sink: a learned logit a head that joins the softmax's denominator and
  carries no value, as the running maximum's and sum's starting point.

The trace shows the kernel as ``paged_gqa_decode_window`` or
``paged_gqa_decode_full``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.paged_attention import (
    PAGES_PER_GROUP,
    sublane_tile,
)

_NEG = -1e30
_LANES = 128

# tests run the kernel through the Pallas interpreter on the CPU; the gate
# in models/kv_cache.py then selects it off the TPU as well
_interpret = False


def _split(q_shape, k_pool_shape, v_pool_shape):
    """``(kv_heads, v_dim)`` of ``q [B, H, Dk]`` against the folded pools,
    or ``None`` where the widths do not divide."""
    n_heads, k_dim = q_shape[1], q_shape[2]
    if k_pool_shape[2] % k_dim:
        return None
    kv_heads = k_pool_shape[2] // k_dim
    if n_heads % kv_heads or v_pool_shape[2] % kv_heads:
        return None
    return kv_heads, v_pool_shape[2] // kv_heads


def supports(q_shape, q_dtype, k_pool_shape, pool_dtype, v_pool_shape) -> bool:
    """Shapes the kernel compiles for: ``q [B, H, Dk]`` against folded pools
    ``[NB, bs, KVH * Dk]`` / ``[NB, bs, KVH * Dv]`` of q's dtype. On the
    chip a page must be whole tiles: rows a multiple of the dtype's sublane
    tile, both row widths and ``Dv`` multiples of 128 lanes (the
    interpreter takes any)."""
    if (len(q_shape) != 3 or len(k_pool_shape) != 3
            or len(v_pool_shape) != 3
            or tuple(k_pool_shape[:2]) != tuple(v_pool_shape[:2])):
        return False
    dtype = jnp.dtype(pool_dtype)
    if dtype != jnp.dtype(q_dtype) or dtype not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    split = _split(q_shape, k_pool_shape, v_pool_shape)
    if split is None:
        return False
    if _interpret:
        return True
    return (k_pool_shape[1] % sublane_tile(dtype) == 0
            and k_pool_shape[2] % _LANES == 0 and split[1] % _LANES == 0)


def _decode_kernel(lengths_ref, base_ref, table_ref, q_ref, expand_ref,
                   own_ref, sink_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
                   *, batch, max_blocks, block_size, kv_heads, v_dim, pages,
                   window, scale):
    n_heads = q_ref.shape[1]
    group = n_heads // kv_heads
    group_tokens = pages * block_size
    precision = (jax.lax.Precision.HIGHEST if k_buf.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)

    def span(b):
        """``(lo, hi)``: the row's visible positions, counted from its
        table's first column. An idle row (pos 0, table -1) still attends
        to one position."""
        b = jnp.minimum(b, batch - 1)
        length = jnp.maximum(lengths_ref[b], 1)
        lo = 0 if window is None else jnp.maximum(length - window, 0)
        base = base_ref[b]
        hi = jnp.clip(length - base, 1, max_blocks * block_size)
        return jnp.clip(lo - base, 0, hi - 1), hi

    def first_group(b):
        return span(b)[0] // group_tokens

    def copies(b, g, slot):
        """(is the page live, its K copy, its V copy) for the ``pages``
        pages of group ``g`` of row ``b``. A page wholly outside the row's
        span is not copied: its columns are masked, and what the buffer
        holds instead is finite."""
        lo, hi = span(b)
        out = []
        for i in range(pages):
            j = g * pages + i
            page = jnp.maximum(
                table_ref[b * max_blocks + jnp.minimum(j, max_blocks - 1)], 0)
            out.append(((j * block_size < hi) & ((j + 1) * block_size > lo),
                        pltpu.make_async_copy(k_hbm.at[page],
                                              k_buf.at[slot, i],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[page],
                                              v_buf.at[slot, i],
                                              sems.at[1, slot])))
        return out

    def start(b, g, slot):
        for is_live, k_copy, v_copy in copies(b, g, slot):
            @pl.when(is_live)
            def _():
                k_copy.start()
                v_copy.start()

    def wait(b, g, slot):
        for is_live, k_copy, v_copy in copies(b, g, slot):
            @pl.when(is_live)
            def _():
                k_copy.wait()
                v_copy.wait()

    # what scratch memory held before is never read as K or V: a masked
    # column's score is replaced and its probability is zero, but zero
    # times a NaN bit pattern is NaN
    k_buf[...] = jnp.zeros_like(k_buf)
    v_buf[...] = jnp.zeros_like(v_buf)

    col_pos = jax.lax.broadcasted_iota(jnp.int32, (n_heads, group_tokens), 1)
    head_kv = jax.lax.broadcasted_iota(jnp.int32, (n_heads, v_dim), 0) // group
    expand, own = expand_ref[...], own_ref[...]

    start(0, first_group(0), 0)

    def row_body(b, step):
        lo, hi = span(b)
        g0 = lo // group_tokens
        g1 = (hi + group_tokens - 1) // group_tokens
        # the row's query in every KV head's lanes, its own kept
        q_wide = (jax.lax.dot_general(
            q_ref[b], expand, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * own).astype(k_buf.dtype)

        def group_body(g, carry):
            step, m, l, acc = carry
            slot = step % 2
            last = g + 1 == g1
            nb = jnp.where(last, b + 1, b)
            ng = jnp.where(last, first_group(b + 1), g + 1)

            @pl.when(nb < batch)
            def _():
                start(nb, ng, 1 - slot)

            wait(b, g, slot)
            k = k_buf[slot].reshape(group_tokens, k_buf.shape[-1])
            v = v_buf[slot].reshape(group_tokens, v_buf.shape[-1])
            s = jax.lax.dot_general(
                q_wide, k, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * scale
            pos = col_pos + g * group_tokens
            s = jnp.where((pos >= lo) & (pos < hi), s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l + p.sum(axis=1, keepdims=True)
            acc_new = alpha * acc + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
            return step + 1, m_new, l_new, acc_new

        # the sink is the softmax's first column: it starts the running
        # maximum and counts one in the sum, with no value (absent: -1e30,
        # which the first live column's rescale wipes out exactly)
        step, _, l, acc = jax.lax.fori_loop(
            g0, g1, group_body,
            (step, sink_ref[...], jnp.ones((n_heads, 1), jnp.float32),
             jnp.zeros((n_heads, kv_heads * v_dim), jnp.float32)))
        out = jnp.zeros((n_heads, v_dim), jnp.float32)
        for kv in range(kv_heads):
            out = jnp.where(head_kv == kv,
                            acc[:, kv * v_dim:(kv + 1) * v_dim], out)
        o_ref[b] = (out / l).astype(o_ref.dtype)
        return step

    jax.lax.fori_loop(0, batch, row_body, jnp.int32(0))


def paged_attention_gqa_decode(q, k_pool, v_pool, block_table, lengths, *,
                               window=None, sink=None, base=None,
                               pages_per_group=None):
    """``q [B, H, Dk]`` against the last ``min(lengths[b], window)`` of the
    ``lengths[b]`` positions of row ``b``, whose pages ``block_table [B,
    MB]`` names in the folded pools from position ``base[b]`` on (None: 0);
    ``sink [H]`` joins each head's denominator. Returns ``[B, H, Dv]`` in
    q's dtype. ``-1`` in the table reads block 0 and a length under 1 reads
    one position (an idle row's result is finite and thrown away)."""
    if not supports(q.shape, q.dtype, k_pool.shape, k_pool.dtype,
                    v_pool.shape):
        raise ValueError(
            f"paged_attention_gqa_decode does not support q {q.shape} "
            f"against {k_pool.dtype} pools {k_pool.shape} / {v_pool.shape}")
    batch, n_heads, k_dim = q.shape
    kv_heads, _ = _split(q.shape, k_pool.shape, v_pool.shape)
    if base is None:
        base = jnp.zeros((batch,), jnp.int32)
    if sink is None:
        sink = jnp.full((n_heads,), _NEG, jnp.float32)
    pages = min(pages_per_group or PAGES_PER_GROUP, block_table.shape[1])
    return _decode(q, k_pool, v_pool, block_table, lengths, base,
                   sink.astype(jnp.float32), kv_heads=kv_heads,
                   window=window, pages=pages, interpret=_interpret)


# jitted so that a model's layers of one kind, which call it with the same
# shapes, share one trace and one lowering of the kernel
@functools.partial(jax.jit, static_argnames=("kv_heads", "window", "pages",
                                             "interpret"))
def _decode(q, k_pool, v_pool, block_table, lengths, base, sink, *, kv_heads,
            window, pages, interpret):
    batch, n_heads, k_dim = q.shape
    num_blocks, block_size, k_row = k_pool.shape
    v_row = v_pool.shape[2]
    v_dim = v_row // kv_heads
    max_blocks = block_table.shape[1]
    # q's lanes padded to whole tiles; E puts dimension d of q into lane
    # kv * Dk + d of every KV head kv, and ``own`` keeps a head's own
    k_pad = -(-k_dim // _LANES) * _LANES
    q = jnp.pad(q, ((0, 0), (0, 0), (0, k_pad - k_dim)))
    lane = jnp.arange(k_row)
    expand = (jnp.arange(k_pad)[:, None] == (lane % k_dim)[None, :]).astype(
        q.dtype)
    own = ((lane // k_dim)[None, :]
           == (jnp.arange(n_heads) // (n_heads // kv_heads))[:, None]
           ).astype(jnp.float32)
    kernel = functools.partial(
        _decode_kernel, batch=batch, max_blocks=max_blocks,
        block_size=block_size, kv_heads=kv_heads, v_dim=v_dim, pages=pages,
        window=window, scale=1.0 / math.sqrt(k_dim))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(),
            in_specs=[whole, whole, whole, whole, any_space, any_space],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, pages, block_size, k_row), k_pool.dtype),
                pltpu.VMEM((2, pages, block_size, v_row), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct((batch, n_heads, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 2**20),
        interpret=interpret,
        name=("paged_gqa_decode_window" if window is not None
              else "paged_gqa_decode_full"),
    )(lengths.astype(jnp.int32), base.astype(jnp.int32),
      block_table.astype(jnp.int32).reshape(-1),
      q, expand, own, sink.reshape(n_heads, 1), k_pool, v_pool)
