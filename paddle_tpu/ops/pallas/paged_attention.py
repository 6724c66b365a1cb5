"""Paged decode attention: one query token a row against the KV block pool.

The ``s == 1`` step of ``models/kv_cache.py``'s paged cache (the
block_multihead_attention decode case). The kernel walks each row's block
table up to the row's live length and copies the pages it names straight
from the pool in HBM into a double-buffered VMEM scratch, in the pool's own
dtype: no gather of the table's width, no ``[B, L, H, D]`` copy, no float32
copy of K or V in HBM, no repeat of the KV heads.

One invocation serves all rows (no grid: a grid step a row would leave the
DMA queue empty at every row's start), ``pages_per_group`` pages a loop
step, the next group's copies in flight while this one is computed. A page
is the pool's contiguous ``[block_size * KVH, D]`` slab, so all heads of a
group come in one copy and are contracted in one MXU call:
``q [H, D] x K [T * KVH, D]^T`` gives ``[H, T * KVH]`` scores of which the
columns of a row's own KV head are kept (GQA by indexing, never by
repeating) and the others masked out of the softmax, so ``P x V`` over the
same flat layout sums each head's own positions only. The arithmetic wasted
on the masked columns is hidden under the copies: the kernel is bound by the
bytes of the live pages.

Mathematics of ``kv_cache._masked_attention``: scores and softmax in float32
(products of the pool's values accumulated in float32), scale ``1/sqrt(D)``,
probabilities cast to the value dtype before ``P x V``, float32 accumulation
across groups with the running-max rescale.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

# tests run the kernel through the Pallas interpreter on the CPU; the gate
# in models/kv_cache.py then selects it off the TPU as well
_interpret = False

# pages copied and contracted a loop step. Measured on a v5e at the 1.3B
# decode shape (PERF.md, PR 27): 8 and 16 read 32 rows of ~350 positions at
# 80 % of the HBM peak and full tables at 91 %, 4 loses a tenth there; a row
# pays for a whole group's copies and columns whatever it holds, so 16 and
# 32 cost idle and short rows 1.3-2.6 x what 8 does
PAGES_PER_GROUP = 8


def sublane_tile(dtype) -> int:
    """Rows of one VMEM tile of ``dtype`` (8 x 128 words of 32 bits)."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def supports(q_shape, q_dtype, pool_shape, pool_dtype,
             v_pool_shape=None) -> bool:
    """Shapes the kernel compiles for: ``q [B, H, D]`` against K and V pools
    ``[NB, bs, KVH, D]`` of one shape and q's dtype. A page must be a whole number of
    tiles with the KV heads filling the sublanes, so that ``[bs, KVH, D]``
    and ``[bs * KVH, D]`` are the same bytes in HBM and in VMEM."""
    if len(q_shape) != 3 or len(pool_shape) != 4:
        return False
    if v_pool_shape is not None and tuple(v_pool_shape) != tuple(pool_shape):
        return False
    _, n_heads, head_dim = q_shape
    _, _, kv_heads, pool_dim = pool_shape
    dtype = jnp.dtype(pool_dtype)
    if dtype != jnp.dtype(q_dtype) or dtype not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    return (pool_dim == head_dim and head_dim % 128 == 0
            and n_heads % kv_heads == 0
            and kv_heads % sublane_tile(dtype) == 0)


def _decode_kernel(lengths_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, *, batch, max_blocks, block_size,
                   kv_heads, pages):
    n_heads, head_dim = q_ref.shape[1], q_ref.shape[2]
    group_tokens = pages * block_size
    cols = group_tokens * kv_heads
    scale = 1.0 / math.sqrt(head_dim)
    # float32 pools multiply in float32; bf16 products are exact in the
    # float32 accumulator already (named, so that a process-wide default
    # precision cannot ask Mosaic for a float32 pass over bf16 operands)
    precision = (jax.lax.Precision.HIGHEST if k_buf.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)

    def live(b):
        # an idle row (pos 0, table -1) still attends to one position
        return jnp.clip(lengths_ref[b], 1, max_blocks * block_size)

    def copies(b, g, slot):
        """(is the page live, its K copy, its V copy) for the ``pages``
        pages of group ``g`` of row ``b``. A page past the live length is
        not copied: its columns are masked, and its stale V is finite."""
        length = live(b)
        out = []
        for i in range(pages):
            j = g * pages + i
            page = jnp.maximum(
                table_ref[b * max_blocks + jnp.minimum(j, max_blocks - 1)], 0)
            out.append((j * block_size < length,
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

    # what scratch memory held before is never read as V: P x V multiplies
    # masked columns by zero, and zero times a NaN bit pattern is NaN
    v_buf[...] = jnp.zeros_like(v_buf)

    # column c of a group is position c // KVH of KV head c % KVH; query
    # head h reads KV head h // (H / KVH)
    col = jax.lax.broadcasted_iota(jnp.int32, (n_heads, cols), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (n_heads, cols), 0)
    own_head = jax.lax.rem(col, kv_heads) == jax.lax.div(
        row, n_heads // kv_heads)
    col_pos = jax.lax.div(col, kv_heads)

    start(0, 0, 0)

    def row_body(b, step):
        length = live(b)
        n_groups = (length + group_tokens - 1) // group_tokens
        q = q_ref[b]

        def group_body(g, carry):
            step, m, l, acc = carry
            slot = step % 2
            last = g + 1 == n_groups
            nb = jnp.where(last, b + 1, b)
            ng = jnp.where(last, 0, g + 1)

            @pl.when(nb < batch)
            def _():
                start(nb, ng, 1 - slot)

            wait(b, g, slot)
            k = k_buf[slot].reshape(cols, head_dim)
            v = v_buf[slot].reshape(cols, head_dim)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * scale
            mask = own_head & (col_pos < length - g * group_tokens)
            s = jnp.where(mask, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)   # position 0 is live: m_new is finite
            l_new = alpha * l + p.sum(axis=1, keepdims=True)
            acc_new = alpha * acc + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
            return step + 1, m_new, l_new, acc_new

        step, _, l, acc = jax.lax.fori_loop(
            0, n_groups, group_body,
            (step, jnp.full((n_heads, 1), _NEG, jnp.float32),
             jnp.zeros((n_heads, 1), jnp.float32),
             jnp.zeros((n_heads, head_dim), jnp.float32)))
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return step

    jax.lax.fori_loop(0, batch, row_body, jnp.int32(0))


def paged_attention_decode(q, k_pool, v_pool, block_table, lengths, *,
                           pages_per_group=None):
    """``q [B, H, D]`` against ``lengths[b]`` positions of the pages that
    ``block_table [B, MB]`` names in ``k_pool, v_pool [NB, bs, KVH, D]``;
    returns ``[B, H, D]`` in q's dtype. ``-1`` in the table reads block 0
    and a length under 1 reads one position (an idle row's result is
    finite and is thrown away by the caller)."""
    if not supports(q.shape, q.dtype, k_pool.shape, k_pool.dtype):
        raise ValueError(
            f"paged_attention_decode does not support q {q.shape} against a "
            f"{k_pool.dtype} pool {k_pool.shape}")
    pages = min(pages_per_group or PAGES_PER_GROUP, block_table.shape[1])
    return _decode(q, k_pool, v_pool, block_table, lengths, pages=pages,
                   interpret=_interpret)


# jitted so that a model's layers, which call it with the same shapes, share
# one trace and one lowering of the kernel: traced anew for each of 24
# layers it was most of a decode program's start-up, cache hit or not
@functools.partial(jax.jit, static_argnames=("pages", "interpret"))
def _decode(q, k_pool, v_pool, block_table, lengths, *, pages, interpret):
    batch, _, head_dim = q.shape
    num_blocks, block_size, kv_heads, _ = k_pool.shape
    max_blocks = block_table.shape[1]
    page_rows = block_size * kv_heads
    kernel = functools.partial(
        _decode_kernel, batch=batch, max_blocks=max_blocks,
        block_size=block_size, kv_heads=kv_heads, pages=pages)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(),
            in_specs=[whole, any_space, any_space],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, pages, page_rows, head_dim), k_pool.dtype),
                pltpu.VMEM((2, pages, page_rows, head_dim), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="paged_attention_decode",
    )(lengths.astype(jnp.int32), block_table.astype(jnp.int32).reshape(-1),
      q,
      k_pool.reshape(num_blocks, page_rows, head_dim),
      v_pool.reshape(num_blocks, page_rows, head_dim))
