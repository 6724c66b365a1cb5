"""Paged decode attention over a latent cache: one query token a row, every
head of it against the one row a token that all heads share (``pool [NB, bs,
row]``, ``kv_cache.LayerCacheGeometry.latent``).

The third sibling of ``paged_attention.py``, for multi-head latent attention
in its absorbed form. A cached row is ``(c_kv | RoPE(k_r))``: the compressed
K/V of the token and the rotary key that all heads share, padded with zeros
to whole lane tiles. The caller has folded the K up-projection into the query
(``q [H, row]`` = ``(q_nope W_k^T | q_rope | 0)``), so a head's score against
a position is one dot product with that position's row, and the value of the
position is the row's first ``v_dim`` lanes (``c_kv``): one copy of a page
serves as K and as V for all ``H`` heads. The caller applies the V
up-projection to what this returns.

The walk over a row's block table up to its live length, the double-buffered
copies of ``pages_per_group`` pages a loop step straight from the pool in
HBM, in the pool's own dtype, and the float32 running-max softmax are the
siblings'. What differs is the balance: a cached token costs ``2 * H * (row +
v_dim)`` operations against ``row`` elements read, ~60 FLOP/B at 32 heads,
where the siblings do ~1: the MXU's two calls a group (``Q x K^T`` with 32
rows, ``P x V``) are no longer hidden under the copies for free, and a page
is ``bs * row`` elements (20 KB at 16 x 640 bf16), a small copy.

The trace shows the kernel as ``paged_mla_decode``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.paged_attention import sublane_tile

_NEG = -1e30
_LANES = 128

# tests run the kernel through the Pallas interpreter on the CPU; the gate
# in models/kv_cache.py then selects it off the TPU as well
_interpret = False

# pages copied and contracted a loop step: a latent page is a sixth of a
# GPT page's bytes, so a group holds more of them for the same bytes in
# flight. Measured on a v5e at 32 heads x 640 lanes (PERF.md, PR 37): 100
# rows of 1.3-4 K positions read at 28 / 38 / 45 / 44 % of the HBM peak with
# 8 / 16 / 32 / 64 pages a group, 128 rows of 2.5 K at 28 / 40 / 51 / 47 %,
# 128 rows of 300 at 19 / 19 / 22 / 15 %
PAGES_PER_GROUP = 32


def supports(q_shape, q_dtype, pool_shape, pool_dtype, v_dim: int) -> bool:
    """Shapes the kernel compiles for: ``q [B, H, D]`` with ``D`` no wider
    than the pool's row, against ``pool [NB, bs, row]`` of q's dtype whose
    first ``v_dim`` lanes are the value. On the chip a page must be whole
    tiles: rows a multiple of the dtype's sublane tile, ``row`` and
    ``v_dim`` multiples of 128 lanes (the interpreter takes any)."""
    if len(q_shape) != 3 or len(pool_shape) != 3:
        return False
    dtype = jnp.dtype(pool_dtype)
    if dtype != jnp.dtype(q_dtype) or dtype not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    if not 0 < v_dim <= pool_shape[2] or q_shape[2] > pool_shape[2]:
        return False
    if _interpret:
        return True
    return (pool_shape[1] % sublane_tile(dtype) == 0
            and pool_shape[2] % _LANES == 0 and v_dim % _LANES == 0)


def _decode_kernel(lengths_ref, table_ref, q_ref, pool_hbm, o_ref, buf, sems,
                   *, batch, max_blocks, block_size, v_dim, pages, scale):
    n_heads = q_ref.shape[1]
    group_tokens = pages * block_size
    # named here: the process-wide default may ask Mosaic for a float32
    # pass over bfloat16 operands (PERF.md, PR 27)
    precision = (jax.lax.Precision.HIGHEST if buf.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)

    def length_of(b):
        """The row's visible positions; an idle row (pos 0, table -1)
        still attends to one."""
        return jnp.clip(lengths_ref[jnp.minimum(b, batch - 1)], 1,
                        max_blocks * block_size)

    def copies(b, g, slot):
        """(is the page live, its copy) for the ``pages`` pages of group
        ``g`` of row ``b``. A page past the row's length is not copied: its
        columns are masked, and what the buffer holds instead is finite."""
        hi = length_of(b)
        out = []
        for i in range(pages):
            j = g * pages + i
            page = jnp.maximum(
                table_ref[b * max_blocks + jnp.minimum(j, max_blocks - 1)], 0)
            out.append((j * block_size < hi,
                        pltpu.make_async_copy(pool_hbm.at[page],
                                              buf.at[slot, i],
                                              sems.at[slot])))
        return out

    def start(b, g, slot):
        for is_live, copy in copies(b, g, slot):
            @pl.when(is_live)
            def _():
                copy.start()

    def wait(b, g, slot):
        for is_live, copy in copies(b, g, slot):
            @pl.when(is_live)
            def _():
                copy.wait()

    # what scratch memory held before is never read as a row: a masked
    # column's score is replaced and its probability is zero, but zero
    # times a NaN bit pattern is NaN
    buf[...] = jnp.zeros_like(buf)
    col_pos = jax.lax.broadcasted_iota(jnp.int32, (n_heads, group_tokens), 1)

    start(0, 0, 0)

    def row_body(b, step):
        hi = length_of(b)
        groups = (hi + group_tokens - 1) // group_tokens
        q = q_ref[b]

        def group_body(g, carry):
            step, m, l, acc = carry
            slot = step % 2
            last = g + 1 == groups
            nb = jnp.where(last, b + 1, b)
            ng = jnp.where(last, 0, g + 1)

            @pl.when(nb < batch)
            def _():
                start(nb, ng, 1 - slot)

            wait(b, g, slot)
            rows = buf[slot].reshape(group_tokens, buf.shape[-1])
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(col_pos + g * group_tokens < hi, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l + p.sum(axis=1, keepdims=True)
            acc_new = alpha * acc + jax.lax.dot_general(
                p.astype(rows.dtype), rows[:, :v_dim],
                (((1,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32)
            return step + 1, m_new, l_new, acc_new

        step, _, l, acc = jax.lax.fori_loop(
            0, groups, group_body,
            (step, jnp.full((n_heads, 1), _NEG, jnp.float32),
             jnp.zeros((n_heads, 1), jnp.float32),
             jnp.zeros((n_heads, v_dim), jnp.float32)))
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return step

    jax.lax.fori_loop(0, batch, row_body, jnp.int32(0))


def paged_mla_decode(q, pool, block_table, lengths, *, v_dim: int,
                     scale: float, pages_per_group=None):
    """``q [B, H, D]`` (the absorbed query, ``D`` <= the pool's row width)
    against the ``lengths[b]`` positions of row ``b``, whose pages
    ``block_table [B, MB]`` names in ``pool [NB, bs, row]``: scores ``q .
    row * scale``, softmax in float32, values the rows' first ``v_dim``
    lanes. Returns ``[B, H, v_dim]`` in q's dtype. ``-1`` in the table reads
    block 0 and a length under 1 reads one position (an idle row's result
    is finite and thrown away)."""
    if not supports(q.shape, q.dtype, pool.shape, pool.dtype, v_dim):
        raise ValueError(
            f"paged_mla_decode does not support q {q.shape} {q.dtype} "
            f"against a {pool.dtype} pool {pool.shape} with values of "
            f"{v_dim}")
    pages = min(pages_per_group or PAGES_PER_GROUP, block_table.shape[1])
    return _decode(q, pool, block_table, lengths, v_dim=int(v_dim),
                   scale=float(scale), pages=pages, interpret=_interpret)


# jitted so that a model's layers, which call it with the same shapes,
# share one trace and one lowering of the kernel (traced anew for each
# layer it costs seconds of Python a program: PERF.md, PR 27)
@functools.partial(jax.jit, static_argnames=("v_dim", "scale", "pages",
                                             "interpret"))
def _decode(q, pool, block_table, lengths, *, v_dim, scale, pages, interpret):
    batch, n_heads, q_dim = q.shape
    _, block_size, row = pool.shape
    max_blocks = block_table.shape[1]
    # the pool's padding lanes hold zeros, and so do the query's
    q = jnp.pad(q, ((0, 0), (0, 0), (0, row - q_dim)))
    kernel = functools.partial(
        _decode_kernel, batch=batch, max_blocks=max_blocks,
        block_size=block_size, v_dim=v_dim, pages=pages, scale=scale)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(),
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, pages, block_size, row), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((batch, n_heads, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 2**20),
        interpret=interpret,
        name="paged_mla_decode",
    )(lengths.astype(jnp.int32), block_table.astype(jnp.int32).reshape(-1),
      q, pool)
