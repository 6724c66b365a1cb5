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
rows, ``P x V``) take about as long as the group's copies, and a page is
``bs * row`` elements (20 KB at 16 x 640 bf16), a small copy. So a group
costs the larger of the two only if nothing else stands between them:

- a group that lies inside its row, and whose successor does too, issues
  its successor's copies with no test a page, waits for its own with one
  wait, and reads a slot fixed when compiling, all in one block with its
  products. Only a row's last group (and the one before it) tests pages:
  it copies the live ones only and runs its products over the sub-blocks
  of ``SUB_PAGES`` that hold them;
- an idle row (its table starts with -1: the scheduler's freed slot) is
  neither copied nor multiplied, and its result is zeros; the last group
  of a live row prefetches the next live row's first.

At the decode shape of the latent serving cell (PERF.md, section 6) the loop
before this one cost 1.37 us a 32-page group on a v5e, against 0.95 us for
its copies and waits alone and 0.67 us for its products alone.

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
# flight, and a row's last group costs only its live sub-blocks. Measured
# on a v5e at 32 heads x 640 lanes, 128 rows of which 89 live of 1.3-3.9 K
# positions, in % of the HBM peak by 1,152 B a position (PERF.md, section
# 6): this loop without the sub-blocks 58 % at 32 pages a group and 65 % at
# 64; the loop before it 38 / 44 / 43 % at 16 / 32 / 64
PAGES_PER_GROUP = 64

# pages a sub-block of a row's last group, the products' unit there
SUB_PAGES = 8


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
    row_width = buf.shape[-1]
    sub = SUB_PAGES if pages % SUB_PAGES == 0 else pages
    # named here: the process-wide default may ask Mosaic for a float32
    # pass over bfloat16 operands (PERF.md, PR 27)
    precision = (jax.lax.Precision.HIGHEST if buf.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)

    def is_live(b):
        """A row whose table names a page. An idle one (the scheduler's
        freed slot: table -1) is neither copied nor multiplied."""
        return table_ref[jnp.minimum(b, batch - 1) * max_blocks] >= 0

    def next_live(b):
        """The first live row from ``b`` on, ``batch`` if there is none."""
        return jax.lax.while_loop(
            lambda r: (r < batch) & jnp.logical_not(is_live(r)),
            lambda r: r + 1, b)

    def length_of(b):
        return jnp.clip(lengths_ref[jnp.minimum(b, batch - 1)], 1,
                        max_blocks * block_size)

    def live_pages(b, g):
        """Pages of group ``g`` of row ``b`` that hold one of its positions:
        ``pages`` inside the row, fewer in its last group, 0 past it."""
        n = pl.cdiv(length_of(b) - g * group_tokens, block_size)
        return jnp.where(b < batch, jnp.clip(n, 0, pages), 0)

    def copy(b, g, i, slot):
        page = jnp.maximum(table_ref[b * max_blocks + g * pages + i], 0)
        return pltpu.make_async_copy(pool_hbm.at[page], buf.at[slot, i],
                                     sems.at[slot])

    def start_all(b, g, slot):
        for i in range(pages):
            copy(b, g, i, slot).start()

    def start_some(b, g, slot, n):
        """The first ``n`` pages only: a page past the row's length is not
        copied, and its columns are masked."""
        for i in range(pages):
            pl.when(i < n)(lambda i=i: copy(b, g, i, slot).start())

    def wait_pages(slot, k):
        """One wait for ``k`` pages' copies into ``slot``: the semaphore
        counts bytes, and so does the wait of a ``k``-page descriptor."""
        first = buf.at[slot, pl.ds(0, k)]
        pltpu.make_async_copy(first, first, sems.at[slot]).wait()

    def wait_some(slot, n):
        k = 1 << (pages.bit_length() - 1)
        while k:
            pl.when((n & k) != 0)(functools.partial(wait_pages, slot, k))
            k //= 2

    def update(q, rows, col0, hi, state):
        """The running-max softmax over ``rows`` [T, row], whose first
        column is position ``col0``."""
        m, l, acc = state
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < hi, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l + p.sum(axis=1, keepdims=True)
        acc_new = alpha * acc + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :v_dim],
            (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    def whole_group(q, slot, g, hi, state):
        rows = buf[slot].reshape(group_tokens, row_width)
        return update(q, rows, g * group_tokens, hi, state)

    def live_part(q, slot, g, hi, state, n):
        """The products of a group over the sub-blocks that hold its ``n``
        live pages, not over the whole group's masked columns."""
        def one(k, state):
            rows = buf[slot, pl.ds(k * sub, sub)].reshape(
                sub * block_size, row_width)
            return update(q, rows, g * group_tokens + k * sub * block_size,
                          hi, state)

        return jax.lax.cond(
            n == pages, lambda st: whole_group(q, slot, g, hi, st),
            lambda st: jax.lax.fori_loop(0, pl.cdiv(n, sub), one, st), state)

    # what scratch memory held before is never read as a row: a masked
    # column's score is replaced and its probability is zero, but zero
    # times a NaN bit pattern is NaN
    buf[...] = jnp.zeros_like(buf)

    first = next_live(0)
    start_some(jnp.minimum(first, batch - 1), 0, 0, live_pages(first, 0))

    def live_row(b, step):
        hi = length_of(b)
        groups = pl.cdiv(hi, group_tokens)
        nb = next_live(b + 1)
        q = q_ref[b]

        def group_body(g, carry):
            step, state = carry
            slot = step % 2
            # the next group to copy: this row's, else the next live row's
            # first, while this one is computed
            last = g + 1 == groups
            tb = jnp.where(last, nb, b)
            tg = jnp.where(last, 0, g + 1)
            n_this = live_pages(b, g)
            n_next = live_pages(tb, tg)

            def steady(s):
                """This group and the next are whole: no test a page, one
                wait, and the copies' issue in one block with the products
                of a slot known when compiling."""
                def run(state):
                    start_all(tb, tg, 1 - s)
                    wait_pages(s, pages)
                    return whole_group(q, s, g, hi, state)
                return run

            def edge(state):
                start_some(jnp.minimum(tb, batch - 1), tg, 1 - slot, n_next)
                wait_some(slot, n_this)
                return live_part(q, slot, g, hi, state, n_this)

            whole = (n_this == pages) & (n_next == pages)
            state = jax.lax.switch(jnp.where(whole, slot, 2),
                                   [steady(0), steady(1), edge], state)
            return step + 1, state

        step, (_, l, acc) = jax.lax.fori_loop(
            0, groups, group_body,
            (step, (jnp.full((n_heads, 1), _NEG, jnp.float32),
                    jnp.zeros((n_heads, 1), jnp.float32),
                    jnp.zeros((n_heads, v_dim), jnp.float32))))
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return step

    def idle_row(b, step):
        o_ref[b] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)
        return step

    jax.lax.fori_loop(
        0, batch,
        lambda b, step: jax.lax.cond(is_live(b), live_row, idle_row, b, step),
        jnp.int32(0))


def paged_mla_decode(q, pool, block_table, lengths, *, v_dim: int,
                     scale: float, pages_per_group=None):
    """``q [B, H, D]`` (the absorbed query, ``D`` <= the pool's row width)
    against the ``lengths[b]`` positions of row ``b``, whose pages
    ``block_table [B, MB]`` names in ``pool [NB, bs, row]``: scores ``q .
    row * scale``, softmax in float32, values the rows' first ``v_dim``
    lanes. Returns ``[B, H, v_dim]`` in q's dtype. A row whose table
    starts with ``-1`` is idle: nothing of it is read and its result is
    zeros. A length under 1 reads one position."""
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
