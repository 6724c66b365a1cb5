"""Static-shape and paged KV caches for incremental decoding.

Capability parity with the reference's serving attention kernels —
masked_multihead_attention (dense static cache, one query token against a
preallocated prefix buffer) and block_multihead_attention (paged KV pool
addressed through block tables), phi/kernels/fusion/gpu/ and
python/paddle/incubate/nn/functional/ — re-designed TPU-first:

- Caches are preallocated to a static max length so every decode step is the
  SAME XLA program (no shape-driven recompiles); writes are per-batch
  ``lax.dynamic_update_slice`` and validity comes from a length mask.
- The paged variant keeps K/V in a block pool indexed by per-sequence block
  tables (vLLM-style), enabling continuous batching without moving memory.
  A decode step (one query token a row) on a TPU reads only the live pages,
  in place, through ``ops/pallas/paged_attention.py`` (or its sibling
  ``paged_attention_gqa.py`` for pools with the KV heads folded into the
  row); every other paged call gathers the table's pages with XLA's fused
  gather.
- Layers may differ in what they cache (``LayerCacheGeometry``, one answer
  from the model for the scheduler and ``DecodeEngine``): KV heads, K and V
  row widths, and a window. A window layer's paged slot carries ``base``:
  its table holds only the pages the window still reaches (a second class
  of blocks in the scheduler), and a layer passes its ``window`` and
  ``sink`` to ``cache_update_attend``.
- A layer may cache one latent row a token in place of K and V rows per
  head (``LayerCacheGeometry.latent``: multi-head latent attention): one
  pool a layer, ``[num_blocks, block_size, row]``, no V pool (``v_pool`` /
  ``v`` is None), every head reads the same row and a position's value is
  the row's first ``v_dim`` lanes. ``latent_cache_write`` puts a chunk's
  rows there (a prefill attends over its own expanded K and V) and
  ``latent_cache_update_attend`` writes one and attends over the rows with
  the query the model has absorbed its K up-projection into: on a TPU
  through ``ops/pallas/paged_mla_decode.py``, elsewhere over the gathered
  table. The latent rows live in the full class of blocks.
"""

from __future__ import annotations

import math
import threading
from collections import namedtuple
from typing import List, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.dispatch import apply
from paddle_tpu.observability.annotations import guarded_by, holds_lock
from paddle_tpu.observability.step_profile import region
from paddle_tpu.tensor import Tensor

# k, v: [B, max_len, KVH, D]; pos: [B] int32 — number of tokens already cached
StaticCacheSlot = namedtuple("StaticCacheSlot", ["k", "v", "pos"])

# k_pool [num_blocks, block_size, KVH, Dk], v_pool [.., KVH, Dv] (K and V may
# differ in width), or with the KV heads folded into the row, [num_blocks,
# block_size, KVH * D] (``LayerCacheGeometry.fold_heads``); block_table:
# [B, max_blocks] int32 (block ids, -1 = unallocated); pos: [B] int32.
# A latent layer has one pool, ``k_pool [num_blocks, block_size, row]``, and
# ``v_pool`` None (a static slot likewise: ``k [B, max_len, row]``, ``v``
# None).
# ``base`` is None where column c of the table is the row's page c (a layer
# that keeps its whole context), else [B] int32: the position of column 0's
# first token (a multiple of the block size), for a window layer's table,
# which holds only the pages the window still reaches.
PagedCacheSlot = namedtuple("PagedCacheSlot", ["k_pool", "v_pool",
                                               "block_table", "pos", "base"],
                            defaults=(None,))

# the fields of a cache slot that are KV buffers: what a compiled serving
# step donates, updates in place and gives back
_POOL_FIELDS = ("k_pool", "v_pool", "k", "v")


def donate_pools(ids, position_ids, caches, *rest):
    """The one donation rule of the compiled serving steps, as
    ``StaticFunction(donate_args=...)`` asks for it: of a launch's
    arguments ``(ids, position_ids, caches, ...)`` the K and V pools of
    every cache slot are donated (they are threaded through the step and
    updated in place, one resident copy), and nothing else is: a block
    table, a position vector or a base may be one array shared by every
    layer, and stays valid after the call."""
    return (False, False,
            [type(c)(*(f in _POOL_FIELDS for f in c._fields))
             for c in caches],
            ) + (False,) * len(rest)


def pools_only(caches):
    """``caches`` with every field but the pools left out (None): what a
    compiled serving step gives back. The table, the positions and the
    base are the caller's own inputs, not donated, so a step that returned
    them would make a fresh device buffer of each for every layer at every
    launch (2.3 ms of host time a launch for 24 layers on a v5e's host:
    PERF.md, PR 29)."""
    return [type(c)(*(t if f in _POOL_FIELDS else None
                      for f, t in zip(c._fields, c))) for c in caches]


# What one layer caches: KV heads, the width of a K row and of a V row,
# ``window`` (None: the whole context; w: a query sees the last w positions,
# itself included), and ``fold_heads`` (the pools keep a token's KV heads
# side by side in one row, ``[NB, bs, KVH * D]``: a model whose KV heads do
# not fill a sublane tile asks for it, so that a page is whole tiles).
# ``latent``: the layer caches one row of ``k_dim`` a token that every query
# head reads (``kv_heads`` 1), whose first ``v_dim`` lanes are the position's
# value; there is no V pool, and the pool's rows are ``k_dim`` padded to
# whole lane tiles (``latent_row_width``).
LayerCacheGeometry = namedtuple(
    "LayerCacheGeometry",
    ["kv_heads", "k_dim", "v_dim", "window", "fold_heads", "latent"],
    defaults=(None, False, False))

_LANES = 128

_NEG = -1e30

# scores of one attention call are formed for this many bytes of float32 at
# a time (a block of query positions), so that a wide prefill over a long
# table fits beside a full pool
_SCORE_BYTES = 512 * 2**20


def cache_geometry(model) -> List[LayerCacheGeometry]:
    """Each layer's cache geometry, as the model states it
    (``model.cache_geometry()``), else one class of layers from its
    config: ``num_key_value_heads`` (or ``num_heads``) heads of
    ``hidden_size // num_heads`` for K and V alike. The one place the
    scheduler and ``DecodeEngine`` size their caches from."""
    if hasattr(model, "cache_geometry"):
        return list(model.cache_geometry())
    return uniform_cache_geometry(model.config)


def uniform_cache_geometry(cfg) -> List[LayerCacheGeometry]:
    kv_heads = getattr(cfg, "num_key_value_heads", None) or cfg.num_heads
    head_dim = cfg.hidden_size // cfg.num_heads
    return [LayerCacheGeometry(kv_heads, head_dim, head_dim)
            for _ in range(cfg.num_layers)]


def window_blocks_per_seq(window: int, block_size: int) -> int:
    """Pages a row of a window layer holds at most: the window's span can
    straddle one page more than it fills."""
    return -(-window // block_size) + 1


def latent_row_width(k_dim: int) -> int:
    """The row width a latent layer's pool is allocated with: ``k_dim``
    rounded up to whole lane tiles (576 -> 640), zeros in the padding, so
    that the decode kernel contracts and slices at tile boundaries only."""
    return -(-k_dim // _LANES) * _LANES


def pool_shapes(geom: LayerCacheGeometry, num_blocks: int, block_size: int):
    """``(k_pool shape, v_pool shape)`` of one layer; a latent layer has
    one pool and ``None`` for the other."""
    if geom.latent:
        return ([num_blocks, block_size, latent_row_width(geom.k_dim)], None)
    if geom.fold_heads:
        return ([num_blocks, block_size, geom.kv_heads * geom.k_dim],
                [num_blocks, block_size, geom.kv_heads * geom.v_dim])
    return ([num_blocks, block_size, geom.kv_heads, geom.k_dim],
            [num_blocks, block_size, geom.kv_heads, geom.v_dim])


def zero_pools(geom: LayerCacheGeometry, num_blocks: int, block_size: int,
               dtype):
    """``(k_pool, v_pool)`` of one layer, zeroed (``v_pool`` None for a
    latent layer): what the scheduler and ``DecodeEngine`` thread through
    their compiled steps."""
    import paddle_tpu as paddle

    return tuple(None if shape is None else paddle.zeros(shape, dtype=dtype)
                 for shape in pool_shapes(geom, num_blocks, block_size))


def _attend(q, keys, values, q_pos, k_pos, window=None, sink=None,
            scale=None):
    """q [B,s,H,D] at positions q_pos [B,s] against keys [B,L,KVH,D] and
    values [B,L,KVH,Dv] at positions k_pos [B,L]: key j is visible to query
    i iff ``k_pos[j] <= q_pos[i]`` and, under a window, ``q_pos[i] -
    k_pos[j] < window``. GQA by grouping the query heads of a KV head, never
    by repeating K or V. ``sink [H]`` is a logit a head that joins the
    softmax's denominator and carries no value. Scores and softmax in
    float32, scale 1/sqrt(D) unless ``scale`` states another,
    probabilities cast to q's dtype."""
    B, s, H, D = q.shape
    L, kvh = keys.shape[1], keys.shape[2]
    g = H // kvh
    keys32 = keys.astype(jnp.float32)
    # the division is what the programs of every model without a stated
    # scale have always held
    scaled = ((lambda x: x / math.sqrt(D)) if scale is None
              else (lambda x: x * scale))

    def block(qb, qp):
        # qb [B,c,H,D], qp [B,c]
        c = qb.shape[1]
        scores = scaled(jnp.einsum(
            "bskgd,blkd->bkgsl",
            qb.astype(jnp.float32).reshape(B, c, kvh, g, D),
            keys32))
        d = qp[:, :, None] - k_pos[:, None, :]                  # [B,c,L]
        mask = d >= 0
        if window is not None:
            mask &= d < window
        scores = jnp.where(mask[:, None, None], scores, _NEG)
        if sink is None:
            attn = jax.nn.softmax(scores, axis=-1)
        else:
            sk = sink.astype(jnp.float32).reshape(1, kvh, g, 1, 1)
            m = jnp.maximum(scores.max(axis=-1, keepdims=True), sk)
            p = jnp.exp(scores - m)
            attn = p / (p.sum(axis=-1, keepdims=True) + jnp.exp(sk - m))
        out = jnp.einsum("bkgsl,blkd->bskgd", attn.astype(q.dtype),
                         values.astype(q.dtype))
        return out.reshape(B, c, H, values.shape[-1])

    def blocked(qb, qp):
        """``block`` for a wide problem: the keys in blocks of the chunk's
        own width with the running-max softmax, and only as far as the
        last block that holds a position some query of the chunk can see
        (a prefill at the start of its row never reads the rest of the
        table's width)."""
        c = qb.shape[1]
        nb = L // c
        q5 = qb.astype(jnp.float32).reshape(B, c, kvh, g, D)
        first_pos = k_pos.reshape(B, nb, c).min(axis=(0, 2))
        trips = jnp.max(jnp.where(first_pos <= qp.max(),
                                  jnp.arange(nb) + 1, 0))
        # the sink starts the running maximum and counts one in the sum
        m0 = jnp.full((B, kvh, g, c), _NEG, jnp.float32)
        l0 = jnp.zeros((B, kvh, g, c), jnp.float32)
        if sink is not None:
            m0 = m0.at[:].set(sink.astype(jnp.float32).reshape(1, kvh, g, 1))
            l0 = l0 + 1.0

        def body(j, carry):
            m, l, acc = carry
            cut = lambda x: jax.lax.dynamic_slice_in_dim(x, j * c, c, axis=1)
            scores = scaled(jnp.einsum("bskgd,blkd->bkgsl", q5,
                                       cut(keys).astype(jnp.float32)))
            d = qp[:, :, None] - cut(k_pos)[:, None, :]
            mask = d >= 0
            if window is not None:
                mask &= d < window
            mask = mask[:, None, None]
            scores = jnp.where(mask, scores, _NEG)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(mask, jnp.exp(scores - m_new[..., None]), 0.0)
            pv = jnp.einsum("bkgsl,blkd->bkgsd", p.astype(q.dtype),
                            cut(values).astype(q.dtype))
            return (m_new, alpha * l + p.sum(axis=-1),
                    alpha[..., None] * acc + pv.astype(jnp.float32))

        _, l, acc = jax.lax.fori_loop(
            0, trips, body,
            (m0, l0, jnp.zeros((B, kvh, g, c, values.shape[-1]),
                               jnp.float32)))
        out = (acc / l[..., None]).astype(q.dtype)
        return jnp.moveaxis(out, 3, 1).reshape(B, c, H, values.shape[-1])

    c = s
    while c > 1 and c % 2 == 0 and B * H * c * L * 4 > _SCORE_BYTES:
        c //= 2
    if c == s:
        return block(q, q_pos)
    n = s // c
    out = jax.lax.map(
        lambda a: (blocked if L % c == 0 else block)(*a),
        (q.reshape(B, n, c, H, D).swapaxes(0, 1),
         q_pos.reshape(B, n, c).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(B, s, H, values.shape[-1])


def _masked_attention(q, keys, values, pos, window=None, sink=None,
                      base=None, scale=None):
    """q [B,s,H,D], the tokens at positions pos[b] .., against the buffer
    keys/values [B,L,KVH,D] whose entry j holds position base[b] + j
    (base None: j)."""
    s, L = q.shape[1], keys.shape[1]
    q_pos = pos[:, None] + jnp.arange(s)[None, :]
    k_pos = jnp.arange(L)[None, :] + (0 if base is None else base[:, None])
    k_pos = jnp.broadcast_to(k_pos, (q.shape[0], L))
    return _attend(q, keys, values, q_pos, k_pos, window, sink, scale)


def _banded_attention(q, k, v, window, sink=None):
    """Causal attention of a chunk over itself under a window: q [B,s,H,D],
    k [B,s,KVH,D], v [B,s,KVH,Dv], all at positions 0 .. s-1 relative to the
    chunk's start. Query blocks of ``window`` positions see their own block
    and the one before, so no ``[H, s, s]`` scores are formed."""
    B, s, H, D = q.shape
    if s <= 2 * window or s % window:
        idx = jnp.broadcast_to(jnp.arange(s)[None, :], (B, s))
        return _attend(q, k, v, idx, idx, window, sink)
    n = s // window

    def blocks(x, shift):
        x = x.reshape(B, n, window, *x.shape[2:])
        if shift:
            x = jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
        return x

    kk = jnp.concatenate([blocks(k, True), blocks(k, False)], axis=2)
    vv = jnp.concatenate([blocks(v, True), blocks(v, False)], axis=2)
    fold = lambda x: x.reshape(B * n, *x.shape[2:])
    # inside a pair of blocks the keys sit at 0 .. 2w-1 and the queries at
    # w .. 2w-1; the first block has nothing before it, so its (zero) first
    # half is put after every query
    q_pos = jnp.broadcast_to(window + jnp.arange(window)[None, :],
                             (B * n, window))
    k_pos = jnp.broadcast_to(jnp.arange(2 * window)[None, :],
                             (B, n, 2 * window))
    first = (jnp.arange(n) == 0)[None, :, None]
    k_pos = jnp.where(first & (k_pos < window), 4 * window, k_pos)
    out = _attend(fold(blocks(q, False)), fold(kk), fold(vv), q_pos,
                  k_pos.reshape(B * n, 2 * window), window, sink)
    return out.reshape(B, s, H, v.shape[-1])


def _causal_raw(q, k, v, *sink, window=None):
    if window is not None:
        return _banded_attention(q, k, v, window, sink[0] if sink else None)
    idx = jnp.broadcast_to(jnp.arange(q.shape[1])[None, :], q.shape[:2])
    return _attend(q, k, v, idx, idx, None, sink[0] if sink else None)


def causal_attention(q, k, v, window=None, sink=None):
    """Cache-free causal attention of one chunk from position 0 (a model's
    eager forward): q [B,s,H,D], k [B,s,KVH,D], v [B,s,KVH,Dv]."""
    args = (q, k, v) + (() if sink is None else (sink,))
    return apply("causal_attention", _causal_raw, *args, window=window)


def _static_cache_raw(qv, kv, vv, ck, cv, pos, *sink, window=None):
    """Write new K/V at per-batch offsets, then length-masked attention."""
    def write(c, new):
        def w1(cb, nb, p):
            return jax.lax.dynamic_update_slice(
                cb, nb.astype(cb.dtype), (p, 0, 0))
        return jax.vmap(w1)(c, new, pos)

    with region("kv_gather"):
        ck2 = write(ck, kv)
        cv2 = write(cv, vv)
    out = _masked_attention(qv, ck2, cv2, pos, window,
                            sink[0] if sink else None)
    return out, ck2, cv2, pos + qv.shape[1]


def static_cache_update_attend(q, k, v, slot: StaticCacheSlot, window=None,
                               sink=None):
    """Cache-write + attend for one forward chunk (prefill or decode step).

    q [B,s,H,D]; k [B,s,KVH,D], v [B,s,KVH,Dv] (already RoPE-rotated where
    applicable); returns (out [B,s,H,Dv], new slot). The
    masked_multihead_attention analogue over a dense static cache."""
    out, ck2, cv2, pos2 = apply(
        "static_cache_attention", _static_cache_raw, q, k, v,
        slot.k, slot.v, slot.pos, *(() if sink is None else (sink,)),
        window=window)
    return out, StaticCacheSlot(ck2, cv2, pos2)


def _paged_write(pool, new, block_table, pos, base=None):
    """Scatter the s new tokens of each row into their pages: token t of
    row b, at position p = pos[b] + t, lands in pool[block_table[b, (p -
    base[b]) // bs], p % bs]. A position before ``base`` (a prompt's part
    that a window layer's table no longer reaches) is dropped."""
    s = new.shape[1]
    block_size = pool.shape[1]
    max_blocks = block_table.shape[1]
    tok_pos = pos[:, None] + jnp.arange(s)[None, :]              # [B, s]
    blk_slot = tok_pos // block_size
    if base is not None:
        blk_slot = blk_slot - base[:, None] // block_size
    blk = jnp.take_along_axis(block_table,
                              jnp.clip(blk_slot, 0, max_blocks - 1),
                              axis=1)                            # [B, s]
    off = tok_pos % block_size                                   # [B, s]
    flat = pool.reshape(-1, *pool.shape[2:])                     # [NB*bs, ..]
    idx = (blk * block_size + off).reshape(-1)                   # [B*s]
    # unallocated (-1) or out-of-table positions must NOT wrap into
    # another sequence's block: route them out of bounds and drop
    valid = ((blk >= 0) & (blk_slot >= 0)
             & (blk_slot < max_blocks)).reshape(-1)
    idx = jnp.where(valid, idx, flat.shape[0])
    return flat.at[idx].set(
        new.reshape(-1, *flat.shape[1:]).astype(pool.dtype),
        mode="drop",
    ).reshape(pool.shape)


def _paged_attend_xla(qv, k_pool, v_pool, block_table, pos, window=None,
                      sink=None, base=None, kv_heads=None):
    """Gather every page of every row's table into a contiguous
    [B, L, KVH, D] view, then length-masked attention: the formulation for
    s > 1 (prefill, chunks, the verify step), for the CPU, and for the
    sharded step (GSPMD does not partition a Pallas kernel)."""
    B = qv.shape[0]
    L = block_table.shape[1] * k_pool.shape[1]

    def gather(pool):
        safe = jnp.maximum(block_table, 0)                       # [B, MB]
        pages = pool[safe]                                       # [B, MB, bs, ..]
        if pool.ndim == 3:                                       # folded heads
            return pages.reshape(B, L, kv_heads, -1)
        return pages.reshape(B, L, *pool.shape[2:])

    with region("kv_gather"):
        keys = gather(k_pool)
        values = gather(v_pool)
    return _masked_attention(qv, keys, values, pos, window, sink, base)


# evidence trail: "pallas" | "xla", set on every trace of the paged attend
# so tests and chip_smoke.py can assert which path the gate selected
_last_path = None


def _paged_kernel(qv, k_pool, v_pool, v_dim=None):
    """The one gate, decided at trace time from what the code can observe:
    the decode kernel of the pools' layout (``paged_attention`` for
    ``[NB, bs, KVH, D]`` pools, ``paged_attention_gqa`` for pools with the
    heads folded into the row, ``paged_mla_decode`` for a latent layer's one
    pool, whose values are its rows' first ``v_dim`` lanes) iff there is one
    query token a row, the platform is a TPU (or a test runs that kernel
    through the interpreter), and the shapes are ones that kernel supports;
    else ``None``. A kernel that was selected and fails raises."""
    from paddle_tpu.ops.pallas import (
        paged_attention,
        paged_attention_gqa,
        paged_mla_decode,
    )

    ker = (paged_mla_decode if v_pool is None
           else paged_attention if k_pool.ndim == 4 else paged_attention_gqa)
    if qv.shape[1] != 1:
        return None
    if not ker._interpret:
        from paddle_tpu.device import is_tpu

        if not is_tpu():
            return None
    ok = ker.supports((qv.shape[0],) + tuple(qv.shape[2:]), qv.dtype,
                      k_pool.shape, k_pool.dtype,
                      v_dim if v_pool is None else v_pool.shape)
    return ker if ok else None


def _paged_attend(qv, k_pool, v_pool, block_table, pos, window=None,
                  sink=None, base=None, kv_heads=None):
    """Attention of qv [B,s,H,D] over the updated pool: position t of row b
    sees the positions up to pos[b] + t that its table names (under a
    window, the last ``window`` of them)."""
    global _last_path

    ker = _paged_kernel(qv, k_pool, v_pool)
    if ker is not None:
        _last_path = "pallas"
        with region("attention"):
            if k_pool.ndim == 4:
                out = ker.paged_attention_decode(
                    qv[:, 0], k_pool, v_pool, block_table, pos + 1)
            else:
                out = ker.paged_attention_gqa_decode(
                    qv[:, 0], k_pool, v_pool, block_table, pos + 1,
                    window=window, sink=sink, base=base)
        return out[:, None]
    _last_path = "xla"
    return _paged_attend_xla(qv, k_pool, v_pool, block_table, pos, window,
                             sink, base, kv_heads)


def _paged_cache_raw(qv, kv, vv, k_pool, v_pool, block_table, pos, *rest,
                     window=None, has_sink=False, has_base=False):
    """Paged write, then attention over the updated pool. A chunk (s > 1)
    through a window layer's own table (``base``) attends over itself: the
    table no longer reaches the chunk's early positions, so such a chunk
    starts its row (pos 0), which is how the scheduler prefills a windowed
    model."""
    rest = list(rest)
    sink = rest.pop(0) if has_sink else None
    base = rest.pop(0) if has_base else None
    kv_heads = kv.shape[2]
    if k_pool.ndim == 3:
        fold = lambda x: x.reshape(*x.shape[:2], -1)
        k_new, v_new = fold(kv), fold(vv)
    else:
        k_new, v_new = kv, vv
    with region("kv_gather"):
        k_pool2 = _paged_write(k_pool, k_new, block_table, pos, base)
        v_pool2 = _paged_write(v_pool, v_new, block_table, pos, base)
    if base is not None and qv.shape[1] > 1:
        out = _banded_attention(qv, kv.astype(qv.dtype), vv.astype(qv.dtype),
                                window, sink)
    else:
        out = _paged_attend(qv, k_pool2, v_pool2, block_table, pos, window,
                            sink, base, kv_heads)
    return out, k_pool2, v_pool2, pos + qv.shape[1]


def paged_cache_update_attend(q, k, v, slot: PagedCacheSlot, window=None,
                              sink=None):
    """block_multihead_attention analogue: write the new tokens into the
    block pool through the block table, then attend over the live pages
    (one query token a row on a TPU: a Pallas kernel reads them in place)
    or over the gathered table (everything else)."""
    if slot.base is not None and window is None:
        raise ValueError("a paged cache slot with a base is a window "
                         "layer's: the layer must state its window")
    rest = [t for t in (sink, slot.base) if t is not None]
    out, kp2, vp2, pos2 = apply(
        "paged_cache_attention", _paged_cache_raw, q, k, v,
        slot.k_pool, slot.v_pool, slot.block_table, slot.pos, *rest,
        window=window, has_sink=sink is not None,
        has_base=slot.base is not None)
    return out, PagedCacheSlot(kp2, vp2, slot.block_table, pos2, slot.base)


def cache_update_attend(q, k, v, slot, window=None, sink=None):
    """Dispatch on cache-slot type (shared by every model's serving branch).
    ``window`` and ``sink`` are the layer's own (a sliding window of that
    many positions; a learned logit a head in the softmax's denominator)."""
    if isinstance(slot, StaticCacheSlot):
        return static_cache_update_attend(q, k, v, slot, window, sink)
    if isinstance(slot, PagedCacheSlot):
        return paged_cache_update_attend(q, k, v, slot, window, sink)
    raise TypeError(f"not a cache slot: {type(slot)!r}")


def _latent_write_raw(rows, buf, pos, *table):
    """A chunk's latent rows into a static buffer ``[B, max_len, row]`` at
    each row's offset, or into a pool through its block table; the rows
    are padded with zeros to the buffer's row width."""
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, buf.shape[-1] - rows.shape[-1]))
                   ).astype(buf.dtype)
    with region("kv_gather"):
        if table:
            buf2 = _paged_write(buf, rows, table[0], pos)
        else:
            buf2 = jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice(
                c, n, (p, 0)))(buf, rows, pos)
    return buf2, pos + rows.shape[1]


def _latent_slot(slot):
    """``(field, table)``: the name of a latent slot's one buffer, and what
    beside ``pos`` locates a row in it (a paged slot's block table)."""
    if isinstance(slot, PagedCacheSlot):
        return "k_pool", (slot.block_table,)
    return "k", ()


def latent_cache_write(rows, slot):
    """Write a chunk's latent rows ``[B, s, k_dim]`` at the slot's
    positions and return the new slot: the cache's half of a prefill that
    attends over its own expanded K and V (such a chunk starts its row, as
    a window layer's does)."""
    field, table = _latent_slot(slot)
    buf2, pos2 = apply("latent_cache_write", _latent_write_raw, rows,
                       getattr(slot, field), slot.pos, *table)
    return slot._replace(**{field: buf2, "pos": pos2})


def _latent_attend_raw(qv, rows, buf, pos, *table, v_dim, scale):
    """Write, then attention of the absorbed query qv [B,s,H,Dq] over the
    updated rows: head h's score against a position is ``qv[h] . row *
    scale`` and the position's value the row's first ``v_dim`` lanes."""
    global _last_path

    buf2, pos2 = _latent_write_raw(rows, buf, pos, *table)
    ker = _paged_kernel(qv, buf2, None, v_dim) if table else None
    if ker is not None:
        _last_path = "pallas"
        with region("mla_decode"):
            out = ker.paged_mla_decode(qv[:, 0], buf2, table[0], pos + 1,
                                       v_dim=v_dim, scale=scale)[:, None]
        return out, buf2, pos2
    _last_path = "xla"
    with region("mla_decode"):
        if table:
            with region("kv_gather"):
                keys = buf2[jnp.maximum(table[0], 0)].reshape(
                    qv.shape[0], -1, 1, buf2.shape[-1])
        else:
            keys = buf2[:, :, None, :]
        q = jnp.pad(qv, ((0, 0),) * 3 + ((0, keys.shape[-1] - qv.shape[-1]),))
        out = _masked_attention(q, keys, keys[..., :v_dim], pos, scale=scale)
    return out, buf2, pos2


def latent_cache_update_attend(q, rows, slot, v_dim: int, scale: float):
    """Latent-cache write + attend in the absorbed form: ``q [B,s,H,Dq]``
    is the query with the layer's K up-projection folded in (``Dq`` <= the
    row), ``rows [B,s,k_dim]`` the new tokens' latent rows. Returns (out
    ``[B,s,H,v_dim]``: each head's weighted sum of the rows' first
    ``v_dim`` lanes, before the V up-projection; the new slot). One query
    token a row over a paged slot on a TPU reads the live pages in place
    (``ops/pallas/paged_mla_decode.py``); everything else gathers."""
    field, table = _latent_slot(slot)
    out, buf2, pos2 = apply(
        "latent_cache_attention", _latent_attend_raw, q, rows,
        getattr(slot, field), slot.pos, *table, v_dim=int(v_dim),
        scale=float(scale))
    return out, slot._replace(**{field: buf2, "pos": pos2})


def make_static_cache(num_layers: int, batch: int, max_len: int,
                      kv_heads: int, head_dim: int,
                      dtype="bfloat16", geometry=None) -> List[StaticCacheSlot]:
    """Preallocate dense decode caches (one slot per layer); ``geometry``
    (``cache_geometry(model)``) sizes each layer's own."""
    import paddle_tpu as paddle

    geometry = geometry or [LayerCacheGeometry(kv_heads, head_dim, head_dim)
                            ] * num_layers
    slots = []
    for g in geometry:
        pos = paddle.zeros([batch], dtype="int32")
        if g.latent:
            slots.append(StaticCacheSlot(paddle.zeros(
                [batch, max_len, latent_row_width(g.k_dim)], dtype=dtype),
                None, pos))
            continue
        k = paddle.zeros([batch, max_len, g.kv_heads, g.k_dim], dtype=dtype)
        v = paddle.zeros([batch, max_len, g.kv_heads, g.v_dim], dtype=dtype)
        slots.append(StaticCacheSlot(k, v, pos))
    return slots


class KVPoolExhausted(RuntimeError):
    """Raised when the block pool cannot cover a request; the serving
    scheduler catches this to preempt instead of OOM-ing."""


class BlockAllocator:
    """Host-side free-list allocator for KV pool blocks (the vLLM block
    manager role). Pure bookkeeping — device state is only the block table.

    Hardened for the serving tier: every block id is tracked as free OR
    allocated, double-free (and freeing a block the allocator never owned)
    raises, and occupancy/fragmentation stats feed ``ServingMetrics``.

    Thread contract: the scheduler thread allocates/frees while the
    ObservabilityEndpoint thread reads occupancy stats (and the async
    serving engine will run admission and decode accounting concurrently)
    — free list and allocated set live under a reentrant ``_lock``."""

    _free: guarded_by("_lock")
    _allocated: guarded_by("_lock")

    def __init__(self, num_blocks: int, block_size: int):
        self.block_size = block_size
        self.num_blocks = num_blocks
        # reentrant: allocate() -> _pop_free(), and the ref-counting
        # subclass's eviction callback re-enters through decref()
        self._lock = threading.RLock()
        self._free = list(range(num_blocks - 1, -1, -1))
        self._allocated: set = set()

    def num_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def num_free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def num_used_blocks(self) -> int:
        with self._lock:
            return len(self._allocated)

    def utilization(self) -> float:
        """Fraction of the pool currently allocated to sequences."""
        with self._lock:
            return len(self._allocated) / max(self.num_blocks, 1)

    def fragmentation(self, live_tokens: int) -> float:
        """Internal fragmentation: fraction of allocated token capacity not
        holding a live token (tail slack of partially-filled blocks)."""
        with self._lock:
            cap = len(self._allocated) * self.block_size
        if cap <= 0:
            return 0.0
        return max(0.0, 1.0 - live_tokens / cap)

    @holds_lock("_lock")
    def _pop_free(self) -> int:
        b = self._free.pop()
        self._allocated.add(b)
        return b

    def allocate(self, n_tokens: int) -> List[int]:
        need = (n_tokens + self.block_size - 1) // self.block_size
        with self._lock:
            if need > len(self._free):
                raise KVPoolExhausted(
                    f"KV pool exhausted: need {need} blocks, "
                    f"{len(self._free)} free")
            return [self._pop_free() for _ in range(need)]

    def extend(self, blocks: List[int], cur_tokens: int, add_tokens: int):
        """Grow a sequence's block list to cover add_tokens more tokens."""
        have = len(blocks) * self.block_size
        with self._lock:
            while cur_tokens + add_tokens > have:
                if not self._free:
                    raise KVPoolExhausted("KV pool exhausted on extend")
                blocks.append(self._pop_free())
                have += self.block_size
        return blocks

    def free(self, blocks: List[int]):
        with self._lock:
            for b in blocks:
                if b not in self._allocated:
                    raise RuntimeError(
                        f"double free: block {b} is not currently allocated")
                self._allocated.remove(b)
                self._free.append(b)
