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
  in place, through ``ops/pallas/paged_attention.py``; every other paged
  call gathers the table's pages with XLA's fused gather.
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

# k_pool, v_pool: [num_blocks, block_size, KVH, D]; block_table: [B, max_blocks]
# int32 (block ids, -1 = unallocated); pos: [B] int32
PagedCacheSlot = namedtuple("PagedCacheSlot", ["k_pool", "v_pool",
                                               "block_table", "pos"])

_NEG = -1e30


def _repeat_kv(x, n_heads):
    """GQA: repeat KV heads up to the query head count."""
    kvh = x.shape[2]
    if kvh == n_heads:
        return x
    return jnp.repeat(x, n_heads // kvh, axis=2)


def _masked_attention(q, keys, values, pos):
    """q [B,s,H,D] against keys/values [B,L,H,D] valid where
    k_idx <= pos[b] + q_idx (causal over the static buffer)."""
    B, s, H, D = q.shape
    L = keys.shape[1]
    scores = jnp.einsum("bshd,blhd->bhsl", q.astype(jnp.float32),
                        keys.astype(jnp.float32)) / math.sqrt(D)
    k_idx = jnp.arange(L)[None, None, None, :]
    q_idx = jnp.arange(s)[None, None, :, None]
    mask = k_idx <= (pos[:, None, None, None] + q_idx)
    scores = jnp.where(mask, scores, _NEG)
    attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhsl,blhd->bshd", attn, values.astype(q.dtype))


def _static_cache_raw(qv, kv, vv, ck, cv, pos):
    """Write new K/V at per-batch offsets, then length-masked attention."""
    n_heads = qv.shape[2]

    def write(c, new):
        def w1(cb, nb, p):
            return jax.lax.dynamic_update_slice(
                cb, nb.astype(cb.dtype), (p, 0, 0))
        return jax.vmap(w1)(c, new, pos)

    with region("kv_gather"):
        ck2 = write(ck, kv)
        cv2 = write(cv, vv)
    out = _masked_attention(qv, _repeat_kv(ck2, n_heads),
                            _repeat_kv(cv2, n_heads), pos)
    return out, ck2, cv2, pos + qv.shape[1]


def static_cache_update_attend(q, k, v, slot: StaticCacheSlot):
    """Cache-write + attend for one forward chunk (prefill or decode step).

    q [B,s,H,D]; k/v [B,s,KVH,D] (already RoPE-rotated where applicable);
    returns (out [B,s,H,D], new slot). The masked_multihead_attention
    analogue over a dense static cache."""
    out, ck2, cv2, pos2 = apply(
        "static_cache_attention", _static_cache_raw, q, k, v,
        slot.k, slot.v, slot.pos)
    return out, StaticCacheSlot(ck2, cv2, pos2)


def _paged_write(pool, new, block_table, pos):
    """Scatter the s new tokens of each row into their pages: token t of
    row b lands in pool[block_table[b, (pos[b]+t)//bs], (pos[b]+t)%bs]."""
    s = new.shape[1]
    block_size = pool.shape[1]
    max_blocks = block_table.shape[1]
    tok_pos = pos[:, None] + jnp.arange(s)[None, :]              # [B, s]
    blk_slot = tok_pos // block_size
    blk = jnp.take_along_axis(block_table,
                              jnp.clip(blk_slot, 0, max_blocks - 1),
                              axis=1)                            # [B, s]
    off = tok_pos % block_size                                   # [B, s]
    flat = pool.reshape(-1, *pool.shape[2:])                     # [NB*bs, H, D]
    idx = (blk * block_size + off).reshape(-1)                   # [B*s]
    # unallocated (-1) or out-of-table positions must NOT wrap into
    # another sequence's block: route them out of bounds and drop
    valid = ((blk >= 0) & (blk_slot < max_blocks)).reshape(-1)
    idx = jnp.where(valid, idx, flat.shape[0])
    return flat.at[idx].set(
        new.reshape(-1, *new.shape[2:]).astype(pool.dtype),
        mode="drop",
    ).reshape(pool.shape)


def _paged_attend_xla(qv, k_pool, v_pool, block_table, pos):
    """Gather every page of every row's table into a contiguous
    [B, L, KVH, D] view, then length-masked attention: the formulation for
    s > 1 (prefill, chunks, the verify step), for the CPU, and for the
    sharded step (GSPMD does not partition a Pallas kernel)."""
    B, n_heads = qv.shape[0], qv.shape[2]
    L = block_table.shape[1] * k_pool.shape[1]

    def gather(pool):
        safe = jnp.maximum(block_table, 0)                       # [B, MB]
        pages = pool[safe]                                       # [B, MB, bs, H, D]
        return pages.reshape(B, L, *pool.shape[2:])

    with region("kv_gather"):
        keys = gather(k_pool)
        values = gather(v_pool)
    return _masked_attention(qv, _repeat_kv(keys, n_heads),
                             _repeat_kv(values, n_heads), pos)


# evidence trail: "pallas" | "xla", set on every trace of the paged attend
# so tests and chip_smoke.py can assert which path the gate selected
_last_path = None


def _use_paged_kernel(ker, qv, pool) -> bool:
    """The one gate, decided at trace time from what the code can observe:
    the decode kernel ``ker`` iff there is one query token a row, the
    platform is a TPU (or a test runs the kernel through the interpreter),
    and the shapes are ones the kernel supports. A kernel that was selected
    and fails raises."""
    if qv.shape[1] != 1:
        return False
    if not ker._interpret:
        from paddle_tpu.device import is_tpu

        if not is_tpu():
            return False
    return ker.supports((qv.shape[0],) + tuple(qv.shape[2:]), qv.dtype,
                        pool.shape, pool.dtype)


def _paged_attend(qv, k_pool, v_pool, block_table, pos):
    """Attention of qv [B,s,H,D] over the updated pool: position t of row b
    sees the pos[b] + t + 1 positions its table names."""
    global _last_path
    from paddle_tpu.ops.pallas import paged_attention as ker

    if _use_paged_kernel(ker, qv, k_pool):
        _last_path = "pallas"
        with region("attention"):
            out = ker.paged_attention_decode(
                qv[:, 0], k_pool, v_pool, block_table, pos + 1)
        return out[:, None]
    _last_path = "xla"
    return _paged_attend_xla(qv, k_pool, v_pool, block_table, pos)


def _paged_cache_raw(qv, kv, vv, k_pool, v_pool, block_table, pos):
    """Paged write, then attention over the updated pool."""
    with region("kv_gather"):
        k_pool2 = _paged_write(k_pool, kv, block_table, pos)
        v_pool2 = _paged_write(v_pool, vv, block_table, pos)
    out = _paged_attend(qv, k_pool2, v_pool2, block_table, pos)
    return out, k_pool2, v_pool2, pos + qv.shape[1]


def paged_cache_update_attend(q, k, v, slot: PagedCacheSlot):
    """block_multihead_attention analogue: write the new tokens into the
    block pool through the block table, then attend over the live pages
    (one query token a row on a TPU: the Pallas kernel reads them in place)
    or over the gathered table (everything else)."""
    out, kp2, vp2, pos2 = apply(
        "paged_cache_attention", _paged_cache_raw, q, k, v,
        slot.k_pool, slot.v_pool, slot.block_table, slot.pos)
    return out, PagedCacheSlot(kp2, vp2, slot.block_table, pos2)


def cache_update_attend(q, k, v, slot):
    """Dispatch on cache-slot type (shared by every model's serving branch)."""
    if isinstance(slot, StaticCacheSlot):
        return static_cache_update_attend(q, k, v, slot)
    if isinstance(slot, PagedCacheSlot):
        return paged_cache_update_attend(q, k, v, slot)
    raise TypeError(f"not a cache slot: {type(slot)!r}")


def make_static_cache(num_layers: int, batch: int, max_len: int,
                      kv_heads: int, head_dim: int,
                      dtype="bfloat16") -> List[StaticCacheSlot]:
    """Preallocate dense decode caches (one slot per layer)."""
    import paddle_tpu as paddle

    slots = []
    for _ in range(num_layers):
        k = paddle.zeros([batch, max_len, kv_heads, head_dim], dtype=dtype)
        v = paddle.zeros([batch, max_len, kv_heads, head_dim], dtype=dtype)
        pos = paddle.zeros([batch], dtype="int32")
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
