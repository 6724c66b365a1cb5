"""Batched serving engine: jit-compiled incremental decoding.

Capability parity with the reference's decoder-serving stack (fused
masked/block multi-head attention ops + FusedMultiTransformer serving layers,
python/paddle/incubate/nn/layer/fused_transformer.py:994,
phi/kernels/fusion/gpu/) — re-designed TPU-first:

- KV caches are preallocated static-shape buffers (dense, or a paged block
  pool with block tables), so prefill compiles once per length bucket and
  EVERY decode step is one cached XLA program — zero recompiles in the
  serving loop.
- Sampling (greedy / temperature / top-k) happens in-graph on device; the
  host loop only feeds back token ids.
- Per-sequence lengths are device-side vectors: one engine step serves a
  ragged batch (right-padded prompts, different completion lengths).
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.core.dispatch import apply
from paddle_tpu.framework import random as rng
from paddle_tpu.jit.api import StaticFunction
from paddle_tpu.models.kv_cache import (
    BlockAllocator,
    PagedCacheSlot,
    StaticCacheSlot,
    cache_geometry,
    donate_pools,
    make_static_cache,
    zero_pools,
    pools_only,
)
from paddle_tpu.observability.step_profile import region
from paddle_tpu.tensor import Tensor


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def splice_carry(carry, values, mask):
    """Patch slots of the device-resident token carry without syncing it.

    ``carry`` is the ``[S]`` int32 ``next_ids`` of the last dispatched step
    (or a host-built seed); ``values`` is ``[S]`` or a broadcastable ``[1]``
    (an admission prefill's single sampled token); ``mask`` is ``[S]`` bool,
    True where ``values`` wins. Used by the dispatch-ahead scheduler to
    inject a newly admitted request's first token into the decode chain
    while earlier steps are still in flight.

    This is an eager cached op over fixed shapes (``where`` dispatches one
    XLA executable per shape/dtype signature and reuses it), so it adds no
    tracked compiled program and cannot recompile in steady state — the
    one-compiled-decode-program invariant is untouched at every
    ``dispatch_depth``."""
    return paddle.where(mask, values, carry)


def _telemetry_stats(lv, gi, pos, blk, paged: bool):
    """On-device step-telemetry block: f32[4] =
    [active-slot count, mean sampled-token entropy (nats),
     mean sampled-token max-prob, kv blocks touched].

    Pure function of tensors the compiled step already produces (logits,
    gather index, post-step cache positions, block table), so fusing it
    into the step adds no new program and no host sync — the stats array
    rides the existing drain fetch. Never feeds back into sampling, which
    keeps tokens bit-identical with telemetry on or off."""
    last = jnp.take_along_axis(
        lv, gi[:, None, None].astype(jnp.int32), axis=1)[:, 0, :]  # [B, V]
    logp = jax.nn.log_softmax(last.astype(jnp.float32), axis=-1)
    p = jnp.exp(logp)
    ent = -(p * logp).sum(axis=-1)                                 # [B]
    pmax = p.max(axis=-1)                                          # [B]
    active = (pos > 0)
    n = jnp.maximum(active.sum(), 1).astype(jnp.float32)
    occ = active.sum().astype(jnp.float32)
    mean_ent = (ent * active).sum() / n
    mean_pmax = (pmax * active).sum() / n
    if paged:
        blocks = (blk >= 0).sum().astype(jnp.float32)
    else:
        blocks = jnp.maximum(blk, 0).sum().astype(jnp.float32)
    return jnp.stack([occ, mean_ent, mean_pmax, blocks])


class SlotStep:
    """The ONE compiled serving step: model chunk (prefill of any bucketed
    width, or a single decode token per slot) + in-graph sampling at each
    sequence's last valid logit row.

    Shared kernel path for ``DecodeEngine`` (static whole-batch loop) and the
    continuous-batching scheduler (``paddle_tpu.serving``): one instance owns
    one jit program cache, so prefill buckets and the fixed-shape decode step
    each compile once and are reused across requests/admissions. The KV
    pools of ``caches`` are donated (``kv_cache.donate_pools``) — callers
    must thread them through and never reuse a pool after the call.
    Nothing else is: ``ids``, ``position_ids``, ``gather_idx`` and each
    slot's ``block_table`` / ``pos`` / ``base`` are ordinary inputs, so one
    table and one position tensor serve every layer and a constant input
    may be kept on the device across launches. ``new_caches`` carries the
    updated pools alone (``kv_cache.pools_only``): the caller states the
    table and the positions of the next launch itself.

    Carry contract (dispatch-ahead decode): ``next_ids`` is a device-
    resident ``[B]`` int32 array sampled in-graph, so a caller can feed it
    straight back as the NEXT step's ``ids`` without a host round-trip,
    reshaped to ``[B, 1]``. ``splice_carry`` patches admission tokens
    into the carry on device.

    ``donate=False`` opts out of pool donation: on TPU donation is a
    compile-time aliasing hint and composes with async dispatch, but
    XLA:CPU executes a donated call SYNCHRONOUSLY (the runtime hands the
    buffer over on the host), which would re-serialize a dispatch-ahead
    pipeline — the async scheduler trades transient double cache
    residency for overlap there."""

    # names of what the model adds to the telemetry block after its first
    # four entries (``model.step_stats()``), known once a step has traced
    extra_stat_names = ()

    def __init__(self, model, temperature: float = 0.0, top_k: int = 0,
                 donate: bool = True, telemetry: bool = True):
        self.model = model
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        # in-program telemetry (``_telemetry_stats``) is baked into the
        # compiled step at construction; it changes outputs, not programs
        self.telemetry = bool(telemetry)
        self._sf = StaticFunction(self._forward_sample, layer=model,
                                  donate_args=donate and donate_pools,
                                  name="serving.SlotStep")

    def __call__(self, ids, position_ids, caches, gather_idx):
        return self._sf(ids, position_ids, caches, gather_idx)

    @property
    def tracker_name(self) -> str:
        """This step's key in the process-wide CompileTracker."""
        return self._sf._tracker_name

    def num_programs(self):
        """Entries in the jit program cache (recompile accounting)."""
        return self._sf._jitted._cache_size()

    @property
    def state_walks(self) -> int:
        """Times the step walked the model's Layer tree for its parameter
        and buffer lists: constant while the model's structure stands."""
        return self._sf.state_walks

    def _model_call(self, ids, position_ids, caches):
        """The model-forward half of the compiled step. Subclasses override
        this to re-stage the forward (e.g. ``ShardedSlotStep`` lowers it
        under a device mesh with sharding-constraint seams) while inheriting
        the in-graph sampling and the jit program cache unchanged."""
        return self.model(ids, position_ids, caches)

    def _forward_sample(self, ids, position_ids, caches, gather_idx):
        logits, new_caches = self._model_call(ids, position_ids, caches)
        temp, k = self.temperature, self.top_k
        key = rng.next_key() if temp > 0 else None

        def pick(lv, gi):
            last = jnp.take_along_axis(
                lv, gi[:, None, None].astype(jnp.int32),
                axis=1)[:, 0, :]  # [B, V]
            l = last.astype(jnp.float32)
            if temp <= 0:
                return jnp.argmax(l, axis=-1).astype(jnp.int32)
            l = l / max(temp, 1e-6)
            if k and k > 0:
                kk = min(k, l.shape[-1])
                kth = jax.lax.top_k(l, kk)[0][..., -1:]
                l = jnp.where(l < kth, -jnp.inf, l)
            return jax.random.categorical(key, l, axis=-1).astype(jnp.int32)

        with region("sampling"):
            next_ids = apply("sample_next", pick, logits, gather_idx,
                             differentiable=False)
        stats = None
        if self.telemetry:
            c0 = new_caches[0]
            paged = hasattr(c0, "block_table")
            blk = c0.block_table if paged else c0.pos
            with region("telemetry"):
                stats = apply("step_telemetry", _telemetry_stats, logits,
                              gather_idx, c0.pos, blk,
                              differentiable=False, paged=paged)
                # a model may add its own traced counts of this call (an
                # expert layer's routed pairs): same block, same read
                names, extra = getattr(self.model, "step_stats",
                                       lambda: ((), None))()
                if names:
                    self.extra_stat_names = tuple(names)
                    stats = apply("step_telemetry_extra",
                                  lambda a, b: jnp.concatenate([a, b]),
                                  stats, extra, differentiable=False)
        return next_ids, stats, pools_only(new_caches)


class DecodeEngine:
    """Continuous-decode engine over a causal LM.

    ``model(input_ids, position_ids, caches)`` must return
    ``(logits, new_caches)`` when caches are given (GPTForCausalLM /
    LlamaForCausalLM contract). Sampling config is fixed at construction
    (it is baked into the compiled step).
    """

    def __init__(self, model, max_seq_len: int = 512,
                 temperature: float = 0.0, top_k: int = 0,
                 use_paged: bool = False, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 cache_dtype: str = "float32"):
        cfg = model.config
        self.model = model
        # each layer's KV heads and K / V row widths, as the model states
        # them (the scheduler sizes its pools from the same answer)
        self.geometry = cache_geometry(model)
        self.num_layers = len(self.geometry)
        self.num_kv_heads = self.geometry[0].kv_heads
        self.head_dim = self.geometry[0].k_dim
        self.max_seq_len = min(max_seq_len,
                               getattr(cfg, "max_position_embeddings", max_seq_len))
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.use_paged = use_paged
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.cache_dtype = cache_dtype
        # SlotStep donates the KV buffers: the decode loop threads them
        # through the compiled step and never reuses one after the call,
        # so the KV caches update in place (no 2x cache residency)
        self._step = SlotStep(model, temperature=temperature, top_k=top_k)
        self._sf = self._step._sf  # back-compat alias (recompile tests)

    # ---- cache construction -------------------------------------------

    def _dense_caches(self, batch: int) -> List[StaticCacheSlot]:
        return make_static_cache(self.num_layers, batch, self.max_seq_len,
                                 self.num_kv_heads, self.head_dim,
                                 self.cache_dtype, geometry=self.geometry)

    def _paged_caches(self, batch: int, tokens_per_seq: int):
        n_blocks = self.num_blocks
        if n_blocks is None:
            per_seq = -(-tokens_per_seq // self.block_size)
            n_blocks = batch * per_seq
        alloc = BlockAllocator(n_blocks, self.block_size)
        per_seq_blocks = [alloc.allocate(tokens_per_seq) for _ in range(batch)]
        max_blocks = max(len(b) for b in per_seq_blocks)
        table = np.full((batch, max_blocks), -1, np.int32)
        for i, blks in enumerate(per_seq_blocks):
            table[i, :len(blks)] = blks
        slots = []
        # one table and one position vector for every layer: only the
        # pools are donated to the compiled step
        table_t = paddle.to_tensor(table)
        pos_t = paddle.zeros([batch], dtype="int32")
        for g in self.geometry:
            # a window layer keeps its whole table here (one class of
            # blocks): the window is the layer's mask, not a saving
            kp, vp = zero_pools(g, n_blocks, self.block_size,
                                self.cache_dtype)
            slots.append(PagedCacheSlot(kp, vp, table_t, pos_t))
        return slots, alloc, per_seq_blocks

    # ---- serving loop --------------------------------------------------

    def generate(self, input_ids, seq_lens=None, max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None) -> List[np.ndarray]:
        """Batch generation. ``input_ids``: [B, P] right-padded prompt ids
        (ndarray or Tensor); ``seq_lens``: [B] true prompt lengths (defaults
        to full width). Returns a list of B 1-D arrays (prompt + completion,
        trimmed at EOS)."""
        was_training = self.model.training
        self.model.eval()
        try:
            ids_np = np.asarray(input_ids.numpy()
                                if isinstance(input_ids, Tensor) else input_ids)
            if ids_np.ndim == 1:
                ids_np = ids_np[None, :]
            B, P = ids_np.shape
            lens = (np.full(B, P, np.int32) if seq_lens is None
                    else np.asarray(seq_lens, np.int32))
            if P > self.max_seq_len:
                raise ValueError(
                    f"prompt width ({P}) exceeds max_seq_len "
                    f"({self.max_seq_len})")
            total = int(lens.max()) + max_new_tokens
            if total > self.max_seq_len:
                raise ValueError(
                    f"prompt+new ({total}) exceeds max_seq_len "
                    f"({self.max_seq_len})")

            # pad prompts to a length bucket to bound prefill recompiles
            Pb = min(_bucket(P), self.max_seq_len)
            if Pb > P:
                ids_np = np.pad(ids_np, ((0, 0), (0, Pb - P)))

            if self.use_paged:
                caches, alloc, blocks = self._paged_caches(
                    B, max(Pb, total))
            else:
                caches = self._dense_caches(B)

            with paddle.no_grad():
                ids = paddle.to_tensor(ids_np.astype(np.int32))
                pos_ids = paddle.to_tensor(np.arange(Pb, dtype=np.int32))
                gather = paddle.to_tensor(lens - 1)
                # the table is the caller's to state at every launch: the
                # step gives back the pools alone
                fixed = ({"block_table": caches[0].block_table}
                         if self.use_paged else {})
                next_ids, _stats, caches = self._sf(ids, pos_ids, caches,
                                                    gather)
                zero_gather = paddle.to_tensor(np.zeros(B, np.int32))

                out_tokens = [np.asarray(next_ids.numpy())]
                finished = np.zeros(B, dtype=bool)
                if eos_token_id is not None:
                    finished |= out_tokens[0] == eos_token_id
                # prefill advanced pos by the padded width; the true valid
                # length is the prompt length (pad rows are masked out)
                cur_lens = lens.copy()

                for _ in range(1, max_new_tokens):
                    if finished.all():
                        break
                    tok = paddle.reshape(next_ids, [B, 1])
                    # one upload a step, for every layer's cache position
                    # and, as [B, 1], the absolute positions for RoPE /
                    # pos-embedding
                    pos_t = paddle.to_tensor(cur_lens.copy())
                    caches = [c._replace(pos=pos_t, **fixed) for c in caches]
                    next_ids, _stats, caches = self._sf(
                        tok, paddle.reshape(pos_t, [B, 1]), caches,
                        zero_gather)
                    cur_lens += 1
                    step_np = np.asarray(next_ids.numpy())
                    if eos_token_id is not None:
                        step_np = np.where(finished, eos_token_id, step_np)
                        finished |= step_np == eos_token_id
                    out_tokens.append(step_np)

            from paddle_tpu.models.generation import trim_at_eos

            gen = np.stack(out_tokens, axis=1)  # [B, T]
            results = []
            for i in range(B):
                seq = trim_at_eos(ids_np[i, :lens[i]], gen[i], eos_token_id)
                results.append(seq.astype(np.int64))
            if self.use_paged:
                for blks in blocks:
                    alloc.free(blks)
            return results
        finally:
            if was_training:
                self.model.train()
