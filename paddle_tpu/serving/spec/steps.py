"""Compiled steps for chunked prefill and speculative verification.

Both wrap an owning ``SlotStep``'s ``_model_call`` seam — the one
override point the sharded engine re-stages under its device mesh — so
chunking and verification inherit tensor-parallel lowering for free.
Each owns its own jit program cache (``StaticFunction``): the chunk
program compiles once per chunk width and the verify program once per
``[S, 1+k]`` grid, and both are pinned by the same CompileTracker /
ProgramInventory machinery as the decode step, so the
zero-steady-state-recompile invariant extends over the new programs.

Greedy-only by design: speculative acceptance compares drafts against
the model's argmax, and a chunked prefill samples its first token once
per admission (not once per chunk), so both features are gated to
``temperature == 0`` at config validation."""

from __future__ import annotations

import jax.numpy as jnp

from paddle_tpu.core.dispatch import apply
from paddle_tpu.jit.api import StaticFunction
from paddle_tpu.models.kv_cache import donate_pools, pools_only
from paddle_tpu.observability.step_profile import region

__all__ = ["ChunkPrefillStep", "SpecVerifyStep"]


def _greedy_rows(lv):
    """Greedy pick at EVERY logit row: [B, T, V] -> [B, T] int32."""
    return jnp.argmax(lv.astype(jnp.float32), axis=-1).astype(jnp.int32)


class ChunkPrefillStep:
    """One ``[1, C]`` prefill chunk of an admitted prompt.

    The chunk offset is pure data — absolute ``position_ids`` plus the
    cache ``pos`` scalar — so one compiled program serves every offset of
    every prompt at a given chunk width. Non-final chunks only write KV
    (the sampled id is discarded without a host sync); the final chunk's
    ``gather_idx`` points at the last valid row and its sampled token is
    the request's first output, exactly like a whole-prompt prefill.

    Deliberately a SEPARATE program from the admission prefill buckets:
    wrapping the model call in ``region("prefill_chunk")`` here keeps the
    step-profile attribution deterministic (the bucket programs keep
    their plain forward regions) and makes chunk device-time first-class
    in a step-profile capture."""

    def __init__(self, step, donate: bool = True):
        self._step = step
        self._sf = StaticFunction(self._forward, layer=step.model,
                                  donate_args=donate and donate_pools,
                                  name="serving.ChunkPrefill")

    def __call__(self, ids, position_ids, caches, gather_idx):
        return self._sf(ids, position_ids, caches, gather_idx)

    @property
    def tracker_name(self) -> str:
        return self._sf._tracker_name

    def num_programs(self):
        return self._sf._jitted._cache_size()

    @property
    def state_walks(self) -> int:
        return self._sf.state_walks

    def _forward(self, ids, position_ids, caches, gather_idx):
        with region("prefill_chunk"):
            logits, new_caches = self._step._model_call(
                ids, position_ids, caches)

            def pick(lv, gi):
                last = jnp.take_along_axis(
                    lv, gi[:, None, None].astype(jnp.int32),
                    axis=1)[:, 0, :]                       # [1, V]
                return jnp.argmax(last.astype(jnp.float32),
                                  axis=-1).astype(jnp.int32)

            next_ids = apply("sample_next", pick, logits, gather_idx,
                             differentiable=False)
        return next_ids, pools_only(new_caches)


class SpecVerifyStep:
    """ONE batched verification step over the slot grid: ``[S, 1+k]``
    token ids (the carry token followed by ``k`` drafts per slot) at
    positions ``pos .. pos+k``.

    Rejection sampling happens INSIDE the compiled program: the greedy
    pick at every row and the per-slot accepted-prefix length (the run of
    drafts matching the model's own argmax one position earlier) are
    computed on device and returned as one ``[S, k+2]`` int32 block —
    ``out[:, :k+1]`` are the greedy tokens, ``out[:, k+1]`` the accept
    counts — so accepted-prefix selection rides the engine's single
    existing token fetch and adds zero host syncs.

    KV safety: all ``1+k`` tokens write into the paged pool, but writes
    beyond a slot's block-table row drop in-kernel and rejected-tail
    positions are overwritten by the next step's writes at the same
    positions before any query can attend to them (causal masking hides
    positions beyond the committed ``pos``) — so a partial accept leaves
    the cache exactly as an autoregressive run would."""

    def __init__(self, step, donate: bool = True):
        self._step = step
        self._sf = StaticFunction(self._forward, layer=step.model,
                                  donate_args=donate and donate_pools,
                                  name="serving.SpecVerify")

    def __call__(self, ids, position_ids, caches):
        return self._sf(ids, position_ids, caches)

    @property
    def tracker_name(self) -> str:
        return self._sf._tracker_name

    def num_programs(self):
        return self._sf._jitted._cache_size()

    @property
    def state_walks(self) -> int:
        return self._sf.state_walks

    def _forward(self, ids, position_ids, caches):
        logits, new_caches = self._step._model_call(
            ids, position_ids, caches)
        with region("spec_verify"):

            def verify(lv, tok):
                g = _greedy_rows(lv)                       # [S, 1+k]
                # draft i (tok[:, i+1]) is accepted iff it equals the
                # greedy pick at the previous row; acceptance is the
                # leading run of matches (cumprod), counted on device
                match = (tok[:, 1:] == g[:, :-1]).astype(jnp.int32)
                acc = jnp.cumprod(match, axis=1).sum(axis=1)  # [S]
                return jnp.concatenate(
                    [g, acc.astype(jnp.int32)[:, None]], axis=1)

            out = apply("spec_verify", verify, logits, ids,
                        differentiable=False)
        return out, pools_only(new_caches)
