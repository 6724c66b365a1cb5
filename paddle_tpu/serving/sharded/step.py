"""Mesh-parallel serving step: tensor-parallel `SlotStep` + sharded KV pool.

One serving replica spans a device mesh with a single ``"tp"`` axis
(Megatron-style tensor parallelism as deployed in vLLM's TP serving
path). The design keeps every invariant the unsharded engine pinned:

- **One compiled program.** ``ShardedSlotStep`` overrides only
  ``SlotStep._model_call`` — the jit cache, donation policy, in-graph
  sampling, and the CompileTracker name are inherited, so prefill
  buckets + the fixed-shape decode step still compile exactly once and
  ``ProgramInventory`` pins zero steady-state recompiles at any tp.
- **Bit-identical tokens.** The default ``plan="exact"`` shards only
  computations whose per-element reduction order is unchanged by the
  partition: qkv/fc_in are column-sharded (each device contracts the
  FULL hidden dim for its output columns), attention and the KV pool
  are head-sharded (attention math is per-head), and activations are
  all-gathered (a pure data movement) before the replicated out_proj /
  fc_out / lm-head matmuls. No floating-point sum is ever reassociated
  across devices, so tokens match the single-device oracle bit for bit
  — the property every dispatch_depth / preemption / failover test
  asserts. ``plan="megatron"`` additionally row-shards out_proj/fc_out
  and vocab-shards the embedding (the textbook layout: less replicated
  compute, but the psum reassociates sums → float-tolerance only, and
  an argmax tie can flip a token; opt-in for real meshes where the
  all-gather seam's replicated matmuls dominate).
- **Host uploads stay tiny.** Block tables / positions / token ids are
  uncommitted host arrays; jax replicates them onto the replica's mesh
  at dispatch. Only weights and KV pools are committed — KV bytes
  split ~1/tp per chip (head dim sharded: the paged scatter/gather
  index only dim 0, so the pool partition needs no collectives).

Thread-safety: all state here is written once at construction
(mesh/plan) or by ``prepare_model``/``shard_pools`` during scheduler
``__init__`` (single-threaded, before the serving loop starts) and is
read-only afterwards — same discipline as ``SlotStep`` itself.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.core.dispatch import apply
from paddle_tpu.models import kv_cache
from paddle_tpu.models.gpt import _seq_constrain
from paddle_tpu.models.serving import SlotStep
from paddle_tpu.observability.step_profile import region
from paddle_tpu.profiler import RecordEvent

__all__ = ["ShardedSlotStep", "TensorParallelSharding",
           "shard_model_params", "plan_param_specs"]

_PLANS = ("exact", "megatron")

# KV pools are [num_blocks, block_size, kv_heads, head_dim]: shard heads
POOL_SPEC = P(None, None, "tp", None)


def plan_param_specs(model, plan: str = "exact"):
    """Map ``id(param) -> PartitionSpec`` for a GPT-family causal LM.

    Walks the model structure explicitly (not by layer type): the exact
    plan must leave the lm head replicated even though it is a
    ColumnParallelLinear, and sharding is per-role, not per-class.
    Anything not in the map stays replicated (``P()``).
    """
    if plan not in _PLANS:
        raise ValueError(f"unknown sharding plan {plan!r}; want one of {_PLANS}")
    gpt = getattr(model, "gpt", None)
    if gpt is None or not hasattr(gpt, "h"):
        raise ValueError(
            "sharded serving currently supports GPT-family models "
            "(model.gpt.h decoder stack); got "
            f"{type(model).__name__}")
    specs = {}
    for blk in gpt.h:
        # column-parallel: weight [H, out] split on out; out columns are
        # per-head blocks (qkv) / intermediate neurons (fc_in), so each
        # device still contracts the FULL hidden dim -> exact
        specs[id(blk.attn.qkv_proj.weight)] = P(None, "tp")
        if blk.attn.qkv_proj.bias is not None:
            specs[id(blk.attn.qkv_proj.bias)] = P("tp")
        specs[id(blk.mlp.fc_in.weight)] = P(None, "tp")
        if blk.mlp.fc_in.bias is not None:
            specs[id(blk.mlp.fc_in.bias)] = P("tp")
        if plan == "megatron":
            # row-parallel contractions: partial sums psum'd over tp
            # (bias stays replicated and is added AFTER the psum)
            specs[id(blk.attn.out_proj.weight)] = P("tp", None)
            specs[id(blk.mlp.fc_out.weight)] = P("tp", None)
    if plan == "megatron":
        specs[id(gpt.embeddings.word_embeddings.weight)] = P("tp", None)
        if not model.config.tie_word_embeddings:
            specs[id(model.lm_head.weight)] = P(None, "tp")
    return specs


def shard_model_params(model, mesh: Mesh, plan: str = "exact"):
    """Commit every model parameter to ``mesh`` — sharded per the plan,
    replicated otherwise. Mutates parameters in place (same
    ``_replace_value`` seam as ``mp_layers._mp_shard``); the jit entry
    collects ``param._value`` per call, so the existing compiled-step
    machinery picks the placement up with no trace changes."""
    nh = model.config.num_heads
    tp = mesh.shape["tp"]
    if nh % tp != 0:
        raise ValueError(
            f"num_heads ({nh}) must divide by tp ({tp}) for head sharding")
    specs = plan_param_specs(model, plan)
    for p in model.parameters():
        spec = specs.get(id(p), P())
        p._replace_value(
            jax.device_put(p._value, NamedSharding(mesh, spec)))


def _paged_cache_xla(q, k, v, k_pool, v_pool, block_table, pos):
    """The paged cache step in the XLA formulation BY NAME: the write, then
    attention over the gathered table. GSPMD does not partition a Pallas
    kernel, and a trace cannot see a sharding, so the gate of
    ``kv_cache._paged_attend`` could not keep its decode kernel out of here."""
    with region("kv_gather"):
        k_pool2 = kv_cache._paged_write(k_pool, k, block_table, pos)
        v_pool2 = kv_cache._paged_write(v_pool, v, block_table, pos)
    out = kv_cache._paged_attend_xla(q, k_pool2, v_pool2, block_table, pos)
    return out, k_pool2, v_pool2, pos + q.shape[1]


class ShardedSlotStep(SlotStep):
    """`SlotStep` lowered under a tp mesh.

    Re-stages the GPT serving forward through the model's OWN sublayers
    in the exact op order of ``GPTForCausalLM.forward`` (bit-identity at
    tp=1 is structural: same ops, same order — the only additions are
    ``with_sharding_constraint`` seams, which move data but never do
    arithmetic). Sampling stays in-program: logits are constrained to
    replicated before the inherited in-graph argmax/top-k, so the
    ``next_ids`` carry is replicated over the replica's mesh and the
    dispatch-ahead splice/reshape ops work unchanged.
    """

    def __init__(self, model, mesh: Mesh, plan: str = "exact",
                 temperature: float = 0.0, top_k: int = 0,
                 donate: bool = True, telemetry: bool = True):
        if plan not in _PLANS:
            raise ValueError(f"unknown sharding plan {plan!r}")
        self.mesh = mesh
        self.plan = plan
        super().__init__(model, temperature=temperature, top_k=top_k,
                         donate=donate, telemetry=telemetry)

    # ---- seams ---------------------------------------------------------

    def _seam(self, x, *spec):
        """Pin an activation's layout: ``_seam(x)`` replicates (the
        all-gather / psum seam), ``_seam(x, None, None, "tp")`` keeps a
        dim sharded. Traced inside the compiled step only."""
        ns = NamedSharding(self.mesh, P(*spec))
        with region("tp_gather"):
            return apply(
                "sharding_constraint",
                lambda v: jax.lax.with_sharding_constraint(v, ns), x)

    # ---- the composed forward -----------------------------------------

    def _model_call(self, ids, position_ids, caches):
        model = self.model
        gpt = model.gpt
        with region("embed"):
            h = gpt.embeddings(ids, position_ids)
        new_caches = []
        for blk, cache in zip(gpt.h, caches):
            h, nc = self._layer(blk, h, cache)
            new_caches.append(nc)
        with region("logits"):
            h = gpt.ln_f(h)
            logits = self._logits(model, gpt, h)
        return logits, new_caches

    def _layer(self, blk, x, cache):
        with region("attention"):
            a, nc = self._attn(blk.attn, blk.ln_1(x), cache)
            x = x + blk.dropout(a)
        with region("mlp"):
            x = x + blk.dropout(self._mlp(blk.mlp, blk.ln_2(x)))
            x = _seq_constrain(x, blk._cfg)
        return x, nc

    def _attn(self, attn, hidden, cache):
        b, s, h = hidden.shape
        qkv = attn.qkv_proj(hidden)  # [b, s, 3h], columns sharded over tp
        qkv = paddle.reshape(qkv, [b, s, attn.num_heads, 3 * attn.head_dim])
        qkv = self._seam(qkv, None, None, "tp", None)  # heads over tp
        q, k, v = paddle.split(qkv, 3, axis=-1)
        # head-sharded paged write + gather + masked attention: pool scatter
        # and block-table gather index dim 0 only, attention einsums are
        # per-head — no collective anywhere in here
        if isinstance(cache, kv_cache.PagedCacheSlot):
            out, kp2, vp2, pos2 = apply(
                "paged_cache_attention", _paged_cache_xla, q, k, v,
                cache.k_pool, cache.v_pool, cache.block_table, cache.pos)
            new_cache = kv_cache.PagedCacheSlot(kp2, vp2, cache.block_table,
                                                pos2)
        else:
            out, new_cache = kv_cache.cache_update_attend(q, k, v, cache)
        if hasattr(new_cache, "k_pool"):
            # pin the updated pools' head shard as the program OUTPUT
            # layout — otherwise GSPMD is free to replicate them and the
            # 1/tp-per-chip KV split would silently vanish
            new_cache = new_cache._replace(
                k_pool=self._seam(new_cache.k_pool, None, None, "tp", None),
                v_pool=self._seam(new_cache.v_pool, None, None, "tp", None))
        out = paddle.reshape(out, [b, s, h])
        if self.plan == "exact":
            out = self._seam(out)  # all-gather heads, then replicated matmul
            return attn.out_proj(out), new_cache
        # megatron: contract the head shard away row-parallel; bias is added
        # AFTER the psum (RowParallelLinear.forward adds it before its
        # constraint, which under GSPMD would count it tp times)
        out = paddle.matmul(out, attn.out_proj.weight)
        out = self._seam(out)  # psum of partial sums
        if attn.out_proj.bias is not None:
            out = out + attn.out_proj.bias
        return out, new_cache

    def _mlp(self, mlp, x):
        t = mlp.fc_in(x)  # [b, s, I], columns sharded over tp
        t = self._seam(t, None, None, "tp")
        t = F.gelu(t, approximate=True)
        if self.plan == "exact":
            t = self._seam(t)  # all-gather, then replicated matmul
            return mlp.fc_out(t)
        t = paddle.matmul(t, mlp.fc_out.weight)
        t = self._seam(t)
        if mlp.fc_out.bias is not None:
            t = t + mlp.fc_out.bias
        return t

    def _logits(self, model, gpt, h):
        if model.config.tie_word_embeddings:
            w = gpt.embeddings.word_embeddings.weight  # [V, H]
            logits = paddle.matmul(h, w, transpose_y=True)
        else:
            logits = model.lm_head(h)
        # replicate for in-graph sampling (gathers the vocab shard under
        # the megatron plan; a no-op layout pin under exact)
        return self._seam(logits)


class TensorParallelSharding:
    """The scheduler-facing sharding policy for one replica.

    ``ContinuousBatchingScheduler(model, cfg, sharding=...)`` calls, in
    order during ``__init__``: ``prepare_model`` (commit weights to the
    mesh), ``make_step`` (build the ``ShardedSlotStep``), and
    ``shard_pools`` (partition the paged KV pools). Duck-typed on
    purpose — the scheduler has no import edge on this module, and a
    custom policy only needs these three methods plus ``describe()``.

    Immutable after ``__init__``; safe to share with the router's
    failover/restart thread.
    """

    def __init__(self, tp: Optional[int] = None,
                 devices: Optional[Sequence] = None, plan: str = "exact"):
        if plan not in _PLANS:
            raise ValueError(f"unknown sharding plan {plan!r}; want {_PLANS}")
        if devices is None:
            if tp is None:
                raise ValueError("give tp= or devices=")
            avail = jax.devices()
            if tp > len(avail):
                raise ValueError(
                    f"tp={tp} but only {len(avail)} devices visible; on CPU "
                    f"force more with --xla_force_host_platform_device_count")
            devices = avail[:tp]
        devices = tuple(devices)
        if tp is None:
            tp = len(devices)
        if tp != len(devices):
            raise ValueError(f"tp={tp} != len(devices)={len(devices)}")
        if len({str(d) for d in devices}) != len(devices):
            raise ValueError("duplicate devices in mesh group")
        self.tp = int(tp)
        self.plan = plan
        self.mesh = Mesh(np.array(devices), ("tp",))

    # ---- scheduler hooks ----------------------------------------------

    def prepare_model(self, model):
        with RecordEvent("serving.shard_weights"):
            shard_model_params(model, self.mesh, self.plan)

    def make_step(self, model, cfg, donate: bool = True):
        return ShardedSlotStep(model, mesh=self.mesh, plan=self.plan,
                               temperature=cfg.temperature, top_k=cfg.top_k,
                               donate=donate,
                               telemetry=getattr(
                                   cfg, "enable_step_telemetry", True))

    def shard_pools(self, pools):
        """Partition the paged K/V pools' head dim over the mesh. Eager
        one-time resharding (pools are zeros at this point); block tables
        and positions are NOT touched — they stay uncommitted host
        uploads that jax replicates at dispatch."""
        kv_heads = pools[0][0].shape[2] if pools else 0
        if pools and kv_heads % self.tp != 0:
            raise ValueError(
                f"kv heads ({kv_heads}) must divide by tp ({self.tp})")
        ns = NamedSharding(self.mesh, POOL_SPEC)
        with RecordEvent("serving.shard_pool"):
            for kp, vp in pools:
                kp._replace_value(jax.device_put(kp._value, ns))
                vp._replace_value(jax.device_put(vp._value, ns))
        return pools

    # ---- introspection -------------------------------------------------

    def device_set(self) -> frozenset:
        return frozenset(self.mesh.devices.flat)

    def describe(self) -> dict:
        return {
            "tp": self.tp,
            "plan": self.plan,
            "devices": [str(d) for d in self.mesh.devices.flat],
        }
