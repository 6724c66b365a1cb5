"""Registry of every host trace span the framework emits.

Each literal ``RecordEvent("<name>")`` under ``paddle_tpu/`` must have an
entry here carrying an **owner** (the subsystem answerable for the span —
where a profiler regression gets routed) and a **category** (the
``TracerEventType``-style grouping ``Profiler.summary()`` renders). The
``tools/check_spans.py`` lint (a tier-1 test) enforces both directions:
an emitted span missing from the manifest fails, and a manifest entry no
span emits anymore fails — the manifest can neither lag nor rot.

Call sites that build the span name at runtime (e.g. the eager collectives'
``comm.<op>``) register their FILE + name prefix in ``DYNAMIC_SPANS``; the
lint requires every non-literal ``RecordEvent(...)`` call site to appear
there, so dynamic names stay deliberate rather than accidental.
"""

from __future__ import annotations

# span name -> {owner, category}; categories match the TracerEventType
# grouping the profiler renders (UserDefined spans sit in the main table).
SPAN_MANIFEST = {
    # checkpoint subsystem
    "checkpoint.snapshot": {"owner": "checkpoint", "category": "UserDefined"},
    "checkpoint.write": {"owner": "checkpoint", "category": "UserDefined"},
    "checkpoint.commit": {"owner": "checkpoint", "category": "UserDefined"},
    "checkpoint.restore": {"owner": "checkpoint", "category": "UserDefined"},
    # data pipeline
    "dataloader.next": {"owner": "io", "category": "Dataloader"},
    "train.prefetch": {"owner": "io", "category": "Dataloader"},
    # training hot path
    "train.step": {"owner": "jit", "category": "ProfileStep"},
    "optimizer.step": {"owner": "optimizer", "category": "Optimization"},
    "offload.prefetch": {"owner": "distributed", "category": "UserDefined"},
    # eager generation
    "generation.prefill": {"owner": "models", "category": "Forward"},
    "generation.decode_step": {"owner": "models", "category": "Forward"},
    # serving scheduler
    "serving.prefill": {"owner": "serving", "category": "Forward"},
    "serving.decode_step": {"owner": "serving", "category": "Forward"},
    "serving.preempt": {"owner": "serving", "category": "UserDefined"},
    "serving.prefix_match": {"owner": "serving", "category": "UserDefined"},
    # the phases of one scheduler step(): a child lies inside its parent and
    # siblings do not overlap, so a device-idle instant has one innermost
    # owner (perfbench/harness/phases.py). serving.sampling_sync,
    # serving.spec_propose and serving.drain are ServingStall.timed()'s
    # (DYNAMIC_SPANS), as is serving.block_accounting round the capacity
    # loops; the literal one is the admission's allocation and table row
    "serving.step": {"owner": "serving", "category": "UserDefined"},
    "serving.sweep": {"owner": "serving", "category": "UserDefined"},
    "serving.admit": {"owner": "serving", "category": "UserDefined"},
    "serving.block_accounting": {"owner": "serving",
                                 "category": "UserDefined"},
    # inside serving.block_accounting: a window layer's pages that fell
    # wholly behind the window go back to their class's free list
    "serving.window_release": {"owner": "serving",
                               "category": "UserDefined"},
    "serving.stage": {"owner": "serving", "category": "UserDefined"},
    "serving.launch": {"owner": "serving", "category": "UserDefined"},
    "serving.commit": {"owner": "serving", "category": "UserDefined"},
    "serving.account": {"owner": "serving", "category": "UserDefined"},
    "serving.reload_weights": {"owner": "serving",
                               "category": "UserDefined"},
    # sharded serving (tensor-parallel mesh placement at replica build)
    "serving.shard_weights": {"owner": "serving",
                              "category": "UserDefined"},
    "serving.shard_pool": {"owner": "serving", "category": "UserDefined"},
    # multi-replica router front end
    "router.route": {"owner": "serving", "category": "UserDefined"},
    "router.failover": {"owner": "serving", "category": "UserDefined"},
    "router.reload": {"owner": "serving", "category": "UserDefined"},
    "router.journey": {"owner": "serving", "category": "UserDefined"},
    # fleet observability (timeline sampler + postmortem capture)
    "fleet.sample": {"owner": "observability", "category": "UserDefined"},
    "fleet.postmortem": {"owner": "observability",
                         "category": "UserDefined"},
    # device-side observability (HBM ledger + program inventory)
    "device.oom_forensics": {"owner": "observability",
                             "category": "UserDefined"},
    "device.program_analysis": {"owner": "observability",
                                "category": "UserDefined"},
}

# file (repo-relative, /-separated) -> name prefix of its runtime-built
# spans. One entry per non-literal RecordEvent(...) call site.
DYNAMIC_SPANS = {
    "paddle_tpu/distributed/collective.py": "comm.",
    # ServingStall.timed(phase) opens serving.<phase> round the code whose
    # time it adds to serving_host_stall_seconds{phase}
    "paddle_tpu/observability/serving_stall.py": "serving.",
}
