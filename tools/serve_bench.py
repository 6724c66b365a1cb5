#!/usr/bin/env python
"""Synthetic serving-load benchmark for the continuous-batching scheduler.

Fully offline: a seeded Poisson arrival process with mixed prompt/output
lengths drives ``paddle_tpu.serving.ContinuousBatchingScheduler`` on a tiny
GPT under ``JAX_PLATFORMS=cpu``, and the run's ``ServingMetrics`` snapshot
(TTFT/TPOT histograms, tokens/s, KV utilization/fragmentation, preemption
count) is written as one JSON artifact — the serving trajectory the perf
axis tracks across rounds.

Arrivals are measured in scheduler ITERATIONS (virtual time), not wall
seconds: the load shape is reproducible on any host speed, while the
latency histograms still record real wall time on this host.

Every load run also writes a per-request chrome-trace artifact
(``*_reqtrace.json``, request_id-correlated lifecycle spans) beside the
JSON/.prom exports; ``--observability`` runs the fully-instrumented
condition (tracing + SLO + live endpoint scraped mid-run) and the
on-vs-off overhead/token-identity measurement -> BENCH_serving_obs.json.

``--chaos`` runs the resilience suite (seeded fault-rate sweep,
fault-window recovery with token identity, cancellations, disarmed-inject
overhead budget) -> BENCH_serving_chaos.json; ``--fault-rate``/
``--cancel-rate`` run one chaos scenario at those rates.

``--replicas N`` runs the multi-replica router suite (tokens/s scaling vs
1 replica, a ``--kill-at T`` replica-kill failover drill with token
identity vs the single-replica oracle + goodput recovery-to-baseline,
prefix-affinity hit rate vs round-robin) -> BENCH_serving_router.json.

Every mode leaves a truthful artifact: a run that dies mid-bench quiesces
every live scheduler/replica and writes the partial JSON with
``"completed": false`` plus the error before re-raising.

  python tools/serve_bench.py --smoke           # fast CI check, tiny load
  python tools/serve_bench.py --requests 64 --rate 0.7 --tight-pool
  python tools/serve_bench.py --smoke --observability
  python tools/serve_bench.py --smoke --chaos
  python tools/serve_bench.py --smoke --fault-rate 0.25 --cancel-rate 0.2
  python tools/serve_bench.py --smoke --replicas 3 --kill-at 6
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import weakref

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tools.bench_io import write_bench_json  # noqa: E402

# every scheduler a bench runner constructs, so a run that dies mid-bench
# can quiesce them (drain in-flight dispatched steps, release KV) before
# the partial artifact is written — at dispatch_depth > 0 an abandoned
# pipeline would otherwise leave device work and blocks in flight
_LIVE_SCHEDS: "weakref.WeakSet" = weakref.WeakSet()

# routers a bench runner constructs: on a mid-bench death every replica
# behind every live router must quiesce too (the router-mode acceptance
# criterion: partial-artifact-on-death quiesces EVERY replica)
_LIVE_ROUTERS: "weakref.WeakSet" = weakref.WeakSet()


def _track(sched):
    _LIVE_SCHEDS.add(sched)
    return sched


def _track_router(router):
    _LIVE_ROUTERS.add(router)
    return router


def _quiesce_live_routers() -> list:
    """Crash-path cleanup for router mode: shut every live router down
    (drivers stopped, every replica scheduler drained + cancelled) and
    report per-replica leak counts. Never raises."""
    report = []
    for router in list(_LIVE_ROUTERS):
        entry = {"replicas": len(router.replicas),
                 "drained_in_flight": None, "cancelled": None,
                 "blocks_leaked": None, "error": None}
        try:
            counts = router.shutdown()
            entry.update(counts)
            leaked = 0
            for rep in router.replicas:
                sched = rep.sched
                if sched.prefix_cache is not None:
                    sched.prefix_cache.flush()
                leaked += (sched.config.total_blocks
                           - sched.allocator.num_free_blocks)
            entry["blocks_leaked"] = leaked
        except BaseException as exc:  # noqa: BLE001
            entry["error"] = f"{type(exc).__name__}: {exc}"
        report.append(entry)
    return report


def _quiesce_live_schedulers() -> list:
    """Crash-path cleanup: shut down every scheduler still alive and report
    what had to be drained. ``shutdown()`` barriers on the in-flight steps
    first (no orphaned device work), then cancels queued/running requests
    so every KV block returns to the pool; ``blocks_leaked`` must come back
    0 for each engine. Never raises — this runs inside the except handler
    that writes the ``completed: false`` artifact."""
    report = []
    for sched in list(_LIVE_SCHEDS):
        entry = {"drained_in_flight": None, "cancelled": None,
                 "blocks_leaked": None, "error": None}
        try:
            counts = sched.shutdown()
            entry.update(counts)
            if sched.prefix_cache is not None:
                sched.prefix_cache.flush()
            total = sched.config.total_blocks
            entry["blocks_leaked"] = total - sched.allocator.num_free_blocks
        except BaseException as exc:  # noqa: BLE001
            entry["error"] = f"{type(exc).__name__}: {exc}"
        report.append(entry)
    return report


def _device_observability_fields(sched, wall_s: float) -> dict:
    """Summarise ``sched.device_observability()`` into the asserted bench
    fields: KV bytes per token, decode-program bandwidth utilization, and
    the share of wall time the device spent inside decode steps."""
    dev = sched.device_observability()
    if not dev.get("enabled"):
        return {"enabled": False}
    st = dev.get("device_step_time") or {}
    step_s = st.get("step_time_s")
    steps = st.get("steps_observed") or 0
    share = (min(1.0, steps * step_s / wall_s)
             if step_s and wall_s > 0 else None)
    out = {
        "enabled": True,
        "kv_bytes_per_token": dev.get("kv_bytes_per_token"),
        "decode_steps_observed": steps,
        "decode_device_step_seconds": step_s,
        "decode_device_time_share": share,
        "serving_decode_bandwidth_util": dev.get("decode_bandwidth_util"),
        "decode_mfu": dev.get("decode_mfu"),
        "chip": dev.get("chip"),
        "memory_census_total_bytes":
            (dev.get("memory") or {}).get("total_bytes"),
    }
    prog = dev.get("decode_program")
    if isinstance(prog, dict):
        out["decode_program"] = {
            k: prog.get(k) for k in ("name", "flops", "bytes_accessed",
                                     "peak_temp_bytes")}
    return out


def run_load(num_requests: int = 16, rate: float = 0.5, seed: int = 0,
             max_num_seqs: int = 4, block_size: int = 8,
             num_blocks=None, max_seq_len: int = 64,
             prompt_lens=(4, 20), new_tokens=(4, 12),
             num_layers: int = 2, enable_tracing: bool = True,
             ttft_slo_s=None, tpot_slo_s=None,
             scrape_every: int = 0, dispatch_depth: int = 0) -> dict:
    """Run one synthetic load; returns the JSON-able artifact dict.

    ``rate`` is the mean number of arrivals per scheduler iteration.
    ``num_blocks`` (when set) tightens the KV pool below the fit-everything
    default so preemption is part of the measured trajectory.
    ``enable_tracing`` toggles request-lifecycle tracing (the token stream
    is identical either way — ``outputs_sha1`` pins it); SLO targets arm
    goodput/breach accounting; ``scrape_every > 0`` stands up the live
    endpoint and HTTP-scrapes ``/metrics`` every N iterations, the
    full-observability condition the overhead budget is measured under."""
    import hashlib

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny(num_layers=num_layers))
    cfg = SchedulerConfig(max_num_seqs=max_num_seqs,
                          max_seq_len=max_seq_len, block_size=block_size,
                          num_blocks=num_blocks,
                          enable_request_tracing=enable_tracing,
                          ttft_slo_s=ttft_slo_s, tpot_slo_s=tpot_slo_s,
                          dispatch_depth=dispatch_depth)
    sched = _track(ContinuousBatchingScheduler(model, cfg))

    rng = np.random.default_rng(seed)
    # Poisson arrivals in virtual (iteration) time, mixed lengths
    gaps = rng.exponential(1.0 / max(rate, 1e-6), num_requests)
    arrive_at = np.cumsum(gaps)
    plens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, num_requests)
    nnew = rng.integers(new_tokens[0], new_tokens[1] + 1, num_requests)
    prompts = [rng.integers(0, 1000, int(p)) for p in plens]

    stream_counts = {}

    def on_token(rid, tok):
        stream_counts[rid] = stream_counts.get(rid, 0) + 1

    endpoint = None
    n_scrapes = 0
    scrape_sample = None
    if scrape_every:
        endpoint = sched.start_endpoint()

    t0 = time.perf_counter()
    it, injected = 0, 0
    while injected < num_requests or sched.has_unfinished():
        while injected < num_requests and arrive_at[injected] <= it:
            sched.add_request(prompts[injected],
                              max_new_tokens=int(nnew[injected]),
                              on_token=on_token)
            injected += 1
        sched.step()
        it += 1
        if scrape_every and it % scrape_every == 0:
            import urllib.request

            scrape_sample = urllib.request.urlopen(
                endpoint.url + "/metrics", timeout=5).read().decode()
            n_scrapes += 1
        if it > 100000:
            raise RuntimeError("serving load did not drain")
    wall = time.perf_counter() - t0
    if endpoint is not None:
        endpoint.stop()
    # snapshot the rate metrics BEFORE roofline attribution: tokens_per_s
    # divides by metrics uptime, and the attribution's AOT cost analysis
    # would silently inflate that denominator
    snap = sched.metrics.snapshot()
    # roofline attribution BEFORE shutdown (needs the live scheduler):
    # sampled decode device-time × the decode program's cost analysis
    device_obs = _device_observability_fields(sched, wall)
    sched.shutdown()      # stop the drain thread; everything has finished

    outs = dict(sched._finished)
    assert len(outs) == num_requests, "every request must finish"
    # streaming contract: callbacks saw exactly the generated tokens
    for rid, out in outs.items():
        assert stream_counts.get(rid, 0) == len(out.generated_ids)
    # one digest over every request's full token stream, in rid order —
    # the on-vs-off token-identity oracle
    digest = hashlib.sha1()
    for rid in sorted(outs):
        digest.update(np.asarray(outs[rid].token_ids, np.int64).tobytes())

    return {
        "bench": "serving_continuous_batching",
        "config": {
            "num_requests": num_requests, "rate": rate, "seed": seed,
            "max_num_seqs": max_num_seqs, "block_size": block_size,
            "num_blocks": cfg.total_blocks, "max_seq_len": max_seq_len,
            "prompt_lens": list(prompt_lens), "new_tokens": list(new_tokens),
            "num_layers": num_layers, "enable_tracing": enable_tracing,
            "ttft_slo_s": ttft_slo_s, "tpot_slo_s": tpot_slo_s,
            "scrape_every": scrape_every, "dispatch_depth": dispatch_depth,
        },
        "iterations": it,
        "wall_s": round(wall, 3),
        "compiled_programs": sched.num_programs(),
        "compile_stats": sched.compile_stats(),
        "metrics": snap,
        "stall_seconds": sched.stall.snapshot(),
        "slo": sched.metrics.slo_snapshot(),
        "flight_recorder_tail": sched.flight.dump(last=8),
        "outputs_sha1": digest.hexdigest(),
        "device_observability": device_obs,
        "n_scrapes": n_scrapes,
        "scrape_sample": scrape_sample,
        # request-lifecycle chrome trace (request_id-correlated spans) —
        # main() writes it as a separate *_reqtrace.json artifact
        "request_trace": sched.tracer.chrome_trace(),
        "request_timelines": sched.tracer.to_json(),
        # Prometheus text exposition of the run's ServingMetrics — main()
        # writes it alongside the JSON artifact for scrape-shaped tooling
        "prometheus_text": sched.metrics.prometheus_text(),
    }


ASYNC_XLA_FLAGS = ("--xla_cpu_multi_thread_eigen=false "
                   "intra_op_parallelism_threads=1")


def _run_async_load(depth: int, num_requests: int = 32,
                    max_new_tokens: int = 8,
                    stream_flush_s: float = 0.0004) -> dict:
    """One seeded high-churn load at a given ``dispatch_depth``.

    The workload is sized so host scheduling work is a real fraction of
    each iteration (8 slots, short generations -> constant admission /
    retirement churn) and every streamed token pays a modeled client
    flush (``stream_flush_s`` — the socket-write wait a real server eats
    per token). Warmup covers every prefill bucket the measured prompts
    hit, then ``mark_steady()`` arms the zero-recompile invariant; the
    measured phase reports wall, decode TPOT, the host-stall share of
    wall, and a sha over every token stream — the cross-depth identity
    oracle."""
    import hashlib

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 2000,
                            size=int(rng.integers(12, 28))).astype(np.int64)
               for _ in range(num_requests)]
    wrng = np.random.default_rng(1)
    # warmup must compile EVERY prefill bucket the measured prompts can
    # land in (here: 16 and 32) — a post-mark_steady bucket compile would
    # trip the recompile alarm and pollute the measured wall
    warm = [wrng.integers(1, 2000, size=n).astype(np.int64)
            for n in (8, 14, 20, 27, 13, 24)]

    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny(hidden_size=256, num_layers=4,
                                    num_heads=8, vocab_size=2048))
    cfg = SchedulerConfig(max_num_seqs=8, max_seq_len=64, block_size=8,
                          max_new_tokens=max_new_tokens,
                          dispatch_depth=depth)
    sched = _track(ContinuousBatchingScheduler(model, cfg))

    def on_token(rid, tok):
        time.sleep(stream_flush_s)      # modeled per-token client flush

    for p in warm:
        sched.add_request(p)
    while sched.has_unfinished():
        sched.step()
    sched.mark_steady()

    snap0 = dict(sched.stall.snapshot())
    drain0 = sched.stall.drain_wait_seconds
    outs = {}
    t0 = time.perf_counter()
    for p in prompts:
        sched.add_request(p, on_token=on_token)
    while sched.has_unfinished():
        for o in sched.step():
            outs[o.request_id] = o.generated_ids
    wall = time.perf_counter() - t0
    device_obs = _device_observability_fields(sched, wall)
    sched.shutdown()

    assert len(outs) == num_requests, "every measured request must finish"
    digest = hashlib.sha1()
    for rid in sorted(outs):
        digest.update(np.asarray(outs[rid], np.int64).tobytes())
    snap1 = sched.stall.snapshot()
    stall = snap1["total"] - snap0["total"]
    phases = {k: round(snap1[k] - snap0[k], 6)
              for k in snap0 if k != "total"}
    toks = sum(len(v) for v in outs.values())
    cs = sched.compile_stats()
    return {
        "dispatch_depth": depth,
        "wall_s": round(wall, 4),
        "tpot_ms": round(wall / toks * 1e3, 4),
        "generated_tokens": toks,
        "host_stall_s": round(stall, 4),
        "host_stall_share_pct": round(100.0 * stall / wall, 2),
        "stall_phases_s": phases,
        "drain_wait_s": round(sched.stall.drain_wait_seconds - drain0, 4),
        "outputs_sha1": digest.hexdigest(),
        "device_observability": device_obs,
        "compile_stats": cs,
        "steady_state_recompiles": cs["steady_state_recompiles"],
    }


def run_async_sweep(depths=(0, 1, 2), repeats: int = 3,
                    num_requests: int = 32,
                    stream_flush_s: float = 0.0004,
                    out_dir: str = REPO_ROOT) -> dict:
    """The BENCH_serving_async artifact: the dispatch-ahead depth sweep.

    Per depth, ``repeats`` fresh engine runs of the same seeded load;
    best-of wall is reported (spike-immune on a shared host), and every
    run's ``outputs_sha1`` must agree both run-to-run (determinism) and
    across depths (the async engine's bit-identity guarantee) — asserted
    hard, this is a correctness oracle, not a perf number. Perf verdicts
    (host-stall share cut, TPOT) are recorded, not asserted: on a 1-core
    host the engine cannot overlap host CPU with device CPU, so the wall
    win comes from overlapping non-CPU host time (the per-token stream
    flush) with compute, and the stall-share collapse shows the same
    reattribution the chip sees. Writes ``BENCH_serving_async.json``."""
    import jax

    # AOT-cache replay corrupts XLA:CPU decode numerics (see the serving
    # test suite's _no_aot_replay fixture) — the identity oracle needs
    # every depth compiled fresh in-process
    jax.config.update("jax_enable_compilation_cache", False)

    per_depth = {}
    for d in depths:
        runs = [_run_async_load(d, num_requests=num_requests,
                                stream_flush_s=stream_flush_s)
                for _ in range(repeats)]
        shas = {r["outputs_sha1"] for r in runs}
        assert len(shas) == 1, (
            f"depth {d} nondeterministic across repeats: {sorted(shas)}")
        best = min(runs, key=lambda r: r["wall_s"])
        best = dict(best)
        best["walls_s"] = [r["wall_s"] for r in runs]
        assert best["steady_state_recompiles"] == 0, (
            f"depth {d} recompiled in steady state")
        per_depth[str(d)] = best

    base = per_depth[str(depths[0])]
    identical = all(per_depth[str(d)]["outputs_sha1"]
                    == base["outputs_sha1"] for d in depths)
    assert identical, ("token streams diverged across dispatch depths: "
                       + json.dumps({d: per_depth[str(d)]["outputs_sha1"]
                                     for d in depths}))
    deeper = [per_depth[str(d)] for d in depths if d > 0]
    best_deep = min(deeper, key=lambda r: r["tpot_ms"]) if deeper else base
    share_cut_x = (base["host_stall_share_pct"]
                   / max(best_deep["host_stall_share_pct"], 1e-9))
    tpot_gain_pct = 100.0 * (base["tpot_ms"] - best_deep["tpot_ms"]) / max(
        base["tpot_ms"], 1e-9)
    artifact = {
        "bench": "serving_async",
        "config": {
            "depths": list(depths), "repeats": repeats,
            "num_requests": num_requests,
            "stream_flush_s": stream_flush_s,
            "model": "gpt_tiny(hidden=256, layers=4, heads=8, vocab=2048)",
            "max_num_seqs": 8, "block_size": 8, "max_seq_len": 64,
            "max_new_tokens": 8, "seed": 0,
            "nproc": os.cpu_count(),
            "xla_flags": os.environ.get("XLA_FLAGS"),
        },
        "per_depth": per_depth,
        "token_identical_across_depths": identical,
        "best_async_depth": best_deep["dispatch_depth"],
        "host_stall_share_cut_x": round(share_cut_x, 2),
        "tpot_improvement_pct": round(tpot_gain_pct, 2),
        "zero_steady_state_recompiles": True,
        "within_budget": identical and share_cut_x >= 2.0
        and tpot_gain_pct > 0,
        "completed": True,
    }
    out_path = os.path.join(out_dir, "BENCH_serving_async.json")
    write_bench_json(out_path, artifact)
    artifact["artifact"] = out_path
    return artifact


def run_prefix_load(share: float, num_requests: int = 12,
                    prompt_len: int = 48, max_new: int = 6, seed: int = 0,
                    max_num_seqs: int = 4, block_size: int = 8,
                    max_seq_len: int = 128, num_layers: int = 1,
                    enable_cache: bool = True) -> dict:
    """One shared-system-prompt workload at a given prefix-share ratio.

    Every prompt is ``shared_prefix + unique_tail`` with
    ``len(shared_prefix) = share * prompt_len`` — the TTFT-dominated shape
    real deployments see (system prompts / few-shot templates). The first
    request drains alone to warm the radix tree (the steady state a long-
    running server lives in); TTFT statistics cover the remaining cohort."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny(num_layers=num_layers))
    cfg = SchedulerConfig(max_num_seqs=max_num_seqs, max_seq_len=max_seq_len,
                          block_size=block_size,
                          enable_prefix_caching=enable_cache)
    sched = _track(ContinuousBatchingScheduler(model, cfg))

    rng = np.random.default_rng(seed)
    L = int(round(share * prompt_len))
    shared = rng.integers(0, 1000, L)
    prompts = [np.concatenate([shared,
                               rng.integers(0, 1000, prompt_len - L)])
               for _ in range(num_requests)]

    # warm in TWO sequential requests: the first seeds the radix tree, the
    # second exercises the hit path so the suffix-bucket prefill program is
    # compiled before the measured cohort (steady state of a live server —
    # otherwise the one-time XLA compile lands in the first cohort TTFT)
    t0 = time.perf_counter()
    warm_rids = []
    for p in prompts[:2]:
        warm_rids.append(sched.add_request(p, max_new_tokens=max_new))
        while sched.has_unfinished():
            sched.step()
    rids = [sched.add_request(p, max_new_tokens=max_new)
            for p in prompts[2:]]
    while sched.has_unfinished():
        sched.step()
    wall = time.perf_counter() - t0

    outs = dict(sched._finished)
    assert len(outs) == num_requests, "every request must finish"
    ttfts = sorted(outs[r].ttft_s for r in rids)
    snap = sched.metrics.snapshot()
    res = {
        "share": share,
        "enable_cache": enable_cache,
        "ttft_mean_s": round(float(np.mean(ttfts)), 6),
        "ttft_p50_s": round(float(ttfts[len(ttfts) // 2]), 6),
        "ttft_max_s": round(float(ttfts[-1]), 6),
        "wall_s": round(wall, 3),
        "prefill_tokens": snap["prefill_tokens"],
        "generated_tokens": snap["generated_tokens"],
        "prefix_cache": sched.prefix_cache_stats(),
        "compile_stats": sched.compile_stats(),
        "warm_rids": warm_rids,
    }
    return res


def run_prefix_suite(ratios=(0.0, 0.5, 0.9), **kw) -> dict:
    """The BENCH_serving_prefix artifact: TTFT + hit rate per share ratio
    with the cache on, plus the cache-off baseline at the highest ratio —
    the measured TTFT reduction the radix-tree prefix cache buys."""
    share = {str(r): run_prefix_load(r, enable_cache=True, **kw)
             for r in ratios}
    top = str(max(ratios))
    baseline = run_prefix_load(max(ratios), enable_cache=False, **kw)
    on, off = share[top]["ttft_mean_s"], baseline["ttft_mean_s"]
    return {
        "bench": "serving_prefix_cache",
        "config": {"ratios": list(ratios), **kw},
        "share": share,
        "baseline_no_cache": {top: baseline},
        "ttft_reduction_pct_at_top_share":
            round(100.0 * (off - on) / off, 2) if off > 0 else 0.0,
        "prefill_tokens_saved_at_top_share":
            baseline["prefill_tokens"] - share[top]["prefill_tokens"],
    }


def run_chaos_load(num_requests: int = 12, rate: float = 0.8, seed: int = 0,
                   max_num_seqs: int = 2, block_size: int = 8,
                   num_blocks=None, max_seq_len: int = 64,
                   prompt_lens=(4, 10), new_tokens=(6, 10),
                   num_layers: int = 1,
                   fault_rate: float = 0.0, cancel_rate: float = 0.0,
                   fault_window=None,
                   fault_sites=("serving.decode_step", "serving.prefill",
                                "serving.block_alloc"),
                   deadline_s=None, max_step_faults: int = 3,
                   dispatch_depth: int = 0) -> dict:
    """One synthetic load under seeded chaos; returns the artifact dict.

    ``fault_rate`` arms a seeded ``FaultPlan`` (per-hit probability) on
    ``fault_sites`` — only inside ``fault_window`` (an iteration range)
    when given, else for the whole run. ``cancel_rate`` cancels that
    fraction of requests (seeded choice) a few iterations after arrival.
    Every request must reach a terminal state (done/cancelled/failed, or
    rejected at admission) and the KV pool must drain to fully free —
    both asserted here, so a fault that leaks ever fails the bench."""
    import hashlib
    from collections import Counter

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.resilience import FaultPlan, arm, disarm, get_injector
    from paddle_tpu.serving import (
        ContinuousBatchingScheduler,
        SchedulerConfig,
        SchedulerOverloaded,
    )

    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny(num_layers=num_layers))
    cfg = SchedulerConfig(max_num_seqs=max_num_seqs,
                          max_seq_len=max_seq_len, block_size=block_size,
                          num_blocks=num_blocks,
                          max_step_faults=max_step_faults,
                          dispatch_depth=dispatch_depth)
    sched = _track(ContinuousBatchingScheduler(model, cfg))

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / max(rate, 1e-6), num_requests)
    arrive_at = np.cumsum(gaps)
    plens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, num_requests)
    nnew = rng.integers(new_tokens[0], new_tokens[1] + 1, num_requests)
    prompts = [rng.integers(0, 1000, int(p)) for p in plens]
    # cancellation schedule from an independent seeded stream so the load
    # shape (arrivals/lengths) is identical across cancel_rate settings
    crng = np.random.default_rng(seed + 1)
    will_cancel = crng.random(num_requests) < cancel_rate
    cancel_delay = crng.integers(1, 5, num_requests)

    plan = None
    if fault_rate > 0:
        plan = FaultPlan(seed=seed)
        for site in fault_sites:
            plan.on(site, prob=fault_rate)
    window = fault_window if fault_window is not None else (0, 10 ** 9)

    tok_box = [0]
    stream_counts = {}

    def on_token(rid, tok):
        stream_counts[rid] = stream_counts.get(rid, 0) + 1
        tok_box[0] += 1

    tokens_per_it = []
    pending_cancels = []
    rejected = 0
    armed = False
    inj_snap = None
    t0 = time.perf_counter()
    it, injected = 0, 0
    try:
        while injected < num_requests or sched.has_unfinished():
            if plan is not None:
                if not armed and window[0] <= it < window[1]:
                    arm(plan)
                    armed = True
                if armed and it >= window[1]:
                    inj_snap = get_injector().snapshot()
                    disarm()
                    armed = False
            while injected < num_requests and arrive_at[injected] <= it:
                i = injected
                try:
                    rid = sched.add_request(prompts[i],
                                            max_new_tokens=int(nnew[i]),
                                            on_token=on_token,
                                            deadline_s=deadline_s)
                    if will_cancel[i]:
                        pending_cancels.append((it + int(cancel_delay[i]),
                                                rid))
                except SchedulerOverloaded:
                    rejected += 1
                injected += 1
            for entry in list(pending_cancels):
                if entry[0] <= it:
                    sched.cancel(entry[1])  # idempotent if already done
                    pending_cancels.remove(entry)
            tok_box[0] = 0
            sched.step()
            tokens_per_it.append(tok_box[0])
            it += 1
            if it > 100000:
                raise RuntimeError("chaos load did not drain")
    finally:
        if armed:
            inj_snap = get_injector().snapshot()
        disarm()
    wall = time.perf_counter() - t0
    sched.shutdown()      # stop the drain thread; everything has finished

    outs = dict(sched._finished)
    # no fault may leak a request: terminal state for every admitted one
    assert len(outs) + rejected == num_requests, (
        f"{num_requests - rejected - len(outs)} requests leaked")
    census = Counter(o.finish_reason for o in outs.values())
    # ...nor a KV block: after drain the pool is fully free again
    if sched.prefix_cache is not None:
        sched.prefix_cache.flush()
    assert sched.allocator.num_free_blocks == cfg.total_blocks, (
        f"block leak: {sched.allocator.num_free_blocks}/{cfg.total_blocks} "
        f"free after drain")

    digest = hashlib.sha1()
    for rid in sorted(outs):
        digest.update(np.asarray(outs[rid].token_ids, np.int64).tobytes())
    done = census.get("eos", 0) + census.get("length", 0)
    return {
        "bench": "serving_chaos_load",
        "config": {
            "num_requests": num_requests, "rate": rate, "seed": seed,
            "max_num_seqs": max_num_seqs, "block_size": block_size,
            "num_blocks": cfg.total_blocks, "max_seq_len": max_seq_len,
            "prompt_lens": list(prompt_lens), "new_tokens": list(new_tokens),
            "num_layers": num_layers, "fault_rate": fault_rate,
            "cancel_rate": cancel_rate,
            "fault_window": list(window) if fault_window else None,
            "fault_sites": list(fault_sites), "deadline_s": deadline_s,
            "max_step_faults": max_step_faults,
            "dispatch_depth": dispatch_depth,
        },
        "iterations": it,
        "wall_s": round(wall, 3),
        "census": dict(census),
        "rejected": rejected,
        "goodput": round(done / num_requests, 4),
        "tokens_per_iteration": tokens_per_it,
        "outputs_sha1": digest.hexdigest(),
        "fault_injection": inj_snap,
        "faults_by_site": sched.metrics.faults_snapshot(),
        "cancelled_by_cause": sched.metrics.cancelled_snapshot(),
        "health": sched.health(),
        "metrics": sched.metrics.snapshot(),
    }


def measure_inject_overhead(load_art: dict) -> dict:
    """Disarmed-injection overhead, attributed against a measured run.

    ``inject()`` unarmed is one global load + one ``is None`` test; its
    unit cost is measured in a tight loop and multiplied by the number of
    injection-point crossings the given run actually drove (1 decode-step
    + ``max_num_seqs`` block-alloc checks per iteration, 2 per prefill) —
    an upper bound pinned <1% of the run's wall by the chaos suite."""
    import time as _time

    from paddle_tpu.resilience import get_injector, inject

    assert not get_injector().armed, "overhead must be measured disarmed"
    N = 200000
    t0 = _time.perf_counter()
    for _ in range(N):
        inject("serving.decode_step")
    per_call_s = (_time.perf_counter() - t0) / N
    cfgd = load_art["config"]
    m = load_art["metrics"]
    n_calls = (load_art["iterations"] * (1 + cfgd["max_num_seqs"])
               + m["prefills"] * 2)
    overhead_pct = 100.0 * per_call_s * n_calls / max(
        load_art["wall_s"], 1e-9)
    return {
        "per_call_ns": round(per_call_s * 1e9, 1),
        "n_calls": int(n_calls),
        "overhead_pct": round(overhead_pct, 4),
        "wall_s": load_art["wall_s"],
        "within_budget": overhead_pct < 1.0,
    }


def run_chaos_suite(smoke: bool = True, out_dir: str = REPO_ROOT,
                    fault_rates=(0.0, 0.1, 0.25, 0.4),
                    cancel_rate: float = 0.25) -> dict:
    """The BENCH_serving_chaos artifact: goodput under a seeded fault-rate
    sweep, a fault-window run proving throughput recovery + token identity
    after transient storms, a cancellation run, and the disarmed-inject
    overhead budget (<1%). Writes ``BENCH_serving_chaos.json``."""
    kw = (dict(num_requests=12, rate=0.8, max_num_seqs=2, block_size=8,
               max_seq_len=64, prompt_lens=(4, 10), new_tokens=(6, 10),
               num_layers=1)
          if smoke else
          dict(num_requests=32, rate=0.6, max_num_seqs=4, block_size=8,
               max_seq_len=128, prompt_lens=(8, 24), new_tokens=(8, 16),
               num_layers=2))

    baseline = run_chaos_load(fault_rate=0.0, cancel_rate=0.0, **kw)

    sweep = {}
    for f in fault_rates:
        art = baseline if f == 0.0 else run_chaos_load(fault_rate=f, **kw)
        sweep[str(f)] = {
            "goodput": art["goodput"],
            "census": art["census"],
            "iterations": art["iterations"],
            "faults_by_site": art["faults_by_site"],
            "requests_failed": art["metrics"]["requests_failed"],
        }
    goodputs = [sweep[str(f)]["goodput"] for f in fault_rates]
    monotone = all(a >= b - 1e-9 for a, b in zip(goodputs, goodputs[1:]))

    # fault window: transient decode-step faults over a bounded iteration
    # range; retries must absorb every one (token identity vs the fault-
    # free run) and per-iteration throughput must recover after the window
    window = (4, 12) if smoke else (8, 24)
    windowed = run_chaos_load(fault_rate=0.3, fault_window=window,
                              fault_sites=("serving.decode_step",),
                              max_step_faults=6, **kw)

    def busy_median(ts):
        nz = sorted(t for t in ts if t > 0)
        return nz[len(nz) // 2] if nz else 0

    post = busy_median(windowed["tokens_per_iteration"][window[1]:])
    base = busy_median(baseline["tokens_per_iteration"])
    recovery_gap_pct = 100.0 * abs(post - base) / max(base, 1e-9)
    token_identical = (windowed["outputs_sha1"]
                       == baseline["outputs_sha1"])

    cancels = run_chaos_load(fault_rate=0.0, cancel_rate=cancel_rate, **kw)
    overhead = measure_inject_overhead(baseline)

    artifact = {
        "bench": "serving_chaos",
        "config": {**kw, "fault_rates": list(fault_rates),
                   "cancel_rate": cancel_rate,
                   "fault_window": list(window), "seed": 0},
        "goodput_vs_fault_rate": sweep,
        "goodput_monotone": monotone,
        "window_recovery": {
            "window": list(window),
            "post_window_tokens_per_it": post,
            "baseline_tokens_per_it": base,
            "recovery_gap_pct": round(recovery_gap_pct, 2),
            "recovered_within_5pct": recovery_gap_pct < 5.0,
            "token_identical_after_faults": token_identical,
            "faults": windowed["fault_injection"],
            "iterations": {"chaos": windowed["iterations"],
                           "baseline": baseline["iterations"]},
        },
        "cancellation": {
            "cancel_rate": cancel_rate,
            "census": cancels["census"],
            "cancelled_by_cause": cancels["cancelled_by_cause"],
            "goodput": cancels["goodput"],
        },
        "disarmed_inject": overhead,
        "within_budget": (monotone and token_identical
                          and recovery_gap_pct < 5.0
                          and overhead["within_budget"]),
        "completed": True,
    }
    out_path = os.path.join(out_dir, "BENCH_serving_chaos.json")
    write_bench_json(out_path, artifact)
    artifact["artifact"] = out_path
    return artifact


def run_router_load(num_replicas: int = 3, num_requests: int = 18,
                    rate: float = 1.0, seed: int = 0,
                    max_num_seqs: int = 2, block_size: int = 8,
                    max_seq_len: int = 64, num_layers: int = 1,
                    prompt_lens=(4, 12), new_tokens=(4, 8),
                    prefix_groups: int = 0, prefix_len: int = 16,
                    policy: str = "affinity",
                    kill_at=None, kill_replica: int = 0,
                    cooldown_s: float = 0.02,
                    enable_prefix_caching: bool = True,
                    router_kw=None, on_drained=None) -> dict:
    """One synthetic Poisson load through a ``ServingRouter``; returns the
    artifact dict.

    ``prefix_groups > 0`` makes requests share long prompt prefixes in
    round-robin groups (the cache-affinity workload: with ``affinity``
    routing each group pins to one replica's radix tree). ``kill_at`` (an
    iteration index) crashes ``kill_replica`` mid-run — the supervisor
    reaps it, fails its work over to survivors, and restarts it; every
    accepted request must still reach a terminal state, the dead replica's
    pool must come back leak-free, and the rid-ordered token digest is
    comparable against a 1-replica run of the same workload (greedy
    streams are placement-independent — the failover identity oracle)."""
    import hashlib
    from collections import Counter

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import (
        ContinuousBatchingScheduler,
        SchedulerConfig,
        SchedulerOverloaded,
        ServingRouter,
    )

    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny(num_layers=num_layers))

    def factory():
        return _track(ContinuousBatchingScheduler(model, SchedulerConfig(
            max_num_seqs=max_num_seqs, max_seq_len=max_seq_len,
            block_size=block_size,
            enable_prefix_caching=enable_prefix_caching)))

    router = _track_router(ServingRouter(
        factory, num_replicas=num_replicas, policy=policy,
        cooldown_s=cooldown_s, affinity_tokens=block_size,
        **(router_kw or {})))

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / max(rate, 1e-6), num_requests)
    arrive_at = np.cumsum(gaps)
    plens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, num_requests)
    nnew = rng.integers(new_tokens[0], new_tokens[1] + 1, num_requests)
    if prefix_groups > 0:
        shared = [rng.integers(0, 1000, prefix_len)
                  for _ in range(prefix_groups)]
        # seeded RANDOM group per request — a cyclic i%groups assignment
        # would accidentally align with round-robin placement and hide
        # the affinity win the suite measures
        grp = rng.integers(0, prefix_groups, num_requests)
        prompts = [np.concatenate([shared[int(grp[i])],
                                   rng.integers(0, 1000, int(p))])
                   for i, p in enumerate(plens)]
    else:
        prompts = [rng.integers(0, 1000, int(p)) for p in plens]

    tok_box = [0]
    stream_counts = {}

    def on_token(rid, tok):
        stream_counts[rid] = stream_counts.get(rid, 0) + 1
        tok_box[0] += 1

    tokens_per_it = []
    rejected = 0
    killed_at_it = None
    t0 = time.perf_counter()
    it, injected = 0, 0
    rids = []
    while injected < num_requests or router.has_unfinished():
        while injected < num_requests and arrive_at[injected] <= it:
            i = injected
            try:
                rids.append(router.submit(prompts[i],
                                          max_new_tokens=int(nnew[i]),
                                          on_token=on_token))
            except SchedulerOverloaded:
                rejected += 1
            injected += 1
        if kill_at is not None and it == kill_at:
            router.crash_replica(kill_replica)
            killed_at_it = it
        tok_box[0] = 0
        router.step()
        tokens_per_it.append(tok_box[0])
        it += 1
        if it > 100000:
            raise RuntimeError("router load did not drain")
    wall = time.perf_counter() - t0
    if on_drained is not None:
        # hook for suites that need the LIVE fleet after the drain (the
        # fleet-trace suite exports journeys and forces an alarm here —
        # after shutdown the replica tracers are no longer resolvable)
        on_drained(router)
    router.shutdown()

    outs = {rid: router.get_finished(rid) for rid in rids}
    missing = [rid for rid, o in outs.items() if o is None]
    assert not missing, f"requests without terminal state: {missing}"
    census = Counter(o.finish_reason for o in outs.values())
    # streaming across failover: callbacks saw each generated token once
    for rid, out in outs.items():
        assert stream_counts.get(rid, 0) == len(out.generated_ids), (
            f"rid {rid}: streamed {stream_counts.get(rid, 0)} vs "
            f"{len(out.generated_ids)} generated")
    # zero leaks on EVERY replica pool, the reaped-and-restarted one
    # included (its old pool was freed by export_restartable)
    for rep in router.replicas:
        sched = rep.sched
        if sched.prefix_cache is not None:
            sched.prefix_cache.flush()
        assert (sched.allocator.num_free_blocks
                == sched.config.total_blocks), (
            f"replica {rep.replica_id} leaked "
            f"{sched.config.total_blocks - sched.allocator.num_free_blocks}"
            f" blocks")

    digest = hashlib.sha1()
    for rid in sorted(outs):
        digest.update(np.asarray(outs[rid].token_ids, np.int64).tobytes())
    done = census.get("eos", 0) + census.get("length", 0)

    # aggregate prefix-cache hit rate over every replica that served
    hit = miss = 0
    for rep in router.replicas:
        pc = rep.sched.prefix_cache
        if pc is not None:
            s = pc.stats()
            hit += s["hit_tokens"]
            miss += s["miss_tokens"]
    dbg = router.debug_state()
    gen_tokens = int(router.metrics.generated_tokens)
    return {
        "bench": "serving_router_load",
        "config": {
            "num_replicas": num_replicas, "num_requests": num_requests,
            "rate": rate, "seed": seed, "max_num_seqs": max_num_seqs,
            "block_size": block_size, "max_seq_len": max_seq_len,
            "num_layers": num_layers, "prompt_lens": list(prompt_lens),
            "new_tokens": list(new_tokens), "prefix_groups": prefix_groups,
            "prefix_len": prefix_len, "policy": policy,
            "kill_at": kill_at, "kill_replica": kill_replica,
            "enable_prefix_caching": enable_prefix_caching,
        },
        "iterations": it,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(gen_tokens / wall, 2) if wall > 0 else None,
        "census": dict(census),
        "rejected": rejected,
        "goodput": round(done / num_requests, 4),
        "tokens_per_iteration": tokens_per_it,
        "killed_at_iteration": killed_at_it,
        "outputs_sha1": digest.hexdigest(),
        "prefix_cache_hit_rate": round(hit / (hit + miss), 4)
                                 if (hit + miss) else None,
        "router": dbg["router"],
        "replicas": dbg["replicas"],
        "supervisor": dbg["supervisor"],
        "faults_by_site": router.metrics.faults_snapshot(),
        "health": router.health(),
        "metrics": router.metrics.snapshot(),
    }


def _busy_median(ts):
    nz = sorted(t for t in ts if t > 0)
    return nz[len(nz) // 2] if nz else 0


def run_router_suite(smoke: bool = True, out_dir: str = REPO_ROOT,
                     num_replicas: int = 3, kill_at=None) -> dict:
    """The BENCH_serving_router artifact: multi-replica scaling vs one
    replica, a replica-kill drill (token identity vs the 1-replica oracle,
    goodput dip + recovery-to-baseline, zero leaks), and the prefix-
    affinity hit-rate win vs round-robin. Writes
    ``BENCH_serving_router.json``."""
    kw = (dict(num_requests=24, rate=1.2, max_num_seqs=2, block_size=8,
               max_seq_len=64, num_layers=1, prompt_lens=(4, 12),
               new_tokens=(5, 8))
          if smoke else
          dict(num_requests=48, rate=1.0, max_num_seqs=4, block_size=8,
               max_seq_len=128, num_layers=2, prompt_lens=(6, 24),
               new_tokens=(8, 16)))
    if kill_at is None:
        kill_at = 6 if smoke else 12

    # the single-replica oracle doubles as the scaling baseline
    single = run_router_load(num_replicas=1, policy="affinity", **kw)

    killed = run_router_load(num_replicas=num_replicas, policy="affinity",
                             kill_at=kill_at, kill_replica=0, **kw)
    token_identical = killed["outputs_sha1"] == single["outputs_sha1"]

    # goodput dip + recovery: per-iteration token throughput around the
    # kill. Recovery is the best SUSTAINED (busy-median window) post-kill
    # throughput vs the pre-kill baseline — the run's tail is drain-down
    # (arrivals exhausted, last requests finishing), which measures load,
    # not capacity; what the drill must prove is that the fleet RETURNS
    # to baseline once the restarted replica rejoins.
    ts = killed["tokens_per_iteration"]
    k = killed["killed_at_iteration"]
    pre = _busy_median(ts[:k]) if k else 0
    post_tail = _busy_median(ts[k:]) if k is not None else 0
    W = 4
    post_windows = ([_busy_median(ts[i:i + W])
                     for i in range(k, max(k + 1, len(ts) - W + 1))]
                    if k is not None else [])
    post_best = max(post_windows, default=0)
    recovery_pct = min(100.0, 100.0 * post_best / max(pre, 1e-9))
    recovery_it = None
    if k is not None and pre > 0:
        for i, m in enumerate(post_windows):
            if m >= 0.95 * pre:
                recovery_it = i
                break

    # affinity vs round-robin on a shared-prefix workload: same load, same
    # replicas, only the placement policy differs — the hit-rate gap is
    # pure routing
    akw = dict(kw)
    akw["num_requests"] = max(kw["num_requests"], 12)
    affinity = run_router_load(num_replicas=num_replicas, policy="affinity",
                               prefix_groups=num_replicas,
                               prefix_len=2 * kw["block_size"], **akw)
    rr = run_router_load(num_replicas=num_replicas, policy="round_robin",
                         prefix_groups=num_replicas,
                         prefix_len=2 * kw["block_size"], **akw)
    hit_aff = affinity["prefix_cache_hit_rate"] or 0.0
    hit_rr = rr["prefix_cache_hit_rate"] or 0.0

    artifact = {
        "bench": "serving_router",
        "config": {**kw, "num_replicas": num_replicas, "kill_at": kill_at,
                   "seed": 0},
        "scaling": {
            "tokens_per_s_1_replica": single["tokens_per_s"],
            "tokens_per_s_n_replicas": killed["tokens_per_s"],
            "speedup_x": round(killed["tokens_per_s"]
                               / max(single["tokens_per_s"], 1e-9), 3),
            "note": "CPU smoke shares one host core budget across "
                    "replicas; the number reports the router's overhead/"
                    "scaling shape, device parallelism is the TPU story",
        },
        "kill_drill": {
            "killed_at_iteration": k,
            "goodput": killed["goodput"],
            "census": killed["census"],
            "token_identical_to_single_replica": token_identical,
            "pre_kill_tokens_per_it": pre,
            "post_kill_tail_tokens_per_it": post_tail,
            "post_kill_best_window_tokens_per_it": post_best,
            "recovery_pct_of_baseline": round(recovery_pct, 2),
            "recovered_95pct": recovery_pct >= 95.0,
            "recovery_time_iterations": recovery_it,
            "failovers": killed["router"]["failovers"],
            "requests_failed_over": killed["router"]["requests_failed_over"],
            "restarts": killed["supervisor"]["restarts"],
            "breakers_after": killed["supervisor"]["breakers"],
            "replica_generations": [r["generation"]
                                    for r in killed["replicas"]],
        },
        "affinity_vs_round_robin": {
            "hit_rate_affinity": hit_aff,
            "hit_rate_round_robin": hit_rr,
            "hit_rate_win": round(hit_aff - hit_rr, 4),
            "affinity_not_worse": hit_aff >= hit_rr - 1e-9,
            "routed_decisions": affinity["router"],
        },
        "within_budget": (token_identical and recovery_pct >= 95.0
                          and killed["goodput"] == 1.0
                          and hit_aff >= hit_rr - 1e-9),
        "completed": True,
    }
    out_path = os.path.join(out_dir, "BENCH_serving_router.json")
    write_bench_json(out_path, artifact)
    artifact["artifact"] = out_path
    return artifact


def run_fleet_trace_suite(smoke: bool = True, out_dir: str = REPO_ROOT,
                          num_replicas: int = 3, kill_at=None) -> dict:
    """The BENCH_serving_fleet_trace artifact: the replica-kill drill
    re-run with journey tracing and the router's timeline sampler on.
    Exports ONE chrome trace with one track per router request spanning
    the failover (route/reap/replay spans interleaved with the resumed
    replica phase timeline, including the explicit ``failover`` phase),
    plus one postmortem bundle captured through the REAL alarm path — a
    forced flight-recorder alarm on a survivor replica, not a direct
    ``capture()`` call. Writes ``BENCH_serving_fleet_trace.json`` and the
    journey chrome artifact ``BENCH_serving_fleet_journeys.json``."""
    kw = (dict(num_requests=12, rate=1.2, max_num_seqs=2, block_size=8,
               max_seq_len=64, num_layers=1, prompt_lens=(4, 12),
               new_tokens=(5, 8))
          if smoke else
          dict(num_requests=32, rate=1.0, max_num_seqs=4, block_size=8,
               max_seq_len=128, num_layers=2, prompt_lens=(6, 24),
               new_tokens=(8, 16)))
    if kill_at is None:
        kill_at = 4 if smoke else 10

    box = {}

    def on_drained(router):
        # must run while the fleet is LIVE: export_fleet_trace resolves
        # journey segments against replica tracers, and the forced alarm
        # exercises the wired flight-callback -> router-store path
        router.replicas[-1].sched.flight.alarm(
            "ttft_breach_storm", "forced by serve_bench --replicas "
            "(artifact demonstration, not a real breach)")
        for _ in range(3):
            router.timeline.sample_once()
        box["trace"] = router.export_fleet_trace()
        box["journeys"] = router.fleet.to_json()
        box["timeline"] = router.timeline.snapshot()
        box["postmortems"] = router.postmortems.summary()
        box["bundle"] = router.postmortems.last()

    art = run_router_load(num_replicas=num_replicas, policy="affinity",
                          kill_at=kill_at, kill_replica=0,
                          router_kw={"timeline_interval_s": 0.05},
                          on_drained=on_drained, **kw)

    trace, journeys = box["trace"], box["journeys"]
    hopped = [j for j in journeys if j["failovers"] > 0]
    tids_meta = [e["tid"] for e in trace["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "thread_name"]
    failover_tids = {e["tid"] for e in trace["traceEvents"]
                     if e.get("ph") == "X" and e["name"] == "req.failover"}
    accepted = kw["num_requests"] - art["rejected"]
    journey_coverage = len(journeys) / max(accepted, 1)
    failover_coverage = (
        len(failover_tids & {j["router_rid"] for j in hopped})
        / max(len(hopped), 1))

    trace_path = os.path.join(out_dir, "BENCH_serving_fleet_journeys.json")
    with open(trace_path, "w") as f:
        json.dump(trace, f)

    bundle = box["bundle"] or {}
    artifact = {
        "bench": "serving_fleet_trace",
        "config": {**kw, "num_replicas": num_replicas, "kill_at": kill_at,
                   "seed": 0},
        "journey_trace_artifact": os.path.basename(trace_path),
        "journey_trace_events": len(trace["traceEvents"]),
        "journeys_tracked": len(journeys),
        "journey_coverage": round(journey_coverage, 4),
        "requests_failed_over": len(hopped),
        "failover_track_coverage": round(failover_coverage, 4),
        "one_track_per_request": len(tids_meta) == len(set(tids_meta))
                                 == len(journeys),
        "goodput": art["goodput"],
        "timeline": box["timeline"],
        "postmortems": box["postmortems"],
        "forced_alarm_bundle": {
            "kind": bundle.get("kind"),
            "reason": bundle.get("reason"),
            "context_keys": sorted(k for k in bundle
                                   if k not in ("seq", "kind", "reason",
                                                "t", "alarm")),
        },
        "within_budget": (journey_coverage == 1.0
                          and failover_coverage == 1.0
                          and len(hopped) > 0
                          and art["goodput"] == 1.0
                          and box["postmortems"]["captures"] >= 2
                          and box["timeline"]["samples_taken"] >= 3),
        "completed": True,
    }
    out_path = os.path.join(out_dir, "BENCH_serving_fleet_trace.json")
    write_bench_json(out_path, artifact)
    artifact["artifact"] = out_path
    return artifact


def measure_observability_overhead(**load_kw) -> dict:
    """Metrics-path overhead on the serving smoke workload.

    Runs one synthetic load, then measures the unit cost of the registry
    primitives the scheduler drives per iteration (counter inc + gauge set +
    histogram record) in a tight loop, and attributes
    ``ops_per_iteration x iterations x unit_cost`` against the measured
    wall — an upper-bound estimate of what the registry-backed metrics add
    to the serving hot loop. Pinned <5% by ``bench_observability`` and the
    tier-1 smoke test."""
    import time as _time

    from paddle_tpu.observability.metrics import MetricsRegistry

    kw = dict(num_requests=6, rate=1.0, max_num_seqs=2, block_size=8,
              max_seq_len=64, prompt_lens=(4, 10), new_tokens=(3, 6),
              num_layers=1)
    kw.update(load_kw)
    art = run_load(**kw)
    m = art["metrics"]

    reg = MetricsRegistry(namespace="ovh")
    c = reg.counter("c")
    g = reg.gauge("g")
    h = reg.histogram("h")
    iters = 20000
    t0 = _time.perf_counter()
    for i in range(iters):
        c.inc()
        g.set(i)
        h.record(0.001 * i)
    per_op_s = (_time.perf_counter() - t0) / (3 * iters)

    # per scheduler iteration: 1 step_time record + 6 gauge sets + 1
    # device-time sampler observe; per token: ~2 counter incs; per
    # prefill: 2; per finish: 2 histogram records + 1
    n_ops = (art["iterations"] * 8
             + m["generated_tokens"] * 2
             + m["prefills"] * 2
             + m["requests_finished"] * 3)
    metrics_s = per_op_s * n_ops
    overhead_pct = 100.0 * metrics_s / max(art["wall_s"], 1e-9)
    return {
        "overhead_pct": round(overhead_pct, 3),
        "per_op_ns": round(per_op_s * 1e9, 1),
        "n_ops": int(n_ops),
        "metrics_s": round(metrics_s, 6),
        "wall_s": art["wall_s"],
        "iterations": art["iterations"],
    }


def measure_tracing_overhead(repeats: int = 2, **load_kw) -> dict:
    """Full-observability overhead on the serving smoke workload.

    Runs the same seeded load with observability OFF (no request tracing,
    no SLO, no endpoint) and ON (tracing + SLO accounting + live endpoint
    scraped every 4 iterations), ``repeats`` times each, and reports:

    - ``token_identical``: every run's ``outputs_sha1`` matches — tracing
      must never perturb the token stream (the hard guarantee);
    - ``measured_overhead_pct``: p50 step-time regression ON vs OFF,
      min over ``repeats`` interleaved paired trials. Min-of-pairs is the
      spike-immune estimator: scheduling noise (GIL hand-offs around the
      scrape handler thread, host load) only ever INFLATES a trial, while
      a real per-step regression shows in every pair — asserted <5% by
      ``bench_observability``;
    - ``attributed_overhead_pct``: deterministic upper bound — unit cost
      of each observability primitive (trace transition/sub-span, stall
      record, flight record, SLO judgement) measured in a tight loop,
      times the op counts the run actually drove, against the run's wall
      (the tier-1 test asserts THIS, wall-noise-proof).
    """
    import time as _time

    from paddle_tpu.observability import (
        FlightRecorder,
        MetricsRegistry,
        RequestTracer,
        ServingStall,
    )

    kw = dict(num_requests=8, rate=0.5, max_num_seqs=2, block_size=8,
              max_seq_len=64, prompt_lens=(4, 10), new_tokens=(12, 20),
              num_layers=1)
    kw.update(load_kw)
    run_load(**kw)                     # warm the process (first-run costs)
    runs = {"off": [], "on": []}
    pair_pcts = []
    for _ in range(max(repeats, 1)):
        pair = {}
        for mode in ("off", "on"):
            on = mode == "on"
            art = run_load(
                enable_tracing=on,
                ttft_slo_s=0.5 if on else None,
                tpot_slo_s=0.5 if on else None,
                scrape_every=4 if on else 0, **kw)
            runs[mode].append(art)
            pair[mode] = art["metrics"]["step_time_s"]["p50"]
        pair_pcts.append(100.0 * (pair["on"] - pair["off"])
                         / max(pair["off"], 1e-12))
    digests = {a["outputs_sha1"] for m in runs for a in runs[m]}
    token_identical = len(digests) == 1
    p50 = {m: min(a["metrics"]["step_time_s"]["p50"] for a in runs[m])
           for m in runs}
    measured_pct = min(pair_pcts)

    # ---- deterministic attribution: unit cost x op count ---------------
    N = 20000
    tracer = RequestTracer()
    tr = tracer.start(0)
    t0 = _time.perf_counter()
    for i in range(N):
        tr.transition("admit" if i % 2 else "running")
    transition_s = (_time.perf_counter() - t0) / N
    tr.phases.clear()
    t0 = _time.perf_counter()
    for _ in range(N):
        tr.subspan("prefill", 0.001)
    subspan_s = (_time.perf_counter() - t0) / N
    stall = ServingStall(MetricsRegistry(namespace="ovh"))
    t0 = _time.perf_counter()
    for _ in range(N):
        stall.record("admission", 0.0)
    stall_s = (_time.perf_counter() - t0) / N
    flight = FlightRecorder(256)
    t0 = _time.perf_counter()
    for i in range(N):
        flight.record_step(running=2, queue_depth=1, free_blocks=4,
                           prefill_tokens=0, generated_tokens=2,
                           preemptions=0, cache_hit_tokens=0,
                           evicted_blocks=0, finished=0)
    flight_s = (_time.perf_counter() - t0) / N

    # fleet-layer primitives (router journeys, timeline sampler,
    # postmortem capture) — charged at the rates a fleet-on deployment
    # drives them: one journey per request, a 1 Hz sampler over the wall,
    # one alarm-triggered bundle per run
    from paddle_tpu.observability import (
        FleetTracer,
        MetricsTimeline,
        PostmortemStore,
    )

    ft = FleetTracer()
    t0 = _time.perf_counter()
    for i in range(N):
        ft.start(i, replica_id=0, generation=0, replica_rid=i,
                 decision="least_loaded")
        ft.finish(i)
    journey_s = (_time.perf_counter() - t0) / N
    M = 2000
    tl = MetricsTimeline()
    tl.add_source("bench", lambda: {"depth": 1.0, "nested": {"v": 2.0}})
    t0 = _time.perf_counter()
    for _ in range(M):
        tl.sample_once()
    sample_s = (_time.perf_counter() - t0) / M
    pm = PostmortemStore(max_bundles=4)
    pm.add_context("bench", lambda: {"state": 1})
    t0 = _time.perf_counter()
    for _ in range(M):
        pm.capture("bench", "unit-cost loop", force=True)
    capture_s = (_time.perf_counter() - t0) / M

    art = min(runs["on"], key=lambda a: a["wall_s"])
    m = art["metrics"]
    n_ops = {
        # per iteration: 1 flight record + 4 explicit stall records
        "flight": art["iterations"],
        "stall": art["iterations"] * 4 + m["prefills"] * 5,
        # per admission: queued->admit->running (+done at finish); resume
        # re-admissions ride the prefills count too
        "transition": m["prefills"] * 2 + m["requests_finished"],
        "subspan": m["prefills"] * 3,
        "journey": m["requests_finished"],
        "timeline_sample": int(art["wall_s"]) + 1,
        "postmortem_capture": 1,
    }
    attributed_s = (n_ops["flight"] * flight_s + n_ops["stall"] * stall_s
                    + n_ops["transition"] * transition_s
                    + n_ops["subspan"] * subspan_s
                    + n_ops["journey"] * journey_s
                    + n_ops["timeline_sample"] * sample_s
                    + n_ops["postmortem_capture"] * capture_s)
    # endpoint scrapes happen between steps: charge their measured wall
    scrape_s = 0.0
    if art["n_scrapes"]:
        import urllib.request

        from paddle_tpu.observability import ObservabilityEndpoint

        with ObservabilityEndpoint() as ep:
            t0 = _time.perf_counter()
            for _ in range(20):
                urllib.request.urlopen(ep.url + "/metrics",
                                       timeout=5).read()
            scrape_s = art["n_scrapes"] * (_time.perf_counter() - t0) / 20
    attributed_pct = 100.0 * (attributed_s + scrape_s) / max(
        art["wall_s"], 1e-9)
    return {
        "token_identical": token_identical,
        "outputs_sha1": sorted(digests),
        "measured_overhead_pct": round(measured_pct, 2),
        "pair_pcts": [round(p, 2) for p in pair_pcts],
        "attributed_overhead_pct": round(attributed_pct, 3),
        "p50_step_s": {k: round(v, 6) for k, v in p50.items()},
        "unit_ns": {"transition": round(transition_s * 1e9, 1),
                    "subspan": round(subspan_s * 1e9, 1),
                    "stall_record": round(stall_s * 1e9, 1),
                    "flight_record": round(flight_s * 1e9, 1),
                    "journey": round(journey_s * 1e9, 1),
                    "timeline_sample": round(sample_s * 1e9, 1),
                    "postmortem_capture": round(capture_s * 1e9, 1)},
        "n_ops": n_ops,
        "n_scrapes": art["n_scrapes"],
        "wall_s": art["wall_s"],
        "repeats": repeats,
    }


def run_observability_suite(smoke: bool = True, out_dir: str = REPO_ROOT,
                            repeats: int = 3) -> dict:
    """The BENCH_serving_obs artifact: one fully-instrumented serving run
    (tracing + SLO + live endpoint scraped mid-flight) demonstrating the
    host-stall breakdown, per-request lifecycle traces, and a real
    ``/metrics`` scrape, plus the on-vs-off overhead/token-identity
    measurement. Writes ``BENCH_serving_obs.json`` and the request-trace
    chrome artifact ``BENCH_serving_obs_reqtrace.json``."""
    kw = (dict(num_requests=10, rate=0.8, max_num_seqs=2, block_size=8,
               max_seq_len=64, prompt_lens=(4, 12), new_tokens=(4, 8),
               num_layers=1)
          if smoke else
          dict(num_requests=32, rate=0.6, max_num_seqs=4, block_size=8,
               max_seq_len=128, prompt_lens=(8, 40), new_tokens=(8, 24),
               num_layers=2))
    art = run_load(enable_tracing=True, ttft_slo_s=0.25, tpot_slo_s=0.25,
                   scrape_every=4, **kw)
    overhead = measure_tracing_overhead(repeats=repeats)
    trace = art.pop("request_trace")
    reqtrace_path = os.path.join(out_dir, "BENCH_serving_obs_reqtrace.json")
    with open(reqtrace_path, "w") as f:
        json.dump(trace, f)
    scrape = art.pop("scrape_sample") or ""
    artifact = {
        "bench": "serving_observability",
        "config": art["config"],
        "stall_seconds": art["stall_seconds"],
        "slo": art["slo"],
        "flight_recorder_tail": art["flight_recorder_tail"],
        "request_timelines": art["request_timelines"],
        "request_trace_artifact": os.path.basename(reqtrace_path),
        "request_trace_events": len(trace["traceEvents"]),
        "metrics_scrape": {
            "n_scrapes": art["n_scrapes"],
            "lines": len(scrape.splitlines()),
            "excerpt": [ln for ln in scrape.splitlines()
                        if "host_stall" in ln or "goodput" in ln
                        or "slo_breach" in ln],
        },
        "overhead": overhead,
        "within_budget": (overhead["token_identical"]
                          and overhead["measured_overhead_pct"] < 5.0),
        "metrics": art["metrics"],
        "completed": True,
    }
    out_path = os.path.join(out_dir, "BENCH_serving_obs.json")
    write_bench_json(out_path, artifact)
    artifact["artifact"] = out_path
    return artifact


def run_stepprofile_load(steps: int = 6, num_layers: int = 2,
                         max_num_seqs: int = 4, dispatch_depth: int = 0,
                         seed: int = 0, telemetry: bool = True,
                         decode_tokens: int = 48, chunk_size: int = 0,
                         spec_k: int = 0, storm: int = 0) -> dict:
    """One seeded serving load held in steady decode while the scheduler's
    StepProfiler captures ``steps`` iterations (``steps=0`` skips the
    capture — the telemetry-invariant conditions). The grid is filled and
    every admission retired BEFORE the capture window so the traced steps
    are pure decode — the program whose region shares the artifact gates.

    ``chunk_size``/``spec_k`` turn the serving/spec/ subsystem on;
    ``storm`` injects that many long prompts right before the capture so
    the traced window contains live ``prefill_chunk`` and ``spec_verify``
    executions (one slot is kept free for them), with every program shape
    warmed beforehand so the capture still compiles nothing."""
    import hashlib

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny(num_layers=num_layers))
    cfg = SchedulerConfig(max_num_seqs=max_num_seqs, max_seq_len=64,
                          block_size=8, dispatch_depth=dispatch_depth,
                          enable_step_telemetry=telemetry,
                          prefill_chunk_size=chunk_size, spec_k=spec_k)
    sched = _track(ContinuousBatchingScheduler(model, cfg))
    rng = np.random.default_rng(seed)
    if spec_k:
        # repetitive continuations: the n-gram proposer keeps proposing,
        # so the capture window is verify steps, not fallback decode
        pats = [rng.integers(2, 40, 5) for _ in range(max_num_seqs)]
        prompts = [np.concatenate([p, p]) for p in pats]
    else:
        prompts = [rng.integers(0, 1000, int(n))
                   for n in rng.integers(4, 12, max_num_seqs)]
    if chunk_size or spec_k:
        # warm the chunk/fallback/verify programs SEQUENTIALLY (a random
        # context alone exercises the no-proposal [S,1] fallback; the
        # repetitive slots below warm the verify grid) so neither the
        # capture nor the post-capture drain compiles anything
        sched.add_request(rng.integers(0, 1000, 20), max_new_tokens=4)
        while sched.has_unfinished():
            sched.step()
    n_base = max_num_seqs - 1 if storm else max_num_seqs
    for p in prompts[:n_base]:
        sched.add_request(p, max_new_tokens=decode_tokens)
    for _ in range(max_num_seqs + 2):     # admit everything: grid full
        sched.step()
    if storm:
        # long prompts landing NOW: their chunked prefill runs inside
        # the captured steps through the spare slot
        for _ in range(storm):
            sched.add_request(rng.integers(0, 1000, 48), max_new_tokens=4)
    programs_before = sched.num_programs()
    t0 = time.perf_counter()
    summary = (sched.capture_step_profile(steps=steps)
               if steps > 0 else None)
    capture_s = time.perf_counter() - t0
    while sched.has_unfinished():
        sched.step()
    telemetry_snap = sched.telemetry_snapshot()
    spec_stats = sched.spec_stats()
    programs_after = sched.num_programs()
    outs = dict(sched._finished)
    digest = hashlib.sha1()
    for rid in sorted(outs):
        digest.update(np.asarray(outs[rid].token_ids, np.int64).tobytes())
    sched.shutdown()
    return {
        "config": {"steps": steps, "num_layers": num_layers,
                   "max_num_seqs": max_num_seqs,
                   "dispatch_depth": dispatch_depth, "seed": seed,
                   "telemetry": telemetry,
                   "decode_tokens": decode_tokens,
                   "chunk_size": chunk_size, "spec_k": spec_k,
                   "storm": storm},
        "capture": summary,
        "capture_s": round(capture_s, 3),
        "telemetry": telemetry_snap,
        "spec_stats": spec_stats,
        "programs_before_capture": programs_before,
        "programs_after": programs_after,
        "outputs_sha1": digest.hexdigest(),
    }


# the decode regions the stepprofile artifact promotes to first-class
# gate fields (bench_compare reports region_share_* leaves)
STEPPROFILE_GATED_REGIONS = ("kv_gather", "attention", "mlp", "sampling")
# chunked-prefill / spec-verify regions, gated from the second capture
# (the one run with the serving/spec/ subsystem on and a storm in-window)
STEPPROFILE_SPEC_REGIONS = ("prefill_chunk", "spec_verify")


def run_stepprofile_suite(steps: int = 6, smoke: bool = True,
                          out_dir: str = REPO_ROOT, seed: int = 0) -> dict:
    """The BENCH_serving_stepprofile artifact: in-step named-region
    attribution of the compiled decode program.

    One captured run (device trace around ``steps`` scheduler steps →
    per-region device-time shares + the region-decomposed decode
    roofline + the zero-sync telemetry block), plus the invariant
    conditions the ISSUE pins: telemetry on vs off at dispatch_depth 0
    and 2 — token streams bit-identical, compiled-program count
    unchanged, and the capture itself must not have compiled anything."""
    layers = 1 if smoke else 2
    seqs = 2 if smoke else 4
    base = run_stepprofile_load(steps=steps, num_layers=layers,
                                max_num_seqs=seqs, dispatch_depth=0,
                                seed=seed, telemetry=True)
    summary = base["capture"] or {}
    shares = summary.get("region_shares", {})

    # second capture with chunked prefill + speculative decoding ON and
    # a prompt storm landing inside the traced window: the new
    # prefill_chunk / spec_verify regions must attribute first-class
    spec_base = run_stepprofile_load(steps=steps, num_layers=layers,
                                     max_num_seqs=2, dispatch_depth=0,
                                     seed=seed, telemetry=True,
                                     decode_tokens=24, chunk_size=16,
                                     spec_k=3, storm=2)
    spec_sum = spec_base["capture"] or {}
    spec_shares = spec_sum.get("region_shares", {})
    spec_groups = spec_sum.get("group_shares", {})
    # prefill_chunk wraps the whole chunk forward, so its model-internal
    # ops attribute to nested leaves (attention/mlp/...) under the
    # prefill_chunk GROUP; the leaf share carries only the chunk's own
    # ops — first-class means present under either view
    spec_region = {r: max(spec_shares.get(r, 0.0), spec_groups.get(r, 0.0))
                   for r in STEPPROFILE_SPEC_REGIONS}
    spec_capture_compiled = (spec_base["programs_after"]
                             != spec_base["programs_before_capture"])

    invariants = {}
    for depth in (0, 2):
        pair = {}
        for tele in (True, False):
            art = run_stepprofile_load(steps=0, num_layers=layers,
                                       max_num_seqs=seqs,
                                       dispatch_depth=depth, seed=seed,
                                       telemetry=tele, decode_tokens=12)
            pair[tele] = art
        invariants[f"depth{depth}"] = {
            "token_identical":
                pair[True]["outputs_sha1"] == pair[False]["outputs_sha1"],
            "programs_equal": (pair[True]["programs_after"]
                               == pair[False]["programs_after"]),
            "programs": {"on": pair[True]["programs_after"],
                         "off": pair[False]["programs_after"]},
            "telemetry_on": pair[True]["telemetry"],
        }
    inv_ok = all(v["token_identical"] and v["programs_equal"]
                 for v in invariants.values())
    capture_compiled = (base["programs_after"]
                        != base["programs_before_capture"])

    artifact = {
        "bench": "serving_stepprofile",
        "config": {"steps": steps, "smoke": smoke, "seed": seed,
                   "num_layers": layers, "max_num_seqs": seqs},
        # first-class gate fields (bench_compare reads these leaves)
        "region_coverage": summary.get("coverage", 0.0),
        **{f"region_share_{r}": shares.get(r, 0.0)
           for r in STEPPROFILE_GATED_REGIONS},
        **{f"region_share_{r}": spec_region.get(r, 0.0)
           for r in STEPPROFILE_SPEC_REGIONS},
        "spec_capture": {
            "region_coverage": spec_sum.get("coverage", 0.0),
            "region_shares": spec_shares,
            "group_shares": spec_groups,
            "spec_stats": spec_base["spec_stats"],
            "capture_enabled": bool(spec_sum.get("enabled")),
            "capture_error": spec_sum.get("error"),
            "capture_compiled_programs": spec_capture_compiled,
            "programs": spec_base["programs_after"],
        },
        "region_shares": shares,
        "group_shares": summary.get("group_shares", {}),
        "aux_modules": summary.get("aux_modules", {}),
        "decode_roofline": summary.get("decode_roofline"),
        "primary_program": summary.get("primary_program"),
        "capture_enabled": bool(summary.get("enabled")),
        "capture_error": summary.get("error"),
        "capture_s": base["capture_s"],
        "trace_events": summary.get("trace_events"),
        "telemetry": base["telemetry"],
        "telemetry_invariants": invariants,
        "capture_compiled_programs": capture_compiled,
        "within_budget": (
            bool(summary.get("enabled"))
            and summary.get("coverage", 0.0) >= 0.9
            and all(shares.get(r, 0.0) > 0.0
                    for r in STEPPROFILE_GATED_REGIONS)
            and bool(spec_sum.get("enabled"))
            and spec_sum.get("coverage", 0.0) >= 0.9
            and all(spec_region.get(r, 0.0) > 0.0
                    for r in STEPPROFILE_SPEC_REGIONS)
            and inv_ok and not capture_compiled
            and not spec_capture_compiled),
        "completed": True,
    }
    out_path = os.path.join(out_dir, "BENCH_serving_stepprofile.json")
    write_bench_json(out_path, artifact)
    artifact["artifact"] = out_path
    return artifact


# ------------------------------------------------------------------------
# chunked prefill + speculative decoding (paddle_tpu/serving/spec/)

def _run_storm_load(chunk_size: int = 0, spec_k: int = 0,
                    num_decoders: int = 2, num_storm: int = 3,
                    storm_prompt_len: int = 96, decode_tokens: int = 48,
                    num_layers: int = 2, seed: int = 0) -> dict:
    """One prefill-storm trajectory: ``num_decoders`` short-prompt
    requests decode continuously while ``num_storm`` long prompts land
    mid-run through the one spare slot. The decoder cohort's inter-token
    gap distribution IS the bubble measurement: an unchunked admission
    prefills a storm prompt in one long compiled call between decode
    steps (every decoder stalls behind it), a chunked admission amortizes
    the same work over bounded ``[1, C]`` chunk steps. Every program
    shape is warmed on a throwaway request pair and ``mark_steady()``
    pins the rest of the run, so the gaps measure steady-state
    scheduling — the artifact also records that zero steady-state
    recompiles happened with the features on."""
    import hashlib

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny(num_layers=num_layers))
    cfg = SchedulerConfig(max_num_seqs=num_decoders + 1, max_seq_len=128,
                          block_size=8, prefill_chunk_size=chunk_size,
                          spec_k=spec_k)
    sched = _track(ContinuousBatchingScheduler(model, cfg))
    rng = np.random.default_rng(seed)
    # repetitive decoder prompts (greedy continuations an n-gram proposer
    # can predict — the spec_k identity leg exercises real accepts)
    pat = rng.integers(2, 40, 8)
    decoders = [np.concatenate([pat, pat]) for _ in range(num_decoders)]
    storms = [rng.integers(0, 1000, storm_prompt_len)
              for _ in range(num_storm)]

    # warm every program shape out-of-band, then pin the measured phase
    # as steady. Sequential on purpose: the random-context request runs
    # ALONE so its no-proposal steps exercise the [S,1] fallback program
    # (a concurrent repetitive slot would keep proposals flowing and
    # leave it cold), then the repetitive one warms the verify grid.
    sched.add_request(rng.integers(0, 1000, storm_prompt_len),
                      max_new_tokens=4)
    while sched.has_unfinished():
        sched.step()
    sched.add_request(np.concatenate([pat, pat]), max_new_tokens=6)
    while sched.has_unfinished():
        sched.step()
    sched.mark_steady()

    stamps = {}

    def on_token(rid, tok):
        stamps.setdefault(rid, []).append(time.perf_counter())

    dec_rids = [sched.add_request(p, max_new_tokens=decode_tokens,
                                  on_token=on_token) for p in decoders]
    for _ in range(num_decoders + 3):   # cohort reaches steady decode
        sched.step()
    storm_t0 = time.perf_counter()
    for p in storms:
        sched.add_request(p, max_new_tokens=4)
    it = 0
    while sched.has_unfinished():
        sched.step()
        it += 1
        if it > 100000:
            raise RuntimeError("storm load did not drain")
    wall = time.perf_counter() - storm_t0
    snap = sched.metrics.snapshot()
    cs = sched.compile_stats()
    spec = sched.spec_stats()
    sched.shutdown()

    outs = dict(sched._finished)
    digest = hashlib.sha1()
    for rid in sorted(outs):
        digest.update(np.asarray(outs[rid].token_ids, np.int64).tobytes())
    # decoder inter-token gaps observed AFTER the storm landed — the
    # window where an unchunked engine's prefill bubble shows up
    gaps = []
    for rid in dec_rids:
        ts = [t for t in stamps.get(rid, ())]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if b > storm_t0)
    gaps_ms = sorted(g * 1e3 for g in gaps)

    def pct(p):
        if not gaps_ms:
            return None
        return round(gaps_ms[min(len(gaps_ms) - 1,
                                 int(p * (len(gaps_ms) - 1)))], 4)

    tpots = [outs[r].tpot_s for r in dec_rids
             if outs[r].tpot_s is not None]
    return {
        "config": {"chunk_size": chunk_size, "spec_k": spec_k,
                   "num_decoders": num_decoders, "num_storm": num_storm,
                   "storm_prompt_len": storm_prompt_len,
                   "decode_tokens": decode_tokens,
                   "num_layers": num_layers, "seed": seed},
        "wall_s": round(wall, 3),
        "iterations": it,
        "decoder_gap_p50_ms": pct(0.50),
        "decoder_gap_p95_ms": pct(0.95),
        "decoder_gap_max_ms": pct(1.0),
        "decoder_tpot_ms": (round(sum(tpots) / len(tpots) * 1e3, 4)
                            if tpots else None),
        "gap_samples": len(gaps_ms),
        "metrics": {k: snap[k] for k in
                    ("prefills", "prefill_tokens", "decode_steps",
                     "generated_tokens", "preemptions") if k in snap},
        "compile_stats": cs,
        "compiled_programs": sched.num_programs(),
        "spec_stats": spec,
        "outputs_sha1": digest.hexdigest(),
    }


def run_chunked_suite(chunk_size: int = 16, smoke: bool = True,
                      out_dir: str = REPO_ROOT, seed: int = 0,
                      spec_k: int = 3) -> dict:
    """BENCH_serving_chunked.json: the prefill-bubble kill, measured.

    Three runs of the same seeded prefill-storm workload — unchunked
    baseline, chunked, and chunked+speculative — pinning (a) bit-identical
    token streams across all three (the subsystem's token-identity
    contract), (b) the decoder cohort's worst inter-token gap cut by
    chunking (the bubble is bounded by the chunk width instead of the
    longest admitted prompt), and (c) zero steady-state recompiles with
    the features on."""
    kw = dict(num_decoders=2, num_storm=2 if smoke else 3,
              storm_prompt_len=96, decode_tokens=32 if smoke else 48,
              num_layers=2, seed=seed)
    off = _run_storm_load(chunk_size=0, spec_k=0, **kw)
    on = _run_storm_load(chunk_size=chunk_size, spec_k=0, **kw)
    both = _run_storm_load(chunk_size=chunk_size, spec_k=spec_k, **kw)

    identical = (off["outputs_sha1"] == on["outputs_sha1"]
                 == both["outputs_sha1"])
    gap_cut = (off["decoder_gap_max_ms"] / on["decoder_gap_max_ms"]
               if on["decoder_gap_max_ms"] else None)
    p95_cut = (off["decoder_gap_p95_ms"] / on["decoder_gap_p95_ms"]
               if on["decoder_gap_p95_ms"] else None)
    recompiles = (on["compile_stats"]["steady_state_recompiles"]
                  + both["compile_stats"]["steady_state_recompiles"])
    artifact = {
        "bench": "serving_chunked",
        "config": {"chunk_size": chunk_size, "spec_k": spec_k,
                   "smoke": smoke, "seed": seed, **kw},
        "unchunked": off,
        "chunked": on,
        "chunked_plus_spec": both,
        "token_identical": identical,
        "decoder_gap_max_cut_x": (round(gap_cut, 3)
                                  if gap_cut is not None else None),
        "decoder_gap_p95_cut_x": (round(p95_cut, 3)
                                  if p95_cut is not None else None),
        "steady_state_recompiles": recompiles,
        # the bubble cut must show in the gap tail (max OR p95: the CPU
        # smoke's tiny model leaves little compute headroom, and one
        # noisy max sample must not flip the gate)
        "within_budget": (identical and recompiles == 0
                          and ((gap_cut or 0) > 1.0
                               or (p95_cut or 0) > 1.0)),
        "completed": True,
    }
    out_path = os.path.join(out_dir, "BENCH_serving_chunked.json")
    write_bench_json(out_path, artifact)
    artifact["artifact"] = out_path
    return artifact


def _run_spec_load(spec_k: int, num_requests: int = 4,
                   max_new: int = 32, num_layers: int = 2,
                   seed: int = 0) -> dict:
    """One seeded repetitive-continuation workload (the n-gram proposer's
    favorable regime) at a given draft depth; ``spec_k=0`` is the
    autoregressive baseline. Two batches with ``mark_steady()`` between
    them pin zero steady-state recompiles; decode_steps counts every
    device step, so the cross-k step reduction is the compile-independent
    win measurement."""
    import hashlib

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny(num_layers=num_layers))
    cfg = SchedulerConfig(max_num_seqs=2, max_seq_len=64, block_size=8,
                          spec_k=spec_k)
    sched = _track(ContinuousBatchingScheduler(model, cfg))
    rng = np.random.default_rng(seed)
    pats = [rng.integers(2, 40, 6) for _ in range(num_requests)]
    prompts = [np.concatenate([p, p, p]) for p in pats]

    # warm both decode programs before pinning steady state: a strictly
    # ascending prompt (no n-gram repeats) exercises the no-proposal
    # [S,1] fallback, the repetitive one the [S,1+k] verify grid
    sched.generate([np.arange(18, dtype=np.int64) + 100],
                   max_new_tokens=4)
    sched.generate(prompts[:1], max_new_tokens=4)
    sched.mark_steady()
    steps0 = sched.metrics.snapshot()["decode_steps"]
    t0 = time.perf_counter()
    outs = sched.generate(prompts, max_new_tokens=max_new)
    wall = time.perf_counter() - t0
    snap = sched.metrics.snapshot()
    cs = sched.compile_stats()
    spec = sched.spec_stats()
    sched.shutdown()
    digest = hashlib.sha1()
    for o in outs:
        digest.update(np.asarray(o, np.int64).tobytes())
    return {
        "spec_k": spec_k,
        "wall_s": round(wall, 3),
        "decode_steps": snap["decode_steps"] - steps0,
        "generated_tokens": sum(len(o) - len(p)
                                for o, p in zip(outs, prompts)),
        "compile_stats": cs,
        "spec_stats": spec,
        "outputs_sha1": digest.hexdigest(),
    }


def run_spec_suite(spec_ks=(2, 4), smoke: bool = True,
                   out_dir: str = REPO_ROOT, seed: int = 0) -> dict:
    """BENCH_serving_spec.json: the accept-rate sweep.

    The same seeded workload decoded autoregressively (``k=0``) and at
    each draft depth in ``spec_ks``; per depth the artifact reports the
    proposal accept rate, tokens per verify step (> 1 is the batching
    win), and the device-step reduction vs the baseline — all under
    bit-identical token streams and zero steady-state recompiles."""
    kw = dict(num_requests=3 if smoke else 6, max_new=24 if smoke else 32,
              num_layers=2, seed=seed)
    base = _run_spec_load(0, **kw)
    sweep = {}
    for k in spec_ks:
        run = _run_spec_load(int(k), **kw)
        st = run["spec_stats"] or {}
        sweep[str(k)] = {
            **run,
            "spec_accept_rate": st.get("accept_rate"),
            "tokens_per_step": st.get("tokens_per_verify_step"),
            "step_cut_x": (round(base["decode_steps"]
                                 / run["decode_steps"], 3)
                           if run["decode_steps"] else None),
            "token_identical_to_baseline":
                run["outputs_sha1"] == base["outputs_sha1"],
        }
    identical = all(v["token_identical_to_baseline"]
                    for v in sweep.values())
    recompiles = sum(v["compile_stats"]["steady_state_recompiles"]
                     for v in sweep.values())
    best_k = max(sweep, key=lambda k: sweep[k]["tokens_per_step"] or 0)
    artifact = {
        "bench": "serving_spec",
        "config": {"spec_ks": list(spec_ks), "smoke": smoke, "seed": seed,
                   **kw},
        "baseline": base,
        "sweep": sweep,
        "best_k": int(best_k),
        "spec_accept_rate": sweep[best_k]["spec_accept_rate"],
        "tokens_per_step": sweep[best_k]["tokens_per_step"],
        "step_cut_x": sweep[best_k]["step_cut_x"],
        "token_identical": identical,
        "steady_state_recompiles": recompiles,
        "within_budget": (
            identical and recompiles == 0
            and (sweep[best_k]["tokens_per_step"] or 0) > 1.0
            and (sweep[best_k]["spec_accept_rate"] or 0) > 0.3),
        "completed": True,
    }
    out_path = os.path.join(out_dir, "BENCH_serving_spec.json")
    write_bench_json(out_path, artifact)
    artifact["artifact"] = out_path
    return artifact


def _respawn_sharded(args, tp: int, replicas: int, out_path: str) -> dict:
    """Parent half of the sharded mode: re-exec this script in a clean
    subprocess whose XLA_FLAGS force an emulated mesh of tp*replicas CPU
    devices (min 2 so tp=1 still runs on a real multi-device world). The
    child prints the one-line metric JSON and writes the artifact; we
    stream its output through and re-load the artifact."""
    import subprocess

    world = max(2, tp * replicas)
    env = dict(os.environ)
    env["SERVE_BENCH_SHARDED_CHILD"] = "1"
    env["JAX_PLATFORMS"] = "cpu"   # CPU-only child: never wants the chip
    # deterministic single-thread eigen like the async sweep: the sharded
    # suite compares token streams against the single-device oracle
    env.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={world}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["PYTHONPATH"] = (REPO_ROOT + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else REPO_ROOT)
    argv = [sys.executable, os.path.abspath(__file__),
            "--tp", str(tp), "--replicas", str(replicas),
            "--seed", str(args.seed), "--out", out_path]
    if args.smoke:
        argv.append("--smoke")
    proc = subprocess.run(argv, env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded bench subprocess exited {proc.returncode} "
            f"(its partial artifact, if any, is at {out_path})")
    with open(out_path) as f:
        return json.load(f)


def run_sharded_suite(tp: int = 2, replicas: int = 1, smoke: bool = True,
                      seed: int = 0, out_dir: str = REPO_ROOT,
                      out_path=None) -> dict:
    """Sharded serving measurement on the (emulated) multi-device world.

    Three conditions, all on identically-seeded models:

    1. **oracle** — one unsharded single-device replica (the reference
       token streams and the throughput baseline);
    2. **sharded** — one replica over a tp-device mesh: token identity
       vs the oracle, per-chip memory census (the KV split must be
       ~1/tp per chip), decode bandwidth-util attribution;
    3. **fleet** (replicas > 1) — a DeviceGroupPlan router fleet on
       DISJOINT device groups: aggregate throughput + per-replica
       device sets (the r15 colocated-contention fix, structurally
       verified).

    Emulated-mesh caveat recorded in the artifact: forced CPU "devices"
    share the same host cores, so cross-condition tokens/s on CPU
    measures dispatch overhead, not chip scaling — the structural
    claims (identity, split, disjointness) are the gated ones.
    """
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.observability.device_memory import (
        tree_device_nbytes, tree_nbytes)
    from paddle_tpu.serving import (ContinuousBatchingScheduler,
                                    SchedulerConfig, ServingRouter)
    from paddle_tpu.serving.sharded import DeviceGroupPlan

    devices = jax.devices()
    need = max(2, tp * replicas)
    assert len(devices) >= need, (
        f"sharded suite needs {need} devices, found {len(devices)} "
        f"(run through serve_bench --tp, which forces the emulated mesh)")

    num_requests = 8 if smoke else 24
    max_new = 6 if smoke else 12
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 1000, int(n))
               for n in rng.integers(4, 14, num_requests)]

    def build(sharding=None):
        paddle.seed(7)
        model = GPTForCausalLM(gpt_tiny(num_layers=2))
        return _track(ContinuousBatchingScheduler(
            model, SchedulerConfig(max_num_seqs=4, max_seq_len=64,
                                   block_size=8),
            sharding=sharding))

    def timed_run(sched):
        t0 = time.perf_counter()
        outs = sched.generate(prompts, max_new_tokens=max_new)
        wall = time.perf_counter() - t0
        toks = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        return outs, wall, toks

    # ---- 1. single-device oracle --------------------------------------
    oracle = build()
    ref_outs, oracle_wall, oracle_toks = timed_run(oracle)
    oracle.shutdown()

    # ---- 2. one sharded replica ---------------------------------------
    plan = DeviceGroupPlan(tp=tp, replicas=max(1, replicas))
    sched = build(plan.sharding(0))
    outs, wall, toks = timed_run(sched)
    identical = all(np.array_equal(a, b) for a, b in zip(ref_outs, outs))
    census = sched.device_ledger.census_report()
    kv_dev = census["owners"]["kv_pool"].get("devices", {})
    kv_total = tree_nbytes(sched._pools)
    fracs = {d: b / kv_total for d, b in kv_dev.items()} if kv_total else {}
    weights_dev = tree_device_nbytes(
        [p for p in sched.model.parameters()])
    dev_fields = _device_observability_fields(sched, wall)
    sharded = {
        "tp": tp,
        "devices": [str(d) for d in sched.device_set()],
        "tokens_per_s": toks / wall if wall > 0 else None,
        "wall_s": wall,
        "token_identical_to_oracle": identical,
        "per_chip_memory_bytes": census["per_device"],
        "kv_split": {
            "per_chip_bytes": kv_dev,
            "total_bytes": kv_total,
            "expected_fraction": 1.0 / tp,
            "max_fraction": max(fracs.values()) if fracs else None,
            "chips": len(kv_dev),
        },
        "weights_per_chip_bytes": weights_dev,
        "device_observability": dev_fields,
    }
    sched.shutdown()

    # ---- 3. disjoint fleet (replicas > 1) -----------------------------
    fleet = None
    if replicas > 1:
        def make_replica(sh):
            paddle.seed(7)
            model = GPTForCausalLM(gpt_tiny(num_layers=2))
            return _track(ContinuousBatchingScheduler(
                model, SchedulerConfig(max_num_seqs=4, max_seq_len=64,
                                       block_size=8),
                sharding=sh))

        router = _track_router(ServingRouter(
            plan.replica_factories(make_replica),
            cooldown_s=0.05, device_ownership="error"))
        sets = [sorted(str(d) for d in rep.sched.device_set())
                for rep in router.replicas]
        flat = [d for s in sets for d in s]
        t0 = time.perf_counter()
        rids = [router.submit(p, max_new_tokens=max_new) for p in prompts]
        done = {}
        guard = 100000
        while len(done) < len(rids) and guard:
            for o in router.step():
                done[o.request_id] = o
            guard -= 1
        fleet_wall = time.perf_counter() - t0
        assert guard, "fleet drain stalled"
        fleet_tokens = sum(len(done[r].token_ids) - len(p)
                           for r, p in zip(rids, prompts))
        fleet_identical = all(
            np.array_equal(done[r].token_ids, ref)
            for r, ref in zip(rids, ref_outs))
        fleet = {
            "replicas": replicas,
            "replica_device_sets": sets,
            "disjoint_replica_device_sets": len(set(flat)) == len(flat),
            "tokens_per_s": fleet_tokens / fleet_wall
            if fleet_wall > 0 else None,
            "wall_s": fleet_wall,
            "token_identical_to_oracle": fleet_identical,
            "group_plan": plan.describe(),
        }
        router.shutdown()

    within = (identical
              and sharded["kv_split"]["chips"] == tp
              and (fleet is None or
                   (fleet["disjoint_replica_device_sets"]
                    and fleet["token_identical_to_oracle"])))
    artifact = {
        "bench": "serving_sharded",
        "config": {
            "tp": tp, "replicas": replicas, "smoke": smoke, "seed": seed,
            "num_requests": num_requests, "max_new_tokens": max_new,
            "plan": "exact",
            "world_devices": [str(d) for d in devices],
            "emulated_cpu_mesh": jax.default_backend() == "cpu",
            "throughput_caveat":
                "emulated CPU devices share host cores; tokens/s here "
                "measures dispatch overhead, not chip scaling",
        },
        "oracle": {
            "tokens_per_s": oracle_toks / oracle_wall
            if oracle_wall > 0 else None,
            "wall_s": oracle_wall,
        },
        "sharded": sharded,
        "fleet": fleet,
        "within_budget": within,
        "completed": True,
    }
    path = out_path or os.path.join(out_dir, "BENCH_serving_sharded.json")
    write_bench_json(path, artifact)
    artifact["artifact"] = path
    return artifact


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast load (CI tier)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-num-seqs", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--tight-pool", action="store_true",
                    help="size the KV pool below worst-case so preemption "
                         "is exercised")
    ap.add_argument("--prefix-share", action="store_true",
                    help="shared-system-prompt workload sweep (share "
                         "ratios 0/0.5/0.9, cache on vs off) -> "
                         "BENCH_serving_prefix.json")
    ap.add_argument("--observability", action="store_true",
                    help="fully-instrumented run (tracing + SLO + live "
                         "endpoint scrape) + on-vs-off overhead/token-"
                         "identity measurement -> BENCH_serving_obs.json")
    ap.add_argument("--profile-steps", type=int, default=None,
                    help="in-step profile: capture a device trace around "
                         "K scheduler steps and attribute decode device "
                         "time to named regions (kv_gather/attention/mlp/"
                         "sampling/...), plus telemetry on-vs-off "
                         "invariants -> BENCH_serving_stepprofile.json")
    ap.add_argument("--chaos", action="store_true",
                    help="resilience suite: seeded fault-rate sweep, "
                         "fault-window recovery, cancellations, disarmed-"
                         "inject overhead -> BENCH_serving_chaos.json")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="single chaos run: per-hit probability of an "
                         "injected transient fault at the serving sites")
    ap.add_argument("--cancel-rate", type=float, default=0.0,
                    help="single chaos run: fraction of requests cancelled "
                         "shortly after arrival (seeded choice)")
    ap.add_argument("--depth", type=int, nargs="*", default=None,
                    help="dispatch-ahead depth sweep (default 0 1 2 when "
                         "given no values): per-depth wall/TPOT/host-stall "
                         "share + cross-depth token identity -> "
                         "BENCH_serving_async.json")
    ap.add_argument("--tp", type=int, default=None,
                    help="sharded serving suite: one replica spans a "
                         "tp-device mesh (tensor-parallel attention/MLP + "
                         "head-sharded KV pool); with --replicas R, a "
                         "DeviceGroupPlan fleet of R disjoint tp-device "
                         "groups behind the router. Respawns itself in a "
                         "fresh subprocess with "
                         "--xla_force_host_platform_device_count so the "
                         "emulated mesh exists before jax initializes -> "
                         "BENCH_serving_sharded.json")
    ap.add_argument("--replicas", type=int, default=None,
                    help="multi-replica router suite over N scheduler "
                         "replicas: tokens/s scaling vs 1 replica, "
                         "replica-kill failover drill (token identity, "
                         "goodput recovery), affinity-vs-round-robin "
                         "hit rate -> BENCH_serving_router.json; also "
                         "runs the fleet-observability drill (cross-"
                         "replica journey chrome trace + forced-alarm "
                         "postmortem bundle) -> "
                         "BENCH_serving_fleet_trace.json")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="router suite: crash replica 0 at this iteration "
                         "of the kill drill (default: mid-run)")
    ap.add_argument("--flush-us", type=float, default=400.0,
                    help="modeled per-token client stream flush for the "
                         "--depth sweep, microseconds")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="chunked-prefill suite: prefill-storm workload, "
                         "unchunked vs chunked-at-N decoder-cohort inter-"
                         "token gaps, token identity, zero steady-state "
                         "recompiles -> BENCH_serving_chunked.json")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="speculative-decoding suite: accept-rate sweep "
                         "over draft depths (this value and 2), tokens/"
                         "verify-step, device-step cut vs autoregressive, "
                         "token identity -> BENCH_serving_spec.json; "
                         "combined with --chunk-size it is the chunked "
                         "suite's chunked+spec identity leg instead")
    ap.add_argument("--out", default=None,
                    help="artifact path (default: BENCH_serving_<mode>.json "
                         "at the repo root)")
    args = ap.parse_args(argv)

    # a CPU correctness drill by construction (counts and identity, never
    # a speed): hard-set, not setdefault — the env may name a device platform
    os.environ["JAX_PLATFORMS"] = "cpu"

    chaos = args.chaos or args.fault_rate > 0 or args.cancel_rate > 0
    # --tp wins over --replicas: "--tp 2 --replicas 2" is the sharded
    # FLEET (disjoint 2-device groups), not the colocated router suite
    mode = ("sharded" if args.tp is not None else
            "router" if args.replicas is not None else
            "async" if args.depth is not None else
            "chaos" if chaos else "obs" if args.observability else
            "stepprofile" if args.profile_steps is not None else
            "prefix" if args.prefix_share else
            "chunked" if args.chunk_size is not None else
            "spec" if args.spec_k is not None else
            "smoke" if args.smoke else "load")
    if mode == "async":
        # the cross-depth sha oracle needs run-to-run-deterministic XLA:CPU
        # execution, which the threaded Eigen backend does not give for
        # this model size; must land before the first jax import (we only
        # setdefault — an explicit caller choice wins and is recorded in
        # the artifact)
        os.environ.setdefault("XLA_FLAGS", ASYNC_XLA_FLAGS)
    out_path = args.out or os.path.join(REPO_ROOT,
                                        f"BENCH_serving_{mode}.json")
    try:
        return _run_mode(args, mode, out_path)
    except BaseException as exc:
        # a bench that dies mid-run must leave a truthful partial artifact
        # (completed: false + the error), never a stale or missing one —
        # and at dispatch_depth > 0 it must first quiesce every live
        # engine (drain in-flight dispatched steps, release all KV) so
        # the artifact also records that nothing leaked
        write_bench_json(out_path, {
            "bench": f"serving_{mode}",
            "completed": False,
            "error": f"{type(exc).__name__}: {exc}",
            "quiesced_routers": _quiesce_live_routers(),
            "quiesced_schedulers": _quiesce_live_schedulers(),
            "config": dict(vars(args)),
        })
        raise


def _run_mode(args, mode: str, out_path: str) -> dict:
    if mode == "sharded":
        tp = max(1, int(args.tp))
        replicas = max(1, int(args.replicas or 1))
        if os.environ.get("SERVE_BENCH_SHARDED_CHILD") != "1":
            # the emulated mesh must exist BEFORE jax initializes, and this
            # process (or a caller embedding us) may already have a live
            # backend — respawn into a fresh interpreter with the forced
            # host device count (the auto_tuner trial-subprocess pattern)
            return _respawn_sharded(args, tp, replicas, out_path)
        artifact = run_sharded_suite(
            tp=tp, replicas=replicas, smoke=args.smoke, seed=args.seed,
            out_dir=os.path.dirname(out_path) or ".", out_path=out_path)
        print(json.dumps({
            "metric": "serving_sharded_tokens_per_s",
            "value": artifact["sharded"]["tokens_per_s"],
            "unit": f"tokens/s, one replica over a tp={tp} emulated mesh",
            "token_identical_to_oracle":
                artifact["sharded"]["token_identical_to_oracle"],
            "kv_split_max_fraction":
                artifact["sharded"]["kv_split"]["max_fraction"],
            "disjoint_replica_device_sets":
                (artifact.get("fleet") or {}).get(
                    "disjoint_replica_device_sets"),
            "within_budget": artifact["within_budget"],
            "artifact": artifact["artifact"],
        }))
        return artifact

    if mode == "router":
        artifact = run_router_suite(
            smoke=args.smoke,
            num_replicas=max(2, args.replicas),
            kill_at=args.kill_at,
            out_dir=os.path.dirname(out_path) or ".")
        fleet = run_fleet_trace_suite(
            smoke=args.smoke,
            num_replicas=max(2, args.replicas),
            kill_at=args.kill_at,
            out_dir=os.path.dirname(out_path) or ".")
        artifact["fleet_trace"] = {
            "artifact": fleet["artifact"],
            "journey_coverage": fleet["journey_coverage"],
            "failover_track_coverage": fleet["failover_track_coverage"],
            "within_budget": fleet["within_budget"],
        }
        print(json.dumps({
            "metric": "serving_router_recovery_pct",
            "value": artifact["kill_drill"]["recovery_pct_of_baseline"],
            "unit": "% of pre-kill per-iteration token throughput after "
                    "a replica kill + supervised restart",
            "token_identical_to_single_replica":
                artifact["kill_drill"]["token_identical_to_single_replica"],
            "goodput": artifact["kill_drill"]["goodput"],
            "speedup_x": artifact["scaling"]["speedup_x"],
            "affinity_hit_rate_win":
                artifact["affinity_vs_round_robin"]["hit_rate_win"],
            "journey_coverage": fleet["journey_coverage"],
            "within_budget": artifact["within_budget"],
            "artifact": artifact["artifact"],
        }))
        return artifact

    if mode == "async":
        depths = tuple(args.depth) if args.depth else (0, 1, 2)
        artifact = run_async_sweep(
            depths=depths,
            repeats=2 if args.smoke else 3,
            num_requests=16 if args.smoke else 32,
            stream_flush_s=args.flush_us * 1e-6,
            out_dir=os.path.dirname(out_path) or ".")
        print(json.dumps({
            "metric": "serving_async_host_stall_share_cut",
            "value": artifact["host_stall_share_cut_x"],
            "unit": "x reduction of host-stall share of wall, best async "
                    "depth vs depth 0",
            "tpot_improvement_pct": artifact["tpot_improvement_pct"],
            "token_identical_across_depths":
                artifact["token_identical_across_depths"],
            "best_async_depth": artifact["best_async_depth"],
            "within_budget": artifact["within_budget"],
            "artifact": artifact["artifact"],
        }))
        return artifact

    if mode == "chaos":
        if args.fault_rate > 0 or args.cancel_rate > 0:
            # single scenario at the requested rates
            kw = (dict(num_requests=12, rate=0.8, seed=args.seed,
                       max_num_seqs=2, block_size=8)
                  if args.smoke else
                  dict(num_requests=args.requests, rate=args.rate,
                       seed=args.seed, max_num_seqs=args.max_num_seqs,
                       block_size=args.block_size))
            artifact = run_chaos_load(fault_rate=args.fault_rate,
                                      cancel_rate=args.cancel_rate, **kw)
            artifact["completed"] = True
            write_bench_json(out_path, artifact)
            print(json.dumps({
                "metric": "serving_chaos_goodput",
                "value": artifact["goodput"],
                "unit": "fraction of requests finished ok under chaos",
                "census": artifact["census"],
                "rejected": artifact["rejected"],
                "artifact": out_path,
            }))
            return artifact
        artifact = run_chaos_suite(
            smoke=args.smoke,
            out_dir=os.path.dirname(out_path) or ".")
        rates = artifact["config"]["fault_rates"]
        print(json.dumps({
            "metric": "serving_chaos_goodput_min",
            "value": min(artifact["goodput_vs_fault_rate"][str(r)]
                         ["goodput"] for r in rates),
            "unit": f"min goodput over fault rates {rates}",
            "goodput_monotone": artifact["goodput_monotone"],
            "recovery_gap_pct":
                artifact["window_recovery"]["recovery_gap_pct"],
            "token_identical_after_faults":
                artifact["window_recovery"]["token_identical_after_faults"],
            "disarmed_inject_overhead_pct":
                artifact["disarmed_inject"]["overhead_pct"],
            "within_budget": artifact["within_budget"],
            "artifact": artifact["artifact"],
        }))
        return artifact

    if mode == "obs":
        out_dir = os.path.dirname(out_path) or "."
        artifact = run_observability_suite(smoke=args.smoke,
                                           out_dir=out_dir)
        print(json.dumps({
            "metric": "serving_tracing_overhead_pct",
            "value": artifact["overhead"]["measured_overhead_pct"],
            "unit": "% p50 step-time regression, full observability on "
                    "vs off",
            "attributed_pct": artifact["overhead"][
                "attributed_overhead_pct"],
            "token_identical": artifact["overhead"]["token_identical"],
            "within_budget": artifact["within_budget"],
            "artifact": artifact["artifact"],
        }))
        return artifact

    if mode == "stepprofile":
        artifact = run_stepprofile_suite(
            steps=max(1, args.profile_steps), smoke=args.smoke,
            seed=args.seed, out_dir=os.path.dirname(out_path) or ".")
        print(json.dumps({
            "metric": "serving_stepprofile_coverage",
            "value": artifact["region_coverage"],
            "unit": "fraction of decode-step device time attributed to "
                    "named regions",
            "region_share_kv_gather": artifact["region_share_kv_gather"],
            "region_share_attention": artifact["region_share_attention"],
            "region_share_mlp": artifact["region_share_mlp"],
            "region_share_sampling": artifact["region_share_sampling"],
            "telemetry_invariants_ok": all(
                v["token_identical"] and v["programs_equal"]
                for v in artifact["telemetry_invariants"].values()),
            "within_budget": artifact["within_budget"],
            "artifact": artifact["artifact"],
        }))
        return artifact

    if mode == "chunked":
        artifact = run_chunked_suite(
            chunk_size=max(1, args.chunk_size), smoke=args.smoke,
            seed=args.seed, spec_k=args.spec_k or 3,
            out_dir=os.path.dirname(out_path) or ".")
        print(json.dumps({
            "metric": "serving_chunked_gap_max_cut",
            "value": artifact["decoder_gap_max_cut_x"],
            "unit": "x reduction of the decoder cohort's worst inter-"
                    "token gap under a prefill storm, chunked vs "
                    "unchunked",
            "gap_p95_cut_x": artifact["decoder_gap_p95_cut_x"],
            "token_identical": artifact["token_identical"],
            "steady_state_recompiles":
                artifact["steady_state_recompiles"],
            "within_budget": artifact["within_budget"],
            "artifact": artifact["artifact"],
        }))
        return artifact

    if mode == "spec":
        ks = sorted({2, max(1, args.spec_k)})
        artifact = run_spec_suite(
            spec_ks=ks, smoke=args.smoke, seed=args.seed,
            out_dir=os.path.dirname(out_path) or ".")
        print(json.dumps({
            "metric": "serving_spec_tokens_per_step",
            "value": artifact["tokens_per_step"],
            "unit": f"tokens per verify step at best draft depth "
                    f"k={artifact['best_k']}",
            "spec_accept_rate": artifact["spec_accept_rate"],
            "step_cut_x": artifact["step_cut_x"],
            "token_identical": artifact["token_identical"],
            "steady_state_recompiles":
                artifact["steady_state_recompiles"],
            "within_budget": artifact["within_budget"],
            "artifact": artifact["artifact"],
        }))
        return artifact

    if mode == "prefix":
        # prompts must be long enough that prefill is compute-bound (the
        # win is skipped prefill FLOPs); a 192-token prompt vs a ~32-token
        # suffix is a ~64x attention-compute gap even on the CPU smoke
        kw = (dict(num_requests=8, prompt_len=192, max_new=4,
                   max_num_seqs=2, block_size=16, max_seq_len=256,
                   num_layers=2, seed=args.seed)
              if args.smoke else
              dict(num_requests=24, prompt_len=384, max_new=8,
                   max_num_seqs=args.max_num_seqs, block_size=16,
                   max_seq_len=512, num_layers=2, seed=args.seed))
        artifact = run_prefix_suite(**kw)
        artifact["completed"] = True
        write_bench_json(out_path, artifact)
        top = str(max(artifact["config"]["ratios"]))
        print(json.dumps({
            "metric": "serving_prefix_ttft_reduction_pct",
            "value": artifact["ttft_reduction_pct_at_top_share"],
            "unit": f"% vs cache-off at share {top}",
            "hit_rate_at_top_share":
                artifact["share"][top]["prefix_cache"]["hit_rate"],
            "artifact": out_path,
        }))
        return artifact

    if args.smoke:
        kw = dict(num_requests=6, rate=1.0, seed=args.seed,
                  max_num_seqs=2, block_size=8, max_seq_len=64,
                  prompt_lens=(4, 10), new_tokens=(3, 6), num_layers=1)
    else:
        kw = dict(num_requests=args.requests, rate=args.rate,
                  seed=args.seed, max_num_seqs=args.max_num_seqs,
                  block_size=args.block_size)
    if args.tight_pool:
        # pool for roughly half the slots at full depth -> forced preemption
        mb = -(-kw.get("max_seq_len", 64) // kw["block_size"])
        kw["num_blocks"] = max(mb, kw["max_num_seqs"] * mb // 2)

    artifact = run_load(**kw)
    # device-side observability is load-bearing in this artifact: the
    # step-time and byte counts must be present; the utilisation must be
    # ABSENT, because this drill runs on CPU and a host has no peaks
    dev = artifact["device_observability"]
    assert dev["enabled"] and dev["kv_bytes_per_token"] > 0, dev
    bw = dev["serving_decode_bandwidth_util"]
    assert bw is None, dev
    share = dev["decode_device_time_share"]
    assert share is not None and 0.0 < share <= 1.0, dev
    artifact["completed"] = True
    stem = out_path[:-5] if out_path.endswith(".json") else out_path
    prom_text = artifact.pop("prometheus_text")
    prom_path = stem + ".prom"
    # per-request chrome-trace artifact (request_id-correlated spans)
    # beside the JSON/.prom exports
    reqtrace_path = stem + "_reqtrace.json"
    with open(reqtrace_path, "w") as f:
        json.dump(artifact.pop("request_trace"), f)
    artifact.pop("scrape_sample", None)
    write_bench_json(out_path, artifact)
    with open(prom_path, "w") as f:
        f.write(prom_text)
    print(json.dumps({"metric": "serving_tokens_per_s",
                      "value": artifact["metrics"]["tokens_per_s"],
                      "unit": "tokens/s",
                      "serving_decode_bandwidth_util": bw,
                      "kv_bytes_per_token": dev["kv_bytes_per_token"],
                      "artifact": out_path,
                      "prometheus": prom_path,
                      "request_trace": reqtrace_path}))
    return artifact


if __name__ == "__main__":
    main(sys.argv[1:])
