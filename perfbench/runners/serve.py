"""Runner for configurations of ``kind: serve``: one causal LM behind the
program's ``ContinuousBatchingScheduler``, driven by a load generator from
one thread on the scheduler's own clock (``time.perf_counter``).

The configuration file sets sizes and precisions only. Every feature switch
of ``SchedulerConfig`` stays at the program's default, so a later PR that
makes a feature the default is measured by the same file.

Set-up (all of it counted in ``setup_s``): model from the seed, the check
of the paged-cache logits against the plain reference, the KV pool sized
from what the chip has left, one warm-up request for each prefill bucket
the cell's prompt lengths can reach (which also compiles the decode
program), and the ramp. Then the window, then the grace period.

In a traced run the host-clock metrics cover the window up to the traced
slice, and the device metrics come from the slice (the last seconds of the
window): starting and stopping the profiler stalls this thread, and that
stall must not be read as the server's.
"""

from __future__ import annotations

import time

import numpy as np

from perfbench.harness import reference, xplane
from perfbench.harness.load import STREAM_TOKENS, rng
from perfbench.harness.spec import load_class

_MODEL_KEYS = ("vocab_size", "hidden_size", "num_layers", "num_heads",
               "intermediate_size", "max_position_embeddings",
               "layer_norm_eps")


def build_model(config: dict, seed: int):
    import paddle_tpu as paddle

    paddle.seed(seed)
    cfg = load_class(config["config_class"])(
        **{k: config[k] for k in _MODEL_KEYS})
    model = load_class(config["model_class"])(cfg)
    model.eval()
    if config["weights_dtype"] == "bfloat16":
        model.bfloat16()
    elif config["weights_dtype"] != "float32":
        raise ValueError(f"weights_dtype {config['weights_dtype']!r}")
    return cfg, model


def check_paged_logits(model, cfg, config: dict, seed: int, say) -> dict:
    """Last-position logits through a paged cache (one prefill, then one
    decode position at a time, each fed the arg-max of the one before)
    against the plain float32 reference over the same tokens."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.api import StaticFunction
    from paddle_tpu.models.kv_cache import PagedCacheSlot

    chk = config["reference_check"]
    n, steps = int(chk["prompt_tokens"]), int(chk["decode_positions"])
    bs = int(config["scheduler"]["block_size"])
    dtype = config["scheduler"]["cache_dtype"]
    heads, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    nb = -(-(n + steps) // bs)
    ids = rng(seed, STREAM_TOKENS, 999).integers(
        0, cfg.vocab_size, n).astype(np.int32)
    forward = StaticFunction(lambda i, p, c: model(i, p, c), layer=model,
                             name="perfbench.paged_logits")
    table = np.arange(nb, dtype=np.int32)[None]
    pools = [tuple(paddle.zeros([nb, bs, heads, hd], dtype=dtype)
                   for _ in "kv") for _ in range(cfg.num_layers)]
    got, seq, at = [], list(ids), 0
    feed = ids
    with paddle.no_grad():
        for _ in range(steps + 1):
            caches = [PagedCacheSlot(kp, vp, paddle.to_tensor(table),
                                     paddle.to_tensor(
                                         np.array([at], np.int32)))
                      for kp, vp in pools]
            out, caches = forward(
                paddle.to_tensor(np.asarray(feed, np.int32)[None]),
                paddle.to_tensor(np.arange(at, at + len(feed),
                                           dtype=np.int32)), caches)
            pools = [(c.k_pool, c.v_pool) for c in caches]
            last = np.asarray(out.numpy(), np.float32)[0, -1]
            got.append(last)
            at += len(feed)
            feed = [int(last.argmax())]
            seq += feed
    got = np.stack(got)
    # the reference sees the same tokens: the prompt and the arg-max fed
    # back at each decode position (the last arg-max is fed to nobody)
    want = np.asarray(reference.logits(
        reference.weights_of(model), np.asarray(seq[:-1], np.int32),
        cfg.num_layers, cfg.num_heads, cfg.layer_norm_eps, last=steps + 1))
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    ok = bool(np.isfinite(got).all() and err <= chk["rtol_of_scale"] * scale)
    say(f"paged-cache logits vs plain float32 reference over {steps + 1} "
        f"positions after a {n}-token prompt: max err {err:.4g} = "
        f"{err / scale:.3%} of scale {scale:.4g} (limit "
        f"{chk['rtol_of_scale']:.0%}): {'ok' if ok else 'FAILED'}")
    return {"ok": ok, "err": err, "scale": scale}


def prefill_buckets(traffic: dict, sched_cfg, max_seq_len: int) -> list:
    """The prefill widths this mix's prompts can reach."""
    from paddle_tpu.models.serving import _bucket

    lo = min(c["prompt_tokens"].get("min", c["prompt_tokens"].get("value"))
             for c in traffic["classes"])
    hi = max(c["prompt_tokens"].get("max", c["prompt_tokens"].get("value"))
             for c in traffic["classes"])
    b = min(_bucket(lo, sched_cfg.prefill_bucket), max_seq_len)
    top = min(_bucket(hi, sched_cfg.prefill_bucket), max_seq_len)
    out = [b]
    while b < top:
        b = min(b * 2, max_seq_len)
        out.append(b)
    return out


class Drive:
    """The loop that injects due requests and steps the scheduler."""

    def __init__(self, sched, load):
        self.sched, self.load = sched, load
        self.by_rid = {}
        self.steps = []          # one tuple to a step() call, see _step
        self.tokens_at = []      # time of every token, run clock
        self.live_tokens = 0     # K/V positions of requests in decode
        self.t0 = None

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _on_token(self, rid: int, _token: int) -> None:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench.on_token"):
            t = self.now()
            req = self.by_rid[rid]
            if req.first_s is None:
                req.first_s = t
                self.live_tokens += len(req.prompt)
            req.last_s = t
            req.tokens += 1
            self.live_tokens += 1
            self.tokens_at.append(t)

    def _inject(self) -> None:
        for req in self.load.pop_due(self.now()):
            try:
                req.rid = self.sched.add_request(
                    req.prompt, max_new_tokens=req.out_tokens,
                    on_token=self._on_token)
            except Exception as e:   # refused: queue full, overloaded
                req.rejected = f"{type(e).__name__}: {e}"
                req.finished_s = self.now()
                self.load.finished(req, req.finished_s)
                continue
            self.by_rid[req.rid] = req
            req.sent_s = self.now()

    def _step(self) -> None:
        m = self.sched.metrics
        prefills, live = m.prefills, self.live_tokens
        t0 = self.now()
        outs = self.sched.step()
        t1 = self.now()
        self.steps.append((t0, t1, m.prefills - prefills, m.running,
                           self.sched.allocator.num_used_blocks,
                           m.queue_depth, live))
        for out in outs:
            req = self.by_rid.get(out.request_id)
            if req is None:
                continue
            req.finish_reason = out.finish_reason
            req.finished_s = t1
            if req.first_s is not None:
                self.live_tokens -= len(req.prompt) + req.tokens
            self.load.finished(req, t1)

    def run_until(self, end_s: float) -> None:
        from jax.profiler import TraceAnnotation

        if self.t0 is None:
            self.t0 = time.perf_counter()
        while self.now() < end_s:
            with TraceAnnotation("bench.inject"):
                self._inject()
            if self.sched.has_unfinished():
                with TraceAnnotation("bench.step"):
                    self._step()
                continue
            nxt = self.load.next_due_s()
            with TraceAnnotation("bench.wait_due"):
                wait = (end_s if nxt is None else min(nxt, end_s)) - self.now()
                if wait > 0:
                    time.sleep(min(wait, 0.002))


def set_up(ctx) -> dict:
    """Model, reference check, pool, scheduler, warm-up of the cell's own
    shapes: everything before the ramp."""
    import jax

    from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

    config, traffic, say = ctx.cell.config, ctx.cell.traffic, ctx.say
    cfg, model = build_model(config, ctx.seed)
    jax.block_until_ready([p._value for p in model.parameters()])
    ctx.phase_done("model from the seed")
    check = check_paged_logits(model, cfg, config, ctx.seed, say)
    ctx.phase_done("reference check")

    sizes = dict(config["scheduler"])
    cache_bytes = 2 if sizes["cache_dtype"] == "bfloat16" else 4
    block_bytes = (cfg.num_layers * 2 * sizes["block_size"]
                   * cfg.hidden_size * cache_bytes)
    stats = jax.devices()[0].memory_stats()
    if stats is not None:
        # fill the chip as a deployment would: what the weights leave, less
        # the headroom for the widest prefill and the decode step's scratch
        sizes["num_blocks"] = int(
            (stats["bytes_limit"] - stats["bytes_in_use"]
             - config["kv_pool"]["hbm_headroom_bytes"]) // block_bytes)
    scfg = SchedulerConfig(**sizes)
    sched = ContinuousBatchingScheduler(model, scfg)
    say(f"pool: {scfg.total_blocks} blocks x {scfg.block_size} tokens = "
        f"{scfg.total_blocks * block_bytes / 2**30:.2f} GiB; slots "
        f"{scfg.max_num_seqs}; dispatch_depth {scfg.dispatch_depth}")
    ctx.phase_done("scheduler and pool")

    buckets = prefill_buckets(traffic, scfg, sched.max_seq_len)
    r = rng(ctx.seed, STREAM_TOKENS, 998)
    for b in buckets:
        sched.add_request(r.integers(0, cfg.vocab_size, b - 2), 2)
    sched.run()
    sched.mark_steady()
    ctx.phase_done(f"warm-up of prefill buckets {buckets} and the decode "
                   f"program")
    return {"cfg": cfg, "sched": sched, "scfg": scfg, "check": check,
            "cache_bytes": cache_bytes}


def not_served(requests: list, now_s: float, limits: dict,
               first_token_by_s) -> dict:
    """``{index: why}`` for the requests still in the system at ``now_s``
    (the moment scoring stops) that are not being served: the run does not
    wait out long outputs, so what an unfinished request has by then is
    held to the pace a server owes it. One with a first token has failed if
    the time since that token, over the tokens it has, is more than
    ``limits.stalled_gap_ms`` (a mix that states none is not checked): that
    catches a request that is starved and one that crawls. One without a
    first token has failed once it has waited ``first_token_by_s`` from its
    due time (``None`` in a closed loop, which queues by design)."""
    gap_s = limits.get("stalled_gap_ms")
    out = {}
    for r in requests:
        if r.sent_s is None or r.finished_s is not None:
            continue
        if r.first_s is None:
            if (first_token_by_s is not None
                    and now_s - r.due_s >= first_token_by_s):
                out[r.index] = (f"no first token {now_s - r.due_s:.1f} s "
                                f"after it was due")
        elif (gap_s is not None
              and (now_s - r.first_s) / r.tokens * 1e3 > gap_s):
            out[r.index] = (f"{r.tokens} tokens in the "
                            f"{now_s - r.first_s:.1f} s since its first")
    return out


def measure(ctx, st: dict, traffic: dict) -> dict:
    """Ramp, window and grace of one load on a scheduler that is set up."""
    sched, scfg, say = st["sched"], st["scfg"], ctx.say
    config = ctx.cell.config
    load = ctx.cell.generator().make(traffic, ctx.seed,
                                     st["cfg"].vocab_size, ctx.seconds)
    ph = load.phases
    drive = Drive(sched, load)
    w0, w1 = ph.window
    score_end = ph.end_s

    ctx.phase_done()
    drive.run_until(w0)
    setup_s = time.perf_counter() - ctx.t_process
    ctx.phase_done(f"ramp ({ph.ramp_s:g} s)")
    preempt0, compiles0 = sched.metrics.preemptions, ctx.compiles.count
    trace = trace_summary = None
    closed_loop = load.outstanding_target is not None
    limits = traffic.get("limits", {})
    first_token_by_s = None if closed_loop else ph.grace_s
    if ctx.trace:
        drive.run_until(max(w0 + 1.0, w1 - ctx.trace_seconds))
        score_end = drive.now()
        lagging = not_served(load.schedule, score_end, limits,
                             first_token_by_s)
        compiles_in_window = ctx.compiles.count - compiles0
        with xplane.Capture(ctx.trace_dir) as cap:
            at_span = drive.now()
            drive.run_until(at_span + ctx.trace_seconds)
        trace, trace_summary = cap.trace, cap.summary
        if trace_summary is not None:
            # run clock -> trace clock, for readers that cut the trace by
            # the benchmark's own step records
            trace["run_clock_offset_s"] = trace_summary["t0"] - at_span
        say(f"trace: {cap.path}")
    else:
        drive.run_until(ph.end_s)
        lagging = not_served(load.schedule, drive.now(), limits,
                             first_token_by_s)
        compiles_in_window = ctx.compiles.count - compiles0

    cs = sched.compile_stats()
    for req in load.schedule:
        tr = sched.tracer.get(req.rid) if req.rid is not None else None
        if tr is None:
            continue
        segs = list(tr.phases) + [(tr.current_phase, tr._cur_t0, None)]
        admit = next((s[1] for s in segs if s[0] == "admit"), None)
        req.admit_s = None if admit is None else admit - drive.t0
        req.prefill_s = sum(
            tr.subspans.get(k, (0, 0.0))[1]
            for k in ("prefill", "sampling_sync"))
    return {
        "kind": "serve", "setup_s": setup_s, "window": (w0, w1),
        "score_end_s": score_end, "requests": load.schedule,
        "closed_loop": closed_loop, "lagging": lagging,
        "steps": drive.steps, "tokens_at": drive.tokens_at,
        "limits": limits,
        "max_num_seqs": scfg.max_num_seqs, "total_blocks": scfg.total_blocks,
        "preemptions": sched.metrics.preemptions - preempt0,
        "compiles_in_window": compiles_in_window
        + cs["steady_state_recompiles"],
        "requests_failed_counter": sched.metrics.requests_failed,
        "reference_check": st["check"], "model": config,
        "weight_bytes": 2 if config["weights_dtype"] == "bfloat16" else 4,
        "cache_bytes": st["cache_bytes"],
        "trace": trace, "trace_summary": trace_summary,
    }


def run(ctx) -> dict:
    return measure(ctx, set_up(ctx), ctx.cell.traffic)


def sweep(ctx, rates: list) -> int:
    """Find an open-loop cell's knee: one set-up, then the cell's mix at
    each of ``rates`` for ``--seconds`` each, emptying the scheduler in
    between. Prints one JSON line a rate; the knee is read off by hand as
    ``PERF.md`` says (the highest rate at which 90 % of the requests due
    meet both limits and the queue is no deeper at the window's end than at
    its start)."""
    import copy
    import json

    from perfbench.harness import serve_view as view
    from perfbench.harness.spec import load_module
    from perfbench.harness.stats import median, percentile

    st = set_up(ctx)
    sched = st["sched"]
    for rate in rates:
        traffic = copy.deepcopy(ctx.cell.traffic)
        traffic["arrivals"]["rate_per_s"] = rate
        # residents follow the rate: the file's number is for the file's rate
        traffic["ramp"]["residents"] = int(round(
            ctx.cell.traffic["ramp"].get("residents", 0) * rate
            / ctx.cell.traffic["arrivals"]["rate_per_s"]))
        rec = measure(ctx, st, traffic)
        ok, attempted, failed, _ = verdict(rec)
        a, b = view.scored_span(rec)
        steps = view.steps_in(rec, a, b)
        read = lambda name: load_module("metrics", name).read(rec)
        print(json.dumps({
            "sweep_rate_per_s": rate, "attempted": attempted,
            "failed": failed, "correct": ok,
            "slo_attain": read("slo_attain"),
            "ttft_p50_ms": read("ttft_p50_ms"),
            "ttft_tail_ms": read("ttft_tail_ms"),
            "tpot_p50_ms": read("tpot_p50_ms"),
            "decode_step_p50_ms": read("decode_step_p50_ms"),
            "slot_occupancy": read("slot_occupancy"),
            "prefill_wall_share": read("prefill_wall_share"),
            "gen_late_p99_ms": read("gen_late_p99_ms"),
            "queue_depth_start": steps[0][5] if steps else None,
            "queue_depth_end": steps[-1][5] if steps else None,
            "queue_depth_max": max((s[5] for s in steps), default=None),
            "tpot_p90_ms": percentile([x * 1e3 for x in view.tpot_s(rec)],
                                      90),
            "running_median": median([s[3] for s in steps]),
        }), flush=True)
        for rid in [r.rid for r in rec["requests"]
                    if r.rid is not None and r.finished_s is None]:
            sched.cancel(rid)
        sched.run()
    return 0


def verdict(rec: dict):
    """``(correct, attempted, failed, notes)``.

    Scored are, in an open loop, the requests due in the scored window and,
    in a closed loop (a throughput cell), those that left the system inside
    it. A request has failed if it was refused, if it finished other than
    ``length`` with its exact token count, or if scoring stopped while it
    was not being served (``not_served``: the run does not wait out long
    outputs, but a request without a first token, starved or crawling is a
    failure, not a latency). That holds for every request of the run, ramp
    and grace included: one that failed outside the scored ones is counted
    as attempted and failed too.
    """
    from perfbench.harness import serve_view as view
    from perfbench.harness.stats import percentile

    def wrong(r):
        if r.rejected is not None:
            return r.rejected
        if r.finished_s is not None and (r.finish_reason != "length"
                                         or r.tokens != r.out_tokens):
            return f"{r.finish_reason} {r.tokens}/{r.out_tokens}"
        return rec["lagging"].get(r.index)

    a, b = view.scored_span(rec)
    if rec["closed_loop"]:
        pool = [r for r in rec["requests"]
                if r.finished_s is not None and a <= r.finished_s < b]
    else:
        pool = view.scored(rec)
    scored = {r.index for r in pool}
    bad = [r for r in pool if wrong(r)]
    elsewhere = [r for r in rec["requests"]
                 if r.index not in scored and wrong(r)]
    notes = [f"{len(pool)} requests scored, {len(bad)} of them failed; "
             f"{len(elsewhere)} failed outside them"
             + "".join(f"; #{r.index}: {wrong(r)}"
                       for r in (bad + elsewhere)[:3])]
    ok = bool(pool) and not bad and not elsewhere
    chk = rec["reference_check"]
    notes.append(f"reference check ok={chk['ok']} (err {chk['err']:.4g} of "
                 f"scale {chk['scale']:.4g})")
    ok &= chk["ok"]
    notes.append(f"compiles inside the window: {rec['compiles_in_window']}")
    ok &= rec["compiles_in_window"] == 0
    notes.append(f"scheduler's requests_failed: "
                 f"{rec['requests_failed_counter']}")
    ok &= rec["requests_failed_counter"] == 0
    limit = rec["limits"].get("gen_late_p99_ms")
    if limit is not None:
        late = percentile([(r.sent_s - r.due_s) * 1e3 for r in pool
                           if r.sent_s is not None], 99)
        notes.append(f"generator lateness p99 {late} ms (limit {limit})")
        ok &= late is not None and late <= limit
    return (ok, len(pool) + len(elsewhere), len(bad) + len(elsewhere),
            notes)


def counts(rec: dict) -> dict:
    """What a CPU rehearsal may print: counts, no time."""
    reqs = rec["requests"]
    return {"requests_drawn": len(reqs),
            "requests_finished": sum(r.finished_s is not None for r in reqs),
            "tokens": len(rec["tokens_at"]), "steps": len(rec["steps"]),
            "preemptions": rec["preemptions"],
            "compiles_in_window": rec["compiles_in_window"]}
