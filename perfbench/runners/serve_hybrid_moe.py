"""Runner for configurations of ``kind: serve_hybrid_moe``: a causal LM
whose layers differ in what they cache (full-attention and sliding-window
layers with their own KV heads and row widths) over sparse experts of which
this chip holds a share, behind the program's ``ContinuousBatchingScheduler``.

It brings what differs from ``runners/serve.py`` (the model from the public
config's keys, the reference check through the scheduler the window is
measured on, the pool sized by block class) and takes the rest from that file
unedited: ``Drive``, ``measure``, ``verdict``, ``counts``, ``sweep``,
``prefill_buckets``. Its records are ``kind: serve`` records with one more
group, ``hybrid``, for the readers this configuration adds.

Set-up (all of it counted in ``setup_s``): model from the seed, created in
the weights' own type; the pools; one warm-up request for each prefill
bucket the cell's prompts can reach (which also compiles the decode
program); the served side of the reference check, taken through this very
scheduler with every slot live (``served_logits``); the ramp. The plain
float32 reference (``harness/reference_mimo_v2.py``) computes its side at
the published widths after the window (``compare``).
"""

from __future__ import annotations

import time

import numpy as np

from perfbench.harness import reference_mimo_v2 as reference
from perfbench.harness.load import STREAM_TOKENS, rng
from perfbench.harness.spec import load_class, load_module

serve = load_module("runners", "serve")
verdict, counts = serve.verdict, serve.counts


def build_model(config: dict, seed: int):
    import paddle_tpu as paddle

    paddle.seed(seed)
    cfg = load_class(config["config_class"]).from_public(
        config, experts_held=(config["experts_held_first"],
                              config["experts_held"]),
        dtype=config["weights_dtype"])
    model = load_class(config["model_class"])(cfg)
    model.eval()
    return cfg, model


def reference_config(config: dict) -> dict:
    """The configuration file as the reference reads it."""
    return dict(config, experts_held=(config["experts_held_first"],
                                      config["experts_held"]))


class Probe:
    """Stands in for the scheduler's step function while the check runs:
    every launch goes first through a program of the same model over the
    same arguments that gives back the logits and each expert layer's
    choice (nothing donated, its cache writes dropped), then through the
    scheduler's own step program, whose sampled tokens are kept beside
    them. Everything else it is asked for is the step's."""

    def __init__(self, step, model):
        from paddle_tpu.jit.api import StaticFunction

        self.step, self.calls = step, []
        moe = [l.mlp for l in model.model.layers
               if hasattr(l.mlp, "last_experts")]
        self.logits = StaticFunction(
            lambda i, p, c: (model(i, p, c)[0],
                             [m.last_experts for m in moe]),
            layer=model, name="perfbench.probe")

    def __getattr__(self, name):
        return getattr(self.step, name)

    def __call__(self, ids, position_ids, caches, gather_idx):
        logits, chosen = self.logits(ids, position_ids, caches)
        out = self.step(ids, position_ids, caches, gather_idx)
        self.calls.append({
            "logits": np.asarray(logits.numpy(), np.float32),
            "chosen": [np.asarray(c.numpy()) for c in chosen],
            "sampled": np.asarray(out[0].numpy())})
        return out


def check_prompts(config: dict, vocab_size: int, seed: int) -> list:
    """The checked requests' prompts, from the seed."""
    r = rng(seed, STREAM_TOKENS, 999)
    return [r.integers(0, vocab_size, int(n)).astype(np.int32)
            for n in config["reference_check"]["prompt_tokens"]]


def served_logits(model, cfg, config: dict, sched, seed: int) -> dict:
    """The checked requests through the scheduler that the window is
    measured on, with every other slot live: requests of unequal length are
    admitted into all but the last slots and decode beside them (both
    allocators, the window roll and its releases, the tables of all rows,
    the decode program and the expert step at the cell's own shapes); then
    the checked prompts are admitted and decode ``decode_positions`` steps:
    a long one, prefilled past the window and across pages, and a short one,
    whose few visible positions make the sink a large part of the softmax
    in prefill and in the decode kernel (past the window it is 1 / 129 of
    it, under the rounding). Returns, a request, its ``logits [positions +
    1, V]`` (from ``Probe``), its ``tokens`` (the prompt and what was fed)
    and each expert layer's ``choices`` for every one of them; the share of
    (row, step) pairs in which the scheduler's own program sampled the
    probe's arg-max; and whether both pools were whole once everything was
    cancelled."""
    from paddle_tpu.models import kv_cache

    chk = config["reference_check"]
    steps = int(chk["decode_positions"])
    lo, hi = chk["filler_prompt_tokens"]
    prompts = check_prompts(config, cfg.vocab_size, seed)
    slots = sched.config.max_num_seqs
    r = rng(seed, STREAM_TOKENS, 997)
    released0 = sched._window_released.value
    fillers = [sched.add_request(
        r.integers(0, cfg.vocab_size, int(r.integers(lo, hi + 1))),
        steps + 32) for _ in range(slots - len(prompts))]
    while sched.metrics.running < len(fillers):
        sched.step()
    probe = sched._step_fn = Probe(sched._step_fn, model)
    try:
        fed = [[] for _ in prompts]
        rids = [sched.add_request(p, steps + 1,
                                  on_token=lambda _, t, f=f: f.append(int(t)))
                for p, f in zip(prompts, fed)]
        sched.step()
        at = [next(s for s, q in enumerate(sched._slots)
                   if q is not None and q.request_id == rid) for rid in rids]
        live = sched.metrics.running
        while min(map(len, fed)) < steps + 1:
            sched.step()
    finally:
        sched._step_fn = probe.step
    for f in fillers:
        sched.cancel(f)
    sched.run()
    prefills = probe.calls[:len(prompts)]
    decode = probe.calls[len(prompts):len(prompts) + steps]
    # a prompt in its bucket, one row; then the slot grid, one token a row
    assert all(c["logits"].shape[0] == 1 and c["logits"].shape[1] >= len(p)
               for c, p in zip(prefills, prompts))
    assert all(c["logits"].shape[:2] == (slots, 1) for c in decode)
    requests = []
    for prompt, first, slot, f in zip(prompts, prefills, at, fed):
        n = len(prompt)
        sampled = [int(first["sampled"][0])] + [int(c["sampled"][slot])
                                                for c in decode]
        assert sampled == f, (sampled, f)
        requests.append({
            "logits": np.stack([first["logits"][0, n - 1]]
                               + [c["logits"][slot, 0] for c in decode]),
            # the last sampled token is fed to nobody
            "tokens": np.concatenate([prompt, np.asarray(f[:-1], np.int32)]),
            "choices": [np.concatenate([first["chosen"][l][:n]]
                                       + [c["chosen"][l][slot][None]
                                          for c in decode])
                        for l in range(len(first["chosen"]))]})
    return {
        "requests": requests, "live_rows": live,
        "step_tokens_agree": float(np.mean(
            [c["logits"][:, 0].argmax(-1) == c["sampled"] for c in decode])),
        "window_released": sched._window_released.value - released0,
        "pools_whole": (sched.allocator.num_used_blocks == 0
                        and sched.window_allocator.num_used_blocks == 0),
        "decode_path": kv_cache._last_path}


def errors(got, want) -> dict:
    """Largest and root-mean-square difference, and the scale they are
    shares of (the largest reference logit)."""
    return {"err": float(np.abs(got - want).max()),
            "rms": float(np.sqrt(np.mean((got - want) ** 2))),
            "scale": float(np.abs(want).max())}


def compare(served: dict, weights: dict, config: dict, say) -> dict:
    """What ``served_logits`` gave against the plain float32 reference's
    full forward over each request's tokens; every reading is the worse of
    the requests'.

    The served path rounds every product and residual sum to bfloat16 (8
    significant bits) and the reference does not; over 14 residual adds that
    is a random walk of order 1 % of the logit scale. Near a tie of the 8th
    and 9th score + bias the same rounding sends a token to another expert
    than the reference would choose. That is no error of the served path
    (either choice is the router's, to the precision the configuration
    states), but compared naively it is one expert's whole weighted output
    in one position: 3.6-3.7 % of scale in three of the builder's first six
    runs, beside 1.3-1.7 % in the other three, and larger than what a
    missing sink costs. So the reference follows a served choice **only up
    to a tie**: where, by its own float32 scores, no expert the served set
    leaves out scores more than ``router_tie_margin`` above one it holds.
    Everywhere else it keeps its own choice (so a wrong choice costs the
    logits what it costs) and counts the row: the share of (token, layer)
    rows ``beyond`` the margin may not pass ``router_beyond_limit``, and the
    share that differ at all ``router_differs_limit``. The logits are held
    to ``rtol_of_scale`` (largest error) and ``rms_rtol_of_scale`` (root
    mean square), both shares of the largest reference logit. The
    scheduler's own step program has to sample the probe's arg-max
    (``step_tokens_agree_limit``: the same code at the same shapes over the
    same arguments), with all rows live, at least one window page released,
    and both pools whole at the end. ``PERF.md`` section 4 has the readings
    on both sides of every limit."""
    chk = config["reference_check"]
    worst, reports = {"err": 0.0, "rms": 0.0}, []
    for req in served["requests"]:
        got = req["logits"]
        routing = {"follow": req["choices"], "own": [], "report": [],
                   "margin": chk["router_tie_margin"]}
        want = np.asarray(reference.logits(
            weights, req["tokens"], reference_config(config),
            last=got.shape[0], routing=routing))
        e = errors(got, want)
        e["finite"] = bool(np.isfinite(got).all())
        reports += [dict(r, rows=len(req["tokens"]))
                    for r in routing["report"]]
        say(f"  prompt of {len(req['tokens']) - got.shape[0] + 1} tokens, "
            f"{got.shape[0]} positions: max err {e['err']:.4g} = "
            f"{e['err'] / e['scale']:.3%} of scale {e['scale']:.4g}, rms "
            f"{e['rms'] / e['scale']:.3%}")
        if not e["finite"] or e["err"] / e["scale"] >= worst["err"]:
            worst.update(err=e["err"] / e["scale"], abs=e)
        worst["rms"] = max(worst["rms"], e["rms"] / e["scale"])
        worst["finite"] = worst.get("finite", True) and e["finite"]
    rows = sum(r["rows"] for r in reports)
    share = lambda key: sum(r[key] * r["rows"] for r in reports) / rows
    differs, beyond = share("differs"), share("beyond")
    gap = max(r["gap_max"] for r in reports)
    ok = bool(worst["finite"]
              and worst["err"] <= chk["rtol_of_scale"]
              and worst["rms"] <= chk["rms_rtol_of_scale"]
              and differs <= chk["router_differs_limit"]
              and beyond <= chk["router_beyond_limit"]
              and served["step_tokens_agree"]
              >= chk["step_tokens_agree_limit"]
              and served["live_rows"] == config["scheduler"]["max_num_seqs"]
              and served["window_released"] > 0 and served["pools_whole"])
    say(f"{len(served['requests'])} requests among {served['live_rows']} "
        f"live rows through the scheduler (decode path "
        f"{served['decode_path']}, {served['window_released']:g} window "
        f"pages released meanwhile, pools whole after: "
        f"{served['pools_whole']}; its own step program sampled the probe's "
        f"arg-max in {served['step_tokens_agree']:.2%} of (row, step) "
        f"pairs, limit {chk['step_tokens_agree_limit']:.0%}) vs plain "
        f"float32 reference: largest error {worst['err']:.3%} of scale "
        f"(limit {chk['rtol_of_scale']:.1%}), rms {worst['rms']:.3%} (limit "
        f"{chk['rms_rtol_of_scale']:.2%}); served choices differ from the "
        f"reference router's in {differs:.2%} of (token, layer) rows (limit "
        f"{chk['router_differs_limit']:.0%}), {beyond:.3%} beyond a tie of "
        f"margin {chk['router_tie_margin']:g} (limit "
        f"{chk['router_beyond_limit']:.1%}; largest gap {gap:.4g}; a router "
        f"ignoring the correction bias: {share('differs_without_bias'):.1%} "
        f"and {share('beyond_without_bias'):.1%}): "
        f"{'ok' if ok else 'FAILED'}")
    return dict(worst["abs"], ok=ok, decode_path=served["decode_path"],
                err_of_scale=worst["err"], rms_of_scale=worst["rms"],
                router_differs=differs, router_beyond=beyond,
                router_gap_max=gap,
                step_tokens_agree=served["step_tokens_agree"])


def pool_blocks(config: dict, geometry, free_bytes: int) -> dict:
    """Blocks of the full-context class from the bytes the chip has left:
    less the headroom and the window class's pools (which hold
    ``max_num_seqs`` rows of the window's pages, whatever ``max_seq_len``),
    capped at what the slots can hold at ``max_seq_len``."""
    from paddle_tpu.models.kv_cache import window_blocks_per_seq

    sizes = config["scheduler"]
    bs, slots = sizes["block_size"], sizes["max_num_seqs"]
    cache_bytes = 2 if sizes["cache_dtype"] == "bfloat16" else 4
    row = lambda g: bs * g.kv_heads * (g.k_dim + g.v_dim) * cache_bytes
    full_block = sum(row(g) for g in geometry if not g.window)
    window_bytes = sum(
        row(g) * slots * window_blocks_per_seq(g.window, bs)
        for g in geometry if g.window)
    cap = slots * -(-sizes["max_seq_len"] // bs)
    left = (free_bytes - config["kv_pool"]["hbm_headroom_bytes"]
            - window_bytes)
    return {"num_blocks": int(min(cap, left // full_block)),
            "full_block_bytes": full_block, "window_bytes": window_bytes,
            "cache_bytes": cache_bytes}


def set_up(ctx) -> dict:
    import jax

    from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

    config, traffic, say = ctx.cell.config, ctx.cell.traffic, ctx.say
    cfg, model = build_model(config, ctx.seed)
    jax.block_until_ready([p._value for p in model.parameters()])
    ctx.phase_done("model from the seed")

    sizes = dict(config["scheduler"])
    stats = jax.devices()[0].memory_stats()
    pool = pool_blocks(config, model.cache_geometry(),
                       2**62 if stats is None else
                       stats["bytes_limit"] - stats["bytes_in_use"])
    if stats is not None:
        sizes["num_blocks"] = pool["num_blocks"]
    scfg = SchedulerConfig(**sizes)
    sched = ContinuousBatchingScheduler(model, scfg)
    say(f"pools: full class {scfg.total_blocks} blocks x {scfg.block_size} "
        f"tokens = {scfg.total_blocks * pool['full_block_bytes'] / 2**30:.2f}"
        f" GiB; window class {sched.window_allocator.num_blocks} blocks = "
        f"{pool['window_bytes'] / 2**30:.2f} GiB; slots "
        f"{scfg.max_num_seqs}; dispatch_depth {scfg.dispatch_depth}")
    ctx.phase_done("scheduler and pools")

    buckets = serve.prefill_buckets(traffic, scfg, sched.max_seq_len)
    r = rng(ctx.seed, STREAM_TOKENS, 998)
    for b in buckets:
        sched.add_request(r.integers(0, cfg.vocab_size, b - 2), 2)
    sched.run()
    ctx.phase_done(f"warm-up of prefill buckets {buckets} and the decode "
                   f"program")
    served = served_logits(model, cfg, config, sched, ctx.seed)
    sched.mark_steady()
    sched.window_blocks_peak = 0       # set-up's rows do not count
    ctx.phase_done("the checked request among live rows, with its probe")
    return {"cfg": cfg, "sched": sched, "scfg": scfg, "served": served,
            "model": model, "cache_bytes": pool["cache_bytes"],
            "telemetry0": sched.telemetry_snapshot() or {}}


def hybrid_record(st: dict) -> dict:
    """What this configuration's readers read beside a serve record."""
    from paddle_tpu.models import kv_cache
    from paddle_tpu.nn import moe

    sched = st["sched"]
    return {"telemetry0": st["telemetry0"],
            "telemetry1": sched.telemetry_snapshot() or {},
            "window_blocks_peak": sched.window_blocks_peak,
            "window_blocks_total": sched.window_allocator.num_blocks,
            "window_blocks_released":
                sched._window_released.value,
            "decode_path": kv_cache._last_path,
            "check_decode_path": st["served"]["decode_path"],
            "expert_path": moe._last_path}


def run(ctx) -> dict:
    """Set-up, the window, and only then the reference's side of the check
    (a float32 forward of ~13 s that is no part of setting the system up):
    the served side was taken in set-up, before anything was measured."""
    st = set_up(ctx)
    # the verdict reads the check from the record: filled in below
    st["check"] = {"ok": False, "err": float("nan"), "scale": float("nan")}
    rec = serve.measure(ctx, st, ctx.cell.traffic)
    rec["hybrid"] = hybrid_record(st)
    ctx.say(f"hybrid: {rec['hybrid']}")
    t0 = time.perf_counter()
    rec["reference_check"] = compare(
        st["served"], reference.weights_of(st["model"]), ctx.cell.config,
        ctx.say)
    ctx.say(f"after the window: the reference's side of the check "
            f"{time.perf_counter() - t0:.1f} s")
    return rec


def sweep(ctx, rates: list) -> int:
    raise SystemExit("perfbench: this configuration's cell is a closed "
                     "loop; it has no rate to sweep")
