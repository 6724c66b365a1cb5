"""Runner for configurations of ``kind: serve_latent_moe``: a causal LM whose
layers cache one latent row a token for all heads (multi-head latent
attention: expanded in prefill, absorbed in decode) over sparse experts of
which this chip holds a share, beside a shared expert, behind the program's
``ContinuousBatchingScheduler``.

It brings what differs (the reference check through the scheduler the window
is measured on, against this model's plain reference; the pool sized from the
model's own cache geometry; what its readers read) and takes the rest
unedited: ``Drive``, ``measure``, ``verdict``, ``counts``, ``sweep``,
``prefill_buckets`` from ``runners/serve.py``, and ``build_model``,
``reference_config``, ``check_prompts``, ``errors`` from
``runners/serve_hybrid_moe.py``. Its records are ``kind: serve`` records with
the group ``hybrid`` that ``harness/hybrid_view.py`` reads (the compiled
step's own counts, the paths taken) and one more, ``latent``.

Set-up (all of it counted in ``setup_s``): model from the seed, created in
the weights' own type; the pool; one warm-up request for each prefill bucket
the cell's prompts can reach (which also compiles the decode program); the
served side of the reference check, taken through this very scheduler with
every slot live (``served_logits``); the ramp. The plain float32 reference
(``harness/reference_joyai_flash.py``) computes its side at the published
widths after the window (``compare``).
"""

from __future__ import annotations

import math
import time

import numpy as np

from perfbench.harness import reference_joyai_flash as reference
from perfbench.harness.load import STREAM_TOKENS, rng
from perfbench.harness.spec import load_module

hybrid = load_module("runners", "serve_hybrid_moe")
serve = hybrid.serve
verdict, counts = serve.verdict, serve.counts
build_model, reference_config = hybrid.build_model, hybrid.reference_config
check_prompts, errors = hybrid.check_prompts, hybrid.errors


class Probe:
    """``serve_hybrid_moe``'s probe for a pool that fills the chip: every
    launch goes first through a program of the same model over the same
    arguments that gives back the logits and each expert layer's choice,
    then through the scheduler's own step program, whose sampled tokens are
    kept beside them. A program that donated nothing would copy every
    layer's pool to write the new rows into it (10 GiB here: it does not
    fit), so this one donates the pools as the step does and hands them on
    to the step, which writes the same rows at the same positions again and
    reads its own. Everything else it is asked for is the step's."""

    def __init__(self, step, model, donate: bool):
        from paddle_tpu.jit.api import StaticFunction
        from paddle_tpu.models.kv_cache import donate_pools, pools_only

        self.step, self.calls = step, []
        moe = [l.mlp for l in model.model.layers
               if hasattr(l.mlp, "last_experts")]

        def forward(ids, position_ids, caches):
            logits, new_caches = model(ids, position_ids, caches)
            return (logits, [m.last_experts for m in moe],
                    pools_only(new_caches))

        self.logits = StaticFunction(forward, layer=model,
                                     donate_args=donate and donate_pools,
                                     name="perfbench.probe")

    def __getattr__(self, name):
        return getattr(self.step, name)

    def __call__(self, ids, position_ids, caches, gather_idx):
        logits, chosen, pools = self.logits(ids, position_ids, caches)
        caches = [c._replace(k_pool=p.k_pool, v_pool=p.v_pool)
                  for c, p in zip(caches, pools)]
        out = self.step(ids, position_ids, caches, gather_idx)
        self.calls.append({
            "logits": np.asarray(logits.numpy(), np.float32),
            "chosen": [np.asarray(c.numpy()) for c in chosen],
            "sampled": np.asarray(out[0].numpy())})
        return out


def served_logits(model, cfg, config: dict, sched, seed: int) -> dict:
    """The checked requests through the scheduler that the window is
    measured on, with every other slot live: requests of unequal length are
    admitted into all but the last slots and decode beside them (the
    allocator, the tables of all rows, the expanded prefill of each bucket,
    the absorbed decode program and the expert step at the cell's own
    shapes); then the checked prompts are admitted and decode
    ``decode_positions`` steps: a long one, whose prefill writes its latent
    rows across many pages and whose decode reads them back through the
    latent kernel over page boundaries, and a short one, whose few visible
    positions make every single row (and its rotary part) a large share of
    the softmax. Returns, a request, its ``logits [positions + 1, V]`` (from
    ``Probe``), its ``tokens`` (the prompt and what was fed) and each expert
    layer's ``choices`` for every one of them; the share of (row, step)
    pairs in which the scheduler's own program sampled the probe's arg-max;
    and whether the pool was whole once everything was cancelled."""
    from paddle_tpu.models import kv_cache

    chk = config["reference_check"]
    steps = int(chk["decode_positions"])
    lo, hi = chk["filler_prompt_tokens"]
    prompts = check_prompts(config, cfg.vocab_size, seed)
    slots = sched.config.max_num_seqs
    r = rng(seed, STREAM_TOKENS, 997)
    fillers = [sched.add_request(
        r.integers(0, cfg.vocab_size, int(r.integers(lo, hi + 1))),
        steps + 32) for _ in range(slots - len(prompts))]
    while sched.metrics.running < len(fillers):
        sched.step()
    probe = sched._step_fn = Probe(sched._step_fn, model, sched._donate)
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
        "pools_whole": sched.allocator.num_used_blocks == 0,
        "decode_path": kv_cache._last_path}


def compare(served: dict, weights: dict, config: dict, say) -> dict:
    """What ``served_logits`` gave against the plain float32 reference's
    full forward (expanded form, no cache) over each request's tokens; every
    reading is the worse of the requests'.

    As for ``serve_hybrid_moe`` (its ``compare`` says why at length): the
    served path rounds every product and residual sum to bfloat16 and the
    reference does not, and near a tie of the 8th and 9th score + bias that
    rounding sends a token to another expert than the reference would
    choose, which is no error of the served path; so the reference follows
    a served choice **only up to a tie** (``router_tie_margin``), keeps its
    own elsewhere, and counts the rows that differ and those ``beyond`` the
    margin. The logits are held to ``rtol_of_scale`` (largest error) and
    ``rms_rtol_of_scale`` (root mean square), both shares of the largest
    reference logit. The scheduler's own step program has to sample the
    probe's arg-max (``step_tokens_agree_limit``), with all rows live, the
    decode path the one the configuration states (``decode_path``: the
    Pallas kernel on the chip), and the pool whole at the end. The
    configuration's ``reference_check.why`` has the readings on both sides
    of every limit."""
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
              and served["decode_path"] == chk["decode_path"]
              and served["pools_whole"])
    say(f"{len(served['requests'])} requests among {served['live_rows']} "
        f"live rows through the scheduler (decode path "
        f"{served['decode_path']}, wanted {chk['decode_path']}; pool whole "
        f"after: {served['pools_whole']}; its own step program sampled the "
        f"probe's arg-max in {served['step_tokens_agree']:.2%} of (row, "
        f"step) pairs, limit {chk['step_tokens_agree_limit']:.0%}) vs plain "
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
    """Blocks of the pool from the bytes the chip has left, less the
    headroom, by the model's own cache geometry (a block's bytes are what
    the program allocates for it: the latent row padded to whole lane
    tiles), capped at what the slots can hold at ``max_seq_len``."""
    from paddle_tpu.models.kv_cache import pool_shapes

    sizes = config["scheduler"]
    bs = sizes["block_size"]
    cache_bytes = 2 if sizes["cache_dtype"] == "bfloat16" else 4
    block_bytes = cache_bytes * sum(
        math.prod(shape) for g in geometry
        for shape in pool_shapes(g, 1, bs) if shape is not None)
    cap = sizes["max_num_seqs"] * -(-sizes["max_seq_len"] // bs)
    left = free_bytes - config["kv_pool"]["hbm_headroom_bytes"]
    return {"num_blocks": int(min(cap, left // block_bytes)),
            "block_bytes": block_bytes, "cache_bytes": cache_bytes}


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
    say(f"pool: {scfg.total_blocks} blocks x {scfg.block_size} tokens = "
        f"{scfg.total_blocks * pool['block_bytes'] / 2**30:.2f} GiB at "
        f"{pool['block_bytes'] // scfg.block_size} B a token; slots "
        f"{scfg.max_num_seqs}; dispatch_depth {scfg.dispatch_depth}")
    ctx.phase_done("scheduler and pool")

    buckets = serve.prefill_buckets(traffic, scfg, sched.max_seq_len)
    r = rng(ctx.seed, STREAM_TOKENS, 998)
    for b in buckets:
        sched.add_request(r.integers(0, cfg.vocab_size, b - 2), 2)
    sched.run()
    ctx.phase_done(f"warm-up of prefill buckets {buckets} and the decode "
                   f"program")
    served = served_logits(model, cfg, config, sched, ctx.seed)
    sched.mark_steady()
    ctx.phase_done("the checked requests among live rows, with their probe")
    # the verdict reads the check from the record: ``run`` fills it in
    return {"cfg": cfg, "sched": sched, "scfg": scfg, "served": served,
            "model": model, "cache_bytes": pool["cache_bytes"],
            "check": {"ok": False, "err": float("nan"),
                      "scale": float("nan")},
            "telemetry0": sched.telemetry_snapshot() or {}}


def latent_records(st: dict) -> dict:
    """What this configuration's readers read beside a serve record: the
    group ``hybrid`` as ``harness/hybrid_view.py`` reads it, and
    ``latent``."""
    from paddle_tpu.models import kv_cache
    from paddle_tpu.nn import moe

    sched = st["sched"]
    gauge = sched.metrics.registry.get("kv_bytes_per_token")
    return {"hybrid": {"telemetry0": st["telemetry0"],
                       "telemetry1": sched.telemetry_snapshot() or {},
                       "decode_path": kv_cache._last_path,
                       "check_decode_path": st["served"]["decode_path"],
                       "expert_path": moe._last_path},
            "latent": {"kv_bytes_per_token":
                       None if gauge is None else gauge.value}}


def run(ctx) -> dict:
    """Set-up, the window, and only then the reference's side of the check
    (a float32 forward that is no part of setting the system up): the
    served side was taken in set-up, before anything was measured."""
    st = set_up(ctx)
    rec = serve.measure(ctx, st, ctx.cell.traffic)
    rec.update(latent_records(st))
    ctx.say(f"hybrid: {rec['hybrid']}; latent: {rec['latent']}")
    t0 = time.perf_counter()
    rec["reference_check"] = compare(
        st["served"], reference.weights_of(st["model"]), ctx.cell.config,
        ctx.say)
    ctx.say(f"after the window: the reference's side of the check "
            f"{time.perf_counter() - t0:.1f} s")
    return rec


def sweep(ctx, rates: list) -> int:
    """``runners/serve.py::sweep`` over this runner's set-up: a copy of that
    module of its own (``load_module`` makes one) is told which set-up to
    run. A sweep reads rates, not logits: the check is not compared."""
    swept = load_module("runners", "serve")

    def set_up_unchecked(ctx):
        st = set_up(ctx)
        st["check"]["ok"] = True
        return st

    swept.set_up = set_up_unchecked
    return swept.sweep(ctx, rates)
