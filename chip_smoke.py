#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py                 # one TPU chip; anything else fails
    python3 chip_smoke.py --chips 4       # the four-chip mode (builder-run)
    python3 chip_smoke.py --rehearse-cpu  # CPU rehearsal, proves nothing

Drives the main path once through the entry points a user would call, at the
full width of GPT-3 1.3B (``paddle_tpu.models.gpt.gpt3_1p3b()``: 24 layers,
hidden 2048, 16 heads x 128, vocabulary 50304, 2048 positions; seeded random
weights), and checks what comes out:

- kernels: every Pallas kernel the repo ships compiles for the chip
  (``interpret=False``) and agrees with its XLA reference;
- serve:   ``GPTForCausalLM`` in bf16 behind ``ContinuousBatchingScheduler``
  (ragged prompts over three prefill buckets, more requests than slots),
  every request finished, no fault, no steady-state recompile, and the
  paged-cache logits agree with the plain eager forward;
- train:   a few ``TrainStep`` steps in the configuration of
  ``perfbench/configs/gpt3_1p3b_train_amp.json``, loss finite and falling,
  flash path Pallas.

Any phase that raises or any check that fails ends the run with a non-zero
exit code and no result line. The last line of a passing run is one JSON
object, ``{"ok": true, "device": {...}}``, with the device as JAX reports it.
The times it prints are the builder's notes, not results: it states no speed.

ONE PROCESS FOR EACH CHIP. A TPU belongs to one process at a time, and a
process that has touched JAX holds it. This parent therefore imports neither
``jax`` nor ``paddle_tpu``: it runs each phase as a child, one after another
(each child starts with empty device memory, so the peak it reports is that
phase's own), and loads the compile-cache helper by file path.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "CHIP_SMOKE_PHASE_RESULT "
REHEARSAL_NOTE = ("REHEARSAL on CPU at gpt_tiny size with kernels in "
                  "interpret mode: this proves nothing about the chip")
# the contract allows 1200 s, compilation included; leave room to report
DEADLINE_S = 1150.0

PHASES = {1: ("kernels", "serve", "train"), 4: ("serve_tp4", "hybrid4")}

# ---- sizes ---------------------------------------------------------------
# chip: GPT-3 1.3B at its published widths. rehearsal: gpt_tiny, same code.
SERVE = {
    "chip": dict(max_num_seqs=4, max_seq_len=2048, block_size=16,
                 # three prefill buckets (128, 256, 1024), six requests on
                 # four slots so admission and retirement both happen
                 prompt_lens=(90, 120, 230, 250, 900, 1000),
                 new_tokens=(32, 40, 48, 32, 40, 48),
                 logits_prompt=256),
    "rehearsal": dict(max_num_seqs=4, max_seq_len=256, block_size=16,
                      prompt_lens=(10, 14, 25, 30, 100, 120),
                      new_tokens=(8, 10, 12, 8, 10, 12),
                      logits_prompt=32),
}
TRAIN = {"chip": dict(batch=4, seqlen=1024, steps=4),
         "rehearsal": dict(batch=2, seqlen=128, steps=4)}
# device memory kept out of the KV pool: the widest prefill's logits and
# scores, the decode step's gathered pages, and the allocator's slack
HBM_HEADROOM = 3 << 30

# Why the logits check tolerates 5 % of the logit scale: bf16 keeps 8
# significant bits (eps = 2^-8 = 0.4 %). Both forwards round every matmul
# output and every residual sum to bf16, but they sum attention in different
# orders (flash blocks on the plain path; one f32 softmax over the gathered
# pages on the cache path), so they drift apart like a random walk over the
# 2 x 24 residual adds: sqrt(48) x 0.4 % = 2.7 % of the scale. 5 % leaves
# headroom; a wrong mask, position or page table is an error of order 100 %.
LOGITS_RTOL = 0.05


def _say(msg: str) -> None:
    print(msg, flush=True)


def _check(cond: bool, what: str) -> None:
    # not an assert: the checks must survive ``python -O``
    if not cond:
        raise SystemExit(f"chip_smoke: CHECK FAILED: {what}")
    _say(f"  ok: {what}")


# ==========================================================================
# child side: one phase, in the one process that holds the chip
# ==========================================================================

def _start(rehearse: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    _say(f"[start] jax {jax.__version__} platform={info['platform']} "
         f"device_kind={info['kind']!r} devices={info['count']}")
    want = "cpu" if rehearse else "tpu"
    if info["platform"] != want:
        raise SystemExit(
            f"chip_smoke: needs platform {want!r} but JAX found platform "
            f"{info['platform']!r} ({info['kind']!r}); there is no fallback"
            + ("" if rehearse else
               " — use --rehearse-cpu for the CPU rehearsal"))
    return info


class _CompileCounter:
    """This process's compile traffic, from JAX's own monitoring events:
    compile ``requests``, the persistent-cache ``cache_hits`` among them,
    ``cache_writes`` (compiled and stored), ``not_persisted`` (compiled but
    never stored: JAX keeps only programs that took over a second, so the
    eager per-op programs are compiled again by every process), and
    ``compile_s``, the seconds spent compiling or loading from the cache."""

    _EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
               "/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_writes"}

    def __init__(self):
        import jax.monitoring as mon

        self.counts = dict.fromkeys(self._EVENTS.values(), 0)
        self.compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name in self._EVENTS:
            self.counts[self._EVENTS[name]] += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def report(self) -> dict:
        c = self.counts
        return dict(c, not_persisted=(c["requests"] - c["cache_hits"]
                                      - c["cache_writes"]),
                    compile_s=round(self.compile_s, 2))


def _peak_hbm(devs=None):
    import jax

    peaks = []
    for d in devs or jax.devices()[:1]:
        st = d.memory_stats()
        if st is None:          # the CPU backend keeps no such statistics
            return None
        peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks)


def _gib(n) -> str:
    return "n/a" if n is None else f"{n / 2**30:.2f} GiB"


# ---- kernels --------------------------------------------------------------

def _close(got, want, rtol: float, what: str) -> None:
    """``got`` within ``rtol`` of ``want``'s own scale (its largest
    magnitude): the check for results whose rounding, not whose value,
    depends on the path taken."""
    import numpy as np

    got, want = (np.asarray(a, np.float32) for a in (got, want))
    err, scale = float(np.abs(got - want).max()), float(np.abs(want).max())
    _check(np.isfinite(got).all() and err <= rtol * scale,
           f"{what}: max err {err:.3g} = {err / scale:.2g} x scale "
           f"{scale:.3g} (limit {rtol:g} x scale)")


def phase_kernels(rehearse: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import fused_adamw as fad
    from paddle_tpu.ops.pallas import fused_rms_norm as frn

    interpret = rehearse
    rng = np.random.default_rng(0)
    times = {}

    def timed(name, fn, *args):
        """First call (compiles) and second call of one jitted function."""
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        t1 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times[name] = (round(t1 - t0, 2), round(time.perf_counter() - t1, 4))
        return out

    def normal(shape, dtype=jnp.float32):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32)
                           ).astype(dtype)

    # fused AdamW over a flat buffer vs the formula
    n = 8 * 128 * (64 if rehearse else 4096)
    p, g, m = (np.asarray(normal(n)) for _ in range(3))
    v = np.abs(np.asarray(normal(n))) * 0.01
    wd = np.full(n, 0.01, np.float32)
    out = timed("fused_adamw", lambda: fad.fused_adamw_flat(
        p, g, m, v, wd, 1e-3, np.full(n, 0.9, np.float32),
        np.full(n, 0.999, np.float32), interpret=interpret))
    m2 = 0.9 * m + 0.1 * g
    v2 = 0.999 * v + 0.001 * g * g
    ref = p * (1 - 1e-3 * 0.01) - 1e-3 * (m2 / 0.1) / (
        np.sqrt(v2 / 0.001) + 1e-8)
    # fp32 elementwise: only the order of the few operations differs
    _close(out[0], ref, 1e-5, f"fused_adamw_flat(interpret={interpret}) "
                              f"params over {n} elements")
    _close(out[1], m2, 1e-6, "fused_adamw_flat first moment")

    # fused RMSNorm, forward and backward under jit(grad), at the widths the
    # Llama path uses and at the gate's limit. fp32 differs from the XLA
    # composition by summation order only; bf16 rounds results to 8 bits.
    widths = (256,) if rehearse else (2048, 4096, 5120, 8192)
    rows = 100 if rehearse else 1000      # not a block multiple: pads
    for d in widths:
        for dtype, rtol in ((jnp.float32, 1e-4), (jnp.bfloat16, 2e-2)):
            x, w, gy = normal((rows, d), dtype), normal(d, dtype), \
                normal((rows, d), dtype)

            def fwd_and_grads(norm):
                return jax.jit(lambda x_, w_: (
                    norm(x_, w_),
                    jax.grad(lambda a, b: jnp.sum(
                        (norm(a, b) * gy).astype(jnp.float32)),
                        argnums=(0, 1))(x_, w_)))

            name = f"rms_norm[{d},{jnp.dtype(dtype).name}]"
            fk, (dxk, dwk) = timed(name, fwd_and_grads(
                lambda a, b: frn.rms_norm_pallas(a, b, 1e-6, None,
                                                 interpret)), x, w)
            fr, (dxr, dwr) = fwd_and_grads(
                lambda a, b: frn.rms_ref(a, b, 1e-6))(x, w)
            for got, want, what in ((fk, fr, "fwd"), (dxk, dxr, "dx"),
                                    (dwk, dwr, "dw")):
                _close(got, want, rtol,
                       f"{name} {what} (block_rows {frn._block_rows(d)})")
    if not rehearse:
        frn.rms_norm_routed(jnp.ones((64, 2048), jnp.bfloat16),
                            jnp.ones((2048,), jnp.bfloat16), 1e-6)
        _check(frn._last_path == "pallas",
               "rms_norm_routed selects the Pallas kernel on the chip")

    # splash varlen path of flash_attn_unpadded vs the dense-mask path
    T, H, D = (128, 2, 64) if rehearse else (512, 4, 128)
    cu = np.asarray([0, T // 5, T // 2, T], np.int32)
    q, k, vv = (np.asarray(normal((T, H, D))) for _ in range(3))
    t = paddle.to_tensor
    cut = t(cu)
    if rehearse:
        # the gate selects splash only on a TPU: call the kernel wrapper
        # directly, through the interpreter
        fa._interpret = True
        seg = jnp.searchsorted(jnp.asarray(cu), jnp.arange(T),
                               side="right") - 1
        got = timed("splash_varlen", lambda: fa._splash_varlen(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(vv), seg, seg, True,
            1.0 / np.sqrt(D)))
    else:
        got = timed("splash_varlen", lambda: fa.flash_attn_unpadded(
            t(q), t(k), t(vv), cut, cut, causal=True)[0]._value)
        _check(fa._last_path == "splash",
               "flash_attn_unpadded selects the splash kernel on the chip")
    fa._FLASH_ENABLED = False
    want, _ = fa.flash_attn_unpadded(t(q), t(k), t(vv), cut, cut,
                                     causal=True)
    fa._FLASH_ENABLED = True
    # the MXU multiplies fp32 operands in bf16 passes at default precision
    _close(got, want.numpy(), 1e-2, "splash varlen vs the dense mask")

    if not rehearse:
        # Pallas flash attention, forward and backward, at the train shape,
        # in bf16 (8 bits: the two paths round at different places)
        B, S, Hh, Dd = 2, 1024, 16, 128
        q, k, vv = (normal((B, S, Hh, Dd), jnp.bfloat16) for _ in range(3))

        def loss_and_grads(attn):
            return jax.jit(jax.value_and_grad(
                lambda a, b, c: jnp.sum(attn(a, b, c).astype(jnp.float32)
                                        ** 2), argnums=(0, 1, 2)))

        vk, gk = timed("flash_attention", loss_and_grads(
            lambda a, b, c: fa.flash_attention_fwd(a, b, c, causal=True)),
            q, k, vv)
        _check(fa._last_path == "pallas",
               "flash_attention_fwd selects the Pallas kernel on the chip")
        vr, gr = loss_and_grads(lambda a, b, c: fa._attention_reference(
            a, b, c, None, True, 1.0 / np.sqrt(Dd)))(q, k, vv)
        _close(vk, vr, 1e-3, "flash attention loss")
        for got, want, what in zip(gk, gr, ("dq", "dk", "dv")):
            _close(got, want, 3e-2, f"flash attention {what}")
    # paged decode attention vs the gather formulation: ragged live
    # lengths, scattered pages, an idle row (table -1, pos 0) among them.
    # On the chip the decode program's shape, through the gate
    from paddle_tpu.models import kv_cache as kvc
    from paddle_tpu.ops.pallas import paged_attention as pad

    B, MB, NB, dtype = (4, 5, 24, jnp.float32) if rehearse else \
        (32, 128, 3480, jnp.bfloat16)
    Hh, Dd, bs = (8 if rehearse else 16), 128, 16
    pad._interpret = interpret
    kp, vp = (normal((NB, bs, Hh, Dd), dtype) for _ in "kv")
    q = normal((B, 1, Hh, Dd), dtype)
    pos = rng.integers(1, MB * bs, B).astype(np.int32)
    pos[0], pos[1] = MB * bs - 1, 0
    table = rng.integers(0, NB, (B, MB)).astype(np.int32)
    table[1] = -1
    table, pos = jnp.asarray(table), jnp.asarray(pos)
    got = timed("paged_attention_decode", jax.jit(kvc._paged_attend),
                q, kp, vp, table, pos)
    _check(kvc._last_path == "pallas",
           "a paged cache step with one query token a row selects the "
           "Pallas kernel" + (" (forced: interpret mode)" if rehearse
                              else " on the chip"))
    want = jax.jit(kvc._paged_attend_xla)(q, kp, vp, table, pos)
    live = np.arange(B) != 1
    _close(np.asarray(got, np.float32)[live],
           np.asarray(want, np.float32)[live],
           1e-5 if rehearse else 2e-2,
           f"paged_attention_decode vs the gathered pages ({B} rows, "
           f"{MB} pages a row, {jnp.dtype(dtype).name})")
    _check(np.isfinite(np.asarray(got, np.float32)).all(),
           "the idle row's result is finite")
    pad._interpret = False
    for name, (setup_s, run_s) in times.items():
        _say(f"  time {name}: set-up {setup_s} s, run {run_s} s")
    return {"kernels": sorted(times)}


# ---- serve ----------------------------------------------------------------

def _model_config(rehearse: bool, **kw):
    from paddle_tpu.models.gpt import gpt3_1p3b, gpt_tiny

    cfg = gpt_tiny(**kw) if rehearse else gpt3_1p3b(**kw)
    if not rehearse:
        got = (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
               cfg.vocab_size, cfg.max_position_embeddings)
        _check(got == (24, 2048, 16, 50304, 2048),
               f"GPT-3 1.3B at published widths {got}")
    return cfg


def _last_logits(forward, cfg, ids, block_size: int = 0, shard_pools=None,
                 decode_last: bool = False):
    """Last-position logits of ``forward`` for one prompt, as fp32: the
    plain ``forward(ids)`` when ``block_size`` is 0, else
    ``forward(ids, position_ids, caches)`` through a fresh paged cache
    (``shard_pools`` splits its pools over a mesh first): the whole prompt
    in one call, or with ``decode_last`` all but its last token and then
    that token alone, which is a decode step."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.kv_cache import PagedCacheSlot

    n = ids.shape[1]
    with paddle.no_grad():
        if not block_size:
            out = forward(paddle.to_tensor(ids))
        else:
            heads, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
            nb = -(-n // block_size)
            pools = [tuple(paddle.zeros([nb, block_size, heads, hd],
                                        dtype="bfloat16") for _ in "kv")
                     for _ in range(cfg.num_layers)]
            if shard_pools is not None:
                pools = shard_pools(pools)
            table = paddle.to_tensor(np.arange(nb, dtype=np.int32)[None])
            caches = [PagedCacheSlot(kp, vp, table,
                                     paddle.zeros([1], dtype="int32"))
                      for kp, vp in pools]
            for lo, hi in ((0, n - 1), (n - 1, n)) if decode_last \
                    else ((0, n),):
                out, caches = forward(
                    paddle.to_tensor(ids[:, lo:hi]),
                    paddle.to_tensor(np.arange(lo, hi, dtype=np.int32)),
                    caches)
    last = np.asarray(out.numpy(), np.float32)[0, -1]
    _check(last.shape == (cfg.vocab_size,) and np.isfinite(last).all(),
           f"logits finite, shape {last.shape}")
    return last


def phase_serve(rehearse: bool, tp: int = 0) -> dict:
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.observability.device_memory import tree_device_nbytes
    from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

    size = SERVE["rehearsal" if rehearse else "chip"]
    compiles = _CompileCounter()
    t0 = time.perf_counter()
    paddle.seed(0)
    cfg = _model_config(rehearse)
    model = GPTForCausalLM(cfg)
    model.eval()
    # bf16 by model.bfloat16() for the weights and cache_dtype="bfloat16"
    # for the KV pool (what inference.Config.enable_low_precision maps to)
    model.bfloat16()
    # values, not only presence: for one prompt, the last-position logits
    # through the paged cache against the plain eager forward. Logits, not
    # tokens: with random weights the arg-max flips on rounding.
    ids = np.random.default_rng(7).integers(
        0, cfg.vocab_size, (1, size["logits_prompt"])).astype(np.int32)
    plain = _last_logits(model, cfg, ids)
    paged = _last_logits(model, cfg, ids, size["block_size"])
    _close(paged, plain, LOGITS_RTOL,
           "paged-cache logits against the eager forward")
    logits_err = float(np.abs(paged - plain).max())
    # the same position as a decode step (one query token): on the chip
    # through the Pallas kernel, which no fallback may replace in silence
    from paddle_tpu.models import kv_cache as kvc

    decoded = _last_logits(model, cfg, ids, size["block_size"],
                           decode_last=True)
    if rehearse:
        _say(f"  the paged decode kernel did NOT run: gpt_tiny's head size "
             f"{cfg.hidden_size // cfg.num_heads} is not one its gate "
             f"accepts (a multiple of 128); path {kvc._last_path!r}")
    else:
        _check(kvc._last_path == "pallas",
               "the eager decode step took the Pallas paged-attention "
               "kernel")
    _close(decoded, plain, LOGITS_RTOL,
           "decode-step logits through the paged cache against the eager "
           "forward (PR 21 read 1.2 % for the prefill-only check)")
    decode_err = float(np.abs(decoded - plain).max())

    heads, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    block_bytes = cfg.num_layers * 2 * size["block_size"] * heads * hd * 2
    stats = jax.devices()[0].memory_stats()
    if stats is None:       # CPU rehearsal: every slot at max_seq_len
        num_blocks = None
    else:
        # fill the chip: what is left after the weights, less the headroom.
        # (The scheduler builds the whole pool on ONE device before a
        # sharding splits it, so the four-chip mode is sized the same.)
        num_blocks = (stats["bytes_limit"] - stats["bytes_in_use"]
                      - HBM_HEADROOM) // block_bytes
    scfg = SchedulerConfig(
        max_num_seqs=size["max_num_seqs"], max_seq_len=size["max_seq_len"],
        block_size=size["block_size"], num_blocks=num_blocks,
        cache_dtype="bfloat16")
    sharding = None
    if tp:
        from paddle_tpu.serving.sharded import TensorParallelSharding

        sharding = TensorParallelSharding(tp=tp)
    sched = ContinuousBatchingScheduler(model, scfg, sharding=sharding)
    pool_bytes = scfg.total_blocks * block_bytes
    _say(f"  pool: {scfg.total_blocks} blocks x {scfg.block_size} tokens = "
         f"{_gib(pool_bytes)} bf16; slots {scfg.max_num_seqs}; max_seq_len "
         f"{sched.max_seq_len}; dispatch_depth {scfg.dispatch_depth}; "
         f"donation {sched._donate}")
    _check(sched.max_seq_len == size["max_seq_len"],
           f"max_seq_len {sched.max_seq_len}")
    if tp:
        per_dev = tree_device_nbytes(sched._pools)
        _say(f"  pool bytes per device: {per_dev}")
        _check(len(per_dev) == tp and
               all(b * tp == pool_bytes for b in per_dev.values()),
               f"each of {tp} chips holds 1/{tp} of the pool")
        in_use = [d.memory_stats()["bytes_in_use"]
                  for d in jax.devices()[:tp]] if stats else None
        _say(f"  bytes in use per device (memory_stats): {in_use}")
        # the sharded step re-stages the forward by hand: its logits, under
        # the mesh and over head-sharded pools, against the single-device
        # eager forward taken before the weights were sharded
        from paddle_tpu.jit.api import StaticFunction

        sharded = _last_logits(
            StaticFunction(sched._step_fn._model_call, layer=model,
                           name="chip_smoke.sharded_forward"),
            cfg, ids, size["block_size"], sharding.shard_pools)
        _close(sharded, plain, LOGITS_RTOL,
               f"tp={tp} sharded step's logits against the single-device "
               f"eager forward")

    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int64)
               for n in size["prompt_lens"]]

    def batch():
        rids = [sched.add_request(p, max_new_tokens=k)
                for p, k in zip(prompts, size["new_tokens"])]
        outs = sched.run()
        bad = [(rid, outs[rid].finish_reason, len(outs[rid].generated_ids))
               for rid, want in zip(rids, size["new_tokens"])
               if outs[rid].finish_reason not in ("length", "eos")
               or len(outs[rid].generated_ids) != want]
        _check(not bad,
               f"{len(rids)} requests on {scfg.max_num_seqs} slots finished "
               f"'length'/'eos' with their full token count (not: {bad})")
        return [[int(x) for x in outs[r].generated_ids] for r in rids]

    # one request alone first: its prefill traces, then the decode program,
    # so the gate's last record is the decode program's
    sched.add_request(prompts[0], max_new_tokens=2)
    sched.run()
    want_path = "xla" if rehearse or tp else "pallas"
    _check(kvc._last_path == want_path,
           f"the scheduler's decode program took the {want_path!r} paged "
           f"attention (no silent fallback)")
    warm = batch()                       # compiles every other shape
    setup_s = time.perf_counter() - t0
    sched.mark_steady()
    t1 = time.perf_counter()
    steady = batch()                     # the same shapes again
    run_s = time.perf_counter() - t1
    _check(sched.metrics.requests_failed == 0,
           "metrics.requests_failed == 0")
    faults = sched.metrics.faults_snapshot()
    _check(not faults, f"no fault noted at any site ({faults})")
    cs = sched.compile_stats()
    _check(cs["steady_state_recompiles"] == 0,
           f"second batch compiled nothing: {cs}")
    _check(steady == warm,
           "second batch's tokens equal the first's (same prompts, other "
           "slots)")
    _check(sched.allocator.num_used_blocks == 0, "every KV block returned")
    peak = _peak_hbm(jax.devices()[:tp or 1])
    _say(f"  time serve: set-up {setup_s:.1f} s (model, logits check, "
         f"scheduler, first batch incl. compiles), run {run_s:.1f} s "
         f"(second batch, {sum(size['new_tokens'])} new tokens)")
    _say(f"  compiles: scheduler programs {cs['compiles']}; jax "
         f"{compiles.report()}")
    _say(f"  peak HBM (serve): {_gib(peak)}")
    digest = hashlib.sha256(json.dumps(warm).encode()).hexdigest()[:16]
    return {"tokens_sha": digest, "logits_err": logits_err,
            "decode_logits_err": decode_err,
            "setup_s": round(setup_s, 1), "run_s": round(run_s, 1),
            "peak_hbm_bytes": peak, "pool_blocks": scfg.total_blocks,
            "compiles": compiles.report()}


# ---- train ----------------------------------------------------------------

def phase_train(rehearse: bool) -> dict:
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.ops.pallas import flash_attention as fa

    size = TRAIN["rehearsal" if rehearse else "chip"]
    compiles = _CompileCounter()
    t0 = time.perf_counter()
    paddle.seed(0)
    # as the train_pretrain cell's configuration: fp32 params (the master
    # copy; bf16 compute from auto_cast O1), bf16 AdamW moments,
    # dots_saveable recompute, vocab-chunked fused linear-CE
    cfg = _model_config(rehearse, recompute="dots_saveable")
    model = GPTForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4, weight_decay=0.1,
                          parameters=model.parameters(),
                          moment_dtype="bfloat16")

    def loss_fn(m, ids, labels):
        with paddle.amp.auto_cast(level="O1"):
            return m.loss_fused(ids, labels, num_chunks=8)

    step = TrainStep(model, loss_fn, optimizer)
    ids_np = np.random.default_rng(4).integers(
        0, cfg.vocab_size, (size["batch"], size["seqlen"])).astype(np.int32)
    ids = paddle.to_tensor(ids_np)
    losses = [float(np.asarray(step(ids, ids).numpy()))]
    setup_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    for _ in range(size["steps"] - 1):
        losses.append(float(np.asarray(step(ids, ids).numpy())))
    run_s = time.perf_counter() - t1
    _say(f"  losses on a fixed {size['batch']} x {size['seqlen']} batch: "
         f"{[round(l, 4) for l in losses]}")
    _check(all(np.isfinite(losses)), "every loss finite")
    _check(losses[-1] < losses[0], "loss falling")
    want = "xla" if rehearse else "pallas"
    _check(fa._last_path == want,
           f"ops.pallas.flash_attention._last_path == {want!r}")
    peak = _peak_hbm()
    _say(f"  time train: set-up {setup_s:.1f} s (model, optimizer, first "
         f"step incl. compile), run {run_s:.1f} s ({size['steps'] - 1} "
         f"steps)")
    _say(f"  compiles: jax {compiles.report()}")
    _say(f"  peak HBM (train): {_gib(peak)}")
    return {"losses": losses, "setup_s": round(setup_s, 1),
            "run_s": round(run_s, 1), "peak_hbm_bytes": peak,
            "compiles": compiles.report()}


# ---- four chips -----------------------------------------------------------

def phase_hybrid4(rehearse: bool) -> dict:
    """The hybrid dp x sep x mp train step of ``__graft_entry__`` on the
    process's own four devices (equality with the single-device losses)."""
    import __graft_entry__ as entry    # beside this script

    t0 = time.perf_counter()
    entry._dryrun_impl(4)
    _say("  ok: _dryrun_impl(4): hybrid step-1 and step-2 losses equal the "
         "single-device reference")
    _say(f"  time hybrid4: {time.perf_counter() - t0:.1f} s")
    _say(f"  peak HBM (hybrid4): {_gib(_peak_hbm())}")
    return {}


CHILD_PHASES = {
    "kernels": phase_kernels,
    "serve": phase_serve,
    "train": phase_train,
    "serve_tp4": lambda rehearse: phase_serve(rehearse, tp=4),
    "hybrid4": phase_hybrid4,
}


def run_child(phase: str, rehearse: bool) -> int:
    _say(f"[{phase}] begin")
    device = _start(rehearse)
    result = CHILD_PHASES[phase](rehearse)
    _say(f"[{phase}] PASS")
    _say(RESULT_TAG + json.dumps({"phase": phase, "device": device,
                                  **result}))
    return 0


# ==========================================================================
# parent side: JAX-free, children one after another
# ==========================================================================

def _load_cache_helper():
    import importlib.util as ilu

    spec = ilu.spec_from_file_location(
        "_pt_compile_cache",
        os.path.join(REPO, "paddle_tpu", "utils", "compile_cache.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_phase_child(phase: str, rehearse: bool, env: dict, budget_s: float):
    """Run one phase in a child, echoing its output; returns its result
    record, or None if it failed. The child is killed on every way out."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    if rehearse:
        cmd.append("--rehearse-cpu")
    proc = subprocess.Popen(cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
                            text=True, bufsize=1)
    result = None
    # the watchdog only has to end a hung child; reading stays here
    watchdog = threading.Timer(budget_s, proc.kill)
    watchdog.daemon = True
    watchdog.start()
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait()
        if rc != 0:
            _say(f"[{phase}] FAILED: exit code {rc}"
                 + ("" if watchdog.is_alive() else
                    f" (killed at its {budget_s:.0f} s limit)"))
            return None
        return result
    finally:
        watchdog.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def _differences(records: dict, expected: dict) -> list:
    """What a run's values (not its times) differ in from an earlier
    record's — a warm-cache run must reproduce the cold one exactly."""
    return [f"{phase}.{key} {rec[key]!r} differs from the expected "
            f"{expected.get(phase, {}).get(key)!r}"
            for phase, rec in records.items()
            for key in ("tokens_sha", "logits_err", "losses")
            if key in rec and rec[key] != expected.get(phase, {}).get(key)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(PHASES), default=1,
                    help="1: kernels, serve, train (the driver's run); "
                         "4: tp=4 serving and the hybrid train step")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run every phase at gpt_tiny size on CPU with "
                         "kernels in interpret mode; proves nothing about "
                         "the chip")
    ap.add_argument("--record", metavar="PATH",
                    help="write this run's record (tokens digest, logits "
                         "check, set-up seconds, compile counts) as JSON")
    ap.add_argument("--expect", metavar="PATH",
                    help="fail unless tokens and logits check equal the "
                         "record at PATH (the cold run, for a warm one)")
    ap.add_argument("--phase", choices=sorted(CHILD_PHASES),
                    help=argparse.SUPPRESS)   # child entry
    args = ap.parse_args(argv)
    if args.phase:
        return run_child(args.phase, args.rehearse_cpu)

    t0 = time.monotonic()
    if args.rehearse_cpu:
        _say(REHEARSAL_NOTE)
    env = dict(os.environ)
    cache_was_set = bool(env.get("JAX_COMPILATION_CACHE_DIR"))
    cache_dir = _load_cache_helper().compile_cache_dir(env)
    _say(f"[parent] compile cache: {cache_dir} "
         f"({'set by the caller' if cache_was_set else 'the default'})")
    if args.rehearse_cpu:
        # the explicit flag, and only it, selects the CPU — and there the
        # cache is placed but not used: a replayed XLA:CPU executable has
        # given wrong numerics (tests/conftest.py), which would fail a
        # rehearsal for a reason the chip does not have
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={args.chips}")
        env["XLA_FLAGS"] = " ".join(flags)

    records = {}
    for phase in PHASES[args.chips]:
        budget = DEADLINE_S - (time.monotonic() - t0)
        rec = _run_phase_child(phase, args.rehearse_cpu, env, budget)
        if rec is None:
            _say(f"chip_smoke: FAILED in phase {phase!r}; no result")
            return 1
        records[phase] = rec
    device = next(iter(records.values()))["device"]

    summary = {"device": device, "chips": args.chips,
               "wall_s": round(time.monotonic() - t0, 1), "phases": records}
    if args.record:
        os.makedirs(os.path.dirname(os.path.abspath(args.record)),
                    exist_ok=True)
        with open(args.record, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
    if args.expect:
        with open(args.expect) as f:
            cold = json.load(f)["phases"]
        diffs = _differences(records, cold)
        for line in diffs:
            _say(f"chip_smoke: FAILED: {line}")
        if diffs:
            return 1
        for phase, rec in records.items():
            if "setup_s" in rec:
                _say(f"[parent] {phase}: set-up {cold[phase]['setup_s']} s "
                     f"-> {rec['setup_s']} s; jax compiles "
                     f"{cold[phase]['compiles']} -> {rec['compiles']}")
        _say("[parent] tokens, logits check and losses equal the expected "
             "record")
    _say(f"[parent] all phases passed in {summary['wall_s']} s")
    if args.rehearse_cpu:
        _say(REHEARSAL_NOTE)
        return 0
    _say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
