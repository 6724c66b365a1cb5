#!/usr/bin/env python
"""Training hot-path benchmark: steps/s + stall breakdown, zero-stall vs
single-buffered.

Two phases over the IDENTICAL deterministic batch stream and model init:

- **baseline**: single-buffered input path (``DevicePrefetcher(depth=0)`` —
  the host fetch + H2D transfer runs inline on the consumer) and a blocking
  per-step loss sync, i.e. the fully synchronous loop this PR removes.
- **hot**: double-buffered device prefetch (background H2D overlapping
  compute), donated input buffers, and a dispatch-ahead loop that holds
  ``NonBlockingStepResult``s and syncs ONCE at the end.

Both phases run the same fully-donated compiled TrainStep, so losses must be
**bit-identical** — the artifact pins that alongside the speedup ratio and
the ``train_input_stall_seconds`` / ``train_sync_stall_seconds`` breakdown
(read from the process registry as per-phase deltas). Smoke mode is
CPU-deterministic and asserts the hot path is not slower than baseline
(ratio >= 1.0 within noise) and that prefetch collapsed the input stall.

  python tools/train_bench.py --smoke          # tiny fixture, CI check
  python tools/train_bench.py --steps 30       # GPT-2-small on the chip
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

# smoke noise floor: CPU timing jitter on a tiny fixture; the structural
# win (overlapped host work + one sync) is far above this when real
RATIO_NOISE_FLOOR = 0.95
STALL_FRAC_LIMIT = 0.10


class SyntheticBatches:
    """Deterministic (ids, labels) stream with real per-batch input latency.

    ``host_work`` scales a synthetic tokenize/augment cost (numpy sorts);
    ``io_latency_s`` emulates the storage/network read a real input
    pipeline blocks on per batch (a sleep: it releases the GIL and no CPU,
    so — like real I/O — it overlaps fully behind a prefetch stage, whereas
    on a CPU-backend smoke run numpy work merely competes with XLA for the
    same cores). Token content is seeded per index, so every iteration and
    every phase sees the same batches.
    """

    def __init__(self, n: int, batch: int, seqlen: int, vocab: int,
                 host_work: int = 2, io_latency_s: float = 0.0):
        self.n = n
        self.batch = batch
        self.seqlen = seqlen
        self.vocab = vocab
        self.host_work = host_work
        self.io_latency_s = io_latency_s

    def __len__(self):
        return self.n

    def __iter__(self):
        import numpy as np

        for i in range(self.n):
            rng = np.random.default_rng(1000 + i)
            ids = rng.integers(0, self.vocab,
                               (self.batch, self.seqlen)).astype(np.int32)
            for _ in range(self.host_work):
                np.sort(rng.standard_normal(1 << 16))
            if self.io_latency_s:
                time.sleep(self.io_latency_s)
            yield ids, ids.copy()


def _build(on_tpu: bool):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import (
        GPTConfig,
        GPTForCausalLM,
        GPTPretrainingCriterion,
    )

    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=1024)
        batch, seqlen = 8, 512
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                        num_heads=2, max_position_embeddings=128)
        batch, seqlen = 4, 64
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    criterion = GPTPretrainingCriterion(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())

    def loss_fn(m, ids, labels):
        return criterion(m(ids), labels)

    return model, loss_fn, optimizer, cfg, batch, seqlen


def _stall_delta(before: dict, after: dict) -> dict:
    return {k: round(after[k] - before[k], 6)
            for k in ("train_input_stall_seconds",
                      "train_sync_stall_seconds",
                      "train_prefetched_batches_total")}


def _run_phase(on_tpu: bool, *, steps: int, warmup: int, depth: int,
               donate_inputs: bool, host_work: int,
               io_latency_s: float) -> dict:
    """One phase: fresh model/optimizer (same seed), fresh batch stream."""
    from paddle_tpu.io.dataloader import DevicePrefetcher
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.observability.train_stall import stall_snapshot

    model, loss_fn, optimizer, cfg, batch, seqlen = _build(on_tpu)
    step = TrainStep(model, loss_fn, optimizer,
                     donate_inputs=donate_inputs, nonblocking=True)
    stream = SyntheticBatches(warmup + steps, batch, seqlen, cfg.vocab_size,
                              host_work=host_work,
                              io_latency_s=io_latency_s)
    loader = DevicePrefetcher(stream, depth=depth)

    losses = []
    pending = []
    t0 = None
    m0 = None
    it = iter(loader)
    for i in range(warmup + steps):
        x, y = next(it)
        res = step(x, y)
        if i < warmup:
            losses.append(res.loss_value())  # sync: compile + settle
            if i == warmup - 1:
                m0 = stall_snapshot()
                t0 = time.perf_counter()
        elif depth == 0:
            # single-buffered reference: blocking loss read EVERY step
            losses.append(res.loss_value())
        else:
            # dispatch-ahead: results stay on device until the epoch sync
            pending.append(res)
    losses.extend(r.loss_value() for r in pending)
    wall = time.perf_counter() - t0
    # drain the loader so the prefetch thread exits before teardown
    for _ in it:
        pass
    stalls = _stall_delta(m0, stall_snapshot())
    # roofline attribution while the TrainStep is alive: the compiled
    # step's cost-analysis FLOPs over the measured per-step wall and the
    # chip's nominal peak (tools/chip_ceiling.py audits the denominator)
    from paddle_tpu.observability.program_inventory import (
        get_program_inventory,
        roofline_utilization,
    )

    inv = get_program_inventory()
    mfu = bw_util = chip = None
    train_entries = inv.entries(kind="train_step")
    if train_entries and wall > 0:
        an = inv.analyze(train_entries[-1])
        if "flops" in an:
            roof = roofline_utilization(an["flops"], an["bytes_accessed"],
                                        wall / steps)
            if roof is not None:   # None on CPU: no peaks, no utilisation
                mfu, bw_util = roof["mfu"], roof["bandwidth_util"]
                chip = roof["chip"]
    return {
        "prefetch_depth": depth,
        "donate_inputs": donate_inputs,
        "steps": steps,
        "wall_s": round(wall, 4),
        "steps_per_s": round(steps / wall, 3),
        "input_stall_s": stalls["train_input_stall_seconds"],
        "sync_stall_s": stalls["train_sync_stall_seconds"],
        "prefetched_batches": stalls["train_prefetched_batches_total"],
        "train_mfu": mfu,
        "train_bandwidth_util": bw_util,
        "chip": chip,
        "losses": losses,
        "donation": step.donation_report(),
    }


def run_region_breakdown(on_tpu: bool, steps: int = 4) -> dict:
    """In-step device-time attribution for the compiled TrainStep.

    Captures a device trace around ``steps`` live train iterations and
    attributes the program's measured device time to the named regions
    annotating ``TrainStep._step`` — the ``forward``/``backward``/
    ``optimizer`` phase groups, with the model-body leaf regions
    (embed/attention/mlp/logits) nested under forward/backward."""
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.observability.program_inventory import (
        get_program_inventory,
    )
    from paddle_tpu.observability.step_profile import (
        StepProfiler,
        parse_hlo_instruction_bytes,
        parse_hlo_instruction_regions,
    )

    model, loss_fn, optimizer, cfg, batch, seqlen = _build(on_tpu)
    step = TrainStep(model, loss_fn, optimizer, nonblocking=True)
    batches = list(SyntheticBatches(2 + steps, batch, seqlen,
                                    cfg.vocab_size, host_work=0))
    for x, y in batches[:2]:              # compile + settle
        step(x, y).loss_value()

    inv = get_program_inventory()

    def programs():
        rows = []
        entries = inv.entries(kind="train_step")
        for e in entries:
            hlo = inv.hlo_text(e)
            if not hlo:
                continue
            module, regions = parse_hlo_instruction_regions(hlo)
            row = {"name": e.name, "module": module, "regions": regions,
                   "nbytes": parse_hlo_instruction_bytes(hlo)}
            an = inv.analyze(e)
            if "flops" in an:
                row["flops"] = an["flops"]
                row["bytes_accessed"] = an["bytes_accessed"]
            if e is entries[-1]:
                row["primary"] = True
                rows.insert(0, row)
            else:
                rows.append(row)
        return rows

    state = {"i": 0}

    def one_step():
        x, y = batches[2 + state["i"] % steps]
        state["i"] += 1
        step(x, y).loss_value()

    summary = StepProfiler(one_step, programs).capture(steps=steps)
    groups = summary.get("group_shares", {})
    return {
        "enabled": bool(summary.get("enabled")),
        "error": summary.get("error"),
        "coverage": summary.get("coverage", 0.0),
        "region_shares": summary.get("region_shares", {}),
        "group_shares": groups,
        "region_share_forward": groups.get("forward", 0.0),
        "region_share_backward": groups.get("backward", 0.0),
        "region_share_optimizer": groups.get("optimizer", 0.0),
        "aux_modules": summary.get("aux_modules", {}),
        "roofline": summary.get("decode_roofline"),
    }


def run_bench(on_tpu: bool = False, steps: int = 20, warmup: int = 3,
              depth: int = 2, host_work: int = 2,
              io_latency_s: float = 0.004, smoke: bool = False,
              out_path=None) -> dict:
    baseline = _run_phase(on_tpu, steps=steps, warmup=warmup, depth=0,
                          donate_inputs=False, host_work=host_work,
                          io_latency_s=io_latency_s)
    hot = _run_phase(on_tpu, steps=steps, warmup=warmup, depth=depth,
                     donate_inputs=True, host_work=host_work,
                     io_latency_s=io_latency_s)
    ratio = hot["steps_per_s"] / baseline["steps_per_s"]
    identical = baseline.pop("losses") == hot.pop("losses")
    input_stall_frac = hot["input_stall_s"] / max(hot["wall_s"], 1e-9)
    profile = run_region_breakdown(on_tpu)
    art = {
        "bench": "train_hotpath",
        "mode": "smoke" if smoke else ("tpu" if on_tpu else "cpu"),
        "config": {"steps": steps, "warmup": warmup,
                   "prefetch_depth": depth, "host_work": host_work,
                   "io_latency_s": io_latency_s},
        "baseline": baseline,
        "hot": hot,
        "speedup_ratio": round(ratio, 3),
        # acceptance-facing names: the hot path's residual stalls
        "train_input_stall_seconds": hot["input_stall_s"],
        "train_sync_stall_seconds": hot["sync_stall_s"],
        "input_stall_frac_of_wall": round(input_stall_frac, 4),
        "train_mfu": hot["train_mfu"],
        "train_bandwidth_util": hot["train_bandwidth_util"],
        "losses_bit_identical": identical,
        "ratio_ok": ratio >= RATIO_NOISE_FLOOR,
        # in-step device-time attribution of the compiled TrainStep
        "region_profile": profile,
        "region_coverage": profile["coverage"],
        "region_share_forward": profile["region_share_forward"],
        "region_share_backward": profile["region_share_backward"],
        "region_share_optimizer": profile["region_share_optimizer"],
    }
    if out_path:
        from tools.bench_io import write_bench_json

        write_bench_json(out_path, art)
        art["artifact"] = out_path
    if smoke:
        assert identical, \
            "hot-path losses diverged from the single-buffered baseline"
        assert ratio >= RATIO_NOISE_FLOOR, (
            f"hot path slower than single-buffered baseline: ratio {ratio:.3f}"
            f" < {RATIO_NOISE_FLOOR} ({baseline['steps_per_s']} -> "
            f"{hot['steps_per_s']} steps/s)")
        assert input_stall_frac < STALL_FRAC_LIMIT, (
            f"prefetch did not collapse the input stall: "
            f"{hot['input_stall_s']} s over {hot['wall_s']} s wall")
        mfu = art["train_mfu"]
        if on_tpu:
            assert mfu is not None and 0.0 < mfu <= 1.0, (
                f"train_mfu must be attributable and in (0, 1]: {mfu}")
        else:
            assert mfu is None, f"a CPU run has no peaks to divide by: {mfu}"
        if profile["enabled"]:
            for g in ("forward", "backward", "optimizer"):
                assert profile["group_shares"].get(g, 0.0) > 0.0, (
                    f"train step profile missing the {g!r} phase: "
                    f"{profile['group_shares']}")
    return art


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tpu", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--host-work", type=int, default=2)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    smoke = a.smoke or not a.tpu
    steps = a.steps if a.steps is not None else (20 if smoke else 30)
    out = a.out or os.path.join(
        REPO_ROOT, "BENCH_train_smoke.json" if smoke else
        "BENCH_train_tpu.json")
    art = run_bench(on_tpu=a.tpu, steps=steps, depth=a.depth,
                    host_work=a.host_work, smoke=smoke, out_path=out)
    print(json.dumps(art, indent=2))


if __name__ == "__main__":
    main()
