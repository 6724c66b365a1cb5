"""Runner for configurations of ``kind: train``: one model under the
program's ``TrainStep``, fed by the program's ``io.DevicePrefetcher`` at its
default depth from a generator of seeded host batches.

The loop keeps one step in flight: it dispatches step ``n`` and then reads
the loss of step ``n - 1`` (as a job that logs its loss does), so the device
never waits for the host and the host clock follows the device to within
one step. The window starts when the last warm-up step's loss has been read
and holds as many whole steps as fit ``--seconds``; the last of them ends in
the host read of its own loss.

Set-up: model and optimizer from the seed, the plain reference's loss on
the first batch (before the first step changes the weights), the first step
(compile or cache read) and the warm-up steps.
"""

from __future__ import annotations

import math
import time

import numpy as np

from perfbench.harness import reference, xplane
from perfbench.harness.spec import load_class

_MODEL_KEYS = ("vocab_size", "hidden_size", "num_layers", "num_heads",
               "intermediate_size", "max_position_embeddings",
               "layer_norm_eps", "recompute")


def run(ctx) -> dict:
    from jax.profiler import TraceAnnotation

    import paddle_tpu as paddle
    from paddle_tpu.io import DevicePrefetcher
    from paddle_tpu.jit.api import TrainStep

    config, say, phase_done = ctx.cell.config, ctx.say, ctx.phase_done
    paddle.seed(ctx.seed)
    cfg = load_class(config["config_class"])(
        **{k: config[k] for k in _MODEL_KEYS})
    model = load_class(config["model_class"])(cfg)
    o = config["optimizer"]
    optimizer = load_class(o["class"])(
        learning_rate=o["learning_rate"], weight_decay=o["weight_decay"],
        parameters=model.parameters(), moment_dtype=o["moment_dtype"])
    chunks, level = config["loss_chunks"], config["amp_level"]

    def loss_fn(m, ids, labels):
        with paddle.amp.auto_cast(level=level):
            return m.loss_fused(ids, labels, num_chunks=chunks)

    step = TrainStep(model, loss_fn, optimizer)
    job = ctx.cell.generator().make(ctx.cell.traffic, ctx.seed,
                                    cfg.vocab_size, ctx.seconds)
    phase_done("model and optimizer from the seed")

    ids0, labels0 = job.batch_at(0)
    ref_loss = reference.next_token_loss(
        reference.weights_of(model), ids0, labels0, cfg.num_layers,
        cfg.num_heads, cfg.layer_norm_eps)
    phase_done("plain reference's loss on the first batch")

    def to_tensors(batches):
        for ids, labels in batches:
            yield paddle.to_tensor(ids), paddle.to_tensor(labels)

    feed = iter(DevicePrefetcher(to_tensors(job.batches())))

    def dispatch():
        with TraceAnnotation("bench.next_batch"):
            ids, labels = next(feed)
        with TraceAnnotation("bench.train_step"):
            return step(ids, labels)

    def read(loss) -> float:
        with TraceAnnotation("bench.read_loss"):
            return float(np.asarray(loss.numpy()))

    losses = [read(dispatch())]
    phase_done("first step (compile or cache read)")
    t_warm = time.perf_counter()
    pending = dispatch()
    for _ in range(job.warm_steps - 1):
        nxt = dispatch()
        losses.append(read(pending))
        pending = nxt
    losses.append(read(pending))
    # a step's expected time, for deciding whether another one fits
    est = (time.perf_counter() - t_warm) / job.warm_steps
    phase_done(f"{job.warm_steps} warm-up steps")
    n_warm = len(losses)

    # nothing is in flight: the window starts here
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_process
    compiles0 = ctx.compiles.count
    step_ends = []

    def now() -> float:
        return time.perf_counter() - t0

    def loop(until_s: float) -> None:
        """From an idle device: whole steps, one kept in flight, as long as
        the next is expected to end by ``until_s`` on the run clock."""
        base, sent = now(), 0
        pending = None
        while True:
            nxt = None
            if base + (sent + 1) * est <= until_s:
                nxt = dispatch()
                sent += 1
            if pending is not None:
                losses.append(read(pending))
                step_ends.append(now())
            if nxt is None:
                return
            pending = nxt

    trace = trace_summary = None
    if ctx.trace:
        loop(max(2 * est, ctx.seconds - ctx.trace_seconds))
        host_steps = len(step_ends)
        compiles_in_window = ctx.compiles.count - compiles0
        with xplane.Capture(ctx.trace_dir) as cap:
            loop(now() + max(ctx.trace_seconds, 2.5 * est))
        trace, trace_summary = cap.trace, cap.summary
        say(f"trace: {cap.path}")
        step_ends = step_ends[:host_steps]
    else:
        loop(ctx.seconds)
        compiles_in_window = ctx.compiles.count - compiles0
    feed.close()     # stops the prefetcher's thread

    return {
        "kind": "train", "setup_s": setup_s, "window_start_s": 0.0,
        "step_ends_s": step_ends, "losses": losses, "warm_losses": n_warm,
        "tokens_per_step": job.batch * job.sequence, "batch": job.batch,
        "sequence": job.sequence, "reference_loss": ref_loss,
        "loss_check": config["loss_check"], "model": config,
        "device_count_used": ctx.cell.chips,
        "compiles_in_window": compiles_in_window,
        "trace": trace, "trace_summary": trace_summary,
    }


def verdict(rec: dict):
    """``(correct, attempted, failed, notes)``: attempted are the steps
    scored, failed those with a loss that is not finite."""
    chk = rec["loss_check"]
    losses = rec["losses"]
    scored = losses[rec["warm_losses"]:][: len(rec["step_ends_s"])]
    bad = sum(not math.isfinite(x) for x in scored)
    notes = [f"{len(scored)} steps scored, {bad} with a loss not finite"]
    ok = bool(scored) and bad == 0 and all(math.isfinite(x) for x in losses)
    diff = abs(losses[0] - rec["reference_loss"])
    notes.append(f"first step's loss {losses[0]:.5f} vs plain reference "
                 f"{rec['reference_loss']:.5f}: |diff| {diff:.2g} (limit "
                 f"{chk['first_loss_atol']})")
    ok &= diff <= chk["first_loss_atol"]
    tenth = max(1, len(losses) // 10)
    head = sum(losses[:tenth]) / tenth
    tail = sum(losses[-tenth:]) / tenth
    notes.append(f"mean loss of the first {tenth} steps {head:.4f}, of the "
                 f"last {tenth} {tail:.4f}")
    ok &= tail < head
    notes.append(f"compiles inside the window: {rec['compiles_in_window']}")
    ok &= rec["compiles_in_window"] == 0
    return ok, len(scored), bad, notes


def counts(rec: dict) -> dict:
    """What a CPU rehearsal may print: counts, no time."""
    return {"steps_scored": len(rec["step_ends_s"]),
            "losses": len(rec["losses"]),
            "compiles_in_window": rec["compiles_in_window"]}
