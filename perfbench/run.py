#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. It fails (non-zero exit, no result line) unless JAX
finds TPUs, at least as many as the cell asks for; sets up from the seed;
warms only the cell's own shapes; measures for ``--seconds``; and prints as
its last line one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device`` and, traced, ``breakdown``. Earlier
lines are notes for a reader, not results.

``--rehearse-cpu`` (not one of the contract's arguments) runs the same
control flow on the CPU with the sizes in the configuration's ``rehearsal``
group: it prints counts only, no metric, and ``correct`` is false by
construction. ``--sweep`` runs an open-loop cell at several rates in one
process to find its knee (``runners/serve.py::sweep``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)
sys.path.insert(0, REPO_DIR)


class Ctx:
    """What a runner is given."""

    def __init__(self, cell, args, compiles):
        self.cell = cell
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.trace_seconds = float(args.trace_seconds)
        self.compiles = compiles
        self.t_process = T_PROCESS
        self.trace_dir = os.path.join(REPO_DIR, "build", "perfbench_trace",
                                      cell.name)
        self._t_phase = time.perf_counter()

    @staticmethod
    def say(msg: str) -> None:
        print(f"[perfbench] {msg}", flush=True)

    def phase_done(self, what: str = None) -> None:
        """Say how long the part of the set-up that just ended took (or,
        with no name, only restart the clock)."""
        now = time.perf_counter()
        if what:
            self.say(f"set-up: {what} {now - self._t_phase:.1f} s")
        self._t_phase = now


def _environment(rehearse_cpu: bool) -> None:
    """Before JAX is imported: the compile cache goes where the program's
    own helper puts it (``JAX_COMPILATION_CACHE_DIR`` if set, else the fixed
    ``<checkout>/build/jax_cache``), under JAX's own thresholds."""
    import importlib.util

    helper = os.path.join(REPO_DIR, "paddle_tpu", "utils", "compile_cache.py")
    if not os.path.isfile(helper):
        raise SystemExit(f"perfbench: the program is not beside the "
                         f"benchmark ({helper} is missing)")
    if rehearse_cpu:
        # a replayed XLA:CPU executable has given wrong numerics here
        # (tests/conftest.py): the rehearsal compiles everything
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
        return
    spec = importlib.util.spec_from_file_location("_pb_compile_cache", helper)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.compile_cache_dir()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--trace-seconds", type=float, default=3.0,
                    help="length of the traced slice of a --trace 1 run")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--sweep", metavar="RATES",
                    help="comma-separated arrival rates: run the open-loop "
                         "cell at each, one process, and print one line each")
    args = ap.parse_args(argv)

    from perfbench.harness import device as dev
    from perfbench.harness.spec import Cell, SpecError

    try:
        cell = Cell(args.workload)
    except SpecError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    _environment(args.rehearse_cpu)
    try:
        device = dev.describe(cell.chips, args.rehearse_cpu)
    except (dev.NoAccelerator, RuntimeError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    if args.rehearse_cpu:
        if "rehearsal" not in cell.config:
            print("perfbench: this configuration has no rehearsal sizes",
                  file=sys.stderr)
            return 2
        cell.config.update(cell.config["rehearsal"])
        cell.traffic.update(cell.traffic.get("rehearsal", {}))
        Ctx.say("REHEARSAL on the CPU at toy sizes: control flow and counts "
                "only, no metric, correct is false by construction")
    else:
        dev.peaks(device["kind"])      # an unknown chip is an error, now
    Ctx.say(f"cell {cell.name}: config {cell.config_name}, traffic "
            f"{cell.traffic_name}, seed {args.seed}, {args.seconds:g} s, "
            f"trace {args.trace}; device {device}")
    ctx = Ctx(cell, args, dev.CompileCounter())
    runner = cell.runner()
    if args.sweep:
        return runner.sweep(ctx, [float(x) for x in args.sweep.split(",")])
    rec = runner.run(ctx)
    rec["memory_peak_bytes"] = dev.memory_peak_bytes(cell.chips)
    rec["device"] = device
    correct, attempted, failed, notes = runner.verdict(rec)
    if not args.rehearse_cpu:      # the notes state times
        for note in notes:
            ctx.say(f"check: {note}")

    out_device = dict(device, memory_peak_bytes=rec["memory_peak_bytes"])
    line = {"correct": bool(correct and not args.rehearse_cpu),
            "attempted": attempted, "failed": failed, "metrics": {},
            "device": out_device}
    if args.rehearse_cpu:
        ctx.say(f"rehearsal counts: {json.dumps(runner.counts(rec))}")
        print(json.dumps(line))
        return 0
    for entry in cell.metric_entries(traced=ctx.trace):
        value = cell.reader(entry["name"]).read(rec)
        if value is not None:
            line["metrics"][entry["name"]] = {"value": float(value),
                                              "unit": entry["unit"]}
    if ctx.trace:
        summary = rec.get("trace_summary")
        if summary is None:
            print("perfbench: the trace holds no device operation",
                  file=sys.stderr)
            return 4
        out_device["busy_s"] = summary["busy_s"]
        out_device["window_s"] = summary["window_s"]
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
