"""Views of a ``serve_hybrid_moe`` record that its metric readers share: the
traced decode-only steps on the trace's clock, the device time of one
kernel inside them, and the means of what the compiled step counts.

A record of another runner, or of a program that lacks what these read,
reads as ``None`` everywhere.
"""

from __future__ import annotations

from perfbench.harness import xplane


def decode_steps(rec: dict):
    """``[(start_s, end_s, running, live_tokens)]`` on the trace's clock of
    the traced ``step()`` calls that admitted nothing and decoded some rows
    (the benchmark's own step records, as ``decode_roofline`` cuts them);
    ``None`` without a trace or a ``hybrid`` group."""
    trace, summary = rec.get("trace"), rec.get("trace_summary")
    if (rec.get("kind") != "serve" or not rec.get("hybrid") or not trace
            or not summary):
        return None
    off = trace["run_clock_offset_s"]
    t0, t1 = summary["t0"], summary["t1"]
    return [(s[0] + off, s[1] + off, s[3], s[6]) for s in rec["steps"]
            if s[2] == 0 and s[3] > 0
            and t0 <= s[0] + off and s[1] + off <= t1]


def kernel_seconds(rec: dict, pattern, spans: list):
    """``(seconds, events)`` of the device operations whose instruction
    name matches ``pattern`` and that started inside one of ``spans``
    (sorted, disjoint ``(start_s, end_s, ..)``), mean over chips."""
    devices = rec["trace"]["devices"].values()
    total, count = 0.0, 0
    for dev in devices:
        ops = sorted((s, e) for name, s, e in dev["ops"]
                     if pattern.search(xplane.op_name(name)))
        i = 0
        for span in spans:
            while i < len(ops) and ops[i][0] < span[0]:
                i += 1
            while i < len(ops) and ops[i][0] < span[1]:
                total += ops[i][1] - ops[i][0]
                count += 1
                i += 1
    n = max(len(devices), 1)
    return total / n, count / n


def step_mean(rec: dict, name: str):
    """Mean over the decode steps since warm-up of the compiled step's
    count ``name`` (it rides the telemetry block the scheduler reads with
    the tokens)."""
    hybrid = rec.get("hybrid")
    if not hybrid:
        return None
    a, b = hybrid["telemetry0"], hybrid["telemetry1"]
    key = name + "_sum"
    steps = b.get("steps", 0) - a.get("steps", 0)
    if key not in b or steps <= 0:
        return None
    return (b[key] - a.get(key, 0.0)) / steps
