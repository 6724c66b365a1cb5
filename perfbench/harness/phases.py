"""The device's idle time of a traced slice, split over the phases of the
scheduler's ``step()``.

The program marks the phases of ``ContinuousBatchingScheduler.step()`` with
``serving.*`` spans (``RecordEvent`` = ``jax.profiler.TraceAnnotation``), on
the thread that carries the ``bench.`` spans while ``dispatch_depth`` is 0:
they are in ``trace["spans"]`` (``xplane.read``) beside JAX's own host
events, on the clock of the device's operations. A child lies wholly inside
its parent and siblings do not overlap.

``idle_by_phase`` gives **every instant** of every idle piece of the device
to the innermost ``serving.*`` span open then, so one gap that runs through
five phases is split by length (``xplane.label_gaps`` gives the whole gap to
what covered its middle). A span's own time is its interval less its
children's. An instant under no ``serving.*`` span is ``outside``: the
benchmark's loop between two calls of ``step()`` and, below the knee, its
wait for the next request to fall due while nothing is in service
(``bench.wait_due``). The parts add up to the window less the busy time,
exactly.

A trace of a program that has no such spans (every commit before PR 24)
reads as nothing: every reader built on this returns ``None``.
"""

from __future__ import annotations

from collections import defaultdict

from perfbench.harness import xplane
from perfbench.harness.stats import median

PREFIX = "serving."
STEP = "serving.step"
OUTSIDE = "outside"


def own_pieces(spans: list) -> list:
    """``[(start, end, name)]`` in time order: the union of the nested
    ``spans`` cut into pieces that each belong to the innermost span open
    there. A child that outlives its parent is cut at the parent's end."""
    out, stack, at = [], [], None

    def close(until):
        # end every open span that ends by ``until`` (all of them for None)
        nonlocal at
        while stack and (until is None or stack[-1][2] <= until):
            name, _, end = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            if s > at:
                out.append((at, s, stack[-1][0]))
            e = min(e, stack[-1][2])
        at = s if at is None or not stack else max(at, s)
        stack.append((name, s, e))
    close(None)
    return out


def phase_spans(trace: dict) -> list:
    return [x for x in trace["spans"] if x[0].startswith(PREFIX)]


def idle_by_phase(trace: dict, summary: dict) -> dict:
    """``{span name or "outside": idle seconds}`` (mean over chips) of the
    window ``summary`` (``xplane.reduce``) was made for; the values add up
    to ``window_s - busy_s``."""
    t0, t1 = summary["t0"], summary["t1"]
    pieces = own_pieces(phase_spans(trace))
    n = len(trace["devices"])
    out = defaultdict(float)
    for dev in trace["devices"].values():
        busy = xplane.union([(s, e) for _, s, e in dev["ops"]], t0, t1)
        i = 0
        for a, b in xplane.gaps(busy, t0, t1):
            while i < len(pieces) and pieces[i][1] <= a:
                i += 1
            covered, j = 0.0, i
            while j < len(pieces) and pieces[j][0] < b:
                part = min(b, pieces[j][1]) - max(a, pieces[j][0])
                out[pieces[j][2]] += part / n
                covered += part
                j += 1
            out[OUTSIDE] += (b - a - covered) / n
    return dict(out)


def _traced_serve(rec: dict):
    """``(trace, summary)`` of a traced serve record whose program marks
    the phases of ``step()``, else ``None``."""
    trace, summary = rec.get("trace"), rec.get("trace_summary")
    if rec.get("kind") != "serve" or not trace or not summary:
        return None
    if not any(x[0] == STEP for x in trace["spans"]):
        return None
    return trace, summary


def share(rec: dict, names: tuple):
    """What the ``idle_*`` readers return: 100 x the idle seconds of the
    spans ``names`` over the window, so that the readers' shares add up to
    ``device_idle``."""
    ts = _traced_serve(rec)
    if ts is None:
        return None
    trace, summary = ts
    if "idle_by_phase" not in trace:     # seven readers, one reduction
        trace["idle_by_phase"] = idle_by_phase(trace, summary)
    by = trace["idle_by_phase"]
    return 100.0 * sum(by.get(n, 0.0) for n in names) / summary["window_s"]


def named(rec: dict, name: str):
    """The spans ``name`` that lie wholly inside the traced slice, in time
    order; ``None`` where ``share`` gives ``None``."""
    ts = _traced_serve(rec)
    if ts is None:
        return None
    trace, summary = ts
    return sorted((x for x in trace["spans"] if x[0] == name
                   and summary["t0"] <= x[1] and x[2] <= summary["t1"]),
                  key=lambda x: x[1])


def inside(events: list, spans: list) -> list:
    """The ``events`` that lie wholly inside one of ``spans``."""
    return [x for x in events
            if any(s <= x[1] and x[2] <= e for _, s, e in spans)]


def decode_only_step_ms(rec: dict):
    """Median length of the traced ``serving.step`` spans that launched a
    decode and no prefill: the traced twin of ``decode_step_p50_ms``."""
    steps = named(rec, STEP)
    if not steps:
        return None
    decode, prefill = (named(rec, "serving." + n)
                       for n in ("decode_step", "prefill"))
    m = median([e - s for name, s, e in steps
                if inside(decode, [(name, s, e)])
                and not inside(prefill, [(name, s, e)])])
    return None if m is None else m * 1e3
