"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time, the operations that took most of it,
the idle gaps and what the host was doing in each, and time by name.

Read with ``jax.profiler.ProfileData`` alone. A TPU trace has one plane per
chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event per
executed HLO operation, Pallas kernels among them under their kernel's name.
(``XLA Modules`` has one event per executed program, but the serving
programs all print as ``jit__traced``, so it is not read.) The host's plane
``/host:CPU`` has one line per thread, with the
``jax.profiler.TraceAnnotation`` spans under their own names beside JAX's
own host events (``shard_args``, ``PjitFunction(..)``, ..). All events share
one clock, in nanoseconds. Of the host, the reduction keeps the threads that
carry a ``bench.`` span.

An operation's event is named by its whole HLO text; ``op_key`` cuts that to
the instruction's name without its number, the fusion kind and the result's
first array type, so that the same operation of 24 layers adds up.

The traced window is the benchmark's own span ``bench.trace_window`` where
the trace has one, else from the first to the last device event.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.trace_window"
SPAN_PREFIX = "bench."


class Capture:
    """``with Capture(dir) as cap:`` traces the block under the span
    ``bench.trace_window`` with what the reduction needs and no more: device
    events and ``TraceAnnotation`` spans, no Python call tracing (it makes a
    trace of seconds tens of megabytes and slows the host) and no copy of
    every program's HLO. Afterwards ``cap.trace`` is ``read()`` of the file
    and ``cap.summary`` its ``reduce()`` (``None`` if no operation ran on a
    device), and ``cap.path`` the file."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.path = self.trace = self.summary = None

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        self._span.__exit__(*exc)
        jax.profiler.stop_trace()
        if exc[0] is None:
            self.path = newest(self.trace_dir)
            self.trace = read(self.path)
            self.summary = reduce(self.trace)
        return False


def newest(trace_dir: str):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def read(path: str) -> dict:
    """``{"devices": {n: {"ops": [...]}}, "spans": [...]}`` with every
    event as ``(name, start_s, end_s)``. ``path`` may be
    gzip-compressed (the fixtures are)."""
    import gzip

    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            space = ProfileData.from_serialized_xspace(f.read())
    else:
        space = ProfileData.from_file(path)

    def events(line):
        return [(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]

    out = {"devices": {}, "spans": []}
    for plane in space.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            out["devices"][int(m.group(1))] = {"ops": [
                e for line in plane.lines if line.name == OPS_LINE
                for e in events(line)]}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = events(line)
                if any(e[0].startswith(SPAN_PREFIX) for e in evs):
                    out["spans"] += evs
    return out


def union(intervals, t0: float, t1: float) -> list:
    """Merged ``[start, end]`` pieces of ``intervals`` clipped to
    ``[t0, t1]``, in time order."""
    merged = []
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def gaps(busy: list, t0: float, t1: float) -> list:
    """The idle pieces of ``[t0, t1]`` left by the merged ``busy`` pieces."""
    out, at = [], t0
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = e
    if t1 > at:
        out.append((at, t1))
    return out


_HLO_HEAD = re.compile(r"^%?([^ =]+?)(?:\.\d+)?(?:\.(?:remat|clone)\d*)*$")
_HLO_TYPE = re.compile(r"^\(?([a-z]+\d*\[[\d,]*\])")
_HLO_KIND = re.compile(r"kind=k(\w+)")


def op_key(name: str) -> str:
    """``convert f32[4096,16,16,128]`` for ``%convert.295 = f32[4096,16,16,
    128]{..} convert(..)``; ``fusion.Loop f32[..]`` for a loop fusion; a name
    that is not HLO text is kept."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    m = _HLO_HEAD.match(head)
    base = m.group(1) if m else head
    kind = _HLO_KIND.search(rest) if base.startswith("fusion") else None
    typ = _HLO_TYPE.match(rest)
    return (base + (f".{kind.group(1)}" if kind else "")
            + (f" {typ.group(1)}" if typ else ""))


def op_name(name: str) -> str:
    """The instruction's own name (``flash_attention.48``), for finding a
    kernel by the name it was given."""
    return name.partition(" = ")[0].lstrip("%")


def label_gaps(spans, idle: list) -> list:
    """For each idle piece ``(s, e)``, in time order, what the host was
    doing at its middle: the innermost ``bench.`` span there and, after
    ``>``, the innermost other host event inside it; ``"none"`` outside
    every span. Host events of one thread nest, so one pass over both
    sorted lists with a stack of the open events is enough."""
    todo = sorted((x for x in spans if x[0] != WINDOW_SPAN),
                  key=lambda x: (x[1], -x[2]))
    out, stack, i = [], [], 0
    for s, e in idle:
        mid = (s + e) / 2
        while i < len(todo) and todo[i][1] <= mid:
            stack.append(todo[i])
            i += 1
        stack = [x for x in stack if x[2] > mid]
        bench = [x[0] for x in stack if x[0].startswith(SPAN_PREFIX)]
        if not bench:
            out.append("none")
            continue
        inner = stack[-1][0]
        out.append(bench[-1] if inner == bench[-1]
                   else f"{bench[-1]}>{inner}")
    return out


def window(trace: dict):
    spans = [x for x in trace["spans"] if x[0] == WINDOW_SPAN]
    if spans:
        return spans[0][1], spans[0][2]
    ops = [ev for d in trace["devices"].values() for ev in d["ops"]]
    if not ops:
        return None
    return min(s for _, s, _ in ops), max(e for _, _, e in ops)


def busy_within(trace: dict, spans: list):
    """Device-busy seconds (mean over chips) inside the union of the host
    intervals ``spans`` (``(start_s, end_s)`` pairs)."""
    per_chip = []
    for dev in trace["devices"].values():
        total = 0.0
        iv = [(s, e) for _, s, e in dev["ops"]]
        for a, b in spans:
            total += sum(e - s for s, e in union(iv, a, b))
        per_chip.append(total)
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def reduce(trace: dict, top: int = 10, longest: int = 5):
    """The summary the runners put into their records: ``busy_s`` and
    ``window_s`` (mean over chips), ``device_ops`` (the ``top`` operations by
    summed time under their ``op_key``, mean over chips), and ``idle_gaps``:
    idle seconds summed by what the host was doing (``total:<label>``), then
    the ``longest`` single gaps with their labels. ``None`` for a trace in
    which no operation ran on a device."""
    win = window(trace)
    if win is None or not trace["devices"]:
        return None
    t0, t1 = win
    n = len(trace["devices"])
    busy_s = 0.0
    by_op = defaultdict(float)
    by_span = defaultdict(float)
    singles = []
    for dev in trace["devices"].values():
        busy = union([(s, e) for _, s, e in dev["ops"]], t0, t1)
        busy_s += sum(e - s for s, e in busy) / n
        for name, s, e in dev["ops"]:
            if t0 <= s < t1:
                by_op[op_key(name)] += (e - s) / n
        idle = gaps(busy, t0, t1)
        for (s, e), label in zip(idle, label_gaps(trace["spans"], idle)):
            by_span[label] += (e - s) / n
            singles.append((label, e - s))
    if busy_s <= 0:
        return None
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])
    idle = [[f"total:{k}", v] for k, v in rank(by_span)[:longest]]
    idle += [[k, v] for k, v in sorted(singles, key=lambda kv: -kv[1])
             [:longest]]
    return {"busy_s": busy_s, "window_s": t1 - t0, "t0": t0, "t1": t1,
            "device_ops": [[k, v] for k, v in rank(by_op)[:top]],
            "idle_gaps": idle}
