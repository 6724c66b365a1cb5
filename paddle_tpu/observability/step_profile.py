"""In-step profiling: named-region device-time attribution inside one
compiled program, plus the manifest behind the zero-sync telemetry block.

``ProgramInventory`` answers *what a whole program costs* (FLOPs, bytes,
roofline). This module answers *where inside the program the device time
goes* — kv_gather vs attention vs MLP vs the tp all-gather seam vs
sampling — the evidence a Pallas-kernel or chunked-prefill PR needs to
prove a region-level win.

Three pieces:

- ``region("<name>")`` — a checked wrapper over ``jax.named_scope``. The
  scope name is prefixed ``rgn_`` so region path components are
  unambiguous inside XLA ``op_name`` metadata (a plain ``attention``
  would collide with e.g. the ``paged_cache_attention`` dispatch name).
  Every literal ``region("...")`` under ``paddle_tpu/`` must be declared
  in ``REGION_MANIFEST`` (the ``region-manifest`` lint enforces both
  directions, mirroring ``span_manifest.py``). The wrapper costs nothing
  in steady state: it only executes while a program is being *traced*,
  and the serving decode program traces once.
- Trace/HLO parsers + the attribution join. ``jax.profiler.trace``
  emits one complete event per executed HLO thunk carrying
  ``args={hlo_module, hlo_op}``; compiled HLO text maps each instruction
  name to ``metadata={op_name="jit(f)/.../rgn_attention/..."}``. Joining
  the two attributes measured device time per region per program —
  fusion across a region boundary lands on the fusion root's region,
  which is the honest post-optimization answer.
- ``StepProfiler`` — on-demand capture: wrap ``jax.profiler.trace()``
  around K step-callable invocations (plus a drain barrier so
  dispatch-ahead engines commit every in-flight step inside the trace
  window), parse, attribute, and retain the latest summary (bounded:
  latest-only, the postmortem contract).

Attribution semantics: the **innermost** region on an op's scope path
owns its leaf share (``region_shares``; nested ``attention/kv_gather``
time is kv_gather's), the **outermost** owns the group share
(``group_shares``; the train step's forward/backward/optimizer split).
Ops inside a profiled program with no region on their path are
``unattributed`` — they count in the denominator, so
``sum(region_shares) == coverage`` and the bench can pin coverage >= 0.9
instead of quietly renormalizing.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "REGION_MANIFEST", "REGION_PREFIX", "StepProfiler", "attribute_trace",
    "load_trace_events", "parse_hlo_instruction_regions", "region",
]

# Scope-name prefix separating region markers from every other op_name
# path component (jit names, primitive names, dispatch-op names).
REGION_PREFIX = "rgn_"

# region name -> {owner, category}; owners route a region-level perf
# regression, categories mirror the span manifest's grouping. Checked in
# BOTH directions by the ``region-manifest`` lint: an undeclared
# ``region("...")`` literal fails, and a declared region no code
# annotates anymore fails.
REGION_MANIFEST = {
    # serving / eager decode forward (SlotStep and ShardedSlotStep)
    "embed": {"owner": "models", "category": "Forward"},
    "attention": {"owner": "models", "category": "Forward"},
    "kv_gather": {"owner": "models", "category": "Forward"},
    "mlp": {"owner": "models", "category": "Forward"},
    # dropless expert layer (nn/moe.py): routing and sort, grouped products
    "moe_route": {"owner": "models", "category": "Forward"},
    "moe_experts": {"owner": "models", "category": "Forward"},
    "moe_shared": {"owner": "models", "category": "Forward"},
    # multi-head latent attention (models/joyai_flash.py, models/kv_cache.py):
    # the K up-projection folded into a decode query, attention over the
    # latent rows, a prefill's K and V expanded from its own rows
    "mla_absorb": {"owner": "models", "category": "Forward"},
    "mla_decode": {"owner": "models", "category": "Forward"},
    "mla_expand": {"owner": "models", "category": "Forward"},
    "logits": {"owner": "models", "category": "Forward"},
    "sampling": {"owner": "serving", "category": "Forward"},
    "telemetry": {"owner": "serving", "category": "UserDefined"},
    # chunked prefill + speculative decoding (serving/spec/)
    "prefill_chunk": {"owner": "serving", "category": "Forward"},
    "spec_verify": {"owner": "serving", "category": "Forward"},
    # tensor-parallel layout seams (all-gather / psum boundaries)
    "tp_gather": {"owner": "serving", "category": "Forward"},
    # train step phases (TrainStep._step)
    "forward": {"owner": "jit", "category": "Forward"},
    "backward": {"owner": "jit", "category": "Backward"},
    "optimizer": {"owner": "optimizer", "category": "Optimization"},
}


@contextlib.contextmanager
def region(name: str):
    """Annotate the ops traced inside as belonging to region ``name``.

    Delegates to ``jax.named_scope(REGION_PREFIX + name)``; raises on a
    name missing from ``REGION_MANIFEST`` so a typo'd region fails the
    first trace instead of silently never attributing."""
    if name not in REGION_MANIFEST:
        raise ValueError(
            f"region {name!r} is not declared in REGION_MANIFEST "
            f"(observability/step_profile.py); declared: "
            f"{sorted(REGION_MANIFEST)}")
    import jax

    with jax.named_scope(REGION_PREFIX + name):
        yield


# ---- HLO side of the join ----------------------------------------------

_HLO_MODULE = re.compile(r"^HloModule\s+([^,\s]+)", re.MULTILINE)
# one instruction definition per line: ``%name = ... metadata={...
# op_name="..." ...}``. Fusion-internal instructions parse too (names are
# unique module-wide), they just never match a thunk event.
_HLO_INSTR = re.compile(
    r"%([A-Za-z0-9_.\-]+)\s*=.*?op_name=\"([^\"]+)\"")
# a region marker inside one op_name path component. jax transforms wrap
# scope names (``jvp(rgn_kv_gather)`` when the autodiff tape stages a
# dispatched op through jvp), so match the marker anywhere in the
# component, not only at its start.
_RGN_IN_COMPONENT = re.compile(re.escape(REGION_PREFIX) + r"([A-Za-z0-9_]+)")


def parse_hlo_instruction_regions(
        hlo_text: str) -> Tuple[str, Dict[str, Tuple[str, ...]]]:
    """``(module_name, {instruction -> region path})`` for one compiled
    program's HLO text. The region path is the ordered ``rgn_``-marked
    components of the instruction's ``op_name`` metadata, outermost
    first, prefix stripped. A component may carry the marker inside a
    transform wrapper (``jvp(rgn_kv_gather)``); that still counts.
    Instructions with op_name metadata but no region components map to
    ``()`` (they are the *unattributed* time)."""
    m = _HLO_MODULE.search(hlo_text)
    module = m.group(1) if m else ""
    instrs: Dict[str, Tuple[str, ...]] = {}
    for line in hlo_text.splitlines():
        im = _HLO_INSTR.search(line)
        if im is None:
            continue
        name, op_name = im.group(1), im.group(2)
        path = []
        for c in op_name.split("/"):
            rm = _RGN_IN_COMPONENT.search(c)
            if rm is not None:
                path.append(rm.group(1))
        path = tuple(path)
        # first definition wins (top-level entry computation parses
        # before nothing else defines the same name anyway)
        instrs.setdefault(name, path)
    return module, instrs


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_HLO_SHAPE = re.compile(
    r"%([A-Za-z0-9_.\-]+)\s*=\s*([a-z0-9]+)\[([0-9,]*)\]")


def parse_hlo_instruction_bytes(hlo_text: str) -> Dict[str, int]:
    """``{instruction -> result bytes}`` from one program's HLO text
    (array-shaped results only; tuple-shaped instructions are skipped).
    Feeds the byte-dominance fallback in ``attribute_trace``."""
    out: Dict[str, int] = {}
    for m in _HLO_SHAPE.finditer(hlo_text):
        name, dtype, dims = m.group(1), m.group(2), m.group(3)
        sz = _DTYPE_BYTES.get(dtype)
        if sz is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.setdefault(name, n * sz)
    return out


# ---- trace side of the join --------------------------------------------

def load_trace_events(logdir: str) -> List[dict]:
    """Complete (``ph == "X"``) events from the newest trace-event dump
    under a ``jax.profiler.trace`` logdir. Host python/runtime spans ride
    along; the attribution join ignores anything without an
    ``args.hlo_op``."""
    paths = sorted(glob.glob(os.path.join(
        logdir, "**", "*.trace.json.gz"), recursive=True),
        key=os.path.getmtime)
    if not paths:
        return []
    with gzip.open(paths[-1], "rt") as f:
        doc = json.load(f)
    return [e for e in doc.get("traceEvents", [])
            if isinstance(e, dict) and e.get("ph") == "X"]


def attribute_trace(events: Sequence[dict],
                    programs: Sequence[dict]) -> dict:
    """Join executed-thunk events against per-program instruction maps.

    ``programs`` rows: ``{"name", "module", "regions"}`` plus optional
    ``"flops"``/``"bytes_accessed"`` (enables the bytes/roofline
    estimate) and ``"primary": True`` (the program whose in-step roofline
    is decomposed — the serving decode step). Module-name collisions
    (prefill buckets and the decode step jit the same function, so XLA
    names their modules identically) resolve in list order: put the
    primary program first.

    Region shares are fractions of the TOTAL profiled-program device
    time, so ``sum(region_shares) == coverage <= 1`` and unattributed
    time is visible instead of renormalized away. Device time in modules
    that belong to no profiled program (the per-step PRNG-split program,
    fetch-path utilities) is reported under ``aux_modules`` and excluded
    from the coverage denominator — it is not part of any step program.

    The executable the runtime jit cache built and the AOT re-compile
    the inventory analyzes can drift in instruction naming (XLA numbers
    inserted copies and canonicalized loops per compile, and the two
    compiles' fusion choices are not bit-identical). A thunk name with
    no exact map entry therefore falls back to the same-base-name map
    entries that NO trace op matched exactly, splitting its duration
    across the leftovers' region paths weighted by result bytes (rows
    may carry ``"nbytes"`` from ``parse_hlo_instruction_bytes``; without
    it every leftover weighs the same). Exact matches are exact; only
    this drift residue is a byte-weighted estimate, and base names with
    no leftover counterpart stay unattributed rather than guessed."""
    by_module: Dict[str, List[dict]] = {}
    for p in programs:
        by_module.setdefault(p["module"], []).append(p)

    def _resolve(mod: str) -> Optional[List[dict]]:
        plist = by_module.get(mod)
        if plist is None and mod:
            # XLA uniquifies re-registered module names (``jit_f.1``)
            plist = by_module.get(mod.rsplit(".", 1)[0])
        return plist

    def _base(op: str) -> str:
        head, _, tail = op.rpartition(".")
        return head if head and tail.isdigit() else op

    # numbering-drift fallback: per module, the trace-op names seen, so
    # "map entries no trace op matched" is computable before attribution
    seen_ops: Dict[str, set] = {}
    for e in events:
        args = e.get("args") or {}
        mod, op = args.get("hlo_module"), args.get("hlo_op")
        if mod and op and _resolve(mod) is not None:
            seen_ops.setdefault(mod, set()).add(op)
    # fallback[mod][base] -> [(path, weight)], weights summing to 1
    fallback: Dict[str, Dict[str, List[Tuple[Tuple[str, ...], float]]]] = {}
    for mod, ops in seen_ops.items():
        # per base name: {path -> leftover result bytes} (1-byte floor so
        # paths stay comparable when no nbytes info is available)
        leftovers: Dict[str, Dict[Tuple[str, ...], int]] = {}
        for p in _resolve(mod):
            nbytes = p.get("nbytes") or {}
            for iname, path in p["regions"].items():
                if iname not in ops and path:
                    d = leftovers.setdefault(_base(iname), {})
                    d[path] = d.get(path, 0) + max(nbytes.get(iname, 0), 1)
        fallback[mod] = {
            b: [(path, nb / sum(by_path.values()))
                for path, nb in by_path.items()]
            for b, by_path in leftovers.items()}

    total = 0.0
    aux_us: Dict[str, float] = {}
    unattributed = 0.0
    region_us: Dict[str, float] = {}
    group_us: Dict[str, float] = {}
    prog_us: Dict[str, float] = {}
    prog_events: Dict[str, int] = {}
    # per program: region -> us, and per-op execution counts (the max
    # count over any single instruction == program executions)
    prog_region_us: Dict[str, Dict[str, float]] = {}
    prog_op_counts: Dict[str, Dict[str, int]] = {}
    for p in programs:
        prog_us[p["name"]] = 0.0
        prog_events[p["name"]] = 0
        prog_region_us[p["name"]] = {}
        prog_op_counts[p["name"]] = {}

    for e in events:
        args = e.get("args") or {}
        mod, op = args.get("hlo_module"), args.get("hlo_op")
        if not mod or not op:
            continue                      # host span, not a device thunk
        plist = _resolve(mod)
        if plist is None:
            # a device program outside the profiled step (PRNG split,
            # fetch utilities) — reported, not silently dropped
            aux_us[mod] = aux_us.get(mod, 0.0) + float(e.get("dur") or 0.0)
            continue
        dur = float(e.get("dur") or 0.0)
        owner, splits = None, None
        for p in plist:
            got = p["regions"].get(op)
            if got is not None:
                owner, splits = p, ([(got, 1.0)] if got else [])
                break
        if owner is None:
            owner = plist[0]              # known module, unmapped op
            splits = fallback.get(mod, {}).get(_base(op), [])
        total += dur
        name = owner["name"]
        prog_us[name] += dur
        prog_events[name] += 1
        counts = prog_op_counts[name]
        counts[op] = counts.get(op, 0) + 1
        if not splits:
            unattributed += dur
            continue
        pr = prog_region_us[name]
        for path, w in splits:
            leaf, outer = path[-1], path[0]
            region_us[leaf] = region_us.get(leaf, 0.0) + dur * w
            group_us[outer] = group_us.get(outer, 0.0) + dur * w
            pr[leaf] = pr.get(leaf, 0.0) + dur * w

    def shares(d: Dict[str, float], denom: float) -> Dict[str, float]:
        if denom <= 0:
            return {}
        return {k: round(v / denom, 6)
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])}

    out = {
        "total_device_time_us": round(total, 3),
        "unattributed_us": round(unattributed, 3),
        "aux_modules": {k: round(v, 3) for k, v in sorted(
            aux_us.items(), key=lambda kv: -kv[1])},
        "coverage": round((total - unattributed) / total, 6) if total else 0.0,
        "region_time_us": {k: round(v, 3) for k, v in region_us.items()},
        "region_shares": shares(region_us, total),
        "group_shares": shares(group_us, total),
        "programs": {},
    }
    for p in programs:
        name = p["name"]
        t = prog_us[name]
        execs = max(prog_op_counts[name].values(), default=0)
        row = {
            "device_time_us": round(t, 3),
            "events": prog_events[name],
            "executions": execs,
            "region_shares": shares(prog_region_us[name], t),
        }
        if execs and t > 0:
            row["step_device_time_s"] = t / execs * 1e-6
        out["programs"][name] = row
        if not p.get("primary"):
            continue
        out["primary_program"] = name
        fl, by = p.get("flops"), p.get("bytes_accessed")
        if not (execs and t > 0 and by):
            continue
        # in-step roofline: the whole-program bandwidth utilization the
        # harness already reports, decomposed by measured region time.
        # Bytes-touched per region is an ESTIMATE (time share x program
        # bytes) — exact per-region byte counts need per-op cost
        # analysis, which XLA does not expose post-fusion.
        from paddle_tpu.observability.program_inventory import (
            roofline_utilization,
        )

        step_s = t / execs * 1e-6
        rs = row["region_shares"]
        out["decode_roofline"] = {
            "program": name,
            "step_device_time_s": step_s,
            "flops": fl,
            "bytes_accessed": by,
            "region_bytes_est": {r: int(s * float(by))
                                 for r, s in rs.items()},
        }
        roof = roofline_utilization(float(fl or 0), float(by), step_s)
        if roof is not None:   # None on CPU: no peaks, no utilisation
            out["decode_roofline"].update({
                "bandwidth_util": roof["bandwidth_util"],
                "mfu": roof["mfu"],
                "chip": roof["chip"],
                "bandwidth_util_by_region": {
                    r: round(s * roof["bandwidth_util"], 6)
                    for r, s in rs.items()},
            })
    return out


# ---- on-demand capture --------------------------------------------------

# jax.profiler supports ONE active trace per process
_TRACE_LOCK = threading.Lock()


class StepProfiler:
    """On-demand device-trace capture around a step callable.

    ``step_fn`` runs one scheduler/train iteration; ``programs_fn``
    returns the ``attribute_trace`` program rows (resolved lazily at
    capture time, after the programs exist and their HLO is reachable);
    ``barrier`` (optional) drains in-flight dispatched work so a
    dispatch-ahead engine's every step commits inside the trace window.

    ``capture`` is explicitly on-demand — nothing here runs in steady
    state, and the latest summary only is retained (``last_summary``),
    so postmortem bundles attaching it stay bounded."""

    def __init__(self, step_fn, programs_fn, barrier=None):
        self._step_fn = step_fn
        self._programs_fn = programs_fn
        self._barrier = barrier
        self.last_summary: Optional[dict] = None

    def capture(self, steps: int = 8) -> dict:
        """Trace ``steps`` step invocations and attribute device time by
        region. Returns (and retains) the summary dict; a capture racing
        another active profiler trace reports ``enabled: False`` rather
        than crashing the serving loop."""
        import jax

        if not _TRACE_LOCK.acquire(blocking=False):
            return {"enabled": False,
                    "error": "another step-profile capture is in progress"}
        tmpdir = tempfile.mkdtemp(prefix="stepprofile_")
        try:
            t0 = time.perf_counter()
            with jax.profiler.trace(tmpdir):
                for _ in range(max(1, int(steps))):
                    self._step_fn()
                if self._barrier is not None:
                    self._barrier()
            wall_s = time.perf_counter() - t0
            events = load_trace_events(tmpdir)
            summary = attribute_trace(events, self._programs_fn())
            summary.update({
                "enabled": True,
                "steps_requested": int(steps),
                "wall_s": round(wall_s, 4),
                "trace_events": len(events),
            })
        except Exception as exc:  # profiling must never kill serving
            summary = {"enabled": False,
                       "error": f"{type(exc).__name__}: {exc}"}
        finally:
            _TRACE_LOCK.release()
            shutil.rmtree(tmpdir, ignore_errors=True)
        self.last_summary = summary
        return summary
