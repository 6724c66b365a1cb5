"""PR-over-PR perf gate: diff two canonical bench JSON artifacts.

Every bench writes a canonical artifact (``tools/bench_io.py``: sorted
keys, 6 significant digits) precisely so that two runs are textually and
numerically comparable. This tool makes that comparison a CLI gate::

    python tools/bench_compare.py OLD.json NEW.json [--tolerance 0.25]

It walks both artifacts, pairs every numeric leaf by its dotted path, and
classifies each metric by direction from its name:

- **higher is better**: ``tokens_per_s``, ``steps_per_s``, ``*speedup*``,
  ``*ratio*``, ``*hit_rate*``, ``goodput``, ``*util*``, ``*mfu*``,
  ``recovery_pct``, ``ceiling_*`` — a drop beyond tolerance is a
  regression;
- **lower is better**: ``*_s`` / ``*_ms`` / ``*_seconds``, ``*stall*``,
  ``ttft*`` / ``tpot*``, ``*overhead*`` — a rise beyond tolerance is a
  regression;
- **direction-neutral**: per-region composition fields from the in-step
  profiler (``region_share_*``, ``region_shares.*``, ``group_shares.*``,
  ``region_bytes_est.*``, ``bandwidth_util_by_region.*``,
  ``aux_modules.*``) — device time moving from attention to mlp is a mix
  change whose goodness depends on the PR, so these are reported in an
  ``informational`` list (old/new/rel) and never gate. The scalar
  ``region_coverage`` stays gated higher-is-better: losing attribution
  coverage IS a regression;
- everything else (counts, configs, bytes, shas) is compared for drift
  but never fails the gate — changing ``num_requests`` is a workload
  change, not a perf regression, and it shows up as ``noncomparable``.

A directional change additionally needs an absolute delta above
``--abs-floor`` (default 5e-3 in the metric's own unit) to gate: a
0.11ms -> 0.14ms host stall is +28% relative but below shared-host
timer jitter, and relative tolerance alone would flag it forever.

Exit status: 0 when no directional metric regressed beyond tolerance,
1 when at least one did, 2 on usage/IO errors. Timing metrics on shared
CI hosts are noisy, hence the deliberately loose default tolerance
(25% relative); tighten per-metric conclusions by re-running, not by
trusting one sample (never believe a single slow bench).

Typical wiring: regenerate ``BENCH_*.json`` on your branch, then compare
against the committed artifact from the previous PR::

    git show HEAD~1:BENCH_serving_smoke.json > /tmp/old.json
    python tools/serve_bench.py --smoke
    python tools/bench_compare.py /tmp/old.json BENCH_serving_smoke.json
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

__all__ = ["classify", "compare", "compare_files", "main"]

# substring -> direction; first match wins, checked in order (the more
# specific lower-is-better names come first so e.g. "stall_ratio" is
# treated as a stall, not a ratio)

# goodness suffixes outrank everything: "tpot_improvement_pct" and
# "host_stall_share_cut_x" are improvements even though their leaves
# contain a lower-is-better base metric
_GOODNESS_MARKERS = (
    "improvement", "speedup", "_cut", "recovery", "saved", "goodput",
    "hit_rate",
)
_LOWER_MARKERS = (
    "stall", "overhead", "ttft", "tpot", "latency", "wall_s", "wall_ms",
    "_seconds", "_ms", "snapshot_s", "save_s", "restore_s", "evicted",
    "preemptions", "recompiles", "breach", "fault",
    # sharded serving: the largest per-chip share of the KV pool's bytes
    # can only sit at or above 1/tp — growth is shard imbalance
    "max_fraction",
    "kv_bytes_per_token",
)
_HIGHER_MARKERS = (
    "tokens_per_s", "steps_per_s", "images_per_s", "per_s", "speedup",
    "ratio", "hit_rate", "goodput", "util", "mfu", "tflops", "gbs",
    "recovery_pct", "ceiling", "bandwidth", "coverage",
    # speculative decoding: acceptance and multi-token decode throughput
    "accept_rate", "tokens_per_step", "tokens_per_verify_step",
)
# in-step region composition: a share shifting between regions is a mix
# change whose goodness depends on the PR under review, so these leaves
# are direction-neutral — surfaced with old/new values, never gated.
# Checked FIRST (against the full dotted path, since e.g. the leaf under
# ``region_shares.`` is just the region name) so a region named after a
# directional marker can never be gated by accident.
_INFORMATIONAL_MARKERS = (
    "region_share", "region_shares.", "group_shares.",
    "region_bytes_est.", "bandwidth_util_by_region.", "aux_modules.",
)


def classify(path: str) -> Optional[str]:
    """Direction of a metric from its dotted path: ``"higher"``,
    ``"lower"``, ``"info"`` (direction-neutral region composition), or
    ``None`` (not a gated perf metric). Directional markers match only
    the LEAF key — parent keys like ``goodput_vs_fault_rate`` must not
    poison the direction of the ``goodput`` inside them; informational
    markers match the full path, because a region-share leaf is just the
    region's name."""
    full = path.lower()
    for m in _INFORMATIONAL_MARKERS:
        if m in full:
            return "info"
    low = full.split(".")[-1].split("[")[0]
    for m in _GOODNESS_MARKERS:
        if m in low:
            return "higher"
    for m in _LOWER_MARKERS:
        if m in low:
            return "lower"
    for m in _HIGHER_MARKERS:
        if m in low:
            return "higher"
    return None


def _numeric_leaves(obj, prefix: str = "") -> Dict[str, float]:
    out: Dict[str, float] = {}
    if isinstance(obj, dict):
        for k in obj:
            out.update(_numeric_leaves(obj[k], f"{prefix}.{k}" if prefix
                                       else str(k)))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            out.update(_numeric_leaves(v, f"{prefix}[{i}]"))
    elif isinstance(obj, bool):
        pass                      # booleans are contracts, not metrics
    elif isinstance(obj, (int, float)):
        out[prefix] = float(obj)
    return out


def compare(old: dict, new: dict, tolerance: float = 0.25,
            abs_floor: float = 5e-3) -> dict:
    """Pair numeric leaves of two artifacts and judge directional drift.

    Returns ``{regressions, improvements, drift, noncomparable,
    missing, added, ok}``; ``ok`` is False iff any directional metric
    moved the wrong way by more than ``tolerance`` (relative) AND by
    more than ``abs_floor`` (absolute) — sub-floor deltas are drift."""
    a, b = _numeric_leaves(old), _numeric_leaves(new)
    regressions: List[dict] = []
    improvements: List[dict] = []
    drift: List[dict] = []
    informational: List[dict] = []
    noncomparable: List[str] = []
    for path in sorted(set(a) & set(b)):
        va, vb = a[path], b[path]
        if va == vb:
            continue
        rel = (vb - va) / abs(va) if va else float("inf")
        direction = classify(path)
        row = {"metric": path, "old": va, "new": vb,
               "rel_change": round(rel, 4) if rel != float("inf") else None}
        if direction is None:
            noncomparable.append(path)
            continue
        if direction == "info":
            informational.append(row)
            continue
        material = abs(vb - va) > abs_floor
        bad = material and (rel < -tolerance if direction == "higher"
                            else rel > tolerance)
        good = material and (rel > tolerance if direction == "higher"
                             else rel < -tolerance)
        row["direction"] = direction
        if bad:
            regressions.append(row)
        elif good:
            improvements.append(row)
        else:
            drift.append(row)
    return {
        "tolerance": tolerance,
        "abs_floor": abs_floor,
        "regressions": regressions,
        "improvements": improvements,
        "drift": drift,
        "informational": informational,
        "noncomparable": noncomparable,
        "missing": sorted(set(a) - set(b)),
        "added": sorted(set(b) - set(a)),
        "ok": not regressions,
    }


def compare_files(old_path: str, new_path: str,
                  tolerance: float = 0.25,
                  abs_floor: float = 5e-3) -> dict:
    with open(old_path) as f:
        old = json.load(f)
    with open(new_path) as f:
        new = json.load(f)
    out = compare(old, new, tolerance=tolerance, abs_floor=abs_floor)
    out["old_artifact"] = old_path
    out["new_artifact"] = new_path
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diff two canonical bench JSONs; exit 1 on perf "
                    "regression beyond tolerance")
    ap.add_argument("old", help="baseline artifact (e.g. from git show)")
    ap.add_argument("new", help="candidate artifact")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="relative regression tolerance (default 0.25)")
    ap.add_argument("--abs-floor", type=float, default=5e-3,
                    help="minimum absolute delta for a directional "
                         "change to gate (default 5e-3)")
    ap.add_argument("--json", action="store_true",
                    help="print the full comparison as JSON")
    args = ap.parse_args(argv)
    try:
        rep = compare_files(args.old, args.new, tolerance=args.tolerance,
                            abs_floor=args.abs_floor)
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench_compare: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(rep, indent=2))
    else:
        def pct(r):
            return ("n/a" if r["rel_change"] is None
                    else f"{r['rel_change']:+.1%}")

        for r in rep["regressions"]:
            print(f"REGRESSION {r['metric']}: {r['old']} -> {r['new']} "
                  f"({pct(r)})")
        for r in rep["improvements"]:
            print(f"improved   {r['metric']}: {r['old']} -> {r['new']} "
                  f"({pct(r)})")
        for r in rep["informational"]:
            print(f"info       {r['metric']}: {r['old']} -> {r['new']} "
                  f"({pct(r)})")
        print(f"{len(rep['regressions'])} regressions, "
              f"{len(rep['improvements'])} improvements, "
              f"{len(rep['drift'])} within tolerance, "
              f"{len(rep['informational'])} informational region shifts, "
              f"{len(rep['noncomparable'])} non-gated changes "
              f"(tolerance {rep['tolerance']:.0%})")
    return 0 if rep["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
