#!/usr/bin/env python
"""graft_lint driver: one entry point for all eleven static checkers.

    python tools/lint.py                  # paddle_tpu/ + tools/, exit 0/1
    python tools/lint.py --json           # full machine-readable report
    python tools/lint.py --changed        # only files changed vs git HEAD
    python tools/lint.py --rules guarded-by,span-manifest
    python tools/lint.py --rules concurrency   # group alias (lock-order,
                                          # thread-role, blocking-under-
                                          # lock, guarded-by)
    python tools/lint.py --write-baseline # accept current findings

Runs on stdlib only (ast + regex text scans — no jax, no import of the
scanned modules), so the full-repo pass takes seconds
(tests/test_graft_lint.py runs it in tier-1).

Exit code 0 iff every finding is suppressed in-source
(``# graft-lint: disable=<rule>``) or accepted in
``tools/graft_lint/baseline.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tools.graft_lint import (  # noqa: E402
    ALL_CHECKERS,
    Baseline,
    RULE_GROUPS,
    STALE_RULE,
    default_baseline_path,
    run_lint,
)


def _git_changed_files(repo_root: str):
    """Repo-relative .py files changed vs HEAD (staged, unstaged, and
    untracked)."""
    out = set()
    for args in (["git", "diff", "--name-only", "HEAD"],
                 ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            r = subprocess.run(args, cwd=repo_root, capture_output=True,
                               text=True, timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            return None
        if r.returncode != 0:
            return None
        out.update(ln.strip() for ln in r.stdout.splitlines() if ln.strip())
    return sorted(f for f in out if f.endswith(".py"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--root", action="append", default=None,
                    help="directory (or file) to scan; repeatable "
                         "(default: paddle_tpu/ and tools/)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule subset; group aliases "
                         "(e.g. 'concurrency') expand to their members")
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as JSON")
    ap.add_argument("--changed", action="store_true",
                    help="report findings only in files changed vs git HEAD")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default: "
                         "tools/graft_lint/baseline.json)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline from current findings "
                         "and exit 0")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for c in ALL_CHECKERS:
            print(f"{c.rule:24s} {c.description}")
        print(f"{STALE_RULE:24s} suppression comments matching zero "
              f"findings (audit — always on for full runs)")
        for name, members in sorted(RULE_GROUPS.items()):
            print(f"{name:24s} group = {', '.join(members)}")
        return 0

    roots = args.root or [os.path.join(REPO_ROOT, "paddle_tpu"),
                          os.path.join(REPO_ROOT, "tools")]
    rules = ([r.strip() for r in args.rules.split(",") if r.strip()]
             if args.rules else None)

    changed = None
    if args.changed:
        changed = _git_changed_files(REPO_ROOT)
        if changed is None:
            print("lint: --changed needs git; running full scan",
                  file=sys.stderr)
        elif not changed:
            print("lint: OK — no changed .py files")
            return 0

    report = run_lint(REPO_ROOT, roots, rules=rules,
                      baseline_path=args.baseline,
                      changed_files=changed)
    findings = report.pop("_finding_objs")

    if args.write_baseline:
        path = args.baseline or default_baseline_path()
        n = Baseline.write(path, findings)
        print(f"lint: baseline written to "
              f"{os.path.relpath(path, REPO_ROOT)} ({n} entries, "
              f"{report['counts']['total']} findings)")
        return 0

    if args.json:
        print(json.dumps(report, indent=2))
    else:
        shown = [f for f in findings if not f.suppressed and not f.baselined]
        for f in shown:
            print(f.render())
        c = report["counts"]
        status = "OK" if report["ok"] else f"{c['failing']} finding(s)"
        print(f"lint: {status} — {report['files_scanned']} files, "
              f"{len(report['rules'])} rules, {c['baselined']} baselined, "
              f"{c['suppressed']} suppressed, {report['wall_s']}s")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
