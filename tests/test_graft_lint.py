"""graft_lint: the framework-invariant static-analysis suite as tier-1.

Three layers of pinning:

1. Fixture tests per rule — one known-bad and one known-clean snippet per
   checker, run through the real driver machinery (no jax devices needed:
   the suite is stdlib-ast only).
2. Suppression + baseline round trips — ``# graft-lint: disable=...`` in
   its three forms, and the accepted-findings baseline absorbing exactly
   the findings it records (a NEW finding still fails).
3. The acceptance bar, both directions: ``python tools/lint.py`` over the
   real repo exits 0 with zero non-baselined findings, and seeding a
   known-bad construct makes it exit non-zero with a correct file:line.

Plus regression tests for the real bugs the first full-repo run surfaced
(unguarded registry/histogram/flight-recorder state shared with the
ObservabilityEndpoint scrape thread).
"""

import json
import os
import subprocess
import sys
import textwrap
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.graft_lint import Baseline, run_lint  # noqa: E402
from tools.graft_lint.core import Module  # noqa: E402


def _lint(tmp_path, rules=None, baseline=None):
    """Run the suite over the tmp fixture tree; returns (report, findings
    as dicts)."""
    report = run_lint(str(tmp_path), [str(tmp_path)], rules=rules,
                      baseline_path=baseline
                      or str(tmp_path / "no_baseline.json"))
    report.pop("_finding_objs")
    return report


def _write(tmp_path, name, src):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return p


def _rules_hit(report, rule):
    return [f for f in report["findings"] if f["rule"] == rule
            and not f["suppressed"] and not f["baselined"]]


# ---------------------------------------------------------------- fixtures

def test_tracing_hazard_bad_and_clean(tmp_path):
    _write(tmp_path, "mod.py", """
        import jax.numpy as jnp
        import numpy as np

        def to_static(fn):
            return fn

        def helper(x):
            return x.item() + 1          # hazard, reachable via traced()

        @to_static
        def traced(x):
            if bool(x):                   # hazard: bool() on traced value
                return helper(x)
            return jnp.sum(x)             # clean: stays in jnp

        def eager_only(x):
            return np.asarray(x).item()   # NOT reachable from a trace root
    """)
    report = _lint(tmp_path, rules=["tracing-hazard"])
    hits = _rules_hit(report, "tracing-hazard")
    symbols = {f["symbol"] for f in hits}
    assert "helper" in symbols            # call-graph reachability
    assert "traced" in symbols            # direct hazard in the root
    assert "eager_only" not in symbols    # eager code is out of scope
    assert all(f["file"] == "mod.py" and f["line"] > 0 for f in hits)


def test_recompile_hazard_bad_and_clean(tmp_path):
    _write(tmp_path, "mod.py", """
        import numpy as np

        def _bucket(n, lo=16):
            b = lo
            while b < n:
                b *= 2
            return b

        class Sched:
            def bad(self, ids):
                P = len(ids)
                a = np.zeros((1, P), np.int32)      # raw data-dep width
                return self._step_fn(a)

            def good(self, ids):
                Pb = min(_bucket(len(ids)), 512)
                a = np.zeros((1, Pb), np.int32)     # bucketed: clean
                return self._step_fn(a)

            def no_jit_here(self, ids):
                return np.zeros((len(ids),))        # no jit callsite: clean
    """)
    report = _lint(tmp_path, rules=["recompile-hazard"])
    hits = _rules_hit(report, "recompile-hazard")
    assert [f["symbol"] for f in hits] == ["Sched.bad"]


def test_host_sync_in_hot_loop_bad_and_clean(tmp_path):
    _write(tmp_path, "mod.py", """
        import numpy as np

        def hot_path(fn=None, **kw):
            def mark(f):
                return f
            return mark if fn is None else fn

        class Loop:
            @hot_path
            def decode(self, t):
                bad = np.asarray(t.numpy())          # unmetered sync
                with self.stall.timed("sampling_sync"):
                    ok = np.asarray(t.numpy())       # metered: allowed
                return bad, ok

            def not_hot(self, t):
                return t.numpy()                     # unannotated: clean
    """)
    report = _lint(tmp_path, rules=["host-sync-in-hot-loop"])
    hits = _rules_hit(report, "host-sync-in-hot-loop")
    assert hits and all(f["symbol"] == "Loop.decode" for f in hits)
    # only the unmetered line fires (np.asarray + .numpy on one line)
    assert {f["line"] for f in hits} == {min(f["line"] for f in hits)}


def test_host_sync_transitive_helper(tmp_path):
    """The dispatch-path hazard: a readback hidden one call away from a
    @hot_path function must fire (with the call chain named), while the
    reduced-strictness transitive scan skips the np.asarray heuristic
    (helpers legitimately shape host arrays) and honors the metered
    escape hatch."""
    _write(tmp_path, "mod.py", """
        import numpy as np

        def hot_path(fn=None, **kw):
            def mark(f):
                return f
            return mark if fn is None else fn

        class Engine:
            @hot_path
            def _dispatch_decode(self, t):
                return self._stage(t)

            def _stage(self, t):
                host = np.asarray([1, 2])        # host shaping: clean
                pos = np.asarray(host)           # heuristic off: clean
                return t.numpy(), pos            # unmetered sync: fires

            def _metered(self, t):
                with self.stall.timed("drain"):
                    return t.numpy()             # metered: clean

            @hot_path
            def _commit(self, t):
                return self._metered(t)

            def _unreached(self, t):
                return t.numpy()                 # not on a hot path: clean
    """)
    report = _lint(tmp_path, rules=["host-sync-in-hot-loop"])
    hits = _rules_hit(report, "host-sync-in-hot-loop")
    assert len(hits) == 1
    assert hits[0]["symbol"] == "Engine._stage"
    assert "reached from @hot_path via Engine._dispatch_decode" \
        in hits[0]["message"]


def test_guarded_by_bad_and_clean(tmp_path):
    _write(tmp_path, "mod.py", """
        import threading

        def guarded_by(lock):
            return lock

        def holds_lock(lock):
            def mark(f):
                return f
            return mark

        class Ring:
            _items: guarded_by("_lock")

            def __init__(self):
                self._lock = threading.Lock()
                self._items = []                 # exempt: __init__

            def bad_push(self, x):
                self._items.append(x)            # unguarded

            def good_push(self, x):
                with self._lock:
                    self._items.append(x)

            @holds_lock("_lock")
            def _pop_locked(self):
                return self._items.pop()         # caller holds the lock

        class SubRing(Ring):
            def bad_sub(self):
                return len(self._items)          # inherited declaration
    """)
    report = _lint(tmp_path, rules=["guarded-by"])
    hits = _rules_hit(report, "guarded-by")
    assert {f["symbol"] for f in hits} == {"Ring.bad_push", "SubRing.bad_sub"}


def test_donation_alias_bad_and_clean(tmp_path):
    _write(tmp_path, "mod.py", """
        import jax

        class Step:
            def __init__(self, fn, donate):
                self._donate_argnums = (0, 2) if donate else ()
                self._jitted = jax.jit(
                    fn, donate_argnums=self._donate_argnums)

            def bad(self, x, y, z):
                out = self._jitted(x, y, z)
                return out + x               # x (argnum 0) re-read

            def good(self, x, y, z):
                out = self._jitted(x, y, z)
                x = out * 2                  # rebind kills the taint
                return x + y                 # y (argnum 1) is not donated
    """)
    report = _lint(tmp_path, rules=["donation-alias"])
    hits = _rules_hit(report, "donation-alias")
    assert [f["symbol"] for f in hits] == ["Step.bad"]
    assert "`x`" in hits[0]["message"]


# ------------------------------------------------- suppressions + baseline

def test_swallowed_exception_bad_and_clean(tmp_path):
    _write(tmp_path, "mod.py", """
        import logging

        def bare(x):
            try:
                return x()
            except:                       # bad: bare except, no re-raise
                pass

        def broad_silent(x):
            try:
                return x()
            except Exception:             # bad: swallows silently
                pass

        def broad_tuple(x):
            try:
                return x()
            except (ValueError, Exception):   # bad: tuple hides the broad
                pass

        def bare_reraise(x):
            try:
                return x()
            except:                       # clean: re-raises
                raise

        def broad_handled(x):
            try:
                return x()
            except Exception as e:        # clean: the handler DOES something
                logging.warning("x failed: %s", e)
                return None

        def narrow(x):
            try:
                return x()
            except ValueError:            # clean: narrow type may be silent
                pass
    """)
    report = _lint(tmp_path, rules=["swallowed-exception"])
    hits = _rules_hit(report, "swallowed-exception")
    symbols = {f["symbol"] for f in hits}
    assert symbols == {"bare", "broad_silent", "broad_tuple"}
    assert all(f["line"] > 0 for f in hits)

    _write(tmp_path, "mod.py", """
        def f(x):
            try:
                return x()
            # graft-lint: disable-next=swallowed-exception (fixture: the
            # teardown path must not crash)
            except Exception:
                pass
    """)
    report = _lint(tmp_path, rules=["swallowed-exception"])
    assert report["ok"] and report["counts"]["suppressed"] == 1


def test_ledger_bypass_bad_and_clean(tmp_path):
    _write(tmp_path, "mod.py", """
        import numpy as np
        import paddle_tpu as paddle

        class BypassingPool:
            def __init__(self, n):
                # bad: device pool allocation, class never touches the
                # ledger -> device_memory_bytes census under-counts
                self._pools = [paddle.zeros([n, 16], dtype="float32")]

        class AccountedPool:
            def __init__(self, n, ledger):
                self._pools = [paddle.zeros([n, 16], dtype="float32")]
                self._ledger_handle = ledger.register(
                    "kv_pool", "pools", n * 16 * 4)

        class HostSidePool:
            def __init__(self, n):
                # clean: numpy is host memory, not a device allocation
                self._pool = np.zeros((n, 16), np.float32)

        class PoolingLayer:
            def __init__(self):
                # clean: an nn pooling layer, not an array allocation
                self.avg_pool = object()
    """)
    report = _lint(tmp_path, rules=["ledger-bypass"])
    hits = _rules_hit(report, "ledger-bypass")
    assert len(hits) == 1
    assert hits[0]["symbol"].endswith("BypassingPool")
    assert "BypassingPool" in hits[0]["message"]
    assert hits[0]["line"] > 0

    # staging-marker spelling is covered too
    _write(tmp_path, "mod.py", """
        import jax.numpy as jnp

        class Snapshotter:
            def grab(self, tree):
                self._staging = jnp.zeros((4,))   # bad: unledgered staging
    """)
    report = _lint(tmp_path, rules=["ledger-bypass"])
    assert len(_rules_hit(report, "ledger-bypass")) == 1


def test_suppression_forms(tmp_path):
    _write(tmp_path, "mod.py", """
        def hot_path(fn):
            return fn

        class A:
            @hot_path
            def f(self, t):
                a = t.numpy()  # graft-lint: disable=host-sync-in-hot-loop
                # graft-lint: disable-next=host-sync-in-hot-loop (reason
                # may span further comment lines before the code line)
                b = t.numpy()
                c = t.numpy()
                return a, b, c
    """)
    report = _lint(tmp_path, rules=["host-sync-in-hot-loop"])
    hits = _rules_hit(report, "host-sync-in-hot-loop")
    assert len(hits) == 1                 # only the un-suppressed line
    assert report["counts"]["suppressed"] == 2

    _write(tmp_path, "mod.py", """
        # graft-lint: disable-file=host-sync-in-hot-loop
        def hot_path(fn):
            return fn

        class A:
            @hot_path
            def f(self, t):
                return t.numpy()
    """)
    report = _lint(tmp_path, rules=["host-sync-in-hot-loop"])
    assert report["ok"]
    assert report["counts"]["suppressed"] == 1


def test_baseline_round_trip(tmp_path):
    src = """
        def hot_path(fn):
            return fn

        class A:
            @hot_path
            def f(self, t):
                return t.numpy()
    """
    _write(tmp_path, "mod.py", src)
    bl = tmp_path / "baseline.json"
    report = run_lint(str(tmp_path), [str(tmp_path)],
                      baseline_path=str(bl))
    assert not report["ok"]
    Baseline.write(str(bl), report["_finding_objs"])

    # same findings -> absorbed, exit clean
    report2 = _lint(tmp_path, baseline=str(bl))
    assert report2["ok"]
    assert report2["counts"]["baselined"] == 1

    # a NEW finding of the same rule/file is NOT absorbed (counted entries)
    _write(tmp_path, "mod.py", src + """
            @hot_path
            def g(self, t):
                return t.numpy()
    """)
    report3 = _lint(tmp_path, baseline=str(bl))
    assert not report3["ok"]
    assert report3["counts"]["baselined"] == 1
    assert report3["counts"]["failing"] == 1


def test_fingerprint_survives_line_shifts(tmp_path):
    """Baseline entries are line-free: edits above a finding don't
    invalidate it."""
    _write(tmp_path, "mod.py", """
        def hot_path(fn):
            return fn

        class A:
            @hot_path
            def f(self, t):
                return t.numpy()
    """)
    bl = tmp_path / "baseline.json"
    report = run_lint(str(tmp_path), [str(tmp_path)], baseline_path=str(bl))
    Baseline.write(str(bl), report["_finding_objs"])
    _write(tmp_path, "mod.py", """
        # a new comment block
        # shifting every line below it
        def hot_path(fn):
            return fn

        class A:
            @hot_path
            def f(self, t):
                return t.numpy()
    """)
    report2 = _lint(tmp_path, baseline=str(bl))
    assert report2["ok"] and report2["counts"]["baselined"] == 1


def test_span_checker_runs_in_suite():
    """The folded-in sixth checker reconciles the real manifest through
    the one lint entry point."""
    report = run_lint(REPO, [os.path.join(REPO, "paddle_tpu")],
                      rules=["span-manifest"])
    report.pop("_finding_objs")
    assert report["ok"], report["findings"]
    assert report["rules"] == ["span-manifest"]


# ----------------------------------------------- acceptance: both directions

def test_lint_repo_exits_zero():
    """Direction 1: the shipped tree is clean (every finding fixed,
    suppressed with a reason, or explicitly baselined)."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"), "--json"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout[-3000:]
    rep = json.loads(r.stdout)
    assert rep["ok"] and rep["files_scanned"] > 200
    assert len(rep["rules"]) == 12
    assert rep["schema"] == "graft-lint-report/2"
    assert rep["audits"] == ["stale-suppression"]
    # every reported finding carries a content-addressed fingerprint
    for f in rep["findings"]:
        assert len(f["fingerprint"]) == 16
        int(f["fingerprint"], 16)


def test_lint_catches_seeded_bad_construct(tmp_path):
    """Direction 2: a known-bad construct (unguarded guarded_by write, and
    a .item() in a hot decode loop) exits non-zero with correct
    file:line findings."""
    src = textwrap.dedent("""
        import threading

        def guarded_by(lock):
            return lock

        def hot_path(fn):
            return fn

        class Sched:
            _slots: guarded_by("_lock")

            def __init__(self):
                self._lock = threading.Lock()
                self._slots = []

            @hot_path
            def _decode_once(self, next_ids):
                self._slots.append(1)
                return next_ids.item()
    """)
    bad = tmp_path / "bad.py"
    bad.write_text(src)
    lines = src.splitlines()
    slots_line = lines.index("        self._slots.append(1)") + 1
    item_line = lines.index("        return next_ids.item()") + 1
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         "--root", str(tmp_path), "--baseline", str(tmp_path / "bl.json")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert f"bad.py:{slots_line}" in r.stdout       # guarded-by
    assert f"bad.py:{item_line}" in r.stdout        # host-sync-in-hot-loop
    assert "[guarded-by]" in r.stdout
    assert "[host-sync-in-hot-loop]" in r.stdout


def test_lint_seeded_dispatch_helper_sync_both_directions(tmp_path):
    """The async-engine shape, pinned both ways through the real driver:
    a helper called from the hot dispatch path that syncs unmetered exits
    non-zero with the helper's file:line; metering the same sync under
    stall.timed makes the tree exit zero."""
    tmpl = textwrap.dedent("""
        def hot_path(fn=None, **kw):
            def mark(f):
                return f
            return mark if fn is None else fn

        class Engine:
            @hot_path
            def _dispatch_decode(self, t):
                return self._fetch(t)

            def _fetch(self, t):
                %s
    """)
    bad_body = "return t.numpy()"
    good_body = ("with self.stall.timed(\"drain\"):\n"
                 "            return t.numpy()")
    bad = tmp_path / "engine.py"
    bad.write_text(tmpl % bad_body)
    line = (tmpl % bad_body).splitlines().index(
        f"        {bad_body}") + 1
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         "--root", str(tmp_path), "--baseline", str(tmp_path / "bl.json")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert f"engine.py:{line}" in r.stdout
    assert "[host-sync-in-hot-loop]" in r.stdout
    assert "reached from @hot_path" in r.stdout

    bad.write_text(tmpl % good_body)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         "--root", str(tmp_path), "--baseline", str(tmp_path / "bl.json")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout[-2000:]


def test_changed_mode_scopes_findings(tmp_path):
    """--changed machinery: findings restricted to the given file set."""
    _write(tmp_path, "one.py", """
        def hot_path(fn):
            return fn

        class A:
            @hot_path
            def f(self, t):
                return t.numpy()
    """)
    _write(tmp_path, "two.py", """
        def hot_path(fn):
            return fn

        class B:
            @hot_path
            def g(self, t):
                return t.numpy()
    """)
    report = run_lint(str(tmp_path), [str(tmp_path)],
                      baseline_path=str(tmp_path / "bl.json"),
                      changed_files=["one.py"])
    report.pop("_finding_objs")
    assert {f["file"] for f in report["findings"]} == {"one.py"}


def test_parse_error_is_a_finding(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    report = _lint(tmp_path)
    assert not report["ok"]
    assert report["findings"][0]["rule"] == "parse-error"


def test_module_suppression_parsing():
    m = Module("x.py", "x.py",
               "a = 1  # graft-lint: disable=r1,r2\n"
               "# graft-lint: disable-file=r3\n")
    assert m.is_suppressed("r1", 1) and m.is_suppressed("r2", 1)
    assert not m.is_suppressed("r1", 2)
    assert m.is_suppressed("r3", 99)     # file-wide, any line


# ------------------------------------------ regressions from the first run

def test_registry_scrape_during_metric_creation_regression():
    """FIXED by this PR: MetricsRegistry.snapshot()/prometheus_text() read
    ``_metrics`` (and label families read ``_children``) without the lock,
    so an endpoint scrape racing lazy metric creation died with
    "OrderedDict mutated during iteration". Hammer both sides."""
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry("lint_regression")
    errs = []
    stop = threading.Event()

    def creator():
        i = 0
        fam = reg.counter("family")
        while not stop.is_set() and i < 30000:
            reg.counter(f"c{i}").inc()
            fam.labels(k=str(i)).inc()
            if i % 3 == 0:
                reg.histogram(f"h{i}").record(i)
            i += 1

    def scraper():
        try:
            while not stop.is_set():
                reg.snapshot()
                reg.prometheus_text()
        except RuntimeError as e:        # the pre-fix failure mode
            errs.append(e)

    threads = [threading.Thread(target=creator, daemon=True),
               threading.Thread(target=scraper, daemon=True),
               threading.Thread(target=scraper, daemon=True)]
    for t in threads:
        t.start()
    threads[0].join(timeout=30)
    stop.set()
    for t in threads[1:]:
        t.join(timeout=10)
    assert not errs, f"scrape raced metric creation: {errs[0]!r}"


def test_histogram_concurrent_record_is_exact():
    """FIXED by this PR: Histogram had no lock — concurrent record() lost
    count/total updates and the reservoir raced summary()'s numpy read.
    With the lock, count/total are exact under contention."""
    from paddle_tpu.observability.metrics import Histogram

    h = Histogram(max_samples=256)
    N, T = 20000, 4
    errs = []

    def writer():
        try:
            for i in range(N):
                h.record(1.0)
                if i % 500 == 0:
                    h.summary()
        except Exception as e:
            errs.append(e)

    threads = [threading.Thread(target=writer) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs
    assert h.count == N * T
    assert h.total == float(N * T)
    assert h.summary()["count"] == N * T


def test_flight_recorder_concurrent_alarm_and_dump():
    """FIXED by this PR: FlightRecorder.__len__/alarm touched the ring and
    the frozen alarm snapshot without the lock."""
    from paddle_tpu.observability.serving_stall import FlightRecorder

    fr = FlightRecorder(max_steps=64)
    errs = []

    def stepper():
        try:
            for i in range(5000):
                fr.record_step(i=i)
                if i % 50 == 0:
                    fr.alarm("test", f"at {i}")
        except Exception as e:
            errs.append(e)

    def reader():
        try:
            for _ in range(2000):
                len(fr)
                fr.dump(last=8)
                _ = fr.last_alarm_dump
                _ = fr.steps_recorded
        except Exception as e:
            errs.append(e)

    threads = [threading.Thread(target=stepper),
               threading.Thread(target=reader),
               threading.Thread(target=reader)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs
    assert fr.steps_recorded == 5000
    assert fr.last_alarm_dump is not None
    assert fr.last_alarm_dump["kind"] == "test"


def test_request_tracer_get_concurrent_with_finish():
    """FIXED by this PR: RequestTracer.get() read the live/done dicts
    without the lock while finish() rebalanced them."""
    from paddle_tpu.observability.request_trace import RequestTracer

    tr = RequestTracer(enabled=True, max_completed=32)
    errs = []

    def lifecycle():
        try:
            for i in range(4000):
                tr.start(i)
                tr.finish(i)
        except Exception as e:
            errs.append(e)

    def getter():
        try:
            for i in range(8000):
                tr.get(i % 4000)
        except Exception as e:
            errs.append(e)

    threads = [threading.Thread(target=lifecycle),
               threading.Thread(target=getter)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs


def test_annotations_are_runtime_inert():
    from paddle_tpu.observability.annotations import (
        guarded_by,
        holds_lock,
        hot_path,
        lock_order,
        thread_role,
    )

    @hot_path
    def f():
        return 41

    @hot_path(reason="why")
    def g():
        return 42

    @holds_lock("_lock")
    def h():
        return 43

    @thread_role("drain")
    def k():
        return 44

    assert f() == 41 and g() == 42 and h() == 43 and k() == 44
    assert f.__graft_hot_path__ is True
    assert g.__graft_hot_path__ == "why"
    assert h.__graft_holds_lock__ == "_lock"
    assert k.__graft_thread_role__ == "drain"
    assert guarded_by("_lock").lock == "_lock"
    assert "guarded_by" in repr(guarded_by("_lock"))
    decl = lock_order("A._la", "<", "B._lb")
    assert decl.first == "A._la" and decl.second == "B._lb"
    with pytest.raises(ValueError):
        lock_order("A._la", ">", "B._lb")   # only "<" is a valid op


# ------------------------------------- concurrency checkers (PR: lint-conc)

def test_lock_order_cycle_bad_and_clean(tmp_path):
    """ABBA inversion across two methods is a lock-order cycle; a
    consistent nesting order is clean."""
    src = """
        import threading

        class Pair:
            def __init__(self):
                self._la = threading.Lock()
                self._lb = threading.Lock()

            def ab(self):
                with self._la:
                    with self._lb:
                        pass

            def ba(self):
                with self._lb:
                    with self._la:
                        pass
    """
    _write(tmp_path, "bad_cycle.py", src)
    report = _lint(tmp_path, rules=["lock-order"])
    hits = _rules_hit(report, "lock-order")
    assert hits, report["findings"]
    assert "cycle" in hits[0]["message"]
    inner_lines = [i + 1 for i, ln in
                   enumerate(textwrap.dedent(src).splitlines())
                   if ln.strip() in ("with self._lb:", "with self._la:")
                   and "    with" in ln[8:]]
    # the finding anchors at one of the two inner (second) acquisitions
    assert any(h["line"] in inner_lines for h in hits), (hits, inner_lines)

    (tmp_path / "bad_cycle.py").unlink()
    _write(tmp_path, "clean_order.py", """
        import threading

        class Pair:
            def __init__(self):
                self._la = threading.Lock()
                self._lb = threading.Lock()

            def ab(self):
                with self._la:
                    with self._lb:
                        pass

            def ab2(self):
                with self._la:
                    with self._lb:
                        pass
    """)
    report = _lint(tmp_path, rules=["lock-order"])
    assert not _rules_hit(report, "lock-order")


def test_lock_order_transitive_cycle_through_helper(tmp_path):
    """The inversion hides behind a call: f holds A and calls g, which
    takes B while another path nests B then A. The whole-program
    may-acquire propagation still finds the cycle."""
    _write(tmp_path, "transitive_cycle.py", """
        import threading

        class Pair:
            def __init__(self):
                self._la = threading.Lock()
                self._lb = threading.Lock()

            def f(self):
                with self._la:
                    self._takes_b()

            def _takes_b(self):
                with self._lb:
                    pass

            def ba(self):
                with self._lb:
                    with self._la:
                        pass
    """)
    report = _lint(tmp_path, rules=["lock-order"])
    hits = _rules_hit(report, "lock-order")
    assert hits and "cycle" in hits[0]["message"]


def test_lock_order_declaration_enforced(tmp_path):
    """A checked ``lock_order`` declaration: acquiring the declared-first
    lock while holding the declared-second one is a violation at the
    acquisition site; the compliant nesting is clean, and a declaration
    naming a lock that does not exist is itself a finding."""
    src = """
        import threading

        def lock_order(first, op, second):
            return (first, op, second)

        lock_order("Pair._la", "<", "Pair._lb")

        class Pair:
            def __init__(self):
                self._la = threading.Lock()
                self._lb = threading.Lock()

            def bad(self):
                with self._lb:
                    with self._la:
                        pass
    """
    _write(tmp_path, "decl_violation.py", src)
    report = _lint(tmp_path, rules=["lock-order"])
    hits = _rules_hit(report, "lock-order")
    assert hits, report["findings"]
    viol = [h for h in hits if "declared" in h["message"]
            or "lock_order" in h["message"]]
    assert viol
    bad_line = [i + 1 for i, ln in
                enumerate(textwrap.dedent(src).splitlines())
                if ln.strip() == "with self._la:"][0]
    assert any(h["line"] == bad_line for h in viol), (viol, bad_line)

    (tmp_path / "decl_violation.py").unlink()
    _write(tmp_path, "decl_clean.py", """
        import threading

        def lock_order(first, op, second):
            return (first, op, second)

        lock_order("Pair._la", "<", "Pair._lb")

        class Pair:
            def __init__(self):
                self._la = threading.Lock()
                self._lb = threading.Lock()

            def good(self):
                with self._la:
                    with self._lb:
                        pass
    """)
    report = _lint(tmp_path, rules=["lock-order"])
    assert not _rules_hit(report, "lock-order")

    (tmp_path / "decl_clean.py").unlink()
    _write(tmp_path, "decl_unknown.py", """
        def lock_order(first, op, second):
            return (first, op, second)

        lock_order("Ghost._lock", "<", "Phantom._lock")
    """)
    report = _lint(tmp_path, rules=["lock-order"])
    hits = _rules_hit(report, "lock-order")
    assert hits and "unknown lock" in hits[0]["message"]


def test_thread_role_two_role_write_bad_and_clean(tmp_path):
    """A spawn target writing an undeclared attribute with no lock held is
    the two-role write; the same write under the lock is clean."""
    src = """
        import threading

        class Worker:
            def __init__(self):
                self.state = 0
                self._lock = threading.Lock()
                self._t = threading.Thread(target=self._run, name="bg")
                self._t.start()

            def _run(self):
                self.state = 1
    """
    _write(tmp_path, "bad_roles.py", src)
    report = _lint(tmp_path, rules=["thread-role"])
    hits = _rules_hit(report, "thread-role")
    assert hits, report["findings"]
    bad_line = [i + 1 for i, ln in
                enumerate(textwrap.dedent(src).splitlines())
                if ln.strip() == "self.state = 1"][0]
    assert hits[0]["line"] == bad_line
    assert "'bg'" in hits[0]["message"]
    assert "guarded_by" in hits[0]["message"]

    (tmp_path / "bad_roles.py").unlink()
    _write(tmp_path, "clean_roles.py", """
        import threading

        class Worker:
            def __init__(self):
                self.state = 0
                self._lock = threading.Lock()
                self._t = threading.Thread(target=self._run, name="bg")
                self._t.start()

            def _run(self):
                with self._lock:
                    self.state = 1
    """)
    report = _lint(tmp_path, rules=["thread-role"])
    assert not _rules_hit(report, "thread-role")


def test_thread_role_propagates_through_calls(tmp_path):
    """The write sits two calls below the spawn target; role reachability
    still tags it."""
    _write(tmp_path, "deep_roles.py", """
        import threading

        class Worker:
            def __init__(self):
                self.n = 0
                self._t = threading.Thread(target=self._run, name="drain")
                self._t.start()

            def _run(self):
                self._step()

            def _step(self):
                self.n += 1
    """)
    report = _lint(tmp_path, rules=["thread-role"])
    hits = _rules_hit(report, "thread-role")
    assert hits and "`self.n`" in hits[0]["message"]
    assert "'drain'" in hits[0]["message"]


def test_blocking_under_lock_bad_and_clean(tmp_path):
    """sleep/join/queue-get under a held lock is flagged at the blocking
    call; bounded waits and metered stalls escape."""
    src = """
        import queue
        import threading
        import time

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()
                self._t = threading.Thread(target=self._run)

            def _run(self):
                pass

            def bad_sleep(self):
                with self._lock:
                    time.sleep(0.1)

            def bad_join(self):
                with self._lock:
                    self._t.join()

            def bad_queue(self):
                with self._lock:
                    return self._q.get()
    """
    _write(tmp_path, "bad_blocking.py", src)
    report = _lint(tmp_path, rules=["blocking-under-lock"])
    hits = _rules_hit(report, "blocking-under-lock")
    lines = textwrap.dedent(src).splitlines()
    for needle in ("time.sleep(0.1)", "self._t.join()",
                   "return self._q.get()"):
        ln = [i + 1 for i, s in enumerate(lines) if s.strip() == needle][0]
        assert any(h["line"] == ln for h in hits), (needle, hits)
    assert all("Box._lock" in h["message"] for h in hits)

    (tmp_path / "bad_blocking.py").unlink()
    _write(tmp_path, "clean_blocking.py", """
        import queue
        import threading
        import time

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()
                self._t = threading.Thread(target=self._run)
                self.stall = None

            def _run(self):
                pass

            def sleep_outside(self):
                with self._lock:
                    n = 1
                time.sleep(0.1)
                return n

            def bounded_join(self):
                with self._lock:
                    self._t.join(timeout=1.0)

            def bounded_queue(self):
                with self._lock:
                    return self._q.get(timeout=0.5)

            def metered(self):
                with self._lock:
                    with self.stall.timed("drain"):
                        time.sleep(0.1)
    """)
    report = _lint(tmp_path, rules=["blocking-under-lock"])
    assert not _rules_hit(report, "blocking-under-lock")


def test_blocking_under_lock_transitive_through_helper(tmp_path):
    """The sleep hides in a helper; the lock-held call site is flagged
    with the chain to the origin."""
    src = """
        import threading
        import time

        class Box:
            def __init__(self):
                self._lock = threading.Lock()

            def caller(self):
                with self._lock:
                    self._nap()

            def _nap(self):
                time.sleep(0.5)
    """
    _write(tmp_path, "transitive_block.py", src)
    report = _lint(tmp_path, rules=["blocking-under-lock"])
    hits = _rules_hit(report, "blocking-under-lock")
    assert hits, report["findings"]
    call_line = [i + 1 for i, ln in
                 enumerate(textwrap.dedent(src).splitlines())
                 if ln.strip() == "self._nap()"][0]
    assert hits[0]["line"] == call_line
    assert "may block" in hits[0]["message"]
    assert "_nap" in hits[0]["message"]


def test_condition_wait_on_held_lock_is_not_blocking(tmp_path):
    """``cond.wait()`` on the lock you hold RELEASES it while sleeping —
    the scheduler's backoff idiom must stay clean."""
    _write(tmp_path, "cond_wait.py", """
        import threading

        class Engine:
            def __init__(self):
                self._elock = threading.Condition(threading.RLock())

            def backoff(self):
                with self._elock:
                    self._elock.wait(0.2)
    """)
    report = _lint(tmp_path, rules=["blocking-under-lock"])
    assert not _rules_hit(report, "blocking-under-lock")


def test_stale_suppression_audit(tmp_path):
    """A ``disable`` comment that silences nothing is flagged; one that
    suppresses a real finding is not; a docstring that merely MENTIONS
    the directive syntax is not audited."""
    src = '''
        """Module doc. Example: # graft-lint: disable=guarded-by inline."""
        import threading

        def guarded_by(lock):
            return lock

        class A:
            _x: guarded_by("_lock")

            def __init__(self):
                self._lock = threading.Lock()
                self._x = 0

            def bad(self):
                self._x = 1  # graft-lint: disable=guarded-by

            def fine(self):
                return 2  # graft-lint: disable=guarded-by
    '''
    _write(tmp_path, "stale.py", src)
    report = _lint(tmp_path)          # full run: the audit is active
    stale = _rules_hit(report, "stale-suppression")
    assert len(stale) == 1, report["findings"]
    dead_line = [i + 1 for i, ln in
                 enumerate(textwrap.dedent(src).splitlines())
                 if "return 2" in ln][0]
    assert stale[0]["line"] == dead_line
    assert "matches no finding" in stale[0]["message"]
    # the used suppression still works: no unsuppressed guarded-by finding
    assert not _rules_hit(report, "guarded-by")


def test_stale_audit_skipped_on_partial_runs(tmp_path):
    """``disable=all`` can only be audited when every rule ran; a rules
    subset must not flag it."""
    _write(tmp_path, "partial.py", """
        def f():
            return 1  # graft-lint: disable=all
    """)
    report = _lint(tmp_path, rules=["guarded-by"])
    assert not _rules_hit(report, "stale-suppression")
    report = _lint(tmp_path)
    assert len(_rules_hit(report, "stale-suppression")) == 1


def test_rules_concurrency_group_alias(tmp_path):
    """--rules concurrency expands to the four concurrency rules."""
    from tools.graft_lint import RULE_GROUPS, expand_rules

    _write(tmp_path, "empty.py", "x = 1\n")
    report = _lint(tmp_path, rules=["concurrency"])
    assert set(report["rules"]) == {"lock-order", "thread-role",
                                    "blocking-under-lock", "guarded-by"}
    assert report["audits"] == []     # the audit needs a full run
    assert expand_rules(["concurrency", "guarded-by"]) \
        == list(RULE_GROUPS["concurrency"])
    assert expand_rules(None) is None


def test_lint_seeded_concurrency_bad_constructs(tmp_path):
    """Acceptance direction 2 for the new checkers, through the real
    driver: a seeded sleep-under-lock, an undeclared two-role write, and
    a lock-order inversion exit non-zero with correct file:line."""
    src = textwrap.dedent("""
        import threading
        import time

        class Bad:
            def __init__(self):
                self.count = 0
                self._la = threading.Lock()
                self._lb = threading.Lock()
                self._t = threading.Thread(target=self._drain, name="drain")

            def _drain(self):
                self.count += 1

            def sleepy(self):
                with self._la:
                    time.sleep(0.1)

            def ab(self):
                with self._la:
                    with self._lb:
                        pass

            def ba(self):
                with self._lb:
                    with self._la:
                        pass
    """)
    bad = tmp_path / "bad_conc.py"
    bad.write_text(src)
    lines = src.splitlines()
    write_line = lines.index("        self.count += 1") + 1
    sleep_line = lines.index("            time.sleep(0.1)") + 1
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         "--root", str(tmp_path), "--rules", "concurrency",
         "--baseline", str(tmp_path / "bl.json")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert f"bad_conc.py:{write_line}" in r.stdout     # thread-role
    assert f"bad_conc.py:{sleep_line}" in r.stdout     # blocking-under-lock
    assert "[thread-role]" in r.stdout
    assert "[blocking-under-lock]" in r.stdout
    assert "[lock-order]" in r.stdout


# ---------------------------- regressions from the concurrency-rule triage

def _rpc_double(x):
    return x * 2


class _FakeKV:
    """In-memory TCPStore lookalike for driving _RpcAgent in-process."""

    def __init__(self):
        self._d = {}
        self._lock = threading.Lock()

    def set(self, k, v):
        with self._lock:
            self._d[k] = v

    def get(self, k):
        with self._lock:
            return self._d[k]

    def check(self, k):
        with self._lock:
            return k in self._d

    def delete_key(self, k):
        with self._lock:
            self._d.pop(k, None)

    def add(self, k, n):
        with self._lock:
            v = int(self._d.get(k, 0)) + n
            self._d[k] = v
            return v

    def wait(self, k):
        pass


def test_rpc_future_table_locked_handoff_regression():
    """FIXED by this PR (found by the thread-role rule): ``_RpcAgent``'s
    outstanding-call table was inserted by caller threads and swept by
    the poller with NO lock — a caller's dict insert racing the poller's
    iteration killed the poll thread with RuntimeError and every future
    after it timed out. Hammer both sides through a self-call loop."""
    from paddle_tpu.distributed.rpc import _RpcAgent

    agent = _RpcAgent("w0", 0, 1, _FakeKV())
    try:
        results, errs = {}, []

        def caller(base):
            try:
                futs = [(base + i,
                         agent.call(0, _rpc_double, (base + i,), {}))
                        for i in range(25)]
                for x, fut in futs:
                    results[x] = fut.wait(timeout=60)
            except Exception as e:
                errs.append(e)

        threads = [threading.Thread(target=caller, args=(1000 * t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errs, errs[0]
        assert len(results) == 100
        assert all(results[x] == 2 * x for x in results)
        with agent._flock:
            assert not agent._futures   # every future swept exactly once
    finally:
        agent.shutdown()


def test_sparse_table_save_is_consistent_snapshot_regression(tmp_path):
    """FIXED by this PR (found by the blocking-under-lock rule):
    ``MemorySparseTable.save`` pickled to disk while HOLDING the table
    lock, stalling every pull/push for the file I/O. It now snapshots
    row COPIES under the lock and serialises outside — saves racing
    in-place row mutation must load back complete, well-formed tables."""
    import pickle

    import numpy as np

    from paddle_tpu.distributed.ps import MemorySparseTable

    table = MemorySparseTable(0, dim=4)
    stop = threading.Event()
    errs = []

    def pusher():
        try:
            i = 0
            while not stop.is_set():
                ids = np.arange(32) + (i % 8) * 32
                table.pull(ids)
                grads = np.full((32, 4), 0.01, np.float32)
                table.push(ids, grads)
                i += 1
        except Exception as e:
            errs.append(e)

    threads = [threading.Thread(target=pusher, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    try:
        path = str(tmp_path / "table.pkl")
        for _ in range(10):
            table.save(path)
            with open(path, "rb") as f:
                rows = pickle.load(f)
            assert rows              # snapshot is complete + parseable
            for k, v in rows.items():
                assert isinstance(k, int)
                row = np.asarray(v, np.float32)
                assert row.ndim == 1 and np.isfinite(row).all()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errs, errs[0]
