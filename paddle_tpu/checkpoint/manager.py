"""CheckpointManager — crash-safe checkpoint lifecycle over the shard writer.

Commit protocol (per save, in order):

1. plan + snapshot: device shards are copied to host synchronously
   (``_plan_writes``), so training may keep going the moment planning ends;
2. write: shards + metadata fragments stream into ``step_N.tmp`` with
   per-file fsync (async mode does this on a background writer thread);
3. manifest: per-file sizes + crc32 checksums into ``MANIFEST.json``, fsynced;
4. atomic rename ``step_N.tmp`` -> ``step_N`` + parent-dir fsync;
5. ``COMMITTED`` marker, fsynced.

A kill at ANY instant leaves either (a) a ``*.tmp`` dir ``latest()`` never
looks at, or (b) a renamed dir without the marker — skipped too. The
previous commit stays intact and discoverable. Bit corruption is caught by
``latest(verify=...)`` re-checksumming against the manifest and falling
back to the previous commit.

Backpressure: one save may be in flight; the next ``save`` first joins the
writer and records the wait as ``checkpoint_backpressure_stall_seconds``:
the stall a save costs the train loop.
"""

from __future__ import annotations

import atexit
import os
import re
import shutil
import threading
import time
import weakref
from typing import Dict, List, NamedTuple, Optional

from paddle_tpu.checkpoint import manifest as mf
from paddle_tpu.checkpoint import state as st
from paddle_tpu.observability.annotations import (guarded_by, lock_order,
                                                  thread_role)
from paddle_tpu.resilience import inject

_STEP_RE = re.compile(r"^step_(\d+)$")

# Checked by graft_lint (lock-order): the writer-handoff lock is a leaf —
# held only for the three-field swap, never while recording metrics (the
# scrape thread holds metric locks; nesting the other way would let a slow
# scrape stall every save()/wait() handoff).
lock_order("Counter._lock", "<", "CheckpointManager._state_lock")
lock_order("Histogram._lock", "<", "CheckpointManager._state_lock")
_TMP_SUFFIX = ".tmp"


class SimulatedCrash(RuntimeError):
    """Raised by the fault-injection hook (tests only): abandons the save at
    a chosen protocol point, leaving exactly the on-disk state a kill -9
    would."""


class CheckpointInfo(NamedTuple):
    step: int
    path: str


class RestoreResult(NamedTuple):
    step: int
    path: str
    extra: Dict


_managers: "weakref.WeakSet[CheckpointManager]" = weakref.WeakSet()


def _flush_all_managers():
    for m in list(_managers):
        try:
            m.wait()
        except SimulatedCrash:
            pass
        # graft-lint: disable-next=swallowed-exception (interpreter exit
        # path: a failed flush must not turn shutdown into a crash loop)
        except Exception:
            pass


atexit.register(_flush_all_managers)


class CheckpointManager:
    """Lifecycle manager for one checkpoint root directory.

    ``keep_last_n``: retain the newest N commits (0 = keep all).
    ``keep_every_k``: additionally retain every commit whose step is a
    multiple of K forever (0 = none) — the "weekly archive" knob.

    Thread contract: the async writer thread and the caller hand off
    through three fields — the writer handle, its failure, and the
    in-flight tmp dir (``gc()`` runs ON the writer thread while the caller
    may be planning the next save) — all guarded by ``_state_lock``.
    """

    _writer: guarded_by("_state_lock")
    _writer_err: guarded_by("_state_lock")
    _active_tmp: guarded_by("_state_lock")

    def __init__(self, root: str, keep_last_n: int = 3, keep_every_k: int = 0,
                 registry=None):
        from paddle_tpu.observability import get_registry

        self.root = str(root)
        self.keep_last_n = int(keep_last_n)
        self.keep_every_k = int(keep_every_k)
        os.makedirs(self.root, exist_ok=True)
        reg = registry if registry is not None else get_registry()
        self._m_saves = reg.counter(
            "checkpoint_saves_total", "save() calls issued")
        self._m_commits = reg.counter(
            "checkpoint_commits_total", "checkpoints fully committed")
        self._m_restores = reg.counter(
            "checkpoint_restores_total", "restore() calls completed")
        self._m_corrupt = reg.counter(
            "checkpoint_corrupt_skipped_total",
            "torn/corrupt checkpoints skipped by latest()")
        self._m_gc = reg.counter(
            "checkpoint_gc_removed_total", "checkpoints removed by retention")
        self._m_bytes = reg.counter(
            "checkpoint_bytes_written_total", "shard bytes written", "bytes")
        self._m_save_s = reg.histogram(
            "checkpoint_save_seconds", "snapshot+write+commit wall", "s")
        self._m_snap_s = reg.histogram(
            "checkpoint_snapshot_seconds",
            "device->host snapshot wall (the train-step stall)", "s")
        self._m_stall_s = reg.histogram(
            "checkpoint_backpressure_stall_seconds",
            "save() wait on a prior in-flight save", "s")
        self._m_restore_s = reg.histogram(
            "checkpoint_restore_seconds", "restore wall", "s")
        self._state_lock = threading.Lock()
        self._writer: Optional[threading.Thread] = None
        self._writer_err: Optional[BaseException] = None
        self._active_tmp: Optional[str] = None  # in-flight writer's dir
        self._fail_point: Optional[str] = None  # fault injection (tests)
        _managers.add(self)

    # ------------------------------------------------------------ discovery
    def step_dir(self, step: int) -> str:
        return os.path.join(self.root, f"step_{int(step)}")

    def all_steps(self, committed_only: bool = True) -> List[int]:
        steps = []
        try:
            entries = os.listdir(self.root)
        except FileNotFoundError:
            return []
        for name in entries:
            m = _STEP_RE.match(name)
            if not m:
                continue
            d = os.path.join(self.root, name)
            if not os.path.isdir(d):
                continue
            if committed_only and not mf.is_committed(d):
                continue
            steps.append(int(m.group(1)))
        return sorted(steps)

    def latest(self, verify: str | bool = "full") -> Optional[CheckpointInfo]:
        """Newest COMMITTED checkpoint that passes integrity verification,
        falling back step by step past torn/corrupt ones.

        ``verify``: ``"full"`` (crc32, catches bit-flips), ``"quick"``
        (existence+size), or False (trust the marker)."""
        level = "full" if verify is True else verify
        for step in reversed(self.all_steps()):
            d = self.step_dir(step)
            if not level:
                return CheckpointInfo(step, d)
            ok, problems = mf.verify_dir(d, level=level)
            if ok:
                return CheckpointInfo(step, d)
            self._m_corrupt.inc()
            import warnings

            warnings.warn(
                f"checkpoint step_{step} failed verification "
                f"({problems[0]}{'...' if len(problems) > 1 else ''}); "
                "falling back to the previous commit")
        return None

    # ----------------------------------------------------------------- save
    def save(self, step: int, model=None, optimizer=None, train_step=None,
             dataloader=None, state: Optional[Dict] = None,
             extra: Optional[Dict] = None, async_save: bool = False) -> str:
        """Checkpoint full train state at ``step``. Returns the final
        (post-commit) directory path.

        Sync mode blocks through the commit. Async mode returns after the
        device->host snapshot; shards stream from a background writer and
        commit there. At most one save is in flight: a second ``save`` (or
        ``wait()``/process exit) joins it first — backpressure, recorded as
        stall time."""
        from paddle_tpu.distributed.checkpoint import (
            _plan_writes,
            _process_index,
        )
        from paddle_tpu.profiler import RecordEvent, TracerEventType

        self._m_saves.inc()
        t_stall = time.perf_counter()
        self.wait()  # backpressure: never two writers on one root
        stall = time.perf_counter() - t_stall
        if stall > 1e-4:
            self._m_stall_s.observe(stall)

        t0 = time.perf_counter()
        step = int(step)
        tmp = self.step_dir(step) + _TMP_SUFFIX
        final = self.step_dir(step)
        for d in (tmp, final):  # re-saving a step replaces it wholesale
            if os.path.isdir(d):
                shutil.rmtree(d)
        os.makedirs(tmp)

        with RecordEvent("checkpoint.snapshot", TracerEventType.UserDefined):
            tree, extra_json = st.capture_state(
                step, model=model, optimizer=optimizer,
                train_step=train_step, dataloader=dataloader, state=state,
                extra=extra)
            writes, md = _plan_writes(tree, tmp)
        snap_s = time.perf_counter() - t0
        self._m_snap_s.observe(snap_s)
        pidx = _process_index()

        # account the snapshot staging copies for as long as the writer
        # holds them (async: until the background commit releases)
        from paddle_tpu.observability.device_memory import (
            get_device_ledger,
            tree_nbytes,
        )
        staging = get_device_ledger().register(
            "checkpoint_staging", f"step{step}", tree_nbytes(tree))

        with self._state_lock:
            self._active_tmp = tmp

        def _write_and_commit():
            try:
                self._write_and_commit(tmp, final, step, writes, md,
                                       extra_json, pidx, t0)
            finally:
                staging.release()
                with self._state_lock:
                    self._active_tmp = None

        if async_save:
            @thread_role("ckpt-writer")
            def guarded():
                try:
                    _write_and_commit()
                except BaseException as e:
                    with self._state_lock:
                        self._writer_err = e

            t = threading.Thread(target=guarded, daemon=True,
                                 name=f"ckpt-writer-step{step}")
            t.start()
            with self._state_lock:
                self._writer = t
        else:
            _write_and_commit()
        return final

    def _write_and_commit(self, tmp, final, step, writes, md, extra_json,
                          pidx, t0):
        from paddle_tpu.distributed.checkpoint import _write_files
        from paddle_tpu.profiler import RecordEvent, TracerEventType

        with RecordEvent("checkpoint.write", TracerEventType.UserDefined):
            n_bytes = _write_files(tmp, writes, md, pidx, fsync=True)
            st.write_extra(tmp, extra_json)
            self._m_bytes.inc(n_bytes)
        self._maybe_fail("before_commit")  # shards written, nothing visible
        with RecordEvent("checkpoint.commit", TracerEventType.UserDefined):
            # seeded chaos hooks mirroring _maybe_fail's fixed points: a
            # FaultPlan can kill the manifest write or the atomic rename
            inject("ckpt.manifest_write")
            mf.write_manifest(tmp, mf.build_manifest(tmp, step))
            mf.fsync_dir(tmp)
            inject("ckpt.rename")
            os.rename(tmp, final)
            mf.fsync_dir(self.root)
            self._maybe_fail("before_marker")  # renamed but not committed
            mf.mark_committed(final, step)
        self._m_commits.inc()
        self._m_save_s.observe(time.perf_counter() - t0)
        self.gc()

    def wait(self) -> None:
        """Join the in-flight async writer; re-raise its failure, if any."""
        with self._state_lock:
            t, self._writer = self._writer, None
        if t is not None:
            t.join()            # never joins while holding the state lock
        with self._state_lock:
            err, self._writer_err = self._writer_err, None
        if err is not None:
            raise err

    # -------------------------------------------------------------- restore
    def restore(self, step: Optional[int] = None, model=None, optimizer=None,
                train_step=None, dataloader=None, state: Optional[Dict] = None,
                verify: str | bool = "full",
                restore_rng: Optional[bool] = None) -> RestoreResult:
        """Load full train state back into the given objects.

        With ``step=None`` auto-resumes from ``latest()`` (checksum-verified,
        falls back past torn commits). Raises ``FileNotFoundError`` when no
        usable checkpoint exists."""
        from paddle_tpu.profiler import RecordEvent, TracerEventType

        self.wait()
        if step is None:
            info = self.latest(verify=verify)
            if info is None:
                raise FileNotFoundError(
                    f"no committed checkpoint under {self.root}")
        else:
            d = self.step_dir(int(step))
            if not mf.is_committed(d):
                raise FileNotFoundError(f"step_{step} is not committed")
            info = CheckpointInfo(int(step), d)
        t0 = time.perf_counter()
        with RecordEvent("checkpoint.restore", TracerEventType.UserDefined):
            extra = st.restore_state(
                info.path, model=model, optimizer=optimizer,
                train_step=train_step, dataloader=dataloader, state=state,
                restore_rng=restore_rng)
        self._m_restores.inc()
        self._m_restore_s.observe(time.perf_counter() - t0)
        return RestoreResult(info.step, info.path, extra)

    # ------------------------------------------------------------ retention
    def gc(self) -> List[int]:
        """Apply keep-last-N + keep-every-K retention; also sweep orphaned
        ``*.tmp`` dirs and torn (renamed-but-unmarked) step dirs that are no
        longer the newest entry. Returns removed steps."""
        removed: List[int] = []
        committed = self.all_steps()
        keep = set(committed if self.keep_last_n <= 0
                   else committed[-self.keep_last_n:])
        if self.keep_every_k > 0:
            keep.update(s for s in committed if s % self.keep_every_k == 0)
        for s in committed:
            if s not in keep:
                shutil.rmtree(self.step_dir(s), ignore_errors=True)
                removed.append(s)
                self._m_gc.inc()
        newest = committed[-1] if committed else None
        with self._state_lock:
            active_tmp = self._active_tmp
        for name in os.listdir(self.root):
            d = os.path.join(self.root, name)
            if name.endswith(_TMP_SUFFIX) and os.path.isdir(d):
                if d == active_tmp:
                    continue  # an in-flight async writer owns this dir
                shutil.rmtree(d, ignore_errors=True)
                continue
            m = _STEP_RE.match(name)
            if m and os.path.isdir(d) and not mf.is_committed(d):
                # torn: renamed but never marked; keep only if newest overall
                # so post-mortem inspection is possible, sweep otherwise
                if newest is not None and int(m.group(1)) <= newest:
                    shutil.rmtree(d, ignore_errors=True)
        return removed

    # ------------------------------------------------------ fault injection
    def _maybe_fail(self, point: str):
        if self._fail_point == point:
            self._fail_point = None
            raise SimulatedCrash(f"injected crash at {point!r}")

    def summary(self) -> Dict:
        steps = self.all_steps()
        return {"root": self.root, "committed_steps": steps,
                "latest": steps[-1] if steps else None,
                "keep_last_n": self.keep_last_n,
                "keep_every_k": self.keep_every_k}

    def __repr__(self):
        return (f"CheckpointManager(root={self.root!r}, "
                f"committed={self.all_steps()})")
