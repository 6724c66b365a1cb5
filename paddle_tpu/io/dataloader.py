"""DataLoader (parity: python/paddle/io/reader.py:216 + dataloader/worker.py).

The reference forks worker *processes* and ships samples back through
shared-memory (mmap_allocator.cc) because CUDA + Python GIL make in-process
loading slow. On TPU the device transfer is the cost; numpy collation releases
the GIL, so worker *threads* + a bounded prefetch queue give the same overlap
without IPC. The optional C++ packing core (paddle_tpu/lib/libpt_dataloader)
accelerates batch assembly for large samples.

``DevicePrefetcher`` is the last pipeline stage: it overlaps the
host->device transfer itself with the training step (the workers above only
overlap host-side fetch/collate), so a zero-stall loop reads device-resident
batches off a queue.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from paddle_tpu.io.dataset import Dataset, IterableDataset
from paddle_tpu.io.sampler import BatchSampler
from paddle_tpu.observability.annotations import hot_path, thread_role
from paddle_tpu.tensor import Tensor


def default_collate_fn(batch):
    """Stack samples into batch Tensors (parity: dataloader/collate.py)."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return Tensor._from_value(jnp.stack([s._value for s in batch]))
    if isinstance(sample, np.ndarray):
        return Tensor._from_value(jnp.asarray(np.stack(batch)))
    if isinstance(sample, (int, np.integer)):
        return Tensor._from_value(jnp.asarray(np.asarray(batch, np.int64)))
    if isinstance(sample, (float, np.floating)):
        return Tensor._from_value(jnp.asarray(np.asarray(batch, np.float32)))
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return [default_collate_fn(list(group)) for group in transposed]
    if isinstance(sample, dict):
        return {k: default_collate_fn([s[k] for s in batch]) for k in sample}
    if isinstance(sample, (str, bytes)):
        return list(batch)
    raise TypeError(f"cannot collate type {type(sample)}")


class _SentinelType:
    pass


_END = _SentinelType()


class _PrefetchError:
    def __init__(self, exc: BaseException):
        self.exc = exc


class DevicePrefetcher:
    """Double-buffered device prefetch stage over any batch iterable.

    A background thread pulls batches from ``loader`` and dispatches their
    host->device transfer (``jax.device_put`` with the step's input
    ``sharding``) while the current step computes, keeping up to ``depth``
    device-resident batches queued. The consumer's ``next()`` becomes a
    queue pop, and the wait it does pay is recorded as
    ``train_input_stall_seconds`` — the input-bound share of the loop.

    ``depth=0`` is the single-buffered reference path: no thread, the
    fetch+transfer runs inline on the consumer (and is charged to the same
    stall metric): what the overlap is compared with.

    ``sharding`` is ``None`` (commit to the default device), a
    ``jax.sharding.Sharding`` applied to every array leaf, or a callable
    ``leaf_value -> sharding-or-None`` (per-leaf placement, e.g. batch-axis
    sharding only for leaves whose leading dim divides).

    Checkpointing: ``state_dict()`` counts batches the CONSUMER took, not
    batches pulled into the buffer, so a mid-epoch save/resume replays the
    identical sequence with no off-by-``depth`` skip. Single consumer per
    prefetcher.
    """

    def __init__(self, loader, depth: int = 2, sharding=None):
        self.loader = loader
        self.depth = max(int(depth), 0)
        self.sharding = sharding
        inner_state = getattr(loader, "state_dict", None)
        st = inner_state() if callable(inner_state) else {}
        self._epoch = int(st.get("epoch", 0))
        self._consumed = int(st.get("offset", 0))
        # wrapping an already-resumed loader keeps its mid-epoch cursor
        self._resumed = self._consumed > 0

    # ------------------------------------------------------- checkpointing
    def state_dict(self):
        return {"epoch": int(self._epoch), "offset": int(self._consumed)}

    def set_state_dict(self, state):
        self._epoch = int(state.get("epoch", 0))
        self._consumed = int(state.get("offset", 0))
        self._resumed = True
        inner = getattr(self.loader, "set_state_dict", None)
        if callable(inner):
            inner(state)

    def __len__(self):
        return len(self.loader)

    # ------------------------------------------------------------ transfer
    def _to_device(self, batch):
        import jax

        def put(v):
            val = v._value if isinstance(v, Tensor) else v
            if not hasattr(val, "shape"):
                return v
            sh = self.sharding(val) if callable(self.sharding) \
                else self.sharding
            out = jax.device_put(val, sh) if sh is not None \
                else jax.device_put(val)
            return Tensor._from_value(out) if isinstance(v, Tensor) \
                else out
        return jax.tree_util.tree_map(
            put, batch, is_leaf=lambda x: isinstance(x, Tensor))

    # ---------------------------------------------------------------- iter
    @hot_path(reason="the zero-stall loop's input side: consumer pop + "
                     "producer H2D dispatch")
    def __iter__(self):
        from paddle_tpu.observability.train_stall import (
            prefetched_batches_counter,
            record_input_stall,
        )
        from paddle_tpu.profiler import RecordEvent, TracerEventType

        from paddle_tpu.observability.device_memory import (
            get_device_ledger,
            tree_nbytes,
        )

        if self._resumed:
            self._resumed = False  # a resume keeps its mid-epoch cursor
        else:
            self._consumed = 0  # fresh epoch (mirrors DataLoader.__iter__)

        # device-ledger accounting: the prefetch stage owns up to
        # depth queued + 1 in-hand device-resident batches. Sized once
        # from the first transferred batch, released when the iterator
        # winds down — nothing per-batch beyond an `is None` check.
        ledger_handle = None

        def _account(out):
            nonlocal ledger_handle
            if ledger_handle is None:
                ledger_handle = get_device_ledger().register(
                    "prefetch_buffers", "DevicePrefetcher",
                    tree_nbytes(out) * (self.depth + 1))

        if self.depth == 0:
            # inline single-buffered path: transfer on the consumer, fully
            # exposed — the stall metric shows what prefetch removes
            try:
                for batch in self.loader:
                    t0 = time.perf_counter()
                    out = self._to_device(batch)
                    record_input_stall(time.perf_counter() - t0)
                    _account(out)
                    self._consumed += 1
                    yield out
                self._epoch += 1
                self._consumed = 0
            finally:
                if ledger_handle is not None:
                    ledger_handle.release()
            return

        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()

        @thread_role("prefetch-producer")
        def producer():
            try:
                for batch in self.loader:
                    with RecordEvent("train.prefetch",
                                     TracerEventType.Dataloader):
                        out = self._to_device(batch)
                    prefetched_batches_counter().inc()
                    while not stop.is_set():
                        try:
                            q.put(out, timeout=0.2)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return
                q.put(_END)
            except BaseException as e:  # surface in the consumer
                q.put(_PrefetchError(e))

        t = threading.Thread(target=producer, daemon=True,
                             name="DevicePrefetcher")
        t.start()
        try:
            while True:
                t0 = time.perf_counter()
                item = q.get()
                record_input_stall(time.perf_counter() - t0)
                if item is _END:
                    self._epoch += 1
                    self._consumed = 0
                    return
                if isinstance(item, _PrefetchError):
                    raise item.exc
                _account(item)
                self._consumed += 1
                yield item
        finally:
            if ledger_handle is not None:
                ledger_handle.release()
            stop.set()
            # unblock a producer stuck on a full queue
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None, return_list=True,
                 batch_sampler=None, batch_size=1, shuffle=False, drop_last=False,
                 collate_fn=None, num_workers=0, use_buffer_reader=True,
                 prefetch_factor=2, use_shared_memory=True, timeout=0,
                 worker_init_fn=None, persistent_workers=False,
                 use_process_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.use_process_workers = use_process_workers
        self.use_shared_memory = use_shared_memory
        self.prefetch_factor = max(prefetch_factor, 1)
        self.worker_init_fn = worker_init_fn
        self.timeout = timeout
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            if batch_size is None:
                self.batch_sampler = None
                self.batch_size = None
            else:
                self.batch_sampler = BatchSampler(
                    dataset, shuffle=shuffle, batch_size=batch_size,
                    drop_last=drop_last,
                )
        # checkpointable cursor: epoch count + batches consumed this epoch
        self._epoch = 0
        self._offset = 0
        self._resume_skip = 0

    # ------------------------------------------------------- checkpointing
    def state_dict(self):
        """Data position for full-train-state checkpoints: completed epochs
        + batches consumed in the current one."""
        return {"epoch": int(self._epoch), "offset": int(self._offset)}

    def set_state_dict(self, state):
        """Resume mid-epoch: the next ``__iter__`` skips ``offset`` batches
        (indices are drawn but samples aren't materialized on the sync path)
        so the stream continues where the checkpoint left off."""
        self._epoch = int(state.get("epoch", 0))
        self._offset = int(state.get("offset", 0))
        self._resume_skip = self._offset

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no len()")
        if self.batch_sampler is None:
            return len(self.dataset)
        return len(self.batch_sampler)

    # ------------------------------------------------------------------ iter
    def __iter__(self):
        skip = self._resume_skip
        self._resume_skip = 0
        if not skip:
            self._offset = 0  # fresh epoch; a resume keeps its cursor
        if self._iterable_mode:
            inner = self._iter_iterable()
        elif self.num_workers > 0 and self.use_process_workers:
            inner = self._iter_process()
        elif self.num_workers > 0:
            inner = self._iter_threaded()
        else:
            inner = self._iter_sync(skip)
            skip = 0  # sync path skips on indices, without fetching
        # worker paths: drain the already-consumed prefix (fetched but
        # discarded — resume correctness over warm-up cost)
        while skip > 0:
            try:
                next(inner)
            except StopIteration:
                self._epoch += 1
                self._offset = 0
                return
            skip -= 1
        # dataloader.next spans: the time the CONSUMER waits for each batch
        # (fetch+collate inline, or queue wait under workers) — the
        # input-bound share of a training step in a Profiler run
        from paddle_tpu.profiler import RecordEvent, TracerEventType

        while True:
            with RecordEvent("dataloader.next", TracerEventType.Dataloader):
                try:
                    batch = next(inner)
                except StopIteration:
                    self._epoch += 1
                    self._offset = 0
                    return
            self._offset += 1
            yield batch

    def _fetch(self, batch_indices):
        samples = [self.dataset[i] for i in batch_indices]
        return self.collate_fn(samples)

    def _iter_sync(self, skip: int = 0):
        if self.batch_sampler is None:
            for i in range(skip, len(self.dataset)):
                yield self.dataset[i]
            return
        for batch_indices in itertools.islice(self.batch_sampler, skip, None):
            yield self._fetch(batch_indices)

    def _iter_iterable(self):
        it = iter(self.dataset)
        if self.batch_size is None:
            yield from it
            return
        while True:
            batch = list(itertools.islice(it, self.batch_size))
            if not batch:
                return
            if len(batch) < self.batch_size and self.drop_last:
                return
            yield self.collate_fn(batch)

    def _iter_threaded(self):
        """Ordered thread-pool pipeline with bounded prefetch.

        The prefetch bound is on *index distance from the consumer cursor*
        (idx < cursor + depth), never on buffer occupancy — an occupancy bound
        can live-lock when the worker holding the next-needed batch is the one
        being throttled.
        """
        batches = list(self.batch_sampler)
        depth = max(self.num_workers * self.prefetch_factor, 1)
        results: dict = {}
        cond = threading.Condition()
        cursor = [0]  # next index the consumer will take
        stop = [False]
        task_q: "queue.Queue" = queue.Queue()
        for i, b in enumerate(batches):
            task_q.put((i, b))
        for _ in range(self.num_workers):
            task_q.put(None)

        @thread_role("loader-worker")
        def worker(worker_id):
            from paddle_tpu.io import WorkerInfo, _set_worker_info

            _set_worker_info(WorkerInfo(worker_id, self.num_workers,
                                        self.dataset))
            if self.worker_init_fn is not None:
                self.worker_init_fn(worker_id)
            while True:
                item = task_q.get()
                if item is None:
                    return
                idx, b = item
                with cond:
                    while idx >= cursor[0] + depth and not stop[0]:
                        cond.wait(timeout=0.5)
                    if stop[0]:
                        return
                try:
                    out = self._fetch(b)
                except BaseException as e:  # propagate to consumer
                    out = e
                with cond:
                    results[idx] = out
                    cond.notify_all()

        threads = [
            threading.Thread(target=worker, args=(w,), daemon=True)
            for w in range(self.num_workers)
        ]
        for t in threads:
            t.start()
        try:
            for i in range(len(batches)):
                with cond:
                    while i not in results:
                        cond.wait(timeout=0.5)
                    out = results.pop(i)
                    cursor[0] = i + 1
                    cond.notify_all()
                if isinstance(out, BaseException):
                    raise out
                yield out
        finally:
            with cond:
                stop[0] = True
                cond.notify_all()
            try:
                while True:
                    task_q.get_nowait()
            except queue.Empty:
                pass
            for _ in threads:
                task_q.put(None)

    # ------------------------------------------------- process workers (shm)
    def _iter_process(self):
        """Multiprocess workers shipping batches through the native
        shared-memory ring (src/shm_ring.cc — the mmap_allocator.cc
        analogue). Workers run dataset code + numpy collation only (no jax);
        the parent wraps arrays into Tensors. Falls back to threads when the
        native library is unavailable."""
        from paddle_tpu import native

        if self.batch_sampler is None:  # batch_size=None: per-sample mode
            yield from self._iter_sync()
            return
        if native.lib() is None or not self.use_shared_memory:
            yield from self._iter_threaded()
            return
        if self.collate_fn is not default_collate_fn:
            # custom collate may build Tensors (jax) — unsafe in forked
            # workers; honor its semantics on the threaded path instead
            import warnings

            warnings.warn(
                "DataLoader: custom collate_fn is incompatible with process "
                "workers; falling back to threaded workers")
            yield from self._iter_threaded()
            return

        import multiprocessing
        import os
        import pickle

        L = native.lib()
        batches = list(self.batch_sampler)
        W = self.num_workers
        ring_cap = 64 << 20  # 64 MB per worker
        names = [f"/pt_dl_{os.getpid()}_{id(self)}_{w}" for w in range(W)]
        rings = [L.shm_ring_open(n.encode(), ring_cap, 1) for n in names]
        if any(not r for r in rings):
            for r, n in zip(rings, names):
                if r:
                    L.shm_ring_close(r)
            yield from self._iter_threaded()
            return

        ctx = multiprocessing.get_context("fork")

        def worker_main(wid, my_batches):
            # child: attach to the ring, fetch + collate to numpy, push
            from paddle_tpu import native as _n

            Lc = _n.lib()
            ring = Lc.shm_ring_open(names[wid].encode(), ring_cap, 0)
            if not ring:
                os._exit(1)
            try:
                from paddle_tpu.io import WorkerInfo, _set_worker_info

                _set_worker_info(WorkerInfo(wid, self.num_workers,
                                            self.dataset))
                if self.worker_init_fn is not None:
                    self.worker_init_fn(wid)
                for idx, b in my_batches:
                    samples = [self.dataset[i] for i in b]
                    payload = pickle.dumps((idx, _np_collate(samples)),
                                           protocol=pickle.HIGHEST_PROTOCOL)
                    rc = Lc.shm_ring_push(ring, payload, len(payload))
                    if rc == -2:
                        raise RuntimeError(
                            f"batch {idx} pickles to {len(payload)} bytes, "
                            f"larger than the {ring_cap >> 20} MB shm ring; "
                            "reduce batch_size or raise ring capacity")
                    if rc != 0:
                        break
            except BaseException as e:  # ship the error to the parent
                payload = pickle.dumps((-1, repr(e)))
                Lc.shm_ring_push(ring, payload, len(payload))
            finally:
                Lc.shm_ring_mark_closed(ring)
            os._exit(0)

        assignments = [[] for _ in range(W)]
        for i, b in enumerate(batches):
            assignments[i % W].append((i, b))
        procs = [ctx.Process(target=worker_main, args=(w, assignments[w]),
                             daemon=True) for w in range(W)]
        for p in procs:
            p.start()

        import ctypes

        results: dict = {}
        done_rings = set()
        buf_cap = ring_cap
        buf = (ctypes.c_char * buf_cap)()
        try:
            for want in range(len(batches)):
                while want not in results:
                    progressed = False
                    for w in range(W):
                        if w in done_rings:
                            continue
                        avail = L.shm_ring_try_peek(rings[w])
                        if avail == -3:  # empty: is the worker still alive?
                            if not procs[w].is_alive():
                                # worker pushes before exiting — re-peek so a
                                # record landed between peek and is_alive()
                                # isn't dropped
                                avail = L.shm_ring_try_peek(rings[w])
                                if avail < 0:
                                    done_rings.add(w)
                                    continue
                            else:
                                continue
                        if avail < 0:
                            done_rings.add(w)
                            continue
                        n = L.shm_ring_pop(rings[w], buf, buf_cap)
                        if n < 0:
                            done_rings.add(w)
                            continue
                        idx, data = pickle.loads(bytes(buf[:n]))
                        if idx == -1:
                            raise RuntimeError(f"DataLoader worker died: {data}")
                        results[idx] = data
                        progressed = True
                    if not progressed:
                        if len(done_rings) == W and want not in results:
                            raise RuntimeError(
                                "DataLoader workers exited before producing "
                                "all batches (a worker may have been killed)")
                        time.sleep(0.0005)  # rings empty: brief backoff
                yield _wrap_np(results.pop(want))
        finally:
            for r in rings:
                L.shm_ring_close(r)
            for p in procs:
                p.join(timeout=5)
                if p.is_alive():
                    p.terminate()


def _np_collate(batch):
    """Collate samples into nested numpy (no jax — safe in forked workers)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, np.float32)
    if isinstance(sample, (list, tuple)):
        return [_np_collate(list(g)) for g in zip(*batch)]
    if isinstance(sample, dict):
        return {k: _np_collate([s[k] for s in batch]) for k in sample}
    if isinstance(sample, (str, bytes)):
        return list(batch)
    raise TypeError(f"cannot collate type {type(sample)} in process workers")


def _wrap_np(data):
    """numpy tree -> Tensor tree (parent side)."""
    if isinstance(data, np.ndarray):
        return Tensor._from_value(jnp.asarray(data))
    if isinstance(data, list):
        return [_wrap_np(d) for d in data]
    if isinstance(data, dict):
        return {k: _wrap_np(v) for k, v in data.items()}
    return data
