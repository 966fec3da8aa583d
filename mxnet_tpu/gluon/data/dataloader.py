"""DataLoader: batched, shuffled, prefetching input pipeline.

Reference: python/mxnet/gluon/data/dataloader.py — fork-based worker processes
with shared-memory NDArray pickling (dataloader.py:67-138, kCPUShared storage)
plus pthread_atfork engine fixups (src/initialize.cc:71-97). TPU-native
redesign with BOTH worker models:

- ``num_workers>0`` (default): SPAWNED worker processes. Fork is unsafe once
  a PJRT client exists, so workers are spawned fresh with
  ``JAX_PLATFORMS=cpu`` in their environment and never touch the chip. Batches
  travel back through POSIX shared memory (multiprocessing.shared_memory —
  the analog of the reference's kCPUShared storage): the parent maps each
  segment zero-copy and issues one host→HBM transfer per array.
- ``thread_pool=True``: thread workers feeding a bounded reorder buffer
  (NumPy releases the GIL for the hot loops) — lighter startup, right for
  cheap per-sample work.

The batchify step produces host numpy either way; transfer to device is a
single contiguous jax.device_put per batch (the reference's copy-worker
role, threaded_engine_perdevice.cc:138).
"""
from __future__ import annotations

import pickle
import queue
import threading

import numpy as onp

from ...base import MXNetError
from ...ndarray.ndarray import NDArray
from .dataset import Dataset
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]


def default_mp_batchify_fn(data):
    """Worker-side batchify: stack into HOST numpy (no device work in the
    worker — arrays ship to the parent through shared memory)."""
    if isinstance(data[0], NDArray):
        return onp.stack([d.asnumpy() for d in data])
    if isinstance(data[0], (tuple, list)):
        return tuple(default_mp_batchify_fn(list(d)) for d in zip(*data))
    arr = onp.asarray(data)
    if arr.dtype == onp.float64:
        arr = arr.astype(onp.float32)
    return arr


def _wrap_nd(obj):
    if isinstance(obj, onp.ndarray):
        return NDArray(obj)
    if isinstance(obj, (tuple, list)):
        return type(obj)(_wrap_nd(o) for o in obj)
    return obj


def default_batchify_fn(data):
    """Stack samples into a device batch (reference: dataloader
    default_batchify_fn) — the numpy batchify with NDArray-wrapped leaves."""
    return _wrap_nd(default_mp_batchify_fn(data))


# ---------------------------------------------------------------------------
# process workers: spawn + shared-memory transport
# ---------------------------------------------------------------------------
def _to_shm(obj, segments):
    """Replace numpy arrays in a nested batch with shared-memory handles."""
    if isinstance(obj, NDArray):
        obj = obj.asnumpy()
    if isinstance(obj, onp.ndarray):
        from multiprocessing import shared_memory

        shm = shared_memory.SharedMemory(create=True,
                                         size=max(obj.nbytes, 1))
        view = onp.ndarray(obj.shape, obj.dtype, buffer=shm.buf)
        view[...] = obj
        segments.append(shm)
        return ("__shm__", shm.name, obj.shape, str(obj.dtype))
    if isinstance(obj, (tuple, list)):
        return type(obj)(_to_shm(o, segments) for o in obj)
    return obj


def _from_shm(obj, opened):
    """Parent side: map shared segments and rebuild device NDArrays."""
    if isinstance(obj, tuple) and len(obj) == 4 and obj[0] == "__shm__":
        from multiprocessing import shared_memory

        _, name, shape, dtype = obj
        shm = shared_memory.SharedMemory(name=name)
        opened.append(shm)
        host = onp.ndarray(shape, onp.dtype(dtype), buffer=shm.buf)
        # jnp.asarray may alias aligned host memory on the CPU backend, and
        # the segment is unlinked right after this batch is rebuilt — hand
        # the NDArray its own buffer (on TPU this is the staging copy the
        # host→HBM transfer reads from)
        return NDArray(onp.array(host))
    if isinstance(obj, (tuple, list)):
        return type(obj)(_from_shm(o, opened) for o in obj)
    return obj


def _unlink_payload(obj):
    """Free shared segments of a payload that will never be consumed."""
    if isinstance(obj, tuple) and len(obj) == 4 and obj[0] == "__shm__":
        from multiprocessing import shared_memory

        try:
            shm = shared_memory.SharedMemory(name=obj[1])
            shm.close()
            shm.unlink()
        except FileNotFoundError:
            pass
        return
    if isinstance(obj, (tuple, list)):
        for o in obj:
            _unlink_payload(o)


def _shutdown_pool(task_q, result_q, procs):
    """Finalizer: stop workers and free any undelivered shared segments."""
    for _ in procs:
        try:
            task_q.put_nowait(None)
        except Exception:  # noqa: BLE001
            pass
    for p in procs:
        p.join(timeout=2.0)
        if p.is_alive():
            p.terminate()
    while True:
        try:
            _key, payload, _err = result_q.get_nowait()
        except Exception:  # noqa: BLE001 — drained
            break
        _unlink_payload(payload)


def _worker_loop(dataset_pkl, batchify_pkl, task_q, result_q):
    """Spawned worker entry. The parent started it with
    ``JAX_PLATFORMS=cpu`` in its environment, so a worker can never reach
    for the chip the parent holds."""
    dataset = pickle.loads(dataset_pkl)
    batchify = pickle.loads(batchify_pkl)
    while True:
        task = task_q.get()
        if task is None:
            return
        bid, indices = task
        segments = []
        try:
            batch = batchify([dataset[i] for i in indices])
            payload = _to_shm(batch, segments)
        except BaseException as e:  # noqa: BLE001 — report, don't die silent
            # the parent gets no payload, so segments created before the
            # failure must be unlinked HERE or they leak until exit
            for shm in segments:
                shm.close()
                try:
                    shm.unlink()
                except FileNotFoundError:
                    pass
            result_q.put((bid, None, f"{type(e).__name__}: {e}"))
        else:
            result_q.put((bid, payload, None))
            for shm in segments:
                shm.close()  # parent owns unlinking


class DataLoader:
    """Batched loader over a Dataset; see module docstring for the worker
    models. ``pin_memory`` is accepted for reference API parity and is a
    no-op: PJRT stages host→HBM transfers itself, and the shared-memory
    worker transport already lands batches in page-aligned host buffers."""

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, prefetch=None,
                 thread_pool=False, timeout=120):
        self._dataset = dataset
        self._timeout = timeout
        if batch_sampler is None:
            if batch_size is None:
                raise MXNetError("batch_size required when no batch_sampler")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle else \
                    SequentialSampler(len(dataset))
            elif shuffle:
                raise MXNetError("shuffle and sampler are mutually exclusive")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        self._batch_sampler = batch_sampler
        self._thread_pool = thread_pool
        self._num_workers = max(0, num_workers)
        if batchify_fn is None:
            batchify_fn = default_mp_batchify_fn \
                if self._num_workers and not thread_pool \
                else default_batchify_fn
        self._batchify_fn = batchify_fn
        self._prefetch = max(1, prefetch if prefetch is not None
                             else 2 * max(1, self._num_workers))
        self._pool = None
        self._epoch = 0
        self._live_epochs: set[int] = set()
        self._stray: dict[int, dict] = {}

    def __len__(self):
        return len(self._batch_sampler)

    def device_prefetch(self, multi_step=None, depth=None, sharding=None):
        """Wrap this loader in a :class:`DevicePrefetcher`: stack groups
        of ``multi_step`` batches into the ``[K, batch, ...]`` super-
        batches the scanned train step consumes and overlap their H2D
        transfer with the previous super-step's compute."""
        from .prefetcher import DevicePrefetcher

        return DevicePrefetcher(self, multi_step=multi_step, depth=depth,
                                sharding=sharding)

    def _load_batch(self, indices):
        return self._batchify_fn([self._dataset[i] for i in indices])

    def __iter__(self):
        return self._iter()

    def _iter_impl(self):
        if self._num_workers == 0:
            for indices in self._batch_sampler:
                yield self._load_batch(indices)
            return
        if self._thread_pool:
            yield from self._threaded_iter()
        else:
            yield from self._process_iter()

    def _iter(self):
        """Telemetry shim: when enabled, time how long the consumer waits
        for each batch (prefetch-hit ≈ 0; a large latency means the input
        pipeline, not the accelerator, is the bottleneck)."""
        from ... import telemetry as _tm

        inner = self._iter_impl()
        if not _tm.ON:
            yield from inner
            return
        import time as _time

        t = _tm.timer("dataloader.batch")
        n = _tm.counter("dataloader.batches")
        while True:
            wall0 = _time.time()
            t0 = _time.perf_counter()
            try:
                batch = next(inner)
            except StopIteration:
                return
            dt = _time.perf_counter() - t0
            t.record(dt)
            _tm._maybe_span("dataloader.batch", wall0, dt)
            n.inc()
            yield batch

    def _ensure_pool(self):
        """Spawn the persistent worker pool once; reused across epochs (the
        spawn + import cost is paid on the first iteration only, like the
        reference's long-lived fork pool)."""
        if self._pool is not None:
            return self._pool
        import multiprocessing as mp
        import weakref

        ctx = mp.get_context("spawn")
        try:
            dataset_pkl = pickle.dumps(self._dataset)
            batchify_pkl = pickle.dumps(self._batchify_fn)
        except Exception as e:  # noqa: BLE001
            raise MXNetError(
                "DataLoader(num_workers>0): dataset/batchify_fn must be "
                f"picklable for spawned workers ({e}); pass "
                "thread_pool=True to use thread workers instead") from e
        task_q = ctx.Queue()
        result_q = ctx.Queue()
        procs = [ctx.Process(target=_worker_loop,
                             args=(dataset_pkl, batchify_pkl, task_q,
                                   result_q), daemon=True)
                 for _ in range(self._num_workers)]
        # children inherit the env at exec time — pin them to CPU BEFORE
        # they re-import the parent's __main__ (which may pull in jax and
        # otherwise initialize the TPU runtime inside the worker)
        from ...context import spawn_cpu_pinned_env

        with spawn_cpu_pinned_env():
            for p in procs:
                p.start()
        self._pool = (task_q, result_q, procs)
        weakref.finalize(self, _shutdown_pool, task_q, result_q, procs)
        return self._pool

    def _process_iter(self):
        """Persistent spawned workers + shared-memory batch transport.

        Concurrent iterators over one loader share the result queue, so
        each result is routed by its (epoch, batch) key: live epochs'
        batches are stashed for their iterator (``self._stray``); results
        for epochs no longer in ``self._live_epochs`` are unlinked.
        """
        task_q, result_q, procs = self._ensure_pool()
        epoch = self._epoch
        self._epoch += 1
        self._live_epochs.add(epoch)
        batches = list(self._batch_sampler)
        reorder: dict[int, object] = {}

        def route(key, payload, err):
            ep, bid = key
            if ep == epoch:
                if err is not None:
                    raise MXNetError(f"DataLoader worker failed: {err}")
                reorder[bid] = payload
            elif ep in self._live_epochs:
                self._stray.setdefault(ep, {})[bid] = (payload, err)
            else:
                _unlink_payload(payload)

        try:
            for sent in range(min(self._prefetch, len(batches))):
                task_q.put(((epoch, sent), batches[sent]))
            sent = min(self._prefetch, len(batches))
            for want in range(len(batches)):
                mine = self._stray.get(epoch)
                while mine and want not in reorder:
                    bid, (payload, err) = mine.popitem()
                    if err is not None:
                        raise MXNetError(f"DataLoader worker failed: {err}")
                    reorder[bid] = payload
                while want not in reorder:
                    try:
                        key, payload, err = result_q.get(
                            timeout=self._timeout)
                    except queue.Empty:
                        raise MXNetError(
                            f"DataLoader worker timeout ({self._timeout}s); "
                            "a worker may have died — check stderr") \
                            from None
                    route(key, payload, err)
                if sent < len(batches):
                    task_q.put(((epoch, sent), batches[sent]))
                    sent += 1
                opened = []
                try:
                    batch = _from_shm(reorder.pop(want), opened)
                finally:
                    for shm in opened:
                        shm.close()
                        try:
                            shm.unlink()
                        except FileNotFoundError:
                            pass
                yield batch
        finally:
            # early exit (break / error): free undelivered batches; results
            # still in flight are unlinked by whichever iterator drains
            # them (this epoch is dead now) or by pool shutdown
            self._live_epochs.discard(epoch)
            for payload in reorder.values():
                _unlink_payload(payload)
            for payload, _err in self._stray.pop(epoch, {}).values():
                _unlink_payload(payload)

    def _threaded_iter(self):
        batches = list(self._batch_sampler)
        out_q: dict[int, object] = {}
        done = threading.Event()
        lock = threading.Condition()
        idx_iter = iter(enumerate(batches))
        idx_lock = threading.Lock()
        error: list[BaseException] = []

        def worker():
            while not done.is_set():
                with idx_lock:
                    try:
                        i, indices = next(idx_iter)
                    except StopIteration:
                        return
                try:
                    batch = self._load_batch(indices)
                except BaseException as e:  # noqa: BLE001
                    with lock:
                        error.append(e)
                        lock.notify_all()
                    return
                with lock:
                    while (len(out_q) >= self._prefetch and
                           min(out_q, default=i) < i and not done.is_set()):
                        lock.wait(0.1)
                    out_q[i] = batch
                    lock.notify_all()

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(self._num_workers)]
        for t in threads:
            t.start()
        try:
            for i in range(len(batches)):
                with lock:
                    deadline = self._timeout
                    while i not in out_q and not error:
                        if not lock.wait(0.5):
                            deadline -= 0.5
                            if deadline <= 0:
                                raise MXNetError("DataLoader worker timeout")
                    if error:
                        raise error[0]
                    batch = out_q.pop(i)
                    lock.notify_all()
                yield batch
        finally:
            done.set()
            for t in threads:
                t.join(timeout=1.0)

    def __del__(self):
        pass
