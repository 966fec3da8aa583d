"""serve.Predictor — the TPU-native inference fast path.

Wraps any hybridizable ``Block`` for traffic serving (ISSUE 4; the
north-star's "heavy traffic from millions of users" leg). Three layers:

- **Shape bucketing** (``bucketing.py``): one ahead-of-time compiled
  program per bucket in a powers-of-two ladder, so the program set is
  O(log max_batch) regardless of observed batch shapes. Inputs pad with
  zeros to their bucket; outputs slice back. TVM's per-shape AOT
  specialization (arxiv 1802.04799) is the precedent.
- **Dynamic batching**: ``submit()`` enqueues single-item requests and
  returns a ``Future``; a background dispatcher coalesces waiting
  requests into one padded device batch under a ``max_batch`` /
  ``max_wait_us`` policy. Host->device transfer of batch N+1 is issued
  while batch N computes (both are async under PJRT; results of N are
  only awaited after N+1 is dispatched), so transfer overlaps compute —
  PyGraph's capture-and-replay amortization (arxiv 2503.19779) applied
  to serving.
- **Persistent compilation**: ``context.enable_compilation_cache`` turns
  on jax's on-disk compilation cache (at ``JAX_COMPILATION_CACHE_DIR``,
  else a fixed directory in the checkout), and ``warmup()`` precompiles
  every bucket (recording a manifest), so a fresh process restores
  steady-state latency — zero recompiles from the first request on.

The serving call path deliberately bypasses the imperative dispatch /
autograd layers: bucket programs are ``CachedOp.aot_compile`` executables
called with raw device arrays. Telemetry (when enabled) sees every
program call as one dispatch, plus serve-specific gauges/counters and a
latency histogram (p50/p99).
"""
from __future__ import annotations

import json
import os
import queue
import threading
import time
from concurrent.futures import Future

import numpy as onp

from ..base import MXNetError
from ..telemetry.registry import Histogram
from ..testing import chaos
from .bucketing import bucket_ladder, padded_rows, pick_bucket, split_sizes
from .decode.engine import EngineDeadError

__all__ = ["Predictor", "load_manifest"]

_STOP = object()


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class _Request:
    __slots__ = ("rows", "future", "t0", "trace")

    def __init__(self, rows, trace=None):
        self.rows = rows  # one host row per model input
        self.future = Future()
        self.t0 = time.perf_counter()
        # RequestTrace when telemetry is on, else None; also exposed as
        # future.trace so callers can read the phase decomposition
        self.trace = trace
        self.future.trace = trace


def load_manifest(path):
    """Read a warmup manifest written by ``Predictor.warmup(path)``."""
    with open(path) as fh:
        m = json.load(fh)
    if m.get("version") != 1:
        raise MXNetError(f"unsupported warmup manifest version in {path}: "
                         f"{m.get('version')!r}")
    return m


class Predictor:
    """Serve a hybridizable block behind bucketed, batched, AOT-compiled
    XLA programs.

    Parameters
    ----------
    block : HybridBlock (or SymbolBlock)
        The model. Its parameters are captured at construction; the block
        is traced ONCE in inference mode (``autograd.pause``) and each
        bucket is compiled ahead of time from that one graph.
    example : NDArray or tuple of NDArray, optional
        A representative input batch (any leading batch size) fixing the
        per-item shape and dtype of each model input. May be omitted when
        ``manifest`` supplies the specs.
    max_batch : int
        Largest device batch; also the top ladder bucket. Bigger
        ``predict()`` batches split into max_batch chunks.
    buckets : list[int], optional
        Explicit ladder (ascending, last == max_batch). Default: powers
        of two up to ``max_batch``.
    max_wait_us : int
        How long the dispatcher holds an underfull batch open for more
        ``submit()`` traffic before dispatching it anyway.
    cache_dir : str | None | False
        Persistent compilation cache directory. None (default) resolves
        through ``context.compilation_cache_dir()``; a path is used only
        where ``JAX_COMPILATION_CACHE_DIR`` is not set; False disables
        persistence.
    manifest : str, optional
        Path to a warmup manifest from a previous process: adopts its
        ladder/input specs and precompiles every bucket immediately
        (the XLA compiles hit the on-disk cache).
    """

    def __init__(self, block, example=None, *, max_batch=64, buckets=None,
                 max_wait_us=2000, cache_dir=None, manifest=None):
        from .. import telemetry as _tm
        from ..context import enable_compilation_cache
        from ..ndarray.ndarray import NDArray

        self._tm = _tm
        self._NDArray = NDArray
        if cache_dir is not False:
            self.cache_dir = enable_compilation_cache(cache_dir)
        else:
            self.cache_dir = None

        manifest_dict = None
        if manifest is not None:
            manifest_dict = load_manifest(manifest) \
                if isinstance(manifest, str) else dict(manifest)
            max_batch = int(manifest_dict["max_batch"])
            buckets = [int(b) for b in manifest_dict["buckets"]]

        self.max_batch = int(max_batch)
        self.buckets = [int(b) for b in buckets] if buckets \
            else bucket_ladder(self.max_batch)
        if sorted(self.buckets) != self.buckets or \
                self.buckets[-1] != self.max_batch:
            raise MXNetError(
                f"bucket ladder must ascend to max_batch={self.max_batch}, "
                f"got {self.buckets}")
        self.max_wait_us = int(max_wait_us)

        # -- input spec ----------------------------------------------------
        if example is not None:
            examples = example if isinstance(example, (tuple, list)) \
                else (example,)
            examples = [x if isinstance(x, NDArray) else NDArray(x)
                        for x in examples]
            if any(x.ndim < 1 for x in examples):
                raise MXNetError("example inputs need a leading batch axis")
            self._item_shapes = [x.shape[1:] for x in examples]
            self._dtypes = [onp.dtype(x.dtype) for x in examples]
        elif manifest_dict is not None:
            self._item_shapes = [tuple(s["item_shape"])
                                 for s in manifest_dict["inputs"]]
            self._dtypes = [onp.dtype(s["dtype"])
                            for s in manifest_dict["inputs"]]
        else:
            raise MXNetError(
                "Predictor needs an example input (or a warmup manifest) "
                "to fix input shapes/dtypes")

        # -- trace the serving graph once, in inference mode ---------------
        if not hasattr(block, "_serving_graph"):
            raise MXNetError(
                f"Predictor requires a hybridizable block, got "
                f"{type(block).__name__} (plain Blocks have no traceable "
                "graph — subclass HybridBlock)")
        self._block = block
        trace_inputs = tuple(self._zeros_batch(self.max_batch))
        cop, tree, param_arrays = block._serving_graph(trace_inputs)
        self._cop = cop
        self._tree = tree
        self._param_datas = [a._data for a in param_arrays]
        self._n_out = cop._n_main

        # -- program table -------------------------------------------------
        self._programs = {}     # bucket -> jax Compiled
        self._signatures = {}   # bucket -> "f32[8,16],..." trace signature
        self._program_costs = {}  # bucket -> (flops, bytes_accessed)
        self._compile_lock = threading.Lock()
        # stall heartbeat around the device sync in _resolve — the spot
        # where a hung device manifests on this path
        self._hb_resolve = _tm.stall_heartbeat("serve.dispatch")

        # -- batcher state -------------------------------------------------
        self._q = queue.SimpleQueue()
        self._worker = None
        self._worker_lock = threading.Lock()
        self._closed = False
        self._dead = None       # dispatcher crash exception, once fatal
        self._inflight = None   # the double-buffered batch (crash cleanup)
        self._pending_batch = None  # popped but not yet dispatched (ditto)

        # transient dispatch failures retry before failing the futures
        self._retries = _env_int("MXTPU_SERVE_RETRIES", 2)
        self._retry_backoff_ms = _env_int("MXTPU_SERVE_RETRY_BACKOFF_MS", 10)
        self._retry_max_ms = _env_int("MXTPU_SERVE_RETRY_MAX_MS", 1000)

        self._health_name = f"predictor:{id(self):x}"
        _tm.register_health(self._health_name, self._health)

        # -- accounting (always on: these ARE the serving stats) -----------
        self._n_requests = 0
        self._n_batches = 0
        self._n_padded_rows = 0
        self._n_batched_rows = 0  # rows that went through device batches
        self._occupancy_sum = 0.0
        self._latency_ms = Histogram("serve.latency_ms")
        self._stats_lock = threading.Lock()

        if manifest_dict is not None:
            self.warmup()

    # ------------------------------------------------------------------ gen
    def _zeros_batch(self, n):
        from ..ndarray.ndarray import NDArray
        import jax.numpy as jnp

        return [NDArray(jnp.zeros((n,) + shp, dt))
                for shp, dt in zip(self._item_shapes, self._dtypes)]

    def _check_dtype(self, i, got):
        want = self._dtypes[i]
        if onp.dtype(got) != want:
            raise MXNetError(
                f"input {i} dtype mismatch: predictor compiled for "
                f"{want.name}, got {onp.dtype(got).name} — cast the input "
                f"or rebuild the Predictor with a {onp.dtype(got).name} "
                "example")

    # ------------------------------------------------------------- programs
    def _ensure_program(self, bucket):
        prog = self._programs.get(bucket)
        if prog is not None:
            return prog
        with self._compile_lock:
            prog = self._programs.get(bucket)
            if prog is not None:
                return prog
            from ..telemetry.watchdog import format_signature

            examples = self._zeros_batch(bucket)
            prog = self._cop.aot_compile(*examples, *self._param_datas)
            self._signatures[bucket] = format_signature(
                [x._data for x in examples])
            # per-bucket XLA cost, captured once per compile (see
            # telemetry/costs.py) — credited at every dispatch below
            cost = self._tm.record_program_cost(f"serve.bucket{bucket}",
                                                prog)
            self._tm.record_program_memory(f"serve.bucket{bucket}", prog)
            self._program_costs[bucket] = (
                (cost["flops"], cost["bytes_accessed"]) if cost
                else (0.0, 0.0))
            self._programs[bucket] = prog
            return prog

    def warmup(self, manifest_path=None):
        """Precompile every bucket's program; optionally write a manifest.

        After warmup, serving any batch size causes ZERO further traces
        or compiles (asserted via the telemetry compile counters in
        tests/test_serve.py). With the persistent cache on, the XLA
        compiles inside warmup are disk hits on every process after the
        first, so a restart reaches steady-state latency before its
        first request. Returns the manifest dict.

        With the tuned kernel tier on (``MXTPU_TUNE=1``) the persisted
        per-bucket winners are preloaded FIRST, so every bucket's trace
        below resolves its kernel configs from memory — a serving
        process never measures candidates online.
        """
        from ..tune import preload as _tune_preload

        _tune_preload()
        for b in self.buckets:
            self._ensure_program(b)
        manifest = self._manifest_dict()
        if manifest_path:
            tmp = manifest_path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(manifest, fh, indent=1)
            os.replace(tmp, manifest_path)
        return manifest

    def _manifest_dict(self):
        from ..context import env_signature

        import jax

        return {
            "version": 1,
            "env_signature": env_signature(),
            "jax_version": getattr(jax, "__version__", "?"),
            "max_batch": self.max_batch,
            "buckets": list(self.buckets),
            "inputs": [{"item_shape": list(shp), "dtype": dt.name}
                       for shp, dt in zip(self._item_shapes, self._dtypes)],
            "signatures": {str(b): s for b, s in
                           sorted(self._signatures.items())},
            "cache_dir": self.cache_dir,
            "created_unix": time.time(),
        }

    # -------------------------------------------------------------- running
    def _run_program(self, bucket, datas):
        """Call the bucket's executable on raw device arrays; returns the
        MAIN output arrays (aux outputs, if any, are dropped — the trace
        runs in inference mode so there are none to write back)."""
        args = list(datas) + self._param_datas
        if self._cop._uses_rng:
            from .. import random as _rnd

            args.insert(0, _rnd._next_key())
        site = f"serve.bucket{bucket}"
        self._tm.check_memory_admission(site)
        try:
            outs = self._programs[bucket](*args)
        except Exception as e:
            self._tm.memory_oom_forensics(site, e)
            raise
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        tm = self._tm
        if tm.ON:
            tm.record_dispatch()
            tm.record_flops(*self._program_costs.get(bucket, (0.0, 0.0)))
        return tuple(outs)[: self._n_out]

    def predict(self, data):
        """Synchronous bucketed forward of a whole batch.

        ``data``: NDArray (or numpy/jax array) with a leading batch axis,
        or a tuple of them for multi-input models. Batches larger than
        ``max_batch`` split into chunks; every chunk pads to its bucket
        and outputs are unpadded/concatenated back to exactly the input
        row count. Returns the block's output structure (NDArrays).
        """
        import jax.numpy as jnp

        from ..cached_op import unflatten_out

        if self._dead is not None:
            raise EngineDeadError(
                f"Predictor dispatcher crashed: {self._dead!r}"
            ) from self._dead
        if self._closed:
            raise MXNetError("Predictor is closed")
        NDArray = self._NDArray
        inputs = data if isinstance(data, (tuple, list)) else (data,)
        if len(inputs) != len(self._item_shapes):
            raise MXNetError(
                f"predictor compiled for {len(self._item_shapes)} inputs, "
                f"got {len(inputs)}")
        arrs = []
        for i, x in enumerate(inputs):
            x = x if isinstance(x, NDArray) else NDArray(x)
            self._check_dtype(i, x.dtype)
            if x.shape[1:] != self._item_shapes[i]:
                raise MXNetError(
                    f"input {i} item shape mismatch: predictor compiled "
                    f"for {self._item_shapes[i]}, got {x.shape[1:]}")
            arrs.append(x._data)
        n = arrs[0].shape[0]
        if any(a.shape[0] != n for a in arrs):
            raise MXNetError("all inputs must share the batch axis")

        chunk_flats, off = [], 0
        for size in split_sizes(n, self.max_batch):
            bucket = pick_bucket(size, self.buckets)
            self._ensure_program(bucket)
            pad = padded_rows(size, bucket)
            chunk = []
            for a in arrs:
                c = a[off:off + size]
                if pad:
                    c = jnp.concatenate(
                        [c, jnp.zeros((pad,) + c.shape[1:], c.dtype)])
                chunk.append(c)
            outs = self._run_program(bucket, chunk)
            chunk_flats.append([o[:size] for o in outs])
            self._account_batch(size, bucket, qdepth=0)
            off += size
        if len(chunk_flats) == 1:
            flat = chunk_flats[0]
        else:
            flat = [jnp.concatenate([c[j] for c in chunk_flats])
                    for j in range(self._n_out)]
        with self._stats_lock:
            self._n_requests += 1
        if self._tm.ON:
            self._tm.REGISTRY.counter("serve.requests").inc()
        return unflatten_out([NDArray(o) for o in flat], self._tree)

    # ------------------------------------------------------------ batching
    def submit(self, item):
        """Enqueue one request (a SINGLE item, no batch axis; tuple of
        items for multi-input models) for dynamic batching; returns a
        ``concurrent.futures.Future`` resolving to the item's output
        (numpy, in the block's output structure)."""
        if self._dead is not None:
            raise EngineDeadError(
                f"Predictor dispatcher crashed: {self._dead!r}"
            ) from self._dead
        if self._closed:
            raise MXNetError("Predictor is closed")
        items = item if isinstance(item, (tuple, list)) else (item,)
        if len(items) != len(self._item_shapes):
            raise MXNetError(
                f"predictor compiled for {len(self._item_shapes)} inputs, "
                f"got {len(items)}")
        rows = []
        for i, x in enumerate(items):
            if isinstance(x, self._NDArray):
                x = onp.asarray(x._data)
            else:
                x = onp.asarray(x)
            self._check_dtype(i, x.dtype)
            if tuple(x.shape) != self._item_shapes[i]:
                raise MXNetError(
                    f"submit() takes single items of shape "
                    f"{self._item_shapes[i]} for input {i}, got "
                    f"{tuple(x.shape)} — use predict() for whole batches")
            rows.append(x)
        req = _Request(rows, trace=self._tm.new_trace("serve.request"))
        with self._stats_lock:
            self._n_requests += 1
        if self._tm.ON:
            self._tm.REGISTRY.counter("serve.requests").inc()
        self._start_worker()
        self._q.put(req)
        return req.future

    def _start_worker(self):
        if self._worker is not None:
            return
        with self._worker_lock:
            if self._worker is None:
                t = threading.Thread(target=self._dispatch_loop,
                                     name="mxtpu-serve-dispatch",
                                     daemon=True)
                self._worker = t
                t.start()

    def _dispatch_loop(self):
        """Crash guard around the dispatcher: an uncaught error fails
        every queued and in-flight future with :class:`EngineDeadError`
        (real cause chained) and marks the predictor dead — clients get
        an exception, never a hang, and the telemetry health check fails
        (→ ``/healthz`` 503)."""
        try:
            self._dispatch_loop_impl()
        except BaseException as e:  # noqa: BLE001 — converted, never lost
            self._dispatcher_crashed(e)

    def _dispatcher_crashed(self, exc):
        self._dead = exc
        self._closed = True
        tm = self._tm
        tm.REGISTRY.counter("serve.scheduler_crashes").inc()
        if tm.ON:
            tm.event("serve.dispatcher_crash", error=repr(exc))
        err = EngineDeadError(f"Predictor dispatcher crashed: {exc!r}")
        err.__cause__ = exc
        pending, self._pending_batch = self._pending_batch, None
        for req in pending or ():
            tm.finish_trace(req.trace, status="error")
            if not req.future.done():
                req.future.set_exception(err)
        inflight, self._inflight = self._inflight, None
        if inflight is not None:
            for req in inflight[0]:
                tm.finish_trace(req.trace, status="error")
                if not req.future.done():
                    req.future.set_exception(err)
        while True:
            try:
                r = self._q.get_nowait()
            except queue.Empty:
                break
            if r is not _STOP:
                tm.finish_trace(r.trace, status="error")
                if not r.future.done():
                    r.future.set_exception(err)

    def _dispatch_loop_impl(self):
        """Dispatcher: coalesce -> pad -> transfer -> dispatch; resolve the
        PREVIOUS in-flight batch only after the next one is on the device
        (double buffering: transfer of N+1 overlaps compute of N)."""
        inflight = None
        stopping = False
        while not stopping:
            self._inflight = inflight
            try:
                first = self._q.get_nowait() if inflight is not None \
                    else self._q.get()
            except queue.Empty:
                # no follow-up traffic: settle the in-flight batch now
                # rather than withholding results while the line is idle
                self._resolve(inflight)
                inflight = None
                continue
            if first is _STOP:
                break
            if first.trace is not None:  # queue phase: submit -> picked up
                first.trace.mark("queue")
            batch = [first]
            # popped requests live in neither the queue nor _inflight until
            # dispatch returns: expose them so a loop crash fails their
            # futures instead of orphaning them
            self._pending_batch = batch
            deadline = time.perf_counter() + self.max_wait_us * 1e-6
            while len(batch) < self.max_batch:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=remaining)
                except queue.Empty:
                    break
                if nxt is _STOP:
                    stopping = True
                    break
                if nxt.trace is not None:
                    nxt.trace.mark("queue")
                batch.append(nxt)
            current = self._dispatch(batch)
            self._pending_batch = None
            self._resolve(inflight)
            inflight = current
        self._resolve(inflight)
        # drain whatever arrived after the stop sentinel
        leftovers = []
        while True:
            try:
                r = self._q.get_nowait()
            except queue.Empty:
                break
            if r is not _STOP:
                leftovers.append(r)
        while leftovers:
            chunk, leftovers = leftovers[:self.max_batch], \
                leftovers[self.max_batch:]
            self._pending_batch = chunk
            out = self._dispatch(chunk)
            self._pending_batch = None
            self._resolve(out)

    def _dispatch(self, batch):
        """Pad the coalesced requests into one device batch and launch the
        bucket program (both steps async). Returns (requests, outputs)."""
        import jax

        try:
            t_batch = time.perf_counter()  # batch phase: picked up -> here
            for req in batch:
                if req.trace is not None:
                    req.trace.mark("batch", t_batch)
            k = len(batch)
            bucket = pick_bucket(k, self.buckets)
            self._ensure_program(bucket)
            bufs = []
            for i, (shp, dt) in enumerate(zip(self._item_shapes,
                                              self._dtypes)):
                buf = onp.zeros((bucket,) + shp, dt)
                for r_i, req in enumerate(batch):
                    buf[r_i] = req.rows[i]
                bufs.append(buf)
            datas = [jax.device_put(b) for b in bufs]  # async H2D
            outs = self._run_retry(bucket, datas)      # async compute
            self._account_batch(k, bucket, qdepth=self._q.qsize())
            return batch, outs, bucket, time.perf_counter()
        except BaseException as e:  # noqa: BLE001 — fail the futures, not the loop
            for req in batch:
                self._tm.finish_trace(req.trace, status="error")
                if not req.future.done():
                    req.future.set_exception(e)
            return None

    def _run_retry(self, bucket, datas):
        """One program launch behind the transient-failure retry policy
        (``MXTPU_SERVE_RETRIES`` retries, exponential backoff capped at
        ``MXTPU_SERVE_RETRY_MAX_MS``); ``serve.dispatch`` is the chaos
        injection site. Exhaustion fails this batch's futures only — the
        dispatcher itself stays up for later traffic."""
        attempt = 0
        while True:
            try:
                chaos.fault_point("serve.dispatch")
                return self._run_program(bucket, datas)
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:  # noqa: BLE001 — bounded retries
                if attempt >= self._retries:
                    raise
                attempt += 1
                tm = self._tm
                tm.REGISTRY.counter("serve.retries").inc()
                if tm.ON:
                    tm.event("serve.retry", point="serve.dispatch",
                             attempt=attempt, error=repr(e))
                delay_ms = min(self._retry_backoff_ms * (1 << (attempt - 1)),
                               self._retry_max_ms)
                time.sleep(delay_ms * 1e-3)

    def _resolve(self, inflight):
        """Block on an in-flight batch's device results and complete its
        futures with per-row host outputs."""
        if inflight is None:
            return
        from ..cached_op import unflatten_out

        batch, outs, bucket, t_disp = inflight
        tm = self._tm
        hb_on = tm.ON
        if hb_on:
            self._hb_resolve.begin()
        try:
            host = [onp.asarray(o) for o in outs]  # device sync happens here
        except BaseException as e:  # noqa: BLE001
            for req in batch:
                tm.finish_trace(req.trace, status="error")
                if not req.future.done():
                    req.future.set_exception(e)
            return
        finally:
            if hb_on:
                self._hb_resolve.end()
        now = time.perf_counter()
        if tm.ON:
            # dispatch->sync wall time per program: cost_report joins this
            # with the bucket's flops into achieved FLOP/s / MFU
            tm.REGISTRY.timer(f"serve.bucket{bucket}.call").record(
                now - t_disp)
        for i, req in enumerate(batch):
            out_rows = [h[i] for h in host]
            if req.trace is not None:
                req.trace.mark("compute", now)  # dispatch+device -> on host
            res = unflatten_out(out_rows, self._tree)
            if req.trace is not None:
                req.trace.mark("host")          # unpad/unflatten
                tm.finish_trace(req.trace)
            req.future.set_result(res)
            ms = (now - req.t0) * 1e3
            self._latency_ms.record(ms)
            if tm.ON:
                tm.REGISTRY.histogram("serve.latency_ms").record(ms)

    # ----------------------------------------------------------- accounting
    def _account_batch(self, k, bucket, qdepth):
        pad = padded_rows(k, bucket)
        occ = k / bucket
        with self._stats_lock:
            self._n_batches += 1
            self._n_padded_rows += pad
            self._n_batched_rows += k
            self._occupancy_sum += occ
        tm = self._tm
        if tm.ON:
            tm.REGISTRY.counter("serve.batches").inc()
            tm.REGISTRY.gauge("serve.queue_depth").set(qdepth)
            tm.REGISTRY.gauge("serve.batch_occupancy").set(occ)
            tm.REGISTRY.gauge("serve.padding_waste").set(
                pad / bucket if bucket else 0.0)
            tm.REGISTRY.counter("serve.padded_rows").inc(pad)
            tm.REGISTRY.counter("serve.batched_rows").inc(k)

    def stats(self):
        """Serving accounting independent of the global telemetry gate:
        request/batch/program counts, mean occupancy, padding waste, and
        latency percentiles (ms) over recent dynamic-batch traffic."""
        with self._stats_lock:
            n_b = self._n_batches
            pad, rows = self._n_padded_rows, self._n_batched_rows
            occ = self._occupancy_sum / n_b if n_b else 0.0
        p50, p99 = self._latency_ms.percentiles(50, 99)
        return {
            "requests": self._n_requests,
            "batches": n_b,
            "batched_rows": rows,
            "padded_rows": pad,
            "padding_waste": pad / (pad + rows) if pad + rows else 0.0,
            "mean_occupancy": occ,
            "programs": sorted(self._programs),
            "latency_ms_p50": p50,
            "latency_ms_p99": p99,
            "dead": self._dead is not None,
        }

    # -------------------------------------------------------------- health
    def _health(self):
        if self._dead is not None:
            return False, f"dispatcher crashed: {self._dead!r}"
        return True, {"closed": self._closed}

    @property
    def healthy(self):
        return self._dead is None

    # ------------------------------------------------------------ lifecycle
    def close(self):
        """Stop the dispatcher (idempotent). Outstanding futures resolve
        before the worker exits; later ``submit``/``predict`` raise."""
        try:
            self._tm.unregister_health(self._health_name)
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
        if self._closed:
            return
        self._closed = True
        worker = self._worker
        if worker is not None:
            self._q.put(_STOP)
            worker.join(timeout=30)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
