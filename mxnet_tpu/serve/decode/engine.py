"""DecodeEngine: continuous batching over the three decode program families.

The host never computes on tensors — each scheduler tick it only feeds
operands (token ids, positions, page-table rows) to one of the three
AOT executables and applies bookkeeping to the results:

    tick:  expire deadlines -> admit pending into free slots (radix
           prefix lookup, page allocation, then the prefill or
           prefix-join program, bucketed batch x length) -> one
           decode_tick_k for ALL slots (K-1 drafted tokens verified per
           slot when speculation is on) -> read back and commit what was
           dispatched BEFORE that tick / finish the requests it ended

With ``speculate_k == 1`` the scheduler keeps ONE tick in flight: the
token a slot feeds next stays on the device (tick n's token output is
tick n+1's operand; a prefill's first tokens are placed into it by a tiny
program), and every live slot advances by exactly one position a tick, so
lengths, page-table growth and which requests end at tick n are known when
tick n is dispatched. Tick n+1 goes out before tick n's tokens are read
back; the read-back, the commit and the clients' ``on_token`` for tick n
run while the device executes tick n+1, and a request known to end at tick
n has no row in tick n+1. ``stats()["ticks_overlapped"]`` (counter
``serve.ticks_overlapped``) counts the ticks dispatched while an earlier
tick's tokens were not yet read back. With ``speculate_k > 1`` the draft
needs the accepted tokens on the host: nothing stays in flight, every
program is read back before the next is dispatched.

KV memory is PAGED (vLLM-style): a shared pool of
``page_tokens``-position pages backs every slot through per-slot page
tables, so resident bytes scale with live tokens and the pool may be
sized below num_slots * max_len (oversubscription sheds at admission or
starve-retires mid-flight — never crashes). A radix prefix cache maps
previously prefilled prompt prefixes to refcounted pages; a hit maps the
shared pages read-only into the new slot's table and prefills only the
suffix. Speculation (``MXTPU_SPECULATE_K``) drafts K-1 tokens on the
host (``MXTPU_DECODE_DRAFT``) and verifies them in one batched pass —
greedy accept-longest-prefix keeps the committed sequence bitwise equal
to plain greedy decoding.

``submit`` is thread-safe and returns a :class:`DecodeStream` — a
streaming token future: per-token callbacks fire from the scheduler
thread, ``result()`` blocks for the full generation, iteration yields
tokens as they land. Load past the queue-depth budget (or past its
deadline before ever reaching a slot) is SHED with :class:`ShedError`;
a request whose deadline expires mid-generation is EVICTED — its stream
finishes with the tokens produced so far and ``expired=True``.

Self-healing contract: a scheduler-thread crash can NEVER hang a client.
Transient program-run failures retry with capped exponential backoff
(``MXTPU_SERVE_RETRIES`` / ``MXTPU_SERVE_RETRY_BACKOFF_MS`` /
``MXTPU_SERVE_RETRY_MAX_MS``; ``serve.retries`` counts them); an
exception that survives the retries fails EVERY live, pending and queued
stream (those whose last tokens were in flight too) with
:class:`EngineDeadError` carrying the real cause, marks the
engine dead (telemetry health check → ``/healthz`` 503,
``serve.scheduler_crashes``), and later ``submit`` raises immediately.
``drain()`` finishes accepted work while shedding new submissions;
``resume()`` reopens the gate. Chaos points: ``decode.prefill``,
``decode.tick`` (see mxnet_tpu.testing.chaos).
"""
from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque

import numpy as onp

from ...base import MXNetError
from ...telemetry.registry import Histogram
from ...telemetry.spans import span
from ...telemetry.trace import next_id
from ...testing import chaos
from ..bucketing import pick_bucket
from .cache import PagedKVCache
from .prefix import RadixPrefixCache
from .programs import DecodePrograms
from .spec import accept_longest_prefix, make_draft

__all__ = ["DecodeEngine", "DecodeStream", "ShedError", "EngineDeadError"]

_STOP = object()


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class ShedError(MXNetError):
    """The engine refused (or dropped) a request to protect latency."""


class EngineDeadError(MXNetError):
    """The scheduler thread died; ``__cause__`` carries the real crash.

    Every stream that was live, pending or queued at crash time finishes
    with this error (never a hang), and every later ``submit`` raises it
    immediately. The engine's telemetry health check fails, so an
    attached exporter's ``/healthz`` answers 503."""


class DecodeStream:
    """Streaming token future for one submitted prompt.

    - ``on_token(token_id)`` fires from the scheduler thread per token;
    - iteration yields generated token ids as they arrive;
    - ``result(timeout)`` blocks until the stream finishes and returns
      the full generated-token list (raises if the request was shed).

    ``expired`` marks a deadline eviction (partial output), ``truncated``
    marks a generation clipped by KV capacity (cache length or page-pool
    starvation).
    """

    def __init__(self, prompt, max_new_tokens, deadline, on_token=None):
        self.prompt = list(prompt)
        self.max_new_tokens = int(max_new_tokens)
        self.deadline = deadline      # absolute perf_counter() time or None
        self.tokens = []
        self.expired = False
        self.truncated = False
        self.trace = None             # RequestTrace when telemetry is on
        self.rid = next_id()          # the request's one identifier: the
        # prefill span's ``rids`` and the trace's ``trace_id`` carry it
        self.t_submit = time.perf_counter()
        self._t_last = None           # engine: last emit time (TTFT/TPOT)
        self._dispatched = 0          # engine: tokens the programs
        # dispatched so far produce for this stream, read back or not
        self._on_token = on_token
        self._cond = threading.Condition()
        self._done = False
        self._error = None

    # -- engine side -------------------------------------------------------
    def _emit(self, tok):
        with self._cond:
            self.tokens.append(tok)
            self._cond.notify_all()
        if self._on_token is not None:
            self._on_token(tok)

    def _finish(self, error=None):
        with self._cond:
            self._error = error
            self._done = True
            self._cond.notify_all()

    # -- client side -------------------------------------------------------
    @property
    def done(self):
        return self._done

    def result(self, timeout=None):
        with self._cond:
            if not self._cond.wait_for(lambda: self._done, timeout):
                raise MXNetError("DecodeStream.result timed out")
            if self._error is not None:
                raise self._error
            return list(self.tokens)

    def __iter__(self):
        i = 0
        while True:
            with self._cond:
                self._cond.wait_for(
                    lambda: self._done or len(self.tokens) > i)
                if i < len(self.tokens):
                    tok = self.tokens[i]
                else:
                    if self._error is not None:
                        raise self._error
                    return
            i += 1
            yield tok


class _Flight:
    """One dispatched program whose tokens the host has not read back.

    ``out`` is the program's token output, still on the device: (S, K) of
    a tick, (B,) of a prefill. ``rows`` is [(row of ``out``, slot id,
    stream)] as dispatched: the slot may be another request's by the time
    the tokens are read. ``ends`` maps a slot to why its request ends with
    this program's token(s) ("done" | "truncated" | "starved"), for what
    was known at dispatch. ``span`` is the dispatch span (the wait's timer
    sample covers both). Of a tick besides: the ``drafts`` it verifies
    (K > 1), the slots the pool ``starved``, the share of the slots it ran
    with (``occupancy``) and whether it went out while an earlier tick's
    tokens were unread (``overlapped``)."""

    __slots__ = ("tick", "out", "rows", "ends", "span", "drafts", "starved",
                 "occupancy", "overlapped")

    def __init__(self, tick, out, rows, ends, span, drafts=None, starved=(),
                 occupancy=0.0, overlapped=False):
        self.tick = tick
        self.out = out
        self.rows = rows
        self.ends = ends
        self.span = span
        self.drafts = drafts
        self.starved = starved
        self.occupancy = occupancy
        self.overlapped = overlapped


class DecodeEngine:
    """Continuous-batching autoregressive decoding for a causal LM.

    With ``speculate_k == 1`` one decode tick stays in flight: ``on_token``
    for tick n runs while the device executes tick n+1 (see the module's
    notes; ``stats()["ticks_overlapped"]`` says how often).

    Parameters
    ----------
    model : block, optional
        A model with ONE forward pass, ``model(tokens, cache=view)`` ->
        logits (B, T, V): its attention layers call
        ``view.attend(layer, q, k, v)`` and its embedding takes
        ``view.positions(limit)`` (the views: :mod:`cache`). Besides, it
        states ``cache_spec()`` (what a KV cache must hold) and
        ``max_length`` (and ``tp_partition_rules('serve')`` for tp >= 2).
        May be omitted when ``programs`` (e.g. from
        ``DecodeEngine.from_export``) supplies traced graphs.
    num_slots : int
        Concurrent sequences per decode tick (the fixed decode program
        shape). Default: ``MXTPU_DECODE_SLOTS`` (8).
    max_len : int
        KV positions per slot (page-table capacity). Default:
        ``model.max_length``.
    max_prompt_len : int
        Longest admissible prompt; tops the prefill length ladder.
    prefill_batch : int
        Largest prefill batch; tops the prefill batch ladder.
    page_tokens : int
        KV page size in token positions. Default:
        ``MXTPU_KV_PAGE_TOKENS`` (128).
    kv_pages : int
        Pool size in pages. Default: ``MXTPU_KV_PAGES``, else
        num_slots * ceil(max_len / page_tokens) (full reservation).
        Sizing it lower oversubscribes capacity: bytes stay put while
        num_slots grows, which is the whole point of paging.
    speculate_k : int
        Tokens verified per decode tick; 1 (or ``MXTPU_SPECULATE_K``
        unset/0) disables speculation.
    draft : str
        Draft proposer for speculation: 'ngram' (default) or 'last'
        (``MXTPU_DECODE_DRAFT``).
    prefix_cache : bool
        Radix prefix cache over prompt prefixes. Default:
        ``MXTPU_PREFIX_CACHE`` (on).
    max_wait_us : int
        Idle-coalesce window before the first prefill of a burst.
        Default: ``MXTPU_DECODE_MAX_WAIT_US`` (2000).
    deadline_ms : int
        Default per-request deadline; 0 disables. Default:
        ``MXTPU_DECODE_DEADLINE_MS`` (0).
    max_queue : int
        Queue-depth shed threshold (pending, i.e. not-yet-slotted,
        requests). Default ``max(4 * num_slots, 16)``.
    cache_dir : str | None | False
        Persistent XLA compile cache dir (False disables), as Predictor.
    manifest : str | dict, optional
        Warmup manifest from a previous process: adopts its geometry and
        precompiles everything immediately (disk-hit compiles).
    """

    def __init__(self, model=None, *, num_slots=None, max_len=None,
                 max_prompt_len=None, prefill_batch=4, page_tokens=None,
                 kv_pages=None, speculate_k=None, draft=None,
                 prefix_cache=None, max_wait_us=None, deadline_ms=None,
                 max_queue=None, cache_dir=None, manifest=None,
                 programs=None, tp=None):
        from ... import telemetry as _tm
        from ...context import enable_compilation_cache

        self._tm = _tm
        if cache_dir is not False:
            self.cache_dir = enable_compilation_cache(cache_dir)
        else:
            self.cache_dir = None

        manifest_dict = None
        if manifest is not None:
            from .programs import load_decode_manifest

            manifest_dict = load_decode_manifest(manifest) \
                if isinstance(manifest, str) else dict(manifest)
            num_slots = int(manifest_dict["num_slots"])
            max_len = int(manifest_dict["max_len"])
            max_prompt_len = int(manifest_dict["max_prompt_len"])
            prefill_batch = int(manifest_dict["prefill_batch"])
            page_tokens = int(manifest_dict["page_tokens"])
            kv_pages = int(manifest_dict["kv_pages"])
            speculate_k = int(manifest_dict["speculate_k"])
            prefix_cache = bool(manifest_dict["prefix_cache"])

        if programs is not None:
            self.programs = programs
        else:
            if model is None:
                raise MXNetError(
                    "DecodeEngine needs a model (or programs from an "
                    "export)")
            num_slots = int(num_slots or _env_int("MXTPU_DECODE_SLOTS", 8))
            max_len = int(max_len or model.max_length)
            page_tokens = int(page_tokens or
                              _env_int("MXTPU_KV_PAGE_TOKENS", 128))
            if kv_pages is None:
                kv_pages = _env_int("MXTPU_KV_PAGES", 0) or None
            if speculate_k is None:
                speculate_k = _env_int("MXTPU_SPECULATE_K", 0)
            speculate_k = max(1, int(speculate_k))
            if prefix_cache is None:
                prefix_cache = bool(_env_int("MXTPU_PREFIX_CACHE", 1))
            if tp is None:
                tp = _env_int("MXTPU_SERVE_TP", 1)
            self.programs = DecodePrograms(
                model, num_slots=num_slots, max_len=max_len,
                prefill_batch=prefill_batch,
                max_prompt_len=max_prompt_len,
                page_tokens=page_tokens, kv_pages=kv_pages,
                speculate_k=speculate_k, prefix_cache=prefix_cache,
                tp=max(1, int(tp)))
        self.num_slots = self.programs.num_slots
        self.max_len = self.programs.max_len
        self.max_prompt_len = self.programs.max_prompt_len
        self.prefill_batch = self.programs.prefill_batch
        self.page_tokens = self.programs.page_tokens
        self.kv_pages = self.programs.kv_pages
        self.speculate_k = self.programs.speculate_k
        self.prefix_cache = self.programs.prefix_cache

        self._draft = None
        if self.speculate_k > 1:
            self._draft = make_draft(
                draft or os.environ.get("MXTPU_DECODE_DRAFT") or "ngram")

        self.max_wait_us = int(max_wait_us if max_wait_us is not None
                               else _env_int("MXTPU_DECODE_MAX_WAIT_US",
                                             2000))
        dl = deadline_ms if deadline_ms is not None \
            else _env_int("MXTPU_DECODE_DEADLINE_MS", 0)
        self.deadline_ms = int(dl)
        self.max_queue = int(max_queue if max_queue is not None
                             else max(4 * self.num_slots, 16))

        # -- device + scheduler state (owned by the worker thread) ---------
        self._cache = PagedKVCache(self.programs.cache_shape,
                                   self.programs.cache_dtype,
                                   num_slots=self.num_slots,
                                   max_len=self.max_len)
        self._prefix = RadixPrefixCache(self.page_tokens) \
            if self.prefix_cache else None
        self._slot_req = {}     # sid -> DecodeStream
        self._slot_pages = {}   # sid -> owned pool page ids
        self._slot_handles = {}  # sid -> radix pin handles to release
        self._cols = onp.zeros(self.num_slots, dtype="int32")
        # how many ticks may stay dispatched and unread: the draft of
        # K > 1 reads the accepted tokens on the host, K == 1 needs none
        self._depth = 1 if self.speculate_k == 1 else 0
        self._inflight = deque()   # _Flight, oldest first
        # each slot's next input token: K == 1 keeps it on the device (the
        # (S, 1) output of the last tick or place program), K > 1 on the
        # host, where the draft is appended
        self._next_tok = None
        self._last_tok = onp.zeros(self.num_slots, dtype="int32")

        self._q = queue.SimpleQueue()
        self._worker = None
        self._worker_lock = threading.Lock()
        self._closed = False
        self._dead = None        # scheduler crash exception, once fatal
        self._draining = False   # drain(): shed new submits, finish live

        # transient program-run failures retry before the crash path
        self._retries = _env_int("MXTPU_SERVE_RETRIES", 2)
        self._retry_backoff_ms = _env_int("MXTPU_SERVE_RETRY_BACKOFF_MS", 10)
        self._retry_max_ms = _env_int("MXTPU_SERVE_RETRY_MAX_MS", 1000)

        self._health_name = f"decode_engine:{id(self):x}"
        _tm.register_health(self._health_name, self._health)

        # stall heartbeats around the device syncs — where a hung chip
        # manifests on this path — plus the tokens/s window (single-device
        # engine: per-chip == total)
        self._hb_prefill = _tm.stall_heartbeat("serve.prefill")
        self._hb_tick = _tm.stall_heartbeat("serve.decode_tick")
        self._tps_mark = None

        # -- accounting (always on: these ARE the serving stats) -----------
        self._stats_lock = threading.Lock()
        self._n_requests = 0
        self._n_completed = 0
        self._n_shed = 0
        self._n_evicted = 0
        self._n_tokens = 0
        self._n_ticks = 0
        self._n_overlapped = 0
        self._n_prefills = 0
        self._n_starved = 0
        self._n_prefix_hit_tokens = 0
        self._occupancy_sum = 0.0
        self._pending_count = 0
        self._ttft_ms = Histogram("serve.ttft_ms")
        self._tpot_ms = Histogram("serve.tpot_ms")
        self._spec_accept = Histogram("serve.spec_accept_len")

        if manifest_dict is not None:
            self.warmup()

    # ------------------------------------------------------------- warmup
    def warmup(self, manifest_path=None):
        """Precompile decode_tick_k + every (batch, len) prefill (and
        prefix-join) bucket; optionally write a manifest. After this the
        scheduler compiles nothing, whatever traffic arrives (asserted
        via the jit compile counter in tests/test_decode.py). Returns the
        manifest dict."""
        import json

        self.programs.warmup()
        manifest = self.programs.manifest_dict(cache_dir=self.cache_dir)
        if manifest_path:
            tmp = manifest_path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(manifest, fh, indent=1)
            os.replace(tmp, manifest_path)
        return manifest

    def export(self, prefix):
        """Serialize the traced graphs + params + manifest (see
        ``DecodePrograms.export``); returns the manifest path."""
        return self.programs.export(prefix)

    @classmethod
    def from_export(cls, prefix, **kwargs):
        """Rebuild a serving engine from ``export`` artifacts — no model
        class needed; with the persistent compile cache on, no XLA
        compiles either. Extra kwargs pass through (scheduler knobs)."""
        progs = DecodePrograms.from_export(prefix)
        eng = cls(programs=progs, **kwargs)
        eng.warmup()
        return eng

    # ------------------------------------------------------------- submit
    def submit(self, prompt, max_new_tokens=20, deadline_ms=None,
               on_token=None):
        """Enqueue one prompt; returns a :class:`DecodeStream`.

        Raises :class:`ShedError` immediately when the pending queue is
        at budget. ``deadline_ms`` (engine default when None, 0 = none)
        bounds TOTAL time: a request that can't start in time is shed,
        one that can't finish is evicted with partial output.
        """
        if self._dead is not None:
            raise EngineDeadError(
                f"DecodeEngine scheduler crashed: {self._dead!r}"
            ) from self._dead
        if self._closed:
            raise MXNetError("DecodeEngine is closed")
        if self._draining:
            with self._stats_lock:
                self._n_requests += 1
            self._shed_one()
            if self._tm.ON:
                self._tm.REGISTRY.counter("serve.requests").inc()
            raise ShedError(
                "DecodeEngine is draining: new work is shed until "
                "resume()")
        toks = self._normalize_prompt(prompt)
        if max_new_tokens < 1:
            raise MXNetError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        trace = self._tm.new_trace("serve.decode")
        with self._stats_lock:
            self._n_requests += 1
            over = self._pending_count >= self.max_queue
            if not over:
                self._pending_count += 1
        if self._tm.ON:
            self._tm.REGISTRY.counter("serve.requests").inc()
        if over:
            self._shed_one()
            self._tm.finish_trace(trace, status="shed")
            raise ShedError(
                f"decode queue at budget ({self.max_queue} pending); "
                "retry later or raise max_queue")
        dl_ms = self.deadline_ms if deadline_ms is None else int(deadline_ms)
        deadline = (time.perf_counter() + dl_ms * 1e-3) if dl_ms > 0 else None
        # clip generation to cache capacity: the last token's KV lands at
        # position len(prompt) + max_new - 2, which must stay < max_len
        budget = self.max_len - len(toks) + 1
        stream = DecodeStream(toks, min(int(max_new_tokens), budget),
                              deadline, on_token)
        stream.trace = trace
        if trace is not None:
            trace.trace_id = stream.rid
        if stream.max_new_tokens < max_new_tokens:
            stream.truncated = True
        self._start_worker()
        self._q.put(stream)
        return stream

    def _normalize_prompt(self, prompt):
        from ...ndarray.ndarray import NDArray

        if isinstance(prompt, NDArray):
            prompt = onp.asarray(prompt._data)
        toks = [int(t) for t in onp.asarray(prompt).reshape(-1)]
        if not toks:
            raise MXNetError("cannot decode from an empty prompt")
        if len(toks) > self.max_prompt_len:
            raise MXNetError(
                f"prompt length {len(toks)} exceeds max_prompt_len "
                f"{self.max_prompt_len}")
        return toks

    # ---------------------------------------------------------- scheduler
    def _start_worker(self):
        if self._worker is not None:
            return
        with self._worker_lock:
            if self._worker is None:
                t = threading.Thread(target=self._loop,
                                     name="mxtpu-decode-engine",
                                     daemon=True)
                self._worker = t
                t.start()

    def _loop(self):
        pending = deque()
        crash = None
        try:
            # the spans below and in what this calls are flat leaves that
            # tile this thread's time (telemetry/spans.py)
            while not self._gather(pending):
                with span("serve.expire"):
                    self._expire(pending)
                self._admit(pending)
                if self._slot_req:
                    self._tick()
                # the tick just dispatched stays in flight (K == 1) while
                # a slot it left live waits for the next; all before it is
                # read back and committed while the device runs it
                self._settle(keep=self._depth if self._slot_req else 0)
            self._settle()      # closing: deliver what was computed
        except BaseException as e:  # noqa: BLE001 — converted, never lost
            crash = e
        finally:
            if crash is not None:
                self._scheduler_crashed(crash, pending)
            else:
                self._drain(pending)

    def _scheduler_crashed(self, exc, pending):
        """Fatal scheduler error: mark dead, fail every stream with the
        real cause, flip the health check (→ /healthz 503)."""
        self._dead = exc
        self._closed = True
        tm = self._tm
        tm.REGISTRY.counter("serve.scheduler_crashes").inc()
        if tm.ON:
            tm.event("serve.scheduler_crash", error=repr(exc))
        err = EngineDeadError(
            f"DecodeEngine scheduler crashed: {exc!r}")
        err.__cause__ = exc
        self._drain(pending, err=err, status="error")

    def _run_retry(self, key, args, point):
        """One AOT program run behind the transient-failure retry policy:
        up to ``MXTPU_SERVE_RETRIES`` retries with exponential backoff
        capped at ``MXTPU_SERVE_RETRY_MAX_MS``; ``point`` is also a chaos
        injection site. Exhaustion re-raises into the crash path."""
        attempt = 0
        site = self.programs._site(key)
        self._tm.check_memory_admission(site)
        while True:
            try:
                chaos.fault_point(point)
                return self.programs.run(key, args)
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:  # noqa: BLE001 — bounded retries
                # a device OOM is not transient: dump the ledger once and
                # skip the retry storm — the crash path reports upward
                if self._tm.memory_oom_forensics(site, e):
                    raise
                if attempt >= self._retries:
                    raise
                attempt += 1
                tm = self._tm
                tm.REGISTRY.counter("serve.retries").inc()
                if tm.ON:
                    tm.event("serve.retry", point=point, attempt=attempt,
                             error=repr(e))
                delay_ms = min(self._retry_backoff_ms * (1 << (attempt - 1)),
                               self._retry_max_ms)
                time.sleep(delay_ms * 1e-3)

    def _gather(self, pending):
        """Pull new requests off the queue. Blocks when fully idle;
        otherwise drains without waiting (the decode tick itself is the
        coalescing window once slots are live). Returns True on STOP."""
        idle = not self._slot_req and not pending and not self._inflight
        with span("serve.wait_queue" if idle else "serve.gather") as sp:
            n0 = len(pending)
            stop = self._take(pending, idle)
            sp.note(n=len(pending) - n0)
        return stop

    def _take(self, pending, idle):
        try:
            item = self._q.get() if idle else self._q.get_nowait()
        except queue.Empty:
            return False
        if item is _STOP:
            return True
        pending.append(item)
        if idle and self.max_wait_us > 0:
            # a burst is likely arriving together: hold the first prefill
            # open briefly so it batches instead of running B=1
            deadline = time.perf_counter() + self.max_wait_us * 1e-6
            while len(pending) < self.prefill_batch:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    item = self._q.get(timeout=remaining)
                except queue.Empty:
                    break
                if item is _STOP:
                    return True
                pending.append(item)
        else:
            while True:
                try:
                    item = self._q.get_nowait()
                except queue.Empty:
                    break
                if item is _STOP:
                    return True
                pending.append(item)
        return False

    def _expire(self, pending):
        now = time.perf_counter()
        for stream in [s for s in pending
                       if s.deadline is not None and now > s.deadline]:
            pending.remove(stream)
            self._shed_one(admitted=True)
            self._tm.finish_trace(stream.trace, status="shed")
            stream._finish(ShedError(
                "deadline expired before the request reached a slot"))
        for sid in [s for s, st in self._slot_req.items()
                    if st.deadline is not None and now > st.deadline]:
            # a row of this slot still in flight is dropped at its commit
            self._complete(self._vacate(sid), expired=True)

    # ------------------------------------------------------ page admission
    def _alloc_pages(self, n):
        """Claim n pool pages, evicting unpinned prefix-cache pages LRU
        first when the free list is short. None when impossible now."""
        if n == 0:
            return []
        cache = self._cache
        got = cache.pages.alloc(n)
        if got is not None:
            return got
        if self._prefix is not None:
            freed = self._prefix.evict(n - cache.pages.free_count)
            if freed:
                cache.pages.free(freed)
                got = cache.pages.alloc(n)
        return got

    def _prepare(self, stream):
        """Prefix lookup + page allocation for one pending stream.
        Returns the admission meta dict, or None when pages are short
        (caller decides: wait for retirements or shed)."""
        P = self.page_tokens
        plen = len(stream.prompt)
        if self._prefix is not None:
            matched, shared, handle = self._prefix.match(stream.prompt)
        else:
            matched, shared, handle = 0, [], None
        need = -(-plen // P) - len(shared)
        own = self._alloc_pages(need)
        if own is None:
            if handle:
                self._prefix.release(handle)
            return None
        return {"start": matched, "shared": shared, "own": own,
                "handle": handle}

    def _admit(self, pending):
        cache = self._cache
        while pending and cache.slots.free_count:
            group, metas = [], []
            starved = False   # a prompt found the page pool short
            with span("serve.admit.prepare") as sp:
                while (pending and len(group) < self.prefill_batch
                       and len(group) < cache.slots.free_count):
                    meta = self._prepare(pending[0])
                    if meta is None:
                        starved = True
                        if group or self._slot_req or (
                                self._prefix is not None
                                and self._prefix.evictable_pages() > 0):
                            # pages will free up (retirements / evictions
                            # racing pins); try again next tick
                            break
                        # nothing live, nothing evictable: this prompt can
                        # never fit — shed it instead of spinning forever
                        stream = pending.popleft()
                        self._shed_one(admitted=True)
                        self._tm.finish_trace(stream.trace, status="shed")
                        stream._finish(ShedError(
                            f"kv page pool exhausted: prompt needs "
                            f"{-(-len(stream.prompt) // self.page_tokens)} "
                            f"pages, pool has {cache.pages.free_count} free "
                            f"of {self.kv_pages}"))
                        continue
                    group.append(pending.popleft())
                    metas.append(meta)
                sp.note(n=len(group), starved=int(starved))
            if not group:
                break
            # plain and join prefills are separate program families —
            # dispatch each subgroup through its own bucket
            plain = [(s, m) for s, m in zip(group, metas)
                     if m["start"] == 0]
            ext = [(s, m) for s, m in zip(group, metas) if m["start"] > 0]
            for sub in (plain, ext):
                if not sub:
                    continue
                try:
                    self._prefill(sub)
                except BaseException:
                    # hand the subgroup back so the crash path fails
                    # these streams with the real error
                    pending.extendleft(s for s, _ in reversed(sub))
                    raise

    def _prefill(self, sub):
        import jax

        cache = self._cache
        ext = sub[0][1]["start"] > 0
        with span("serve.prefill.host") as sp:
            slots = [cache.slots.alloc() for _ in sub]
            B = pick_bucket(len(sub), self.programs.batch_ladder)
            T = pick_bucket(max(len(s.prompt) - m["start"] for s, m in sub),
                            self.programs.len_ladder)
            tokens = onp.zeros((B, T), dtype="int32")
            valid = onp.ones((B,), dtype="int32")
            start = onp.zeros((B,), dtype="int32")
            table = onp.full((B, cache.pages_per_slot + 1), cache.trash,
                             dtype="int32")
            t_q = time.perf_counter()  # queue phase: submit -> prefill pickup
            for i, ((stream, meta), sid) in enumerate(zip(sub, slots)):
                row = meta["shared"] + meta["own"]
                cache.table[sid, :] = cache.trash
                cache.table[sid, :len(row)] = row
                self._cols[sid] = len(row)
                self._slot_pages[sid] = list(meta["own"])
                self._slot_handles[sid] = [meta["handle"]] \
                    if meta["handle"] else []
                suffix = stream.prompt[meta["start"]:]
                tokens[i, :len(suffix)] = suffix
                valid[i] = len(suffix)
                start[i] = meta["start"]
                table[i] = cache.table[sid]
                if stream.trace is not None:
                    stream.trace.mark("queue", t_q)
            kind = "prefill_ext" if ext else "prefill"
            key = (kind, B, T)
            self.programs.ensure(kind, batch=B, length=T)
            # ids are space-separated: the profiler splits attributes at
            # commas
            sp.note(batch=B, length=T,
                    rids=" ".join(str(s.rid) for s, _ in sub),
                    queue_wait_ms_max=max(
                        t_q - s.t_submit for s, _ in sub) * 1e3)
        with span("serve.prefill.dispatch", batch=B, length=T) as sp:
            args = [jax.device_put(tokens), jax.device_put(valid)]
            if ext:
                args.append(jax.device_put(start))
            args += [jax.device_put(table), cache.k, cache.v]
            outs = self._run_retry(key, args, point="decode.prefill")
            cache.rebind(outs[1], outs[2])
            if self._depth:
                # the first tokens go from this program's output to the
                # next tick's operand without passing through the host
                if self._next_tok is None:
                    self._next_tok = self.programs.token_vector()
                self._next_tok = self.programs.place(
                    self._next_tok, outs[0],
                    slots + [self.num_slots] * (B - len(slots)))
            ends = self._prefill_plan(sub, slots)
        self._inflight.append(_Flight(
            False, outs[0],
            [(i, sid, s) for i, ((s, _), sid) in enumerate(zip(sub, slots))],
            ends, sp))
        if not self._depth:
            self._settle()      # the draft reads the first token

    def _prefill_plan(self, sub, slots):
        """The slots' bookkeeping for a prefill just dispatched: nothing
        of it needs the first tokens' values. Returns the ``ends`` of the
        program's record (a request that asked for one token)."""
        cache = self._cache
        P = self.page_tokens
        tm = self._tm
        ends = {}
        with self._stats_lock:
            self._n_prefills += 1
            self._pending_count -= len(sub)
        for (stream, meta), sid in zip(sub, slots):
            plen = len(stream.prompt)
            cache.lengths[sid] = plen
            self._slot_req[sid] = stream
            if meta["start"]:
                with self._stats_lock:
                    self._n_prefix_hit_tokens += meta["start"]
                if tm.ON:
                    tm.REGISTRY.counter("serve.prefix_hit_tokens").inc(
                        meta["start"])
                if stream.trace is not None:
                    stream.trace.extra["prefix_hit_tokens"] = meta["start"]
            if self._prefix is not None:
                # publish this prompt's full pages for future sharers;
                # adopted pages change owner (tree frees them, not us).
                # A sharer's join is dispatched after this prefill, so it
                # reads the pages written
                a0 = meta["start"] // P
                full = plen // P - a0
                offered = {a0 + t: meta["own"][t] for t in range(full)}
                handle, adopted = self._prefix.insert(stream.prompt,
                                                      offered)
                if handle:
                    self._slot_handles[sid].append(handle)
                if adopted:
                    keep = [pid for t, pid in enumerate(meta["own"])
                            if (a0 + t) not in adopted]
                    self._slot_pages[sid] = keep
            stream._dispatched = 1
            if stream.max_new_tokens <= 1:
                ends[sid] = "done"
                self._vacate(sid)
        self._set_slot_gauge()
        return ends

    def _tick(self):
        import jax

        cache = self._cache
        P = self.page_tokens
        K = self.speculate_k
        W = cache.pages_per_slot
        live = sorted(self._slot_req)
        # grow page tables to cover this tick's K write positions; a slot
        # the pool can't serve is starved: it commits at most one more
        # token and retires truncated (shed capacity, never crash)
        starved = set()
        with span("serve.tick.grow", live=len(live)) as sp:
            for sid in live:
                need = min(-(-(int(cache.lengths[sid]) + K) // P), W)
                short = need - int(self._cols[sid])
                if short > 0:
                    got = self._alloc_pages(short)
                    if got is None:
                        starved.add(sid)
                    else:
                        c = int(self._cols[sid])
                        cache.table[sid, c:c + len(got)] = got
                        self._cols[sid] = c + len(got)
                        self._slot_pages[sid].extend(got)
            self.programs.ensure("decode")
            sp.note(starved=len(starved))
        drafts = {}
        if self._depth:
            tokens = self._next_tok
        else:
            with span("serve.tick.draft"):
                tokens = onp.zeros((self.num_slots, K), dtype="int32")
                tokens[:, 0] = self._last_tok
                for sid in live:
                    stream = self._slot_req[sid]
                    d = self._draft.propose(stream.prompt + stream.tokens,
                                            K - 1)
                    drafts[sid] = d
                    tokens[sid, 1:] = d
                tokens = jax.device_put(tokens)
        with span("serve.tick.dispatch", live=len(live)) as sp:
            # lengths and table are COPIED: the host's arrays move on
            # before the device has taken these
            outs = self._run_retry(("decode", K), [
                tokens, jax.device_put(cache.lengths.copy()),
                jax.device_put(cache.table.copy()), cache.k, cache.v],
                point="decode.tick")
            cache.rebind(outs[1], outs[2])
            rec = _Flight(True, outs[0],
                          [(sid, sid, self._slot_req[sid]) for sid in live],
                          {}, sp, drafts, starved,
                          occupancy=len(live) / self.num_slots,
                          overlapped=any(f.tick for f in self._inflight))
            if self._depth:
                # every row yields exactly one token: account for it now,
                # so that the next tick can be dispatched without it
                self._next_tok = outs[0]
                for _, sid, stream in rec.rows:
                    self._advance(rec, sid, stream, 1)
        self._inflight.append(rec)

    def _advance(self, rec, sid, stream, m):
        """Account ``m`` more tokens of tick ``rec`` for the slot's
        request. A request that ends with them is noted in ``rec.ends``
        and gives its slot back."""
        cache = self._cache
        cache.lengths[sid] += m
        stream._dispatched += m
        if stream._dispatched >= stream.max_new_tokens:
            end = "done"
        elif sid in rec.starved:
            end = "starved"
        elif cache.lengths[sid] >= cache.max_len:
            end = "truncated"
        else:
            return
        rec.ends[sid] = end
        self._vacate(sid)

    def _settle(self, keep=0):
        """Read back and commit the programs in flight, oldest first, all
        but the newest ``keep``. A failed program raises HERE, at its
        read-back; its record stays in ``_inflight`` until committed, so
        the crash path finds every stream it carries."""
        tm = self._tm
        while len(self._inflight) > keep:
            rec = self._inflight[0]
            wait, call, commit, hb = (
                ("serve.wait_tick", "serve.decode_tick.call",
                 "serve.tick.commit", self._hb_tick) if rec.tick else
                ("serve.wait_prefill", "serve.prefill.call",
                 "serve.prefill.commit", self._hb_prefill))
            hb_on = tm.ON
            if hb_on:
                hb.begin()
            try:
                with span(wait, timer=call, after=rec.span):
                    rows = onp.asarray(rec.out)   # device sync: the tokens
            finally:
                if hb_on:
                    hb.end()
            with span(commit) as sp:
                n0 = self._n_tokens
                self._commit(rec, rows)
                sp.note(tokens=self._n_tokens - n0)
            self._inflight.popleft()

    def _commit(self, rec, rows):
        """Deliver a read-back program's tokens: emit (the clients'
        ``on_token`` runs here) and finish the requests that end with
        them. A row whose request finished meanwhile (evicted by its
        deadline) is dropped."""
        cache = self._cache
        tm = self._tm
        rows = rows.reshape(len(rows), -1)      # a prefill's are (B,)
        if tm.ON:
            tm.record_dispatch()
        if rec.tick:
            with self._stats_lock:
                self._n_ticks += 1
                self._n_overlapped += rec.overlapped
                self._occupancy_sum += rec.occupancy
            if rec.overlapped and tm.ON:
                tm.REGISTRY.counter("serve.ticks_overlapped").inc()
        for i, sid, stream in rec.rows:
            if stream.done:
                continue
            if not rec.tick or self._depth:
                toks = [int(rows[i, 0])]
            else:
                m = accept_longest_prefix(rec.drafts[sid], rows[i])
                self._spec_accept.record(m)
                if tm.ON:
                    tm.REGISTRY.histogram("serve.spec_accept_len").record(m)
                m = min(m, stream.max_new_tokens - len(stream.tokens),
                        cache.max_len - int(cache.lengths[sid]))
                if sid in rec.starved:
                    m = min(m, 1)
                toks = [int(t) for t in rows[i][:m]]
                self._advance(rec, sid, stream, m)
            if not self._depth:
                self._last_tok[sid] = toks[-1]
            self._emit_tokens(stream, toks)
            end = rec.ends.get(sid)
            if end:
                if end != "done":
                    stream.truncated = True
                if end == "starved":
                    with self._stats_lock:
                        self._n_starved += 1
                    if tm.ON:
                        tm.REGISTRY.counter("serve.kv_page_starved").inc()
                self._complete(stream)
        if rec.tick and tm.ON:
            # tokens/s/chip over a ~0.5 s window (single-device engine:
            # chips == 1, so per-chip is the engine rate)
            nowt = time.perf_counter()
            if self._tps_mark is None:
                self._tps_mark = (nowt, self._n_tokens)
            else:
                t0, n0 = self._tps_mark
                if nowt - t0 >= 0.5:
                    tm.REGISTRY.gauge("serve.tokens_per_s_chip").set(
                        (self._n_tokens - n0) / (nowt - t0))
                    self._tps_mark = (nowt, self._n_tokens)

    def _emit_tokens(self, stream, toks):
        """Emit a committed token run. The first token ever is TTFT; a
        multi-token (speculative) commit spreads the tick's wall time
        evenly across its tokens, so TPOT honestly reflects the
        amortized per-token latency."""
        if not toks:
            return
        now = time.perf_counter()
        tm = self._tm
        n = len(toks)
        i0 = 0
        if stream._t_last is None:
            ms = (now - stream.t_submit) * 1e3
            self._ttft_ms.record(ms)
            if stream.trace is not None:
                # prefill phase: picked up -> first token on host
                stream.trace.mark("prefill", now)
                stream.trace.extra["ttft_ms"] = ms
            if tm.ON:
                tm.REGISTRY.histogram("serve.ttft_ms").record(ms)
            i0 = 1
        if n - i0 > 0:
            ms = (now - stream._t_last) * 1e3 / (n - i0) \
                if stream._t_last is not None else 0.0
            for _ in range(n - i0):
                self._tpot_ms.record(ms)
                if tm.ON:
                    tm.REGISTRY.histogram("serve.tpot_ms").record(ms)
        stream._t_last = now
        with self._stats_lock:
            self._n_tokens += n
        if tm.ON:
            tm.REGISTRY.counter("serve.tokens_total").inc(n)
        for tok in toks:
            stream._emit(tok)

    def _vacate(self, sid):
        """Give the slot, its pages and its prefix pins back; returns the
        request that held it. Safe while programs that name them are in
        flight: every program takes the pool pair from the program
        dispatched before it, so whoever is handed a page next writes it
        only after every earlier program is done with it."""
        cache = self._cache
        stream = self._slot_req.pop(sid)
        cache.slots.free(sid)
        cache.reset_row(sid)
        self._cols[sid] = 0
        owned = self._slot_pages.pop(sid, [])
        if owned:
            cache.pages.free(owned)
        for handle in self._slot_handles.pop(sid, []):
            self._prefix.release(handle)
        self._set_slot_gauge()
        return stream

    def _complete(self, stream, expired=False):
        """Finish a request whose slot is vacated: its last token is
        emitted (or it is evicted with what it has)."""
        stream.expired = expired
        if stream.trace is not None:
            stream.trace.mark("decode")  # first token -> generation done
            stream.trace.extra["tokens"] = len(stream.tokens)
            if stream.truncated:
                stream.trace.extra["truncated"] = True
        self._tm.finish_trace(stream.trace,
                              status="evicted" if expired else "completed")
        with self._stats_lock:
            self._n_completed += 1
            if expired:
                self._n_evicted += 1
        if expired and self._tm.ON:
            self._tm.REGISTRY.counter("serve.evict_total").inc()
        stream._finish()

    def _shed_one(self, admitted=False):
        with self._stats_lock:
            self._n_shed += 1
            if admitted:
                self._pending_count -= 1
        if self._tm.ON:
            self._tm.REGISTRY.counter("serve.shed_total").inc()

    def _set_slot_gauge(self):
        if self._tm.ON:
            self._tm.REGISTRY.gauge("serve.slots_live").set(
                len(self._slot_req))
            # KV residency for the memory ledger: pool bytes are static
            # per engine build (the gauge keys the ledger's kv line);
            # pages_live tracks actual token residency inside the pool
            self._tm.REGISTRY.gauge("mem.kv_cache_bytes").set(
                self._cache.nbytes)
            self._tm.REGISTRY.gauge("serve.kv_pages_live").set(
                self._cache.pages_live())

    def _drain(self, pending, err=None, status="closed"):
        if err is None:
            err = MXNetError("DecodeEngine closed before completion")
        for sid in list(self._slot_req):
            stream = self._slot_req.pop(sid)
            self._cache.slots.free(sid)
            self._tm.finish_trace(stream.trace, status=status)
            stream._finish(err)
        # requests whose last tokens were dispatched and never read back
        # hold no slot any more: only these records know them
        while self._inflight:
            for _, _, stream in self._inflight.popleft().rows:
                if not stream.done:
                    self._tm.finish_trace(stream.trace, status=status)
                    stream._finish(err)
        for stream in pending:
            self._shed_one(admitted=True)
            self._tm.finish_trace(stream.trace, status=status)
            stream._finish(err)
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is not _STOP:
                self._shed_one(admitted=True)
                self._tm.finish_trace(item.trace, status=status)
                item._finish(err)

    # ----------------------------------------------------------- reporting
    def stats(self):
        """Engine accounting independent of the global telemetry gate.
        ``ticks``, ``ticks_overlapped``, ``mean_slot_occupancy`` and
        ``tokens`` count what has been read back; an idle engine has
        nothing in flight (the scheduler settles it before it waits for
        work), so after the last ``result()`` they count everything."""
        with self._stats_lock:
            ticks = self._n_ticks
            occ = self._occupancy_sum / ticks if ticks else 0.0
            out = {
                "requests": self._n_requests,
                "completed": self._n_completed,
                "shed": self._n_shed,
                "evicted": self._n_evicted,
                "tokens": self._n_tokens,
                "ticks": ticks,
                "ticks_overlapped": self._n_overlapped,
                "prefills": self._n_prefills,
                "pending": self._pending_count,
                "prefix_hit_tokens": self._n_prefix_hit_tokens,
                "page_starved": self._n_starved,
            }
        p50, p99 = self._ttft_ms.percentiles(50, 99)
        out["ttft_ms_p50"], out["ttft_ms_p99"] = p50, p99
        p50, p99 = self._tpot_ms.percentiles(50, 99)
        out["tpot_ms_p50"], out["tpot_ms_p99"] = p50, p99
        out["mean_slot_occupancy"] = occ
        out["slots_live"] = len(self._slot_req)
        out["num_slots"] = self.num_slots
        out["cache_bytes"] = self._cache.nbytes
        out["page_tokens"] = self.page_tokens
        out["kv_pages"] = self.kv_pages
        out["kv_pages_live"] = self._cache.pages_live()
        out["speculate_k"] = self.speculate_k
        if self.speculate_k > 1:
            out["spec_accept_mean"] = self._spec_accept.mean
            out["tokens_per_tick"] = (out["tokens"] / ticks) if ticks \
                else 0.0
        out["prefix_cache"] = self._prefix.stats() \
            if self._prefix is not None else None
        out["dead"] = self._dead is not None
        out["draining"] = self._draining
        out["programs"] = sorted(
            "|".join(str(k) for k in key)
            for key in self.programs._programs)
        return out

    # -------------------------------------------------------------- health
    def _health(self):
        if self._dead is not None:
            return False, f"scheduler crashed: {self._dead!r}"
        return True, {"slots_live": len(self._slot_req),
                      "draining": self._draining}

    @property
    def healthy(self):
        return self._dead is None

    # ---------------------------------------------------------- drain/resume
    def drain(self, timeout=None):
        """Shed new submissions (``ShedError``) while already-accepted
        work — live slots, tokens in flight AND queued-but-unslotted
        requests — runs to completion. Blocks until idle (or ``timeout``
        seconds); returns True when fully drained. ``resume()`` reopens
        the gate."""
        self._draining = True
        deadline = None if timeout is None \
            else time.perf_counter() + float(timeout)
        while True:
            with self._stats_lock:
                pending = self._pending_count
            idle = not self._slot_req and not self._inflight
            if idle and pending <= 0:
                return True
            if self._dead is not None or self._closed:
                return idle
            if deadline is not None and time.perf_counter() > deadline:
                return False
            time.sleep(0.002)

    def resume(self):
        """Accept submissions again after :meth:`drain`."""
        self._draining = False

    # ------------------------------------------------------------ lifecycle
    def close(self):
        """Stop the scheduler (idempotent). Live and queued streams
        finish with an error; later ``submit`` raises."""
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
