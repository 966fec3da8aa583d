"""mxnet_tpu.telemetry — process-wide runtime observability.

A thread-safe registry of counters/gauges/timers, a bounded structured
event log (JSONL + chrome://tracing export merging profiler host spans),
a per-step accountant (``step_report()``), and a recompile watchdog over
every jit compile site (``Op`` fns, ``CachedOp`` programs, the fused
``Trainer.step``). See docs/DESIGN.md "Observability".

Gating: ``MXNET_TELEMETRY=1`` in the environment or ``telemetry.enable()``.
The contract when OFF is near-zero overhead: every instrumentation site in
the hot paths guards on the module-level ``ON`` bool (one attribute read),
and the compile observers live INSIDE jitted function bodies, so they cost
nothing per call — only per trace, and even then they short-circuit on
``ON``.

Typical use::

    from mxnet_tpu import telemetry
    telemetry.enable()
    ...train...
    for row in telemetry.step_report():
        print(row["step"], row["dispatches"], row["recompiles"],
              row["comm_bytes"], row["host_time"])
    telemetry.dump_events("events.jsonl")
    telemetry.export_chrome_trace("trace.json")
"""
from __future__ import annotations

import os

from .events import EventLog
from .registry import Counter, Gauge, Histogram, Registry, Timer
from .step import StepTracker
from .trace import RequestTrace, TraceCollector
from .watchdog import Watchdog, format_signature
from .monitor import Monitor
from .stall import StallMonitor
from . import costs as _costs
from . import memory as _memory
from . import numerics as _numerics
from . import moe as _moe

__all__ = ["enable", "disable", "is_enabled", "configure", "reset",
           "counter", "gauge", "timer", "histogram", "metrics", "event",
           "events", "dump_events", "export_chrome_trace", "mark_step",
           "program_timer", "span", "SPANS", "step_report", "last_step",
           "watchdog_stats",
           "record_fsdp", "record_flops", "record_program_cost",
           "new_trace", "finish_trace", "traces", "latency_report",
           "cost_report", "program_costs", "device_peak_flops",
           "record_program_memory", "program_memory", "memory_report",
           "check_memory_admission", "memory_oom_forensics",
           "memory_ledger_text", "numerics_mode", "record_step_health",
           "numerics_report", "moe_report",
           "start_exporter", "stop_exporter", "exporter_url",
           "stall_heartbeat", "start_stall_watchdog", "stop_stall_watchdog",
           "stall_stats",
           "register_health", "unregister_health", "health_checks",
           "Monitor", "Counter", "Gauge", "Timer", "Histogram", "Registry",
           "RequestTrace", "StallMonitor", "format_signature"]

# THE gate. Instrumentation sites read this module attribute directly
# (``if _telemetry.ON:``) — rebinding a module-level bool is the cheapest
# toggle Python offers short of code patching.
ON = False

REGISTRY = Registry()
EVENTS = EventLog()
WATCHDOG = Watchdog(warmup_steps=1)
STEPS = StepTracker(REGISTRY)
TRACES = TraceCollector()
from .stall import monitor_from_env as _monitor_from_env  # noqa: E402

STALL = _monitor_from_env()
EXPORTER = None  # created by start_exporter() / MXTPU_METRICS_PORT

# monotonic stamp of the last compute dispatch (any site): /healthz turns
# it into seconds-since-last-dispatch, the cheapest liveness signal a
# hung device produces. One-element list so record_dispatch stays a store,
# not a global rebind.
_LAST_DISPATCH = [0.0]

# pre-resolved hot metrics: the dispatch chokepoint and the byte counters
# must not pay a dict lookup per call
_C_DISPATCH = REGISTRY.counter("ops.dispatches")
_C_COMPILES = REGISTRY.counter("jit.compiles")
_C_RECOMPILES = REGISTRY.counter("jit.recompiles")
_C_PUSH_BYTES = REGISTRY.counter("kvstore.push_bytes")
_C_PULL_BYTES = REGISTRY.counter("kvstore.pull_bytes")
# in-program collective traffic (reduce_scatter / all_gather / psum): the
# collectives run inside compiled programs where the host cannot observe
# them, so the dispatch sites report the statically-known per-call bytes
_C_RS_BYTES = REGISTRY.counter("collective.reduce_scatter_bytes")
_C_AG_BYTES = REGISTRY.counter("collective.all_gather_bytes")
_C_PSUM_BYTES = REGISTRY.counter("collective.psum_bytes")
# the same traffic attributed per mesh axis: 'dp' carries the data-parallel
# schedule (FSDP gathers/scatters, grad all_reduces), 'tp' the in-layer
# megatron psums/gathers, 'pp' the stage-boundary activation sends
_C_AXIS_DP_BYTES = REGISTRY.counter("collective_bytes.dp")
_C_AXIS_TP_BYTES = REGISTRY.counter("collective_bytes.tp")
_C_AXIS_PP_BYTES = REGISTRY.counter("collective_bytes.pp")
# statically-known program cost, credited at dispatch time from the
# per-program cost table (telemetry/costs.py)
_C_FLOPS = REGISTRY.counter("telemetry.flops")
_C_BYTES_ACCESSED = REGISTRY.counter("telemetry.bytes_accessed")


# -- gating -----------------------------------------------------------------
def enable():
    """Turn telemetry on process-wide (idempotent)."""
    global ON
    ON = True


def disable():
    global ON
    ON = False


def is_enabled():
    return ON


def configure(watchdog_warmup_steps=None, max_events=None):
    """Tune the layer. ``watchdog_warmup_steps``: marked steps before the
    watchdog arms (0 = warn on any recompile immediately). ``max_events``:
    rebound the event buffer (drops existing events)."""
    global EVENTS
    if watchdog_warmup_steps is not None:
        WATCHDOG.warmup_steps = int(watchdog_warmup_steps)
    if max_events is not None:
        EVENTS = EventLog(maxlen=int(max_events))


def reset():
    """Zero all metrics, events, step rows, traces and watchdog state
    (metric objects stay valid — hot sites hold direct references). The
    program cost table survives: it mirrors compiled programs, which a
    reset does not discard."""
    REGISTRY.reset()
    EVENTS.clear()
    STEPS.reset()
    WATCHDOG.reset()
    TRACES.clear()
    # numerics host state mirrors the zeroed counters; the memory table
    # (like costs) mirrors compiled programs and survives
    _numerics.reset_numerics()


# -- metric access ----------------------------------------------------------
def counter(name) -> Counter:
    return REGISTRY.counter(name)


def gauge(name) -> Gauge:
    return REGISTRY.gauge(name)


def timer(name) -> Timer:
    return REGISTRY.timer(name)


def histogram(name) -> Histogram:
    return REGISTRY.histogram(name)


def metrics() -> dict:
    """Plain-value snapshot of every metric."""
    return REGISTRY.snapshot()


# -- events -----------------------------------------------------------------
def event(name, kind="instant", **fields):
    if ON:
        EVENTS.emit(name, kind=kind, **fields)


def events():
    return EVENTS.events()


def dump_events(path):
    """Write the event buffer as JSONL; returns the number of lines."""
    return EVENTS.dump_jsonl(path)


def export_chrome_trace(path, merge_profiler=True):
    """Write a chrome://tracing JSON (load in Perfetto / chrome://tracing);
    merges profiler._ranges aggregate host spans unless told otherwise."""
    return EVENTS.export_chrome_trace(path, merge_profiler=merge_profiler)


def _maybe_span(name, wall_ts, dur):
    """Timer.time() callback — module-level so registry.py can import it
    lazily without a cycle."""
    if ON:
        EVENTS.emit(name, kind="span", ts=wall_ts, dur=dur)


# -- steps ------------------------------------------------------------------
def mark_step(name=None, inner_steps=1):
    """Close one accounting step (no-op when disabled). Trainer calls this
    at the end of every ``step()``/``update()``; the scanned super-step
    passes ``inner_steps=K`` so the row carries per-inner-step averages."""
    if not ON:
        return None
    return STEPS.mark_step(name, event_log=EVENTS, inner_steps=inner_steps)


def step_report(reset=False):
    """One dict per marked step: {step, dispatches, compiles, recompiles,
    comm_bytes, kvstore_push_bytes, kvstore_pull_bytes, collective_bytes,
    reduce_scatter_bytes, all_gather_bytes, psum_bytes, host_time: {...}}."""
    return STEPS.report(reset=reset)


def last_step():
    return STEPS.last()


from .spans import SPANS, span  # noqa: E402 — needs ON and REGISTRY above


def program_timer(site, span_name=None):
    """A :func:`span` over one compiled-program call whose host time goes
    to ``<site>.compile`` or ``<site>.call``: a trace of the program
    reports record_compile synchronously inside the call, so the
    compile-counter delta tells the two apart. ``span_name`` is the
    profiler-trace name (default ``<site>.call``). Shared by CachedOp and
    the compiled train step."""
    c0 = compile_count()
    return span(span_name or site + ".call",
                timer=lambda: (site + ".compile" if compile_count() > c0
                               else site + ".call"))


# -- compile observation (called from INSIDE traced bodies) -----------------
def record_compile(site, args=None, attrs=None, sig=None):
    """Report a jit trace at ``site``. Executes only at trace time (the
    callers embed this in the traced function body); checks ``ON`` first so
    disabled-mode traces cost one bool test."""
    if not ON:
        return
    if sig is None:
        sig = format_signature(args if args is not None else (), attrs)
    WATCHDOG.record_compile(site, sig, STEPS.steps_marked,
                            _C_COMPILES, _C_RECOMPILES, event_log=EVENTS)


def record_dispatch(n=1):
    """Count a compute dispatch (callers guard on ``telemetry.ON``)."""
    import time as _time

    _C_DISPATCH.inc(n)
    _LAST_DISPATCH[0] = _time.monotonic()


def record_flops(flops, bytes_accessed=0.0):
    """Credit one dispatch's statically-known program cost (callers guard
    on ``telemetry.ON`` and pass the flops captured at compile time)."""
    if flops:
        _C_FLOPS.inc(flops)
    if bytes_accessed:
        _C_BYTES_ACCESSED.inc(bytes_accessed)


def record_comm(push_bytes=0, pull_bytes=0):
    """Count kvstore traffic (callers guard on ``telemetry.ON``)."""
    if push_bytes:
        _C_PUSH_BYTES.inc(push_bytes)
    if pull_bytes:
        _C_PULL_BYTES.inc(pull_bytes)


def record_collective(reduce_scatter_bytes=0, all_gather_bytes=0,
                      psum_bytes=0, tp_bytes=0, pp_bytes=0):
    """Count in-program collective traffic (per-replica payload bytes).

    Called at dispatch time with the statically-known sizes of the
    collectives a compiled program contains — XLA executes them where the
    host cannot count, but the program's schedule is fixed at trace time.
    The first three arguments are 'dp'-axis traffic and also feed the
    per-axis attribution (``collective_bytes.dp``); ``tp_bytes`` /
    ``pp_bytes`` attribute megatron and stage-boundary payloads to their
    axes. Callers guard on ``telemetry.ON``."""
    if reduce_scatter_bytes:
        _C_RS_BYTES.inc(reduce_scatter_bytes)
    if all_gather_bytes:
        _C_AG_BYTES.inc(all_gather_bytes)
    if psum_bytes:
        _C_PSUM_BYTES.inc(psum_bytes)
    dp_bytes = reduce_scatter_bytes + all_gather_bytes + psum_bytes
    if dp_bytes:
        _C_AXIS_DP_BYTES.inc(dp_bytes)
    if tp_bytes:
        _C_AXIS_TP_BYTES.inc(tp_bytes)
    if pp_bytes:
        _C_AXIS_PP_BYTES.inc(pp_bytes)


def record_fsdp(layer_bytes):
    """Count one dispatch's FSDP per-layer collective schedule.

    ``layer_bytes``: iterable of ``(layer, gather_bytes, scatter_bytes)``
    rows computed at build time — the just-in-time weight all_gathers and
    the gradient psum_scatters each layer's bucket performs per step.
    Schedule-level numbers (XLA may CSE re-gathers); callers guard on
    ``telemetry.ON``."""
    for layer, gather_b, scatter_b in layer_bytes:
        if gather_b:
            REGISTRY.counter(f"fsdp.gather_bytes.{layer}").inc(gather_b)
        if scatter_b:
            REGISTRY.counter(f"fsdp.scatter_bytes.{layer}").inc(scatter_b)


def compile_count():
    return _C_COMPILES.value


def watchdog_stats():
    """Per-site compile/signature counts the watchdog has observed."""
    return WATCHDOG.site_stats()


# -- per-request traces ------------------------------------------------------
def new_trace(kind):
    """A RequestTrace when telemetry is ON, else None — the disabled path
    allocates nothing (``if req.trace is not None`` is the whole cost)."""
    if not ON:
        return None
    return RequestTrace(kind)


def finish_trace(trace, status="completed"):
    """Land a finished trace in the collector (None-tolerant so serve
    paths can call it unconditionally on their request objects)."""
    if trace is not None:
        TRACES.finish(trace, status, event_log=EVENTS if ON else None)


def traces(kind=None):
    """Finished RequestTrace objects (most recent, bounded window)."""
    return TRACES.traces(kind)


def latency_report(kind=None):
    """Tail-latency attribution per request kind: total p50/p99 decomposed
    into per-phase time (queue-wait / batch-wait / compute / host for the
    Predictor; queue / prefill / decode for the decode engine)."""
    return TRACES.latency_report(kind)


# -- program cost accounting -------------------------------------------------
def record_program_cost(site, compiled):
    """Capture ``compiled.cost_analysis()`` under ``site`` (unconditional:
    compile-time only — see telemetry/costs.py)."""
    return _costs.record_program_cost(site, compiled)


def program_costs():
    return _costs.program_costs()


def cost_report():
    """Per-program flops/bytes joined with the ``<site>.call`` timers into
    achieved FLOP/s and MFU (None without a known device peak)."""
    return _costs.cost_report(REGISTRY)


# -- device-memory ledger (telemetry/memory.py) -----------------------------
def record_program_memory(site, compiled):
    """Capture ``compiled.memory_analysis()`` under ``site`` (unconditional:
    compile-time only — the memory twin of :func:`record_program_cost`)."""
    return _memory.record_program_memory(site, compiled)


def program_memory():
    return _memory.program_memory()


def memory_report(top_k=10):
    """The device-memory ledger: static per-program peaks, live-buffer
    census, device stats, KV/FSDP residency, headroom."""
    return _memory.memory_report(top_k)


def check_memory_admission(site):
    """Warn-once pre-dispatch admission check (memory.fits)."""
    return _memory.check_admission(site)


def memory_oom_forensics(site, exc):
    """Dump the ledger if ``exc`` is a device OOM; returns True when it
    fired. Callers re-raise either way."""
    return _memory.oom_forensics(site, exc)


def memory_ledger_text(top_k=10):
    return _memory.ledger_text(top_k)


# -- numerics health (telemetry/numerics.py) --------------------------------
def numerics_mode():
    """``MXTPU_NUMERICS`` → off|cheap|full (default cheap)."""
    return _numerics.mode()


def record_step_health(groups, gnorms, max_upds, nonfin, group_norms=None,
                       nmode="cheap"):
    return _numerics.record_step_health(groups, gnorms, max_upds, nonfin,
                                        group_norms, nmode)


def numerics_report():
    """Host-side summary of the in-program numerics monitor."""
    return _numerics.numerics_report()


def moe_report():
    """Expert load of the live routed layers in their last step, read from
    the device now (see telemetry/moe.py); also sets the ``moe.*`` gauges.
    None where the process holds no routed layer."""
    return _moe.report(REGISTRY)


def device_peak_flops():
    return _costs.device_peak_flops()


# -- metrics export server ---------------------------------------------------
def start_exporter(port=0, addr="127.0.0.1", snapshot_path=None,
                   snapshot_s=0.0):
    """Start (or return) the process-wide metrics HTTP server; implies
    ``enable()`` — an exporter over frozen metrics is a trap. ``port=0``
    binds an ephemeral port; read it back from the returned exporter."""
    global EXPORTER
    if EXPORTER is None:
        from .exporter import MetricsExporter

        enable()
        EXPORTER = MetricsExporter(port=port, addr=addr, registry=REGISTRY,
                                   snapshot_path=snapshot_path,
                                   snapshot_s=snapshot_s)
    return EXPORTER


def stop_exporter():
    global EXPORTER
    if EXPORTER is not None:
        EXPORTER.close()
        EXPORTER = None


def exporter_url():
    return EXPORTER.url if EXPORTER is not None else None


# -- component health registry -----------------------------------------------
# Long-lived components (DecodeEngine scheduler, Predictor dispatcher,
# CheckpointManager) register a liveness check; /healthz folds them in and
# returns 503 while any check fails — the serving self-healing contract's
# externally visible half. Checks run on the exporter's request thread, so
# they must be cheap flag reads.
import threading as _threading  # noqa: E402

_HEALTH_LOCK = _threading.Lock()
_HEALTH = {}  # name -> callable returning (ok: bool, detail)


def register_health(name, check):
    """Register ``check() -> (ok, detail)`` under ``name`` (idempotent:
    re-registering a name replaces the check). Components unregister in
    their ``close()``."""
    with _HEALTH_LOCK:
        _HEALTH[name] = check


def unregister_health(name):
    with _HEALTH_LOCK:
        _HEALTH.pop(name, None)


def health_checks():
    """{name: {"ok": bool, "detail": ...}} over every registered check; a
    check that raises reports unhealthy with the exception as detail."""
    with _HEALTH_LOCK:
        items = list(_HEALTH.items())
    out = {}
    for name, check in items:
        try:
            ok, detail = check()
        except Exception as e:  # noqa: BLE001 — a broken check is unhealthy
            ok, detail = False, f"health check raised: {e!r}"
        out[name] = {"ok": bool(ok), "detail": detail}
    return out


# -- stall watchdog ----------------------------------------------------------
def stall_heartbeat(name):
    """The named Heartbeat for a device-blocking site (creates on first
    use). Sites guard begin/end on ``telemetry.ON``."""
    return STALL.heartbeat(name)


def start_stall_watchdog(timeout_s=None, p99_multiple=None, min_samples=None,
                         floor_s=None, check_interval_s=None):
    """Arm the stall monitor thread; implies ``enable()`` (heartbeats are
    recorded only when telemetry is on)."""
    STALL.configure(timeout_s=timeout_s, p99_multiple=p99_multiple,
                    min_samples=min_samples, floor_s=floor_s,
                    check_interval_s=check_interval_s)
    enable()
    return STALL.start()


def stop_stall_watchdog():
    STALL.stop()


def stall_stats():
    return STALL.stats()


if os.environ.get("MXNET_TELEMETRY", "").lower() in ("1", "true", "on"):
    enable()

# production switches: a set MXTPU_METRICS_PORT starts the exporter at
# import, a set MXTPU_STALL_TIMEOUT_S arms the stall monitor — both imply
# enable(). Unset (the default) costs nothing: no thread, no socket.
if os.environ.get("MXTPU_METRICS_PORT"):
    from .exporter import exporter_from_env as _exporter_from_env

    EXPORTER = _exporter_from_env()
    if EXPORTER is not None:
        enable()
if os.environ.get("MXTPU_STALL_TIMEOUT_S"):
    enable()
    STALL.start()
