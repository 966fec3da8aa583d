"""Per-request tracing: one ``RequestTrace`` per serve request.

A trace is a trace id plus an ordered list of phase marks. Each mark
closes the phase *ending* at that instant, so the phase durations are the
gaps between consecutive marks — the decomposition sums EXACTLY to the
total by construction (no double counting, no gaps). Predictor requests
mark ``queue`` (picked up by the batcher) → ``batch`` (coalescing ended,
dispatch begins) → ``compute`` (device results on host) → ``host``
(unpad + unflatten done); decode requests mark ``queue`` (prefill picked
the stream up) → ``prefill`` (first token emitted) → ``decode`` (finish).

Traces are allocated only when telemetry is ON (``telemetry.new_trace``
returns None otherwise — the disabled path allocates nothing) and land in
a bounded collector on finish, where ``latency_report()`` decomposes
p50/p99 into per-phase time and the chrome-trace export gains one span
per phase on a ``trace`` lane.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time

__all__ = ["RequestTrace", "TraceCollector"]

_IDS = itertools.count(1)


def next_id():
    """The next request identifier; traces and the decode engine's streams
    draw from the one counter."""
    return next(_IDS)


class RequestTrace:
    """Phase timestamps for one request. Not thread-safe per instance —
    each request is owned by one pipeline stage at a time (queue → batcher
    → resolver), which is the serve architecture's own invariant."""

    __slots__ = ("trace_id", "kind", "wall0", "t0", "marks", "status",
                 "extra")

    def __init__(self, kind):
        self.trace_id = next_id()
        self.kind = kind
        self.wall0 = time.time()
        self.t0 = time.perf_counter()
        self.marks = []          # [(phase, perf_counter_t), ...]
        self.status = None       # set on finish
        self.extra = {}

    def mark(self, phase, t=None):
        """Close the phase ending now (or at ``t``, a perf_counter stamp
        shared across a batch so siblings agree on the boundary)."""
        self.marks.append((phase, time.perf_counter() if t is None else t))

    @property
    def total_s(self):
        return (self.marks[-1][1] - self.t0) if self.marks else 0.0

    def spans(self):
        """{phase: seconds} in mark order; repeated phases accumulate.
        Sums to ``total_s`` exactly."""
        out = {}
        prev = self.t0
        for phase, t in self.marks:
            out[phase] = out.get(phase, 0.0) + (t - prev)
            prev = t
        return out

    def to_dict(self):
        d = {"trace_id": self.trace_id, "kind": self.kind,
             "status": self.status, "wall0": self.wall0,
             "total_ms": self.total_s * 1e3,
             "phases_ms": {p: s * 1e3 for p, s in self.spans().items()}}
        if self.extra:
            d.update(self.extra)
        return d

    def __repr__(self):
        return (f"RequestTrace(#{self.trace_id} {self.kind} "
                f"{self.status or 'open'} {self.total_s * 1e3:.2f}ms)")


def _pctl(sorted_vals, p):
    """Nearest-rank percentile of an already-sorted list."""
    n = len(sorted_vals)
    if not n:
        return None
    rank = max(int(-(-(p / 100.0 * n) // 1)), 1)
    return sorted_vals[rank - 1]


class TraceCollector:
    """Bounded ring of finished traces + the latency_report aggregation."""

    def __init__(self, capacity=8192):
        self._traces = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.finished = 0

    def finish(self, trace, status="completed", event_log=None):
        trace.status = status
        if not trace.marks:          # shed before any phase boundary
            trace.mark(status)
        with self._lock:
            self._traces.append(trace)
            self.finished += 1
        if event_log is not None:
            # one span per phase on the shared timeline; wall-clock start
            # of each phase = request wall0 + monotonic offset of the
            # previous boundary
            prev = trace.t0
            for phase, t in trace.marks:
                event_log.emit(f"trace.{trace.kind}.{phase}", kind="span",
                               ts=trace.wall0 + (prev - trace.t0),
                               dur=t - prev, trace_id=trace.trace_id,
                               status=status)
                prev = t

    def traces(self, kind=None):
        with self._lock:
            ts = list(self._traces)
        if kind is not None:
            ts = [t for t in ts if t.kind == kind]
        return ts

    def clear(self):
        with self._lock:
            self._traces.clear()
            self.finished = 0

    def latency_report(self, kind=None):
        """{kind: {count, status: {...}, total_ms: {p50,p99,mean},
        phases_ms: {phase: {p50,p99,mean}},
        p99_attribution_ms: {phase: mean-over-p99-tail}}}.

        The attribution answers "where do the slow requests spend their
        time": mean per-phase duration over requests whose total is at or
        beyond the p99."""
        by_kind = {}
        for tr in self.traces(kind):
            by_kind.setdefault(tr.kind, []).append(tr)
        out = {}
        for k, trs in by_kind.items():
            totals = sorted(t.total_s for t in trs)
            p99 = _pctl(totals, 99)
            statuses = {}
            phase_vals = {}
            tail = []
            for t in trs:
                statuses[t.status] = statuses.get(t.status, 0) + 1
                if t.total_s >= (p99 or 0.0):
                    tail.append(t)
                for phase, s in t.spans().items():
                    phase_vals.setdefault(phase, []).append(s)
            phases = {}
            for phase, vals in phase_vals.items():
                vals.sort()
                phases[phase] = {
                    "p50": _pctl(vals, 50) * 1e3,
                    "p99": _pctl(vals, 99) * 1e3,
                    "mean": sum(vals) / len(vals) * 1e3,
                }
            attribution = {}
            for t in tail:
                for phase, s in t.spans().items():
                    attribution[phase] = attribution.get(phase, 0.0) + s
            out[k] = {
                "count": len(trs),
                "status": statuses,
                "total_ms": {"p50": _pctl(totals, 50) * 1e3,
                             "p99": p99 * 1e3,
                             "mean": sum(totals) / len(totals) * 1e3},
                "phases_ms": phases,
                "p99_attribution_ms": {p: v / len(tail) * 1e3
                                       for p, v in attribution.items()},
            }
        return out
