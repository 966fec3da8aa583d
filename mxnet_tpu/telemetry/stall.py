"""Stall watchdog: turn a silent device hang into a diagnosable artifact.

Hot sites (decode ticks, prefill, batch resolve, the train-step dispatch)
wrap their device-blocking region in a ``Heartbeat`` — ``begin()`` /
``end()`` are two monotonic reads and an attribute store; completed
intervals feed a private histogram so each site carries its own running
p99. A single monitor thread wakes every ``check_interval_s`` and fires
when a site has been busy longer than ``p99_multiple`` x its running p99
(with a floor, and only after ``min_samples`` intervals) or longer than
the absolute bound ``MXTPU_STALL_TIMEOUT_S``, whichever is tighter.

Firing dumps every thread's stack plus the last telemetry step rows to
stderr and the event log, bumps ``telemetry.stalls``, and re-arms only after the
site completes (one report per stall episode, not one per poll).
"""
from __future__ import annotations

import os
import sys
import threading
import time
import traceback

from .registry import Histogram

__all__ = ["Heartbeat", "StallMonitor"]

_STACK_LIMIT = 40          # frames per thread in the dump
_EVENT_STACK_CHARS = 8000  # stack text cap inside one event record


class Heartbeat:
    """One instrumented site. ``begin``/``end`` bracket the region that
    blocks on the device; overlapping begins (double-buffered dispatch)
    keep the latest start, which under-reports busy time slightly rather
    than false-firing."""

    __slots__ = ("name", "intervals", "beats", "_busy_since", "_fired")

    def __init__(self, name):
        self.name = name
        # private (unregistered) histogram: stall baselines are plumbing,
        # not part of the exported metric inventory
        self.intervals = Histogram(f"stall.{name}", capacity=512)
        self.beats = 0
        self._busy_since = None
        self._fired = False

    def begin(self):
        self._busy_since = time.monotonic()

    def end(self):
        t0 = self._busy_since
        self._busy_since = None
        self._fired = False
        if t0 is not None:
            self.intervals.record(time.monotonic() - t0)
            self.beats += 1

    def busy_for(self):
        t0 = self._busy_since
        return (time.monotonic() - t0) if t0 is not None else None


def _format_all_stacks(limit=_STACK_LIMIT):
    names = {t.ident: t.name for t in threading.enumerate()}
    chunks = []
    for ident, frame in sys._current_frames().items():
        header = f"--- thread {names.get(ident, '?')} ({ident}) ---"
        stack = "".join(traceback.format_stack(frame, limit=limit))
        chunks.append(header + "\n" + stack)
    return "\n".join(chunks)


class StallMonitor:
    """The monitor thread + heartbeat registry. Construction is inert;
    ``start()`` spawns the daemon thread (idempotent)."""

    def __init__(self, timeout_s=None, p99_multiple=20.0, min_samples=32,
                 floor_s=1.0, check_interval_s=0.5):
        self.timeout_s = timeout_s
        self.p99_multiple = float(p99_multiple)
        self.min_samples = int(min_samples)
        self.floor_s = float(floor_s)
        self.check_interval_s = float(check_interval_s)
        self._beats: dict = {}
        self._lock = threading.Lock()
        self._thread = None
        self._stop = threading.Event()
        self.stalled_sites = ()   # what /healthz reports
        self.fired = 0

    # -- heartbeat registry --------------------------------------------------
    def heartbeat(self, name) -> Heartbeat:
        with self._lock:
            hb = self._beats.get(name)
            if hb is None:
                hb = Heartbeat(name)
                self._beats[name] = hb
        return hb

    def stats(self):
        """{site: {beats, busy_s, p50_s, p99_s}} for report surfaces."""
        out = {}
        with self._lock:
            beats = dict(self._beats)
        for name, hb in beats.items():
            p50, p99 = hb.intervals.percentiles(50, 99)
            out[name] = {"beats": hb.beats, "busy_s": hb.busy_for(),
                         "p50_s": p50, "p99_s": p99}
        return out

    # -- lifecycle -----------------------------------------------------------
    def configure(self, timeout_s=None, p99_multiple=None, min_samples=None,
                  floor_s=None, check_interval_s=None):
        if timeout_s is not None:
            self.timeout_s = float(timeout_s)
        if p99_multiple is not None:
            self.p99_multiple = float(p99_multiple)
        if min_samples is not None:
            self.min_samples = int(min_samples)
        if floor_s is not None:
            self.floor_s = float(floor_s)
        if check_interval_s is not None:
            self.check_interval_s = float(check_interval_s)

    @property
    def running(self):
        return self._thread is not None and self._thread.is_alive()

    def start(self):
        if self.running:
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="mxtpu-stall-watchdog",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
        self._thread = None
        self.stalled_sites = ()

    # -- monitoring ----------------------------------------------------------
    def _threshold_for(self, hb):
        """Tightest applicable bound, or None when the site has no
        baseline yet and no absolute timeout is set."""
        bounds = []
        if self.timeout_s:
            bounds.append(float(self.timeout_s))
        if hb.intervals.count >= self.min_samples:
            p99 = hb.intervals.percentile(99)
            if p99 is not None:
                bounds.append(max(p99 * self.p99_multiple, self.floor_s))
        return min(bounds) if bounds else None

    def check_once(self):
        """One poll over all heartbeats (the thread body; callable
        directly from tests)."""
        stalled = []
        with self._lock:
            beats = list(self._beats.values())
        for hb in beats:
            busy = hb.busy_for()
            if busy is None:
                continue
            threshold = self._threshold_for(hb)
            if threshold is None or busy <= threshold:
                continue
            stalled.append(hb.name)
            if not hb._fired:
                hb._fired = True
                self._fire(hb, busy, threshold)
        self.stalled_sites = tuple(stalled)
        return stalled

    def _loop(self):
        while not self._stop.wait(self.check_interval_s):
            try:
                self.check_once()
            except Exception:  # noqa: BLE001 — the watchdog must not die
                traceback.print_exc(file=sys.stderr)

    def _fire(self, hb, busy_s, threshold_s):
        from . import EVENTS, REGISTRY, STEPS

        self.fired += 1
        REGISTRY.counter("telemetry.stalls").inc()
        stacks = _format_all_stacks()
        rows = STEPS.report()[-3:]
        # a stall is often memory pressure in disguise (allocator thrash,
        # host swap): the ledger rides along in the dump
        try:
            from . import memory as _memory

            ledger = _memory.ledger_text()
        except Exception:  # noqa: BLE001 — the watchdog must not die
            ledger = "<memory ledger unavailable>"
        sys.stderr.write(
            f"\n[mxtpu stall watchdog] site {hb.name!r} busy "
            f"{busy_s:.1f}s > threshold {threshold_s:.1f}s "
            f"(p99 {hb.intervals.percentile(99)!r}s over "
            f"{hb.intervals.count} beats)\n"
            f"last step rows: {rows!r}\n{ledger}\n{stacks}\n")
        sys.stderr.flush()
        EVENTS.emit("telemetry.stall", kind="instant", site=hb.name,
                    busy_s=busy_s, threshold_s=threshold_s,
                    beats=hb.beats, last_rows=rows,
                    ledger=ledger[:_EVENT_STACK_CHARS],
                    stacks=stacks[:_EVENT_STACK_CHARS])

    def reset(self):
        with self._lock:
            self._beats.clear()
        self.stalled_sites = ()
        self.fired = 0


def monitor_from_env():
    """Build a StallMonitor honoring MXTPU_STALL_TIMEOUT_S (absolute bound
    in seconds; also the auto-start trigger — see telemetry.__init__)."""
    timeout = os.environ.get("MXTPU_STALL_TIMEOUT_S")
    try:
        timeout = float(timeout) if timeout else None
    except ValueError:
        timeout = None
    return StallMonitor(timeout_s=timeout)
