"""The program's own spans in a traced run: what the host threads of
``mxnet_tpu`` were doing, on the device's clock.

``mxnet_tpu.telemetry.span`` enters a ``TraceAnnotation("mxtpu:<name>")`` at
each phase boundary of the compiled step's call and of the decode engine's
thread, so under the runner's profiler session the phases land on the host
plane of the same ``.xplane.pb`` as the device's operations. The spans of one
thread are flat leaves that tile its time: none encloses another, so a span's
duration is its self time and a device idle gap splits EXACTLY over the spans
it overlaps (``trace_reduce.attribute_gaps`` gives a whole gap to one span,
which nested spans need and these do not). A name that holds ``.wait_`` says
the thread is blocked on the device or on an empty queue; every other span is
host work.

Both runners write their trace to ``<bench_dir>/.work/trace``; a per-layer
reader gets only the runner's observations, so ``reading()`` finds that
directory from this file's own place (an argument overrides it, for tests).
A program without these spans, as every commit before them, gives a reading
with no spans, and every number here is then None.
"""
import bisect
import os
import statistics

from . import trace_reduce

PREFIX = "mxtpu:"
WAIT = ".wait_"
NO_SPAN = "(no span)"
STEP_FIRST, STEP_LAST = "train.assemble", "train.mark"
TICK = "serve.tick.dispatch"
BLOCKED = ("train.wait_health", "train.wait_overflow")

_READINGS = {}   # (path, mtime) -> reading: one load per process


def default_trace_dir():
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(bench_dir, ".work", "trace")


def span_name(event_name):
    """``serve.tick.grow`` from ``mxtpu:serve.tick.grow``; where a profiler
    packs the attributes into the name (``name#k=v,k=v#``) they go too."""
    return event_name[len(PREFIX):].split("#", 1)[0]


def thread_spans(planes):
    """{thread: [(start, end, name), ...] sorted} of the host plane's
    ``mxtpu:`` events; a thread is one line of the plane (its place and its
    name: lines of two threads may share a name)."""
    threads = {}
    for plane in planes:
        if plane["name"] != trace_reduce.HOST_PLANE:
            continue
        for i, line in enumerate(plane["lines"]):
            spans = sorted((start, start + dur, span_name(name))
                           for name, start, dur in line["events"]
                           if name.startswith(PREFIX))
            if spans:
                threads[(i, line["name"])] = spans
    return threads


def lead_gaps(planes):
    """The idle gaps of the busiest device, as ``trace_reduce`` finds them;
    None where the trace holds no device."""
    devices = [r for r in (trace_reduce.reduce_device(p) for p in planes
                           if trace_reduce.DEVICE_PLANE.match(p["name"]))
               if r]
    if not devices:
        return None
    return max(devices, key=lambda r: r["busy_s"])["gaps"]


def split_gaps(gaps, threads):
    """Seconds of the gaps by the span each part of a gap lies under, and
    under ``NO_SPAN`` what no span covers. Exact while one thread emits (the
    step's caller, the engine's thread): its spans do not overlap."""
    spans = sorted(s for ss in threads.values() for s in ss)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    cover = trace_reduce.Union((s, e) for s, e, _ in spans)
    out = {}
    for lo, hi in gaps:
        first = bisect.bisect_left(starts, lo - longest)
        for s, e, name in spans[first:bisect.bisect_left(starts, hi)]:
            part = min(e, hi) - max(s, lo)
            if part > 0:
                out[name] = out.get(name, 0.0) + part / 1e9
        bare = (hi - lo) - cover.covered(lo, hi)
        if bare > 0:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + bare / 1e9
    return out


def _thread_with_most(threads, name):
    """The spans of the thread that emitted ``name`` most often."""
    count = {t: sum(n == name for _, _, n in ss) for t, ss in threads.items()}
    best = max(count, key=count.get, default=None)
    return threads[best] if best is not None and count[best] else []


def steps_of(spans):
    """The complete step calls of the caller's thread, each as {span name:
    seconds}: from one ``train.assemble`` to its ``train.mark``. A call the
    trace cut at either end is left out."""
    steps, cur = [], None
    for s, e, name in spans:
        if not name.startswith("train."):
            continue
        if name == STEP_FIRST:
            cur = {}
        if cur is None:
            continue
        cur[name] = cur.get(name, 0.0) + (e - s) / 1e9
        if name == STEP_LAST:
            steps.append(cur)
            cur = None
    return steps


def reduce_planes(planes):
    """Everything the readers need from one trace."""
    threads = thread_spans(planes)
    seconds, counts = {}, {}
    for spans in threads.values():
        for s, e, name in spans:
            seconds[name] = seconds.get(name, 0.0) + (e - s) / 1e9
            counts[name] = counts.get(name, 0) + 1
    out = {"threads": len(threads), "seconds": seconds, "counts": counts,
           "idle_s": None, "idle_split": None, "idle_attributed_share": None,
           "steps": 0, "step_ms": {}, "step_host_ms": None,
           "step_blocked_ms": None, "ticks": 0, "tick_ms": {},
           "engine_host_ms_per_tick": None}
    if not threads:
        return out
    gaps = lead_gaps(planes)
    if gaps is not None:
        idle = sum(hi - lo for lo, hi in gaps) / 1e9
        split = split_gaps(gaps, threads)
        out["idle_s"], out["idle_split"] = idle, split
        if idle > 0:
            out["idle_attributed_share"] = \
                100.0 * (1.0 - split.get(NO_SPAN, 0.0) / idle)
    steps = steps_of(_thread_with_most(threads, STEP_LAST))
    if steps:
        names = sorted({n for st in steps for n in st})
        out["steps"] = len(steps)
        out["step_ms"] = {n: 1e3 * statistics.median(
            st.get(n, 0.0) for st in steps) for n in names}
        out["step_host_ms"] = 1e3 * statistics.median(
            sum(v for n, v in st.items() if WAIT not in n) for st in steps)
        out["step_blocked_ms"] = 1e3 * statistics.median(
            sum(st.get(n, 0.0) for n in BLOCKED) for st in steps)
    engine = [(s, e, n) for s, e, n in _thread_with_most(threads, TICK)
              if n.startswith("serve.")]
    ticks = sum(n == TICK for _, _, n in engine)
    if ticks:
        by_name = {}
        for s, e, n in engine:
            by_name[n] = by_name.get(n, 0.0) + (e - s) / 1e9
        out["ticks"] = ticks
        out["tick_ms"] = {n: 1e3 * v / ticks
                          for n, v in sorted(by_name.items())}
        out["engine_host_ms_per_tick"] = sum(
            v for n, v in out["tick_ms"].items() if WAIT not in n)
    return out


def report(r, say=print):
    """The reading as lines for the reader, before the result line."""
    if not r["seconds"]:
        say("program spans: the trace holds no mxtpu: span")
        return
    if r["idle_split"] is not None:
        idle = r["idle_s"]
        parts = sorted(r["idle_split"].items(), key=lambda kv: -kv[1])
        say(f"program spans: idle {idle:.4f}s of the lead device by the "
            f"span over it: " + ", ".join(
                f"{n} {v:.4f}s ({100 * v / idle:.1f}%)" for n, v in parts))
    if r["steps"]:
        say(f"program spans: median ms a step over {r['steps']} steps: "
            + ", ".join(f"{n} {v:.3f}" for n, v in r["step_ms"].items()))
    if r["ticks"]:
        say(f"program spans: engine thread, ms a tick over {r['ticks']} "
            f"ticks: " + ", ".join(f"{n} {v:.3f}"
                                   for n, v in r["tick_ms"].items()))


def reading(trace_dir=None):
    """The reduction of the newest trace under ``trace_dir`` (default: where
    the runners write theirs); None where there is none. Loaded, reduced and
    reported once per process and file."""
    path = trace_reduce.newest_xplane(trace_dir or default_trace_dir())
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _READINGS:
        _READINGS[key] = reduce_planes(
            trace_reduce.load_xplane(path, host_prefix=PREFIX))
        report(_READINGS[key])
    return _READINGS[key]


def metric(obs, name):
    """What a per-layer reader returns: ``name`` of the reading, or None
    where the run saw no device (no ``obs["trace"]``: a CPU run reports
    nothing under a device's name), wrote no trace, or the program has no
    such span."""
    if obs.get("trace") is None:
        return None
    r = reading()
    return r and r[name]
