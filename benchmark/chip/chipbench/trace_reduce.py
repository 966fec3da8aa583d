"""From a profiler trace to numbers: busy union, idle share, time per program
and per kernel, the top operations, and idle gaps by what the host was doing.

The reduction works on a plain structure, so a test can build one by hand::

    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [(name, start_ns, dur_ns), ...]},
        {"name": "XLA Modules", "events": [...]}]},
      {"name": "/host:CPU", "lines": [{"name": "python3", "events": [...]}]}]

``load_xplane`` makes that structure from the ``.xplane.pb`` file the jax
profiler writes, with nothing but ``jax.profiler.ProfileData``. All times
of one file are on one clock.
"""
import bisect
import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
UNATTRIBUTED = "unattributed"
MIN_GAP_NS = 20_000   # shorter holes between two operations are not gaps


def load_xplane(path, host_prefix=SPAN_PREFIX):
    """The structure above from one ``.xplane.pb``. Of the host plane only
    the events whose name starts with ``host_prefix`` are kept (the
    benchmark's own ``TraceAnnotation`` spans); the rest is Python noise."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                if not device and not ev.name.startswith(host_prefix):
                    continue
                events.append((ev.name, float(ev.start_ns),
                               float(ev.duration_ns)))
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def newest_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def start(trace_dir):
    """Start the jax profiler into an emptied ``trace_dir``: device events
    and the benchmark's ``TraceAnnotation`` spans, no Python call tracing
    (it slows the host and swells the file)."""
    import shutil

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop():
    """Stop the profiler; it writes its file. Reduce it after the window
    (``reduce_dir``): the reduction is seconds of Python."""
    import jax

    jax.profiler.stop_trace()


def reduce_dir(trace_dir, unattributed=UNATTRIBUTED):
    """The summary of the newest trace under ``trace_dir``; None if there is
    none or it holds no device events."""
    path = newest_xplane(trace_dir)
    if path is None:
        return None
    return reduce_trace(load_xplane(path), unattributed)


def merge_intervals(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class Union:
    """Merged intervals with the covered length of any [lo, hi] in
    O(log n)."""

    def __init__(self, intervals):
        self.spans = merge_intervals(intervals)
        self.starts = [s for s, _ in self.spans]
        self.ends = [e for _, e in self.spans]
        self.before = [0.0]      # covered length before each span
        for s, e in self.spans:
            self.before.append(self.before[-1] + (e - s))

    @property
    def total(self):
        return self.before[-1]

    def _upto(self, t):
        """Covered length left of time t."""
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.before[i - 1] + min(t, self.ends[i - 1]) \
            - self.starts[i - 1]

    def covered(self, lo, hi):
        return self._upto(hi) - self._upto(lo)

    def gaps(self, min_ns):
        return [(e, s) for e, s in zip(self.ends, self.starts[1:])
                if s - e >= min_ns]


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def hlo_opcode(name):
    """The opcode of an operation as the TPU trace names it: the whole HLO
    instruction, ``%name = <shape> opcode(operands...)``, where the shape
    may be a tuple in parentheses. A bare name gives its stem
    (``custom-call.12`` -> ``custom-call``)."""
    inst, sep, rest = name.partition(" = ")
    if not sep:
        return inst.lstrip("%").split(".")[0]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.partition(" ")[2]
    return rest.partition("(")[0].strip()


def is_custom_call(name):
    """A Pallas kernel reaches XLA as a ``custom-call`` instruction (target
    ``tpu_custom_call``). An operation that merely READS a custom call's
    result has that name among its operands and is not one."""
    return hlo_opcode(name) == "custom-call"


def short_name(name, limit=120):
    """An operation's name cut to a length a result line can carry."""
    return name if len(name) <= limit else name[:limit - 3] + "..."


def reduce_device(plane):
    """One device plane -> its numbers (times in seconds)."""
    ops = _line(plane, OPS_LINE)
    modules = _line(plane, MODULES_LINE)
    busy_events = ops or modules
    if not busy_events:
        return None
    union = Union((s, s + d) for _, s, d in busy_events)
    first, last = union.starts[0], union.ends[-1]
    busy = union.total
    by_op, custom = {}, 0.0
    for name, _, dur in ops:
        by_op[name] = by_op.get(name, 0.0) + dur
        if is_custom_call(name):
            custom += dur
    # a module's name ends in its program's fingerprint, ``jit_f(<digits>)``:
    # the runs of one compiled program share it, two programs of one Python
    # function (the engine's are all ``jit_observed``) do not
    programs = {}
    for name, start, dur in modules:
        p = programs.setdefault(name,
                                {"count": 0, "total_s": 0.0, "runs_s": []})
        inside = union.covered(start, start + dur) if ops else dur
        p["count"] += 1
        p["total_s"] += inside / 1e9
        p["runs_s"].append(inside / 1e9)
    for p in programs.values():
        p["median_s"] = statistics.median(p.pop("runs_s"))
    gaps = union.gaps(MIN_GAP_NS)
    return {"window_s": (last - first) / 1e9, "busy_s": busy / 1e9,
            "custom_call_s": custom / 1e9, "programs": programs,
            "ops": by_op, "gaps": gaps}


def host_spans(planes, prefix=SPAN_PREFIX):
    spans = []
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(prefix):
                    spans.append((start, start + dur, name[len(prefix):]))
    return sorted(spans)


def attribute_gaps(gaps, spans, unattributed=UNATTRIBUTED):
    """Seconds of device idle time by the host span that covers most of
    each gap; innermost (shortest) span wins a tie. A gap no span of the
    benchmark covers is ``unattributed``."""
    total = {}
    for lo, hi in gaps:
        best, best_cover, best_len = unattributed, 0.0, 0.0
        for s, e, name in spans:
            if s >= hi:
                break
            cover = min(e, hi) - max(s, lo)
            if cover <= 0:
                continue
            if cover > best_cover or (cover == best_cover
                                      and e - s < best_len):
                best, best_cover, best_len = name, cover, e - s
        if best_cover < 0.5 * (hi - lo):
            best = unattributed
        total[best] = total.get(best, 0.0) + (hi - lo) / 1e9
    return total


def top(mapping, n=10, scale=1.0):
    rows = sorted(mapping.items(), key=lambda kv: -kv[1])[:n]
    return [[short_name(name), value * scale] for name, value in rows]


def reduce_trace(planes, unattributed=UNATTRIBUTED):
    """All device planes of one trace -> one summary. Busy seconds, window
    and program times are averaged over the devices that ran something;
    operations and gaps come from the busiest device."""
    devices = [r for r in (reduce_device(p) for p in planes
                           if DEVICE_PLANE.match(p["name"])) if r]
    if not devices:
        return None
    n = len(devices)
    lead = max(devices, key=lambda r: r["busy_s"])
    spans = host_spans(planes)
    gaps = attribute_gaps(lead["gaps"], spans, unattributed)
    window = sum(r["window_s"] for r in devices) / n
    busy = sum(r["busy_s"] for r in devices) / n
    return {
        "devices": n, "window_s": window, "busy_s": busy,
        "idle_share": 1.0 - busy / window if window > 0 else None,
        "custom_call_s": sum(r["custom_call_s"] for r in devices) / n,
        "programs": lead["programs"],
        "device_ops": top(lead["ops"], scale=1e-9),
        "idle_gaps": top(gaps),
        "host_spans": len(spans),
    }


def inventory(path, top_n=12):
    """What a trace file holds, as text: planes, lines, event counts and the
    names with most time in each line. For reading a first trace by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            by_name, keys, n, lo, hi = {}, {}, 0, None, None
            for ev in line.events:
                n += 1
                by_name[ev.name] = by_name.get(ev.name, 0.0) + ev.duration_ns
                if ev.name not in keys:
                    keys[ev.name] = {k: (v if isinstance(v, (int, float))
                                         else str(v)[:80])
                                     for k, v in ev.stats}
                lo = ev.start_ns if lo is None else min(lo, ev.start_ns)
                end = ev.start_ns + ev.duration_ns
                hi = end if hi is None else max(hi, end)
            out.append(f"  LINE {line.name!r}: {n} events, "
                       f"{len(by_name)} names, span {lo}..{hi} ns")
            for name, ns in sorted(by_name.items(),
                                   key=lambda kv: -kv[1])[:top_n]:
                out.append(f"    {ns / 1e6:10.3f} ms  {name[:90]}  "
                           f"{keys[name]}")
    return "\n".join(out)
