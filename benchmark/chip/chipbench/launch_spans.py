"""The launch path from inside: every run of a compiled program on the lead
device beside the program's span that launched it and the wait that read it.

The spans (``mxtpu:<name>``, ``program_spans``) and the device's lines are in
one ``.xplane.pb`` on one clock, and the serving and training programs carry
stable module names, so the two can be joined:

- WHICH RUNS. The events of the lead device's ``XLA Modules`` line whose
  name starts with ``jit_mxtpu_serve_decode`` (a tick), ``jit_mxtpu_serve_
  prefill`` (a prefill, a prefix-join too) or ``jit_mxtpu_train_step`` (a
  step). The spans are those of the thread that emitted the kind's dispatch
  span most often (``program_spans._thread_with_most``). The lead device is
  the one with the most busy time, as ``trace_reduce`` picks it.
- RUN -> LAUNCHING SPAN. For a run ``r`` the latest dispatch span ``d`` of
  its kind (``serve.tick.dispatch``, ``serve.prefill.dispatch``,
  ``train.dispatch``) with ``start(d) <= start(r)``. One tick and one step
  are in flight at a time, so that is unique for them; several prefills can
  be out at once behind a busy device: runs that find the same span take,
  in order, the spans no run found before it and then it. A run whose span
  the trace cut off is left out.
- EXPOSED LAUNCH of ``r``: ``start(r) - max(start(d), free(r))``, never
  under 0, where ``free(r)`` is the end of the last device operation before
  ``start(r)`` (the ops line's union): how long the device stood idle
  although the host had ALREADY begun the call. Where the device is the
  slower side this is the gap between two programs; where the host is, the
  whole launch latency (the call's own host time plus the runtime's).
- RUN -> READING WAIT. For a wait span ``w`` (``serve.wait_tick``,
  ``serve.wait_prefill``; of a step call its first wait,
  ``train.wait_overflow`` or ``train.wait_health``) the latest run ``r`` of
  its kind with ``end(r) <= end(w)``, with the same rule where two waits
  find one run.
- READ-BACK of ``w``: ``end(w) - max(start(w), end(r))``: the part of the
  wait the device did not need, the way back of the result.

Besides the join: the engine thread's milliseconds a tick in the three
leaves the serving dispatch spans were cut into (stage, call, account),
from ``program_spans``' own per-tick sums, so that they add up with the
other host spans to ``engine_host_ms_per_tick``. A trace with no
``serve.tick.stage`` is a program from before the cut: its un-narrowed
dispatch span is never read under the narrowed one's name.

Like ``program_spans.reading()`` this reduces the newest trace once per
process and prints its reading ("launch path: ...") before the result line.
"""
import bisect
import os
import statistics

from . import program_spans, trace_reduce

# kind -> (module prefix, dispatch span, wait spans)
KINDS = {
    "tick": ("jit_mxtpu_serve_decode", "serve.tick.dispatch",
             ("serve.wait_tick",)),
    "prefill": ("jit_mxtpu_serve_prefill", "serve.prefill.dispatch",
                ("serve.wait_prefill",)),
    "step": ("jit_mxtpu_train_step", "train.dispatch",
             ("train.wait_overflow", "train.wait_health")),
}
# the engine thread's leaves by what a reader calls them
LEAVES = {
    "stage": ("serve.tick.stage", "serve.prefill.stage"),
    "launch": ("serve.tick.dispatch", "serve.prefill.dispatch"),
    "account": ("serve.tick.account", "serve.prefill.account"),
}
CUT = "serve.tick.stage"     # only a program with the leaves emits it

_READINGS = {}   # (path, mtime) -> reading: one load per process


def lead_device(planes):
    """(runs, busy) of the device with the most busy time: ``runs`` the
    (start, end, name) of its module events, sorted, ``busy`` the union of
    its operations' intervals; None where the trace holds no device."""
    best = None
    for plane in planes:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        modules = lines.get(trace_reduce.MODULES_LINE, [])
        events = lines.get(trace_reduce.OPS_LINE) or modules
        if not events:
            continue
        busy = trace_reduce.Union((s, s + d) for _, s, d in events)
        if best is None or busy.total > best[1].total:
            best = (sorted((s, s + d, n) for n, s, d in modules), busy)
    return best


def latest_before(firsts, seconds):
    """For each of the sorted times ``seconds`` the index of the latest of
    the sorted times ``firsts`` that is not after it; None where there is
    none. Those of ``seconds`` that find the same one take, in order, what
    lies unclaimed before it and then it (two prefills dispatched back to
    back behind a busy device), and one left over finds None (its own was
    cut from the trace)."""
    pick = [bisect.bisect_right(firsts, t) - 1 for t in seconds]
    out, j, claimed = [None] * len(seconds), 0, -1
    while j < len(seconds):
        k = j
        while k < len(seconds) and pick[k] == pick[j]:
            k += 1
        if pick[j] >= 0:
            first = max(claimed + 1, pick[j] - (k - j - 1))
            for n, i in enumerate(range(first, pick[j] + 1)):
                out[j + n] = i
            claimed = pick[j]
        j = k
    return out


def free_before(busy, t):
    """The end of the last device operation that began before ``t``."""
    i = bisect.bisect_left(busy.starts, t) - 1
    return busy.ends[i] if i >= 0 else float("-inf")


def waits_of(spans, dispatch, waits):
    """The (start, end) of a thread's wait spans that read a program's
    result: each one named by ``waits``, but of several names (a step
    call's ``train.wait_overflow`` and ``train.wait_health``) only the
    first after each dispatch span, which is the one that waits."""
    out, armed = [], True
    for s, e, name in spans:
        if name == dispatch:
            armed = True
        elif name in waits and armed:
            out.append((s, e))
            armed = len(waits) == 1
    return out


def join(runs, busy, spans, kind):
    """One kind's exposed launches and read-backs, each as sorted
    (start, end) intervals of the trace's clock."""
    prefix, dispatch, waits = KINDS[kind]
    runs = [(s, e) for s, e, n in runs if n.startswith(prefix)]
    calls = [s for s, _, n in spans if n == dispatch]
    launches = []
    for (start, _), i in zip(runs, latest_before(
            calls, [s for s, _ in runs])):
        if i is not None:
            begun = max(calls[i], free_before(busy, start))
            launches.append((min(begun, start), start))
    reads = waits_of(spans, dispatch, waits)
    ends = sorted(e for _, e in runs)
    readbacks = []
    for (start, end), i in zip(reads, latest_before(
            ends, [e for _, e in reads])):
        if i is not None:
            readbacks.append((max(start, ends[i]), end))
    return {"runs": len(runs), "launches": launches, "readbacks": readbacks}


def _ms(intervals):
    return [(hi - lo) / 1e6 for lo, hi in intervals]


def _p95(values):
    values = sorted(values)
    return values[min(len(values) - 1, int(0.95 * len(values)))]


def reduce_planes(planes, tick_ms=None):
    """Everything the readers need from one trace. ``tick_ms`` is
    ``program_spans``' reading of the same trace, {span: ms a tick}."""
    out = {"kinds": {}, "idle": None, "launch_exposed_ms.serve": None,
           "readback_ms.serve": None, "launch_exposed_ms.train": None,
           "readback_ms.train": None}
    tick_ms = tick_ms or {}
    for leaf, names in LEAVES.items():
        out[f"engine_{leaf}_ms_per_tick.serve"] = sum(
            tick_ms.get(n, 0.0) for n in names) if CUT in tick_ms else None
    device = lead_device(planes)
    threads = program_spans.thread_spans(planes)
    if device is None or not threads:
        return out
    runs, busy = device
    for kind, (_, dispatch, _) in KINDS.items():
        spans = program_spans._thread_with_most(threads, dispatch)
        j = join(runs, busy, spans, kind)
        if j["launches"] or j["readbacks"]:
            out["kinds"][kind] = j
    for kind, side in (("tick", "serve"), ("step", "train")):
        j = out["kinds"].get(kind)
        if j and j["launches"]:
            out[f"launch_exposed_ms.{side}"] = statistics.median(
                _ms(j["launches"]))
        if j and j["readbacks"]:
            out[f"readback_ms.{side}"] = statistics.median(
                _ms(j["readbacks"]))
    gaps = busy.gaps(trace_reduce.MIN_GAP_NS)
    idle = sum(hi - lo for lo, hi in gaps)
    if idle > 0 and out["kinds"]:
        launch = trace_reduce.Union(
            iv for j in out["kinds"].values() for iv in j["launches"])
        either = trace_reduce.Union(
            iv for j in out["kinds"].values()
            for iv in j["launches"] + j["readbacks"])
        launched = sum(launch.covered(lo, hi) for lo, hi in gaps)
        named = sum(either.covered(lo, hi) for lo, hi in gaps)
        out["idle"] = {"idle_s": idle / 1e9, "launch_s": launched / 1e9,
                       "readback_s": (named - launched) / 1e9,
                       "neither_s": (idle - named) / 1e9}
    return out


def report(r, operands=None, say=print):
    """The reading as lines for the reader, before the result line."""
    if not r["kinds"]:
        say("launch path: the trace holds no run of a named program under "
            "a dispatch span")
    for kind, j in r["kinds"].items():
        parts = []
        for what, key in (("exposed launch", "launches"),
                          ("read-back", "readbacks")):
            ms = _ms(j[key])
            if ms:
                parts.append(f"{what} ms median {statistics.median(ms):.4f} "
                             f"p95 {_p95(ms):.4f} over {len(ms)}")
        say(f"launch path: {kind}, {j['runs']} runs: " + "; ".join(parts))
    if r["idle"]:
        i = r["idle"]
        say(f"launch path: idle {i['idle_s']:.4f}s of the lead device: "
            + ", ".join(f"{what} {i[key]:.4f}s "
                        f"({100 * i[key] / i['idle_s']:.1f}%)"
                        for what, key in (
                            ("exposed launch", "launch_s"),
                            ("read-back", "readback_s"),
                            ("neither (the host not yet at the call)",
                             "neither_s"))))
    if r["engine_launch_ms_per_tick.serve"] is not None:
        say("launch path: engine thread, ms a tick: " + ", ".join(
            f"{leaf} {r[f'engine_{leaf}_ms_per_tick.serve']:.3f}"
            for leaf in LEAVES))
    if operands:
        say("launch path: operands handed to the executable: " + ", ".join(
            f"{name} {' '.join(map(str, sorted(v)))}"
            for name, v in sorted(operands.items())))


def operands_of(path):
    """{dispatch span: the values of its ``operands`` attribute} in the
    trace file: ``load_xplane`` keeps names and times only."""
    from jax.profiler import ProfileData

    names = {program_spans.PREFIX + d for _, d, _ in KINDS.values()}
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.split("#", 1)[0] not in names:
                    continue
                for key, value in ev.stats:
                    if key == "operands":
                        out.setdefault(program_spans.span_name(ev.name),
                                       set()).add(int(value))
    return out


def reading(trace_dir=None):
    """The reduction of the newest trace under ``trace_dir`` (default: where
    the runners write theirs); None where there is none. Loaded, reduced and
    reported once per process and file."""
    trace_dir = trace_dir or program_spans.default_trace_dir()
    path = trace_reduce.newest_xplane(trace_dir)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _READINGS:
        spans = program_spans.reading(trace_dir)
        _READINGS[key] = reduce_planes(
            trace_reduce.load_xplane(path, host_prefix=program_spans.PREFIX),
            spans and spans["tick_ms"])
        report(_READINGS[key], operands_of(path))
    return _READINGS[key]


def metric(obs, name):
    """What a per-layer reader returns: ``name`` of the reading, or None
    where the run saw no device (no ``obs["trace"]``: a CPU run reports
    nothing under a device's name), wrote no trace, or the trace holds
    nothing to join."""
    if obs.get("trace") is None:
        return None
    r = reading()
    return r and r[name]
