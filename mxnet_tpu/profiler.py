"""Profiler: mx.profiler API over the JAX/XLA profiler.

Reference: python/mxnet/profiler.py (set_config:34, start/stop, dump:125) over
src/profiler/ (chrome://tracing JSON, aggregate stats). TPU-native mapping:
``start``/``stop`` drive jax.profiler traces (xplane, viewable in
TensorBoard/Perfetto); ``scope``/``record`` map to jax.profiler annotations;
the aggregate-table UX is preserved via ``dumps()`` summarizing named ranges
timed on host.
"""
from __future__ import annotations

import contextlib
import os

__all__ = ["set_config", "start", "stop", "dump", "dumps", "pause", "resume",
           "scope", "record", "Profiler", "mark_step", "dump_memory_csv",
           "memory_records"]

_config = {"profile_all": False, "filename": "profile.json",
           "aggregate_stats": False, "profile_memory": False}
_trace_dir = None
_running = False
_ranges = {}  # name -> [total_s, count]

# -- per-allocation tracking (reference: src/profiler/storage_profiler.h) ---
# every buffer first seen inside a profiler scope is attributed to it:
# _alloc_stats aggregates per (scope, shape, dtype); _scope_by_id lets the
# top-K live-buffer table name each buffer's birth scope
_alloc_stats = {}   # (scope, shape, dtype) -> [count, nbytes_total]
_scope_by_id = {}   # id(jax.Array) -> scope name (pruned against live set)
_steps = []         # (step_name, live_bytes, peak_bytes_or_None)


def set_config(**kwargs):
    """reference parity: profile_symbolic/profile_imperative/... accepted."""
    _config.update(kwargs)


def start(profile_process="worker"):
    global _running, _trace_dir
    if _running:
        return
    import jax

    _trace_dir = _config.get("trace_dir") or \
        os.path.splitext(_config["filename"])[0] + "_xplane"
    jax.profiler.start_trace(_trace_dir)
    _running = True


def stop(profile_process="worker"):
    global _running
    if not _running:
        return
    import jax

    jax.profiler.stop_trace()
    _running = False


def pause(profile_process="worker"):
    stop()


def resume(profile_process="worker"):
    start()


def dump(finished=True, profile_process="worker"):
    """Stop any running trace and write the aggregate table to
    ``_config["filename"]`` (reference: dump writes the chrome trace to the
    configured file; here the host/device aggregate table is the artifact —
    the xplane trace lives in ``trace_dir``)."""
    if _running:
        stop()
    with open(_config["filename"], "w") as f:
        f.write(dumps() + "\n")


# -- xplane → per-op aggregate stats (reference: aggregate_stats.cc) --------
_INFRA_PREFIXES = ("ThreadpoolListener", "ThunkExecutor", "TaskDispatcher",
                   "end:", "$", "Memcpy", "Stream #", "InfeedDequeue")


def _is_op_event(name: str) -> bool:
    if not name or name.startswith(_INFRA_PREFIXES):
        return False
    return "::" not in name


def _pb_varint(buf, i):
    r, s = 0, 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        if not b & 0x80:
            return r, i
        s += 7


def _pb_fields(buf):
    """Yield (field_number, value) over one protobuf message: varints as
    int, length-delimited fields as bytes. Fixed32/64 are skipped; group
    wire types abort the walk (xplane never uses either)."""
    i, n = 0, len(buf)
    try:
        while i < n:
            tag, i = _pb_varint(buf, i)
            wt = tag & 7
            if wt == 0:
                v, i = _pb_varint(buf, i)
            elif wt == 2:
                ln, i = _pb_varint(buf, i)
                v, i = buf[i:i + ln], i + ln
            elif wt == 1:
                i += 8
                continue
            elif wt == 5:
                i += 4
                continue
            else:
                return
            yield tag >> 3, v
    except IndexError:
        return


def _xplane_planes(data):
    """Minimal wire-format decode of a serialized XSpace — the fallback when
    this jax build has no ``jax.profiler.ProfileData`` binding (absent on
    0.4.x). Yields (plane_name, [(line_name, [(event_name, dur_ns), ...])]).

    Field numbers (tensorflow/profiler xplane.proto): XSpace.planes=1;
    XPlane{name=2, lines=3, event_metadata=4}; XLine{name=2, events=4,
    display_name=11}; XEvent{metadata_id=1, duration_ps=3};
    XEventMetadata{id=1, name=2}; map entries {key=1, value=2}.
    """
    for fnum, v in _pb_fields(data):
        if fnum != 1 or not isinstance(v, bytes):
            continue
        plane_name, meta, raw_lines = "", {}, []
        for pf, pv in _pb_fields(v):
            if pf == 2 and isinstance(pv, bytes):
                plane_name = pv.decode("utf-8", "replace")
            elif pf == 3 and isinstance(pv, bytes):
                raw_lines.append(pv)
            elif pf == 4 and isinstance(pv, bytes):
                mid, mname = 0, ""
                for kf, kv in _pb_fields(pv):
                    if kf == 1 and isinstance(kv, int):
                        mid = kv
                    elif kf == 2 and isinstance(kv, bytes):
                        for mf, mv in _pb_fields(kv):
                            if mf == 1 and isinstance(mv, int):
                                mid = mv
                            elif mf == 2 and isinstance(mv, bytes):
                                mname = mv.decode("utf-8", "replace")
                if mname:
                    meta[mid] = mname
        lines = []
        for lv in raw_lines:
            lname, events = "", []
            for lf, lvv in _pb_fields(lv):
                if lf == 2 and isinstance(lvv, bytes) and not lname:
                    lname = lvv.decode("utf-8", "replace")
                elif lf == 11 and isinstance(lvv, bytes):
                    lname = lvv.decode("utf-8", "replace")
                elif lf == 4 and isinstance(lvv, bytes):
                    mid, dur_ps = 0, 0
                    for ef, evv in _pb_fields(lvv):
                        if ef == 1 and isinstance(evv, int):
                            mid = evv
                        elif ef == 3 and isinstance(evv, int):
                            dur_ps = evv
                    events.append((meta.get(mid, ""), dur_ps / 1e3))
            lines.append((lname, events))
        yield plane_name, lines


def _trace_events(path):
    """(plane_name, line_name, [(event_name, dur_ns)]) triples from an
    xplane.pb, via ProfileData when available, else the wire parser."""
    try:
        from jax.profiler import ProfileData
    except ImportError:
        with open(path, "rb") as f:
            data = f.read()
        for plane_name, lines in _xplane_planes(data):
            for line_name, events in lines:
                yield plane_name, line_name, events
        return
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            yield plane.name, line.name, [(ev.name, ev.duration_ns)
                                          for ev in line.events]


def get_device_op_stats(trace_dir=None):
    """Parse the captured xplane trace into {op_name: (calls, total_ns)}.

    Device planes (TPU) and XLA-client lines (CPU) both carry one event per
    executed XLA op; infrastructure events are filtered out. This is the
    data source for the reference's per-op aggregate table
    (src/profiler/aggregate_stats.cc) rebuilt over the XLA profiler.
    """
    import glob

    tdir = trace_dir or _trace_dir
    if tdir is None:
        return {}
    files = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        return {}
    stats: dict[str, list] = {}
    for plane_name, line_name, events in _trace_events(files[-1]):
        device = "device:" in plane_name.lower() or \
            "tpu" in plane_name.lower()
        # CPU runs surface XLA ops on the PjRt client lines; TPU runs
        # on the device plane's op lines
        client = line_name.startswith("tf_XLA") or \
            "XLA Ops" in line_name or "XLA Modules" in line_name
        if not (device or client):
            continue
        for name, ns in events:
            if not _is_op_event(name):
                continue
            s = stats.setdefault(name, [0, 0.0])
            s[0] += 1
            s[1] += ns
    return {k: (c, ns) for k, (c, ns) in stats.items() if ns > 0}


def device_memory_info(device=None):
    """Per-device PJRT memory stats (reference: storage_profiler.h —
    peak/current allocated bytes). Returns {} when the backend does not
    report (CPU)."""
    import jax

    dev = device or jax.devices()[0]
    stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
    return dict(stats) if stats else {}


def dumps(reset=False, format="table"):
    """Aggregate stats table (reference: aggregate_stats.cc UX): host
    ranges, per-op device time from the last captured trace, and peak HBM
    when the backend reports it."""
    lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>12}"]
    for name, (total, count) in sorted(_ranges.items()):
        lines.append(f"{name:<40}{count:>8}{total * 1e3:>12.3f}"
                     f"{total * 1e3 / count:>12.3f}")
    dev = get_device_op_stats()
    if dev:
        lines.append("")
        lines.append(f"{'Device op':<40}{'Calls':>8}{'Total(ms)':>12}"
                     f"{'Avg(ms)':>12}")
        for name, (count, ns) in sorted(dev.items(),
                                        key=lambda kv: -kv[1][1])[:50]:
            lines.append(f"{name[:40]:<40}{count:>8}{ns / 1e6:>12.3f}"
                         f"{ns / 1e6 / count:>12.3f}")
    mem = device_memory_info()
    if mem.get("peak_bytes_in_use"):
        lines.append("")
        lines.append(f"peak_bytes_in_use: {mem['peak_bytes_in_use']:,}")
        if mem.get("bytes_in_use") is not None:
            lines.append(f"bytes_in_use:      {mem['bytes_in_use']:,}")
    if _config.get("profile_memory") and (_alloc_stats or _steps):
        lines.append("")
        lines.append(f"{'Memory scope':<32}{'Shape':<20}{'Count':>6}"
                     f"{'Bytes':>14}")
        by_scope: dict[str, int] = {}
        for s, shp, dt, c, b in memory_records():
            by_scope[s] = by_scope.get(s, 0) + b
            lines.append(f"{s[:32]:<32}{'x'.join(map(str, shp))[:19]:<20}"
                         f"{c:>6}{b:>14,}")
        for s, b in sorted(by_scope.items(), key=lambda kv: -kv[1]):
            lines.append(f"{'total ' + s[:26]:<52}{'':>6}{b:>14,}")
        lines.append("")
        lines.append(f"{'Top live buffers':<32}{'Shape':<20}"
                     f"{'Dtype':<10}{'Bytes':>14}")
        for nbytes, shp, dt, s in _top_live_buffers():
            lines.append(f"{s[:32]:<32}{'x'.join(map(str, shp))[:19]:<20}"
                         f"{dt:<10}{nbytes:>14,}")
        for name, live, peak in _steps:
            extra = f"  peak_bytes_in_use={peak:,}" if peak is not None \
                else ""
            lines.append(f"{name}: live_bytes={live:,}{extra}")
    if reset:
        _ranges.clear()
        _alloc_stats.clear()
        _steps.clear()
        _scope_by_id.clear()
    return "\n".join(lines)


@contextlib.contextmanager
def scope(name="<unk>"):
    """Named profiling scope; shows up in xplane and the aggregate table.
    With ``set_config(profile_memory=True)``, buffers allocated inside the
    scope are attributed to it (reference: storage_profiler.h profiler
    scopes on GPU allocations)."""
    import jax

    track = _config.get("profile_memory")
    if track:
        before = {id(a) for a in jax.live_arrays()}
    from . import telemetry as _telemetry

    # the one span primitive: the event is "mxtpu:<name>" in the xplane,
    # the timer and the event-log span "profiler.<name>"
    with _telemetry.span(name, timer="profiler." + name) as sp:
        yield
    tot, cnt = _ranges.get(name, (0.0, 0))
    _ranges[name] = (tot + sp.seconds, cnt + 1)
    if track:
        live_now = jax.live_arrays()
        # prune attributions of freed buffers every scope exit — id() values
        # recycle, so a stale entry would both mislabel a new buffer and
        # leak map entries in scope-only usage
        alive = {id(a) for a in live_now}
        for bid in [b for b in _scope_by_id if b not in alive]:
            del _scope_by_id[bid]
        for a in live_now:
            if id(a) in before or id(a) in _scope_by_id:
                # already attributed: an inner scope's exit runs first, so
                # skipping claimed ids keeps attribution innermost and stops
                # enclosing scopes double-counting the same buffer
                continue
            _scope_by_id[id(a)] = name
            key = (name, tuple(a.shape), str(a.dtype))
            ent = _alloc_stats.setdefault(key, [0, 0])
            ent[0] += 1
            ent[1] += a.nbytes


record = scope


def mark_step(name=None):
    """Record one training step's memory watermark: total live buffer
    bytes, plus the backend's peak_bytes_in_use when it reports one
    (reference: per-step rows of the GPU memory profiler)."""
    import jax

    arrs = jax.live_arrays()  # one heap walk for bytes AND pruning
    live = sum(a.nbytes for a in arrs)
    peak = device_memory_info().get("peak_bytes_in_use")
    _steps.append((name or f"step{len(_steps)}", live, peak))
    alive = {id(a) for a in arrs}
    for bid in [b for b in _scope_by_id if b not in alive]:
        del _scope_by_id[bid]


def memory_records():
    """Aggregated per-allocation rows: (scope, shape, dtype, count, bytes)."""
    return [(s, shp, dt, c, b)
            for (s, shp, dt), (c, b) in sorted(_alloc_stats.items())]


def dump_memory_csv(path):
    """CSV dump of per-allocation stats (reference: storage_profiler.h:131
    GpuMemoryProfiler CSV: name, requested size, actual size)."""
    import csv

    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["scope", "shape", "dtype", "count", "total_bytes",
                    "kind"])
        for row in memory_records():
            w.writerow([row[0], "x".join(map(str, row[1])), row[2],
                        row[3], row[4], "alloc"])
        for name, live, peak in _steps:
            w.writerow([name, "", "", "", live, "live_bytes"])
            if peak is not None:
                w.writerow([name, "", "", "", peak, "peak_bytes_in_use"])


def _top_live_buffers(k=10):
    return live_buffer_census(k)["top"]


def live_buffer_census(k=10):
    """One heap walk over ``jax.live_arrays()``: total live bytes, buffer
    count, and the top-k buffers as (nbytes, shape, dtype, scope) with
    birth-scope attribution when profiling recorded one. This is the live
    half of ``telemetry.memory_report()``'s ledger (the static half comes
    from per-program ``memory_analysis()``)."""
    import jax

    arrs = jax.live_arrays()
    top = sorted(arrs, key=lambda a: -a.nbytes)[:k]
    return {
        "live_bytes": sum(a.nbytes for a in arrs),
        "count": len(arrs),
        "top": [(a.nbytes, tuple(a.shape), str(a.dtype),
                 _scope_by_id.get(id(a), "<untracked>")) for a in top],
    }


class Profiler:
    """Context-manager style profiler (gluon-era API)."""

    def __init__(self, **kwargs):
        set_config(**kwargs)

    def __enter__(self):
        start()
        return self

    def __exit__(self, *exc):
        stop()
