"""XLA cost accounting: flops / bytes-accessed per compiled program, MFU.

``record_program_cost(site, compiled)`` snapshots ``cost_analysis()`` once
per compile at every AOT site (``CachedOp.aot_compile``, the compiled
train step, Predictor buckets, decode programs). Capture is UNCONDITIONAL
— it happens at compile time, which is off the hot path, and the numbers
must exist even when telemetry is enabled only later (bench warms up with
telemetry off, then turns it on for the accounting pass).

``cost_report()`` joins the cost table with the ``<site>.call`` program
timers into achieved FLOP/s and MFU per program; ``device_peak_flops()``
resolves the denominator from ``MXTPU_PEAK_FLOPS`` or a per-backend peak
table (bf16 dense peak — the unit the TPU datasheets quote). This table
is the measured-cost feed ROADMAP item 4's autotuner trains against.
"""
from __future__ import annotations

import os
import threading
import time

__all__ = ["record_program_cost", "program_costs", "flops_for",
           "device_peak_flops", "peak_flops_info", "table_peak_flops",
           "cost_report", "reset_costs"]

# peak dense-bf16 FLOP/s per chip by device-kind substring (same numbers
# bench.py has always used for its MFU line; CPU has no meaningful dense
# peak — use MXTPU_PEAK_FLOPS to pin a nominal one)
PEAK_BF16 = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6": 918e12,
}

_LOCK = threading.Lock()
# site -> {"flops", "bytes_accessed", "compiles", "captured_at"}
_COSTS: dict = {}

_PEAK_CACHE = (None, None)  # (env string at resolve time, peak or None)


def _cost_dict(compiled):
    """``compiled.cost_analysis()`` (a dict), or None when the backend
    has no analysis."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — analysis is best-effort by contract
        return None
    return ca if isinstance(ca, dict) else None


def record_program_cost(site, compiled):
    """Capture flops/bytes for one compiled program under ``site``.

    Returns ``{"flops", "bytes_accessed"}`` (floats, 0.0 when the backend
    reports nothing) or None when no analysis is available at all. Never
    raises: a cost-analysis failure must not break a compile."""
    ca = _cost_dict(compiled)
    if ca is None:
        return None
    # XLA reports -1 for "unknown" on some backends; clamp to 0
    flops = max(float(ca.get("flops", 0.0) or 0.0), 0.0)
    nbytes = max(float(ca.get("bytes accessed", 0.0) or 0.0), 0.0)
    with _LOCK:
        ent = _COSTS.get(site)
        if ent is None:
            ent = {"flops": flops, "bytes_accessed": nbytes,
                   "compiles": 1, "captured_at": time.time()}
            _COSTS[site] = ent
        else:  # re-capture (new bucket signature at same site): keep latest
            ent.update(flops=flops, bytes_accessed=nbytes,
                       compiles=ent["compiles"] + 1,
                       captured_at=time.time())
    return {"flops": flops, "bytes_accessed": nbytes}


def flops_for(site):
    ent = _COSTS.get(site)
    return ent["flops"] if ent else 0.0


def program_costs():
    """Snapshot copy of the cost table: {site: {flops, bytes_accessed,
    compiles, captured_at}}."""
    with _LOCK:
        return {k: dict(v) for k, v in _COSTS.items()}


def reset_costs():
    with _LOCK:
        _COSTS.clear()


def peak_flops_info():
    """{"peak": float|None, "source": "env"|"device-table"|None}.

    ``MXTPU_PEAK_FLOPS`` (a float, FLOP/s per chip) wins; otherwise the
    local device kind is matched against the bf16 peak table. CPU resolves
    to None — MFU is undefined without a declared peak."""
    global _PEAK_CACHE
    env = os.environ.get("MXTPU_PEAK_FLOPS")
    if _PEAK_CACHE[0] == env and env is not None:
        return {"peak": _PEAK_CACHE[1], "source": "env"}
    if env is not None:
        try:
            peak = float(env)
        except ValueError:
            peak = None
        _PEAK_CACHE = (env, peak)
        return {"peak": peak, "source": "env" if peak else None}
    try:
        import jax

        kind = jax.devices()[0].device_kind
    except Exception:  # noqa: BLE001 — no backend yet
        return {"peak": None, "source": None}
    peak = table_peak_flops(kind)
    return {"peak": peak, "source": "device-table" if peak else None}


def table_peak_flops(device_kind):
    """Peak bf16 FLOP/s of ``device_kind`` from the table alone (no env
    override), or None for a device the table does not know."""
    # longest-match so "TPU v5" does not shadow "TPU v5 lite"
    best = None
    for sub, peak in PEAK_BF16.items():
        if sub.lower() in str(device_kind).lower():
            if best is None or len(sub) > len(best[0]):
                best = (sub, peak)
    return best[1] if best else None


def device_peak_flops():
    """Peak FLOP/s per chip, or None when unknown (see peak_flops_info)."""
    return peak_flops_info()["peak"]


def cost_report(registry=None, peak=None):
    """Per-program rows joining static cost with measured host time.

    {site: {flops, bytes_accessed, compiles, calls, total_s,
            achieved_flops_s, mfu}} — ``calls``/``total_s`` come from the
    ``<site>.call`` Timer when one exists (programs dispatched without
    telemetry enabled have cost but no timing), ``mfu`` is
    achieved/peak or None without a peak."""
    if registry is None:
        from . import REGISTRY as registry  # noqa: N813 — module singleton
    if peak is None:
        peak = device_peak_flops()
    timers = {t.name: t for t in registry
              if type(t).__name__ == "Timer"}
    out = {}
    for site, ent in program_costs().items():
        t = timers.get(site + ".call")
        calls = t.count if t is not None else 0
        total_s = t.total if t is not None else 0.0
        achieved = (ent["flops"] * calls / total_s) if total_s > 0 else None
        row = {"flops": ent["flops"],
               "bytes_accessed": ent["bytes_accessed"],
               "compiles": ent["compiles"],
               "calls": calls, "total_s": total_s,
               "achieved_flops_s": achieved,
               "mfu": (achieved / peak) if (achieved and peak) else None}
        out[site] = row
    return out
