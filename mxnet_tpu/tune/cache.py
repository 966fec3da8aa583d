"""Persistent kernel-tuning cache + in-process lookup tier.

The autotuner (``tune/tuner.py``) measures block/grid candidates for each
Pallas kernel family and records the winner here, keyed by

    ``<kernel>|<shape-bucket>.<dtype>[.flags]``

where every shape dimension is rounded up to its power-of-two bucket —
the same ladder the serving Predictor and decode engine AOT-compile
against, so one offline sweep covers every steady-state trace.

Two tiers:

- **In-process LRU** (``resolve``): the kernel hot path consults it at
  TRACE time only (block sizes are static arguments of the compiled
  program), so steady state pays nothing. A miss with tuning enabled
  returns the XLA-native lowering — never silently slower than the
  untuned default — and is counted (``tune.cache_misses`` +
  ``tune.fallback_xla``).
- **Versioned JSON file** (``save``/``preload``): lives next to the
  persistent XLA compile cache (``context.tuning_cache_path()``), keyed
  by ``context.env_signature()``. A file written under a
  different signature, an unknown schema version, or a corrupt entry is
  skipped with a warning and re-tuned — stale winners are never replayed
  into a different environment. Production processes ``preload()`` at
  warmup and never tune online (``tune.measurements`` stays flat).

Counters/gauges are registered unconditionally (like the Predictor's
serving stats): they only move at trace/tune time, never per dispatch.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import warnings
from collections import OrderedDict

import numpy as onp

from .. import telemetry as _tm

SCHEMA_VERSION = 1
_LRU_CAP = 4096

_C_HITS = _tm.counter("tune.cache_hits")
_C_MISSES = _tm.counter("tune.cache_misses")
_C_FALLBACK = _tm.counter("tune.fallback_xla")
_C_CORRUPT = _tm.counter("tune.cache_corrupt")
_C_MEASURE = _tm.counter("tune.measurements")
_G_ENTRIES = _tm.gauge("tune.entries")

_lock = threading.RLock()
_lru = OrderedDict()            # (kernel, key) -> entry dict
_missed = OrderedDict()         # (kernel, key) -> None, insertion-ordered
_state = {"loaded": False, "dirty": False, "path": None}
_tls = threading.local()
_MISSING = object()


def enabled() -> bool:
    """True when the tuned kernel tier is on (``MXTPU_TUNE``)."""
    return os.environ.get("MXTPU_TUNE", "").lower() in ("1", "true", "on")


def trials() -> int:
    """Measurement trials per candidate (``MXTPU_TUNE_TRIALS``)."""
    try:
        return max(1, int(os.environ.get("MXTPU_TUNE_TRIALS", "") or 3))
    except ValueError:
        return 3


def cache_path():
    from ..context import tuning_cache_path

    return tuning_cache_path()


# ------------------------------------------------------------------- keys
def bucket(n) -> int:
    """Smallest power of two >= n (the serving ladder's bucket rule)."""
    n = max(1, int(n))
    p = 1
    while p < n:
        p <<= 1
    return p


def key_attention(kernel, q_shape, k_shape, dtype, causal, seg) -> str:
    b, h, tq, d = q_shape
    tk = k_shape[2]
    return (f"{kernel}|bh{bucket(b * h)}.tq{bucket(tq)}.tk{bucket(tk)}"
            f".d{bucket(d)}.{onp.dtype(dtype).name}"
            f".c{int(bool(causal))}.s{int(bool(seg))}")


def key_rows(kernel, rows, d, dtype) -> str:
    return (f"{kernel}|rows{bucket(rows)}.d{bucket(d)}"
            f".{onp.dtype(dtype).name}")


# -------------------------------------------------------------- validation
def _config_ok(cfg) -> bool:
    if cfg == "xla":
        return True
    if not isinstance(cfg, dict) or not cfg:
        return False
    return all(isinstance(k, str) and isinstance(v, int) and v > 0
               for k, v in cfg.items())


def _entry_ok(key, ent) -> bool:
    return (isinstance(key, str) and "|" in key and isinstance(ent, dict)
            and _config_ok(ent.get("config")))


# ------------------------------------------------------------ file loading
def _load_locked():
    if _state["loaded"]:
        return
    _state["loaded"] = True
    path = cache_path()
    _state["path"] = path
    if not path or not os.path.exists(path):
        return
    from ..context import env_signature

    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, ValueError) as e:
        _C_CORRUPT.inc()
        warnings.warn(
            f"kernel tuning cache {path} is unreadable ({e}); ignoring it "
            "— re-run the tuner (tools/tune_kernels.py) to rebuild",
            RuntimeWarning, stacklevel=3)
        return
    if not isinstance(doc, dict) or doc.get("version") != SCHEMA_VERSION:
        _C_CORRUPT.inc()
        warnings.warn(
            f"kernel tuning cache {path} has schema version "
            f"{doc.get('version') if isinstance(doc, dict) else '?'!r} "
            f"(this build reads {SCHEMA_VERSION}); ignoring it — stale "
            "winners are re-tuned, not replayed", RuntimeWarning,
            stacklevel=3)
        return
    sig = env_signature()
    if doc.get("env_signature") != sig:
        warnings.warn(
            f"kernel tuning cache {path} was written under a different "
            "environment signature (interpreter/jax/platform-env changed); "
            "not reusing its winners", RuntimeWarning, stacklevel=3)
        return
    for key, ent in (doc.get("entries") or {}).items():
        if not _entry_ok(key, ent):
            _C_CORRUPT.inc()
            warnings.warn(
                f"skipping corrupt tuning-cache entry {key!r} in {path}; "
                "it will fall back to XLA until re-tuned", RuntimeWarning,
                stacklevel=3)
            continue
        _lru_put_locked((key.split("|", 1)[0], key), ent)
    _G_ENTRIES.set(float(len(_lru)))


def _lru_put_locked(k, ent):
    _lru[k] = ent
    _lru.move_to_end(k)
    while len(_lru) > _LRU_CAP:
        _lru.popitem(last=False)


# ----------------------------------------------------------------- resolve
def resolve(kernel, key):
    """Trace-time config lookup for the kernel hot path.

    Returns ``"default"`` (tuning off: use the env-default blocks), a
    config dict (tuned winner), or ``"xla"`` (tuned loss OR miss — use
    the XLA-native lowering, never a possibly-slower untuned kernel).
    A thread-local :func:`override` wins over everything (measurement /
    bench / test hook) and moves no counters.
    """
    ov = getattr(_tls, "overrides", None)
    if ov:
        cfg = ov.get(kernel, _MISSING)
        if cfg is not _MISSING:
            return cfg
    if not enabled():
        return "default"
    with _lock:
        _load_locked()
        ent = _lru.get((kernel, key))
        if ent is not None:
            _lru.move_to_end((kernel, key))
        else:
            if len(_missed) < _LRU_CAP:
                _missed[(kernel, key)] = None
    if ent is None:
        _C_MISSES.inc()
        _C_FALLBACK.inc()
        return "xla"
    _C_HITS.inc()
    cfg = ent["config"]
    if cfg == "xla":
        _C_FALLBACK.inc()
        return "xla"
    return dict(cfg)


def missed():
    """(kernel, key) pairs that resolved to a miss since the last
    ``reset()`` — the offline-tuning worklist: warm the serving process
    once with ``MXTPU_TUNE=1``, read this, tune exactly these buckets."""
    with _lock:
        return list(_missed)


@contextlib.contextmanager
def override(kernel, config):
    """Force ``config`` (dict | ``"xla"`` | ``"default"``) for ``kernel``
    on this thread — how the tuner (and bench) traces each candidate."""
    if not _config_ok(config) and config != "default":
        raise ValueError(f"invalid tuning override for {kernel}: {config!r}")
    ov = getattr(_tls, "overrides", None)
    if ov is None:
        ov = _tls.overrides = {}
    prev = ov.get(kernel, _MISSING)
    ov[kernel] = config
    try:
        yield
    finally:
        if prev is _MISSING:
            del ov[kernel]
        else:
            ov[kernel] = prev


# ------------------------------------------------------------------ record
def record(kernel, key, config, **stats):
    """Install a tuned winner in the process LRU (marking the cache dirty
    for the next ``save``) and surface it as ``tune.winner.*`` gauges."""
    if not _config_ok(config):
        raise ValueError(f"invalid tuned config for {kernel}: {config!r}")
    ent = {"config": config, **stats, "created_unix": time.time()}
    with _lock:
        _load_locked()
        _lru_put_locked((kernel, key), ent)
        _missed.pop((kernel, key), None)
        _state["dirty"] = True
        _G_ENTRIES.set(float(len(_lru)))
    if isinstance(config, dict):
        for p, v in config.items():
            _tm.gauge(f"tune.winner.{kernel}.{p}").set(float(v))
    else:
        _tm.gauge(f"tune.winner.{kernel}.xla").set(1.0)
    return ent


def count_measurement(n=1):
    _C_MEASURE.inc(n)


def measurements() -> int:
    return int(_C_MEASURE.value)


# -------------------------------------------------------------- save/load
def save(path=None):
    """Atomically write the in-process entries, merged over any valid
    entries already on disk (last writer's keys win). Returns the path,
    or None when persistence is disabled."""
    from ..context import env_signature

    import jax

    with _lock:
        _load_locked()
        if path is None:
            path = _state["path"] or cache_path()
        if not path:
            return None
        sig = env_signature()
        entries = {}
        try:
            with open(path) as fh:
                doc = json.load(fh)
            if (isinstance(doc, dict)
                    and doc.get("version") == SCHEMA_VERSION
                    and doc.get("env_signature") == sig):
                entries.update({k: e for k, e in
                                (doc.get("entries") or {}).items()
                                if _entry_ok(k, e)})
        except (OSError, ValueError):
            pass
        entries.update({key: ent for (_, key), ent in _lru.items()})
        doc = {
            "version": SCHEMA_VERSION,
            "env_signature": sig,
            "jax_version": getattr(jax, "__version__", "?"),
            "entries": entries,
            "created_unix": time.time(),
        }
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
        os.replace(tmp, path)
        _state["dirty"] = False
    return path


def preload() -> int:
    """Load the persistent winners into the in-process LRU (no-op when
    tuning is off) — ``Predictor.warmup`` / ``DecodePrograms.warmup``
    call this so every ladder-bucket trace resolves from memory and the
    serving process never touches the tuner. Returns the entry count."""
    if not enabled():
        return 0
    with _lock:
        _load_locked()
        _G_ENTRIES.set(float(len(_lru)))
        return len(_lru)


def entries() -> dict:
    """Snapshot of the resident entries: {``kernel|key``: entry}."""
    with _lock:
        _load_locked()
        return {key: dict(ent) for (_, key), ent in _lru.items()}


def reset():
    """Drop the in-process tier (LRU + loaded latch + miss log) — the
    fresh-process simulation for tests. The persistent file and the
    telemetry counters are untouched."""
    with _lock:
        _lru.clear()
        _missed.clear()
        _state["loaded"] = False
        _state["dirty"] = False
        _state["path"] = None


def status() -> dict:
    with _lock:
        return {
            "enabled": enabled(),
            "entries": len(_lru),
            "loaded": _state["loaded"],
            "path": _state["path"] if _state["loaded"] else cache_path(),
            "hits": int(_C_HITS.value),
            "misses": int(_C_MISSES.value),
            "fallback_xla": int(_C_FALLBACK.value),
            "measurements": int(_C_MEASURE.value),
        }
