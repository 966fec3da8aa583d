"""Device / Context model.

TPU-native equivalent of the reference's ``python/mxnet/context.py`` and the C++
``Context`` (include/mxnet/base.h:94-118, device types kCPU=1 kGPU=2 kCPUPinned=3
kCPUShared=5). Here the first-class accelerator is TPU: ``mx.tpu()`` resolves to a
PJRT TPU device through JAX; ``mx.cpu()`` resolves to the host platform. ``gpu`` is
accepted as an alias for the local accelerator so unmodified reference scripts run.

A Context is a lightweight (device_type, device_id) value object; the actual JAX
``Device`` is resolved lazily (so importing the package never forces a TPU runtime
handshake — important for fork-based DataLoader workers, see reference
src/initialize.cc:71-97 for the class of bug this avoids).
"""
from __future__ import annotations

import contextlib
import threading

from .base import MXNetError

__all__ = [
    "Context",
    "cpu",
    "cpu_pinned",
    "gpu",
    "tpu",
    "device",
    "default_backend",
    "current_context",
    "current_device",
    "num_gpus",
    "num_tpus",
    "tpu_memory_info",
    "gpu_memory_info",
    "compilation_cache_dir",
    "enable_compilation_cache",
    "disable_compilation_cache",
]

_DEVTYPES = ("cpu", "tpu", "cpu_pinned", "cpu_shared", "gpu")


class Context:
    """Execution device handle.

    Reference parity: ``mx.Context`` — usable as a context manager
    (``with mx.tpu(0): ...``) and as the ``ctx``/``device`` argument everywhere.
    """

    _local = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in _DEVTYPES:
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- resolution to a JAX / PJRT device ---------------------------------
    @property
    def _platform(self) -> str:
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            return "cpu"
        if self.device_type == "tpu":
            return "tpu"
        # 'gpu' alias: whatever the default platform is
        return default_backend()

    def jax_device(self):
        """Resolve to the concrete ``jax.Device`` (PJRT device).

        Uses local_devices: under jax.distributed, jax.devices() spans all
        processes and placing onto another process's device is an error.
        """
        import jax

        plat = self._platform
        try:
            devs = jax.local_devices(backend=plat)
        except RuntimeError as e:  # platform absent
            if plat != "cpu":
                raise MXNetError(
                    f"no {plat} devices available (requested {self})"
                ) from e
            raise
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self} out of range: only {len(devs)} {plat} device(s) present"
            )
        return devs[self.device_id]

    # -- context-manager protocol (thread-local stack, like reference) ------
    def __enter__(self):
        stack = getattr(Context._local, "stack", None)
        if stack is None:
            stack = Context._local.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._local.stack.pop()

    # -- value semantics ----------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return repr(self)


Device = Context  # mxnet 2.x renamed Context -> Device; keep both names


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    # On TPU hosts all host memory goes through the same PJRT transfer path;
    # pinned is an alias of cpu kept for API parity.
    return Context("cpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias for the local accelerator so reference GPU scripts run unmodified."""
    return Context("gpu", device_id)


def device(dev: str | Context | None = None, device_id: int = 0) -> Context:
    if dev is None:
        return current_context()
    if isinstance(dev, Context):
        return dev
    if isinstance(dev, str):
        if ":" in dev:
            kind, idx = dev.split(":")
            return Context(kind, int(idx))
        return Context(dev, device_id)
    raise MXNetError(f"cannot interpret {dev!r} as a device")


def default_backend() -> str:
    """``jax.default_backend()``. A failed accelerator init raises: nothing
    here or above it continues on the CPU in the accelerator's place."""
    import jax

    return jax.default_backend()


def env_signature() -> str:
    """Hash of (interpreter, jax version, platform environment): the key of
    the kernel tuning cache and of the serving manifests. Tuned winners and
    exported programs measured under one configuration are refused under
    another."""
    import hashlib
    import os
    import sys

    import jax

    parts = [sys.executable, jax.__version__]
    for k in ("JAX_PLATFORMS", "TPU_NAME", "TPU_LIBRARY_PATH", "PJRT_DEVICE"):
        parts.append(f"{k}={os.environ.get(k, '')}")
    return hashlib.sha256("\0".join(parts).encode()).hexdigest()[:16]


@contextlib.contextmanager
def spawn_cpu_pinned_env():
    """Context manager setting ``JAX_PLATFORMS=cpu`` around
    ``Process.start()``: spawned children inherit the environment at exec
    time, so they come up on the CPU and never reach for the chip the
    parent holds. DataLoader and the benches use it."""
    import os

    saved = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = saved


def default_context() -> Context:
    """The default device: TPU if the runtime has one, else CPU."""
    return tpu(0) if default_backend() == "tpu" else cpu(0)


def current_context() -> Context:
    stack = getattr(Context._local, "stack", None)
    if stack:
        return stack[-1]
    return default_context()


current_device = current_context


def num_gpus() -> int:
    """Reference-parity name; counts local accelerators."""
    return num_tpus()


def _memory_info(ctx):
    dev = ctx.jax_device()
    stats = dev.memory_stats()
    if not stats:
        raise MXNetError(
            f"device {dev} reports no memory statistics (backend without "
            "memory_stats support)")
    total = stats.get("bytes_limit", 0)
    used = stats.get("bytes_in_use", 0)
    return total - used, total


def tpu_memory_info(device_id: int = 0):
    """(free, total) HBM bytes for a local chip (reference:
    mx.context.gpu_memory_info over cudaMemGetInfo)."""
    return _memory_info(tpu(device_id))


def gpu_memory_info(device_id: int = 0):
    """Legacy alias resolving through the gpu() platform alias (so plugin
    accelerator platforms behave the same as mx.gpu() placements)."""
    return _memory_info(gpu(device_id))


def num_tpus() -> int:
    import jax

    try:
        # local (addressable) chips: under jax.distributed, global devices
        # span other hosts and cannot be targeted by this process
        return len(jax.local_devices(backend="tpu"))
    except RuntimeError:
        return 0


# -- persistent compilation cache -------------------------------------------
_compile_cache_state = {"dir": None, "enabled": False}


def compilation_cache_dir() -> str:
    """Where compiled XLA programs persist: ``JAX_COMPILATION_CACHE_DIR``
    when the caller placed the cache from outside, else one fixed
    directory inside the checkout (``<repo>/.jax_cache``). The path is part
    of nothing's key and never moves, so a second process finds what the
    first one compiled."""
    import os

    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def tuning_cache_path() -> str | None:
    """On-disk kernel tuning cache (``tune/``), or None when persistence
    is disabled.

    Default: ``tuning_cache.json`` inside whichever compilation-cache
    directory is in use. ``MXTPU_TUNE_CACHE`` overrides the full path (the
    tune layer still refuses a file whose recorded :func:`env_signature`
    differs); ``MXTPU_TUNE_CACHE=off`` disables persistence while leaving
    the in-process tier working.
    """
    import os

    override = os.environ.get("MXTPU_TUNE_CACHE", "")
    if override.lower() in ("0", "off", "none", "disabled"):
        return None
    if override:
        return override
    return os.path.join(
        _compile_cache_state["dir"] or compilation_cache_dir(),
        "tuning_cache.json")


def enable_compilation_cache(path=None):
    """Turn on jax's persistent compilation cache so compiled XLA programs
    survive the process: the trainer's compiled step, ``serve.Predictor``
    and ``DecodeEngine`` all come through here.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax already has its directory
    and this function sets no other — ``path`` is ignored. Without it the
    directory is ``path``, or :func:`compilation_cache_dir`'s fixed
    in-checkout default. Either way the two thresholds (min compile time /
    entry size) drop to zero so every program is cached, including the
    small per-bucket serving programs the defaults would skip. Idempotent;
    returns the directory in use.
    """
    import os

    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or path or compilation_cache_dir()
    if _compile_cache_state["enabled"] and \
            _compile_cache_state["dir"] == path:
        return path
    if not placed:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    # jax latches the cache decision at the FIRST compile of the process: a
    # compile before this call pins "no cache" for good unless the latch is
    # reset. Framework import / model init always compiles something.
    from jax._src import compilation_cache as _cc

    _cc.reset_cache()
    _compile_cache_state.update(dir=path, enabled=True)
    return path


def disable_compilation_cache():
    """Turn persistence back off (idempotent). The test suite calls this
    after serve tests so later compile-heavy tests don't pay a disk write
    per XLA compile."""
    if not _compile_cache_state["enabled"]:
        return
    import jax
    from jax._src import compilation_cache as _cc

    jax.config.update("jax_enable_compilation_cache", False)
    _cc.reset_cache()
    _compile_cache_state.update(dir=None, enabled=False)
