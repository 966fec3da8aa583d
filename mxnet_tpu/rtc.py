"""Runtime kernel compilation (reference: python/mxnet/rtc.py over
src/common/rtc.cc — NVRTC CudaModule).

TPU-native equivalent: runtime-compiled kernels are Pallas kernels. This
module keeps the CudaModule API shape but compiles PALLAS PYTHON SOURCE
instead of CUDA C: the source string must define ``kernel(in_refs...,
out_refs...)`` in terms of the pallas namespace; ``get_kernel().launch``
invokes it through pallas_call. CUDA source is rejected with a pointer to
the Pallas guide.
"""
from __future__ import annotations

from .base import MXNetError
from .ndarray.ndarray import NDArray

__all__ = ["CudaModule", "PallasModule"]


class PallasKernel:
    def __init__(self, fn, out_shapes, out_dtypes):
        self._fn = fn
        self._out_shapes = out_shapes
        self._out_dtypes = out_dtypes

    def launch(self, args, *unused_launch_dims):
        """Run the kernel over full-array blocks (grid handled by XLA)."""
        import jax
        from jax.experimental import pallas as pl

        datas = [a._data if isinstance(a, NDArray) else a for a in args]
        out_shape = [jax.ShapeDtypeStruct(s, d)
                     for s, d in zip(self._out_shapes, self._out_dtypes)]
        from .context import default_backend

        out = pl.pallas_call(
            self._fn,
            out_shape=out_shape if len(out_shape) > 1 else out_shape[0],
            interpret=default_backend() != "tpu",
        )(*datas)
        if isinstance(out, (tuple, list)):
            return tuple(NDArray(o) for o in out)
        return NDArray(out)


class PallasModule:
    """Compile Pallas kernel source at runtime (the CudaModule role)."""

    def __init__(self, source, options=(), exports=()):
        if "__global__" in source or "blockIdx" in source:
            raise MXNetError(
                "CUDA C source is not supported on TPU; write a Pallas "
                "kernel (see /opt/skills/guides/pallas_guide.md). The "
                "source must define python functions over pl.Ref arguments.")
        self._namespace = {}
        import jax
        import jax.numpy as jnp

        try:
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            self._namespace.update({"pl": pl, "pltpu": pltpu})
        except ImportError:
            pass
        self._namespace.update({"jax": jax, "jnp": jnp})
        exec(compile(source, "<rtc>", "exec"), self._namespace)  # noqa: S102

    def get_kernel(self, name, signature=None, out_shapes=(),
                   out_dtypes=None):
        if name not in self._namespace:
            raise MXNetError(f"kernel {name!r} not defined in module source")
        import numpy as onp

        dtypes = out_dtypes or [onp.float32] * len(out_shapes)
        return PallasKernel(self._namespace[name], list(out_shapes),
                            list(dtypes))


CudaModule = PallasModule  # reference-name alias
