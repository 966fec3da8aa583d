"""gluon.Parameter — a tensor with initialization, grad and sharing semantics.

Reference: python/mxnet/gluon/parameter.py (Parameter:47, deferred init,
grad_req handling). TPU-native notes: parameter data is a PJRT HBM buffer
(NDArray); the gradient buffer is attached through autograd.mark_variables so
tape backward accumulates into it; deferred initialization works exactly like
the reference (shape with -1/0 unknown until the first forward infers it).
"""
from __future__ import annotations

import numpy as onp

from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray.ndarray import NDArray
from .. import initializer as init_mod
from .. import autograd

__all__ = ["Parameter", "Constant", "DeferredInitializationError"]


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its shape could be inferred."""


def _shape_known(shape):
    return shape is not None and all(s is not None and s > 0 for s in shape)


class Parameter:
    def __init__(self, name="param", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_req="write",
                 grad_stype="default"):
        self._name = name
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._grad_req = grad_req if differentiable else "null"
        self._data = None
        self._deferred_init = None  # (init, ctx) pending shape
        self._trainer = None
        # FSDP residency: (manager, position) once the compiled train step
        # adopts this parameter into dp-sharded flat buckets. ``_data`` is
        # then None between steps; data()/set_data route through the manager
        self._provider = None

    # -- identity -----------------------------------------------------------
    @property
    def name(self):
        return self._name

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is not None and _shape_known(self._shape):
            # only unknown dims may be filled in
            for old, new in zip(self._shape, new_shape):
                if old > 0 and old != new:
                    raise MXNetError(
                        f"cannot change shape of {self.name} from "
                        f"{self._shape} to {new_shape}")
        self._shape = tuple(new_shape)

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        self._grad_req = req
        if self._data is not None:
            if req == "null":
                self._data._ag_info = None
                self._data._grad = None
            else:
                self._attach_grad()

    # -- initialization -----------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False, device=None):
        ctx = device or ctx
        if self._data is not None and not force_reinit:
            return
        if isinstance(ctx, (list, tuple)):
            if len(ctx) > 1:
                raise MXNetError(
                    "multi-context parameter replication is superseded by "
                    "mesh sharding on TPU (mxnet_tpu.parallel); pass one ctx")
            ctx = ctx[0]
        effective = init or self.init or default_init or \
            init_mod.Uniform(0.07)
        if not _shape_known(self._shape):
            if not self.allow_deferred_init:
                raise DeferredInitializationError(
                    f"parameter {self.name} has unknown shape {self._shape} "
                    "and allow_deferred_init is False")
            self._deferred_init = (effective, ctx)
            return
        self._init_impl(effective, ctx)

    def _init_impl(self, initializer, ctx):
        import jax.numpy as jnp

        ctx = ctx or current_context()
        arr = NDArray(jnp.zeros(self._shape, self.dtype))
        init_mod.create(initializer)(self.name, arr)
        if ctx is not None:
            arr = arr.as_in_ctx(ctx)
        self._data = arr
        self._deferred_init = None
        if self._grad_req != "null":
            self._attach_grad()

    def _attach_grad(self):
        import jax.numpy as jnp

        grad = NDArray(jnp.zeros(self._data.shape, self._data.dtype))
        autograd.mark_variables([self._data], [grad], [self._grad_req])

    def _finish_deferred_init(self, in_shape=None):
        if self._deferred_init is None:
            return
        if not _shape_known(self._shape):
            raise DeferredInitializationError(
                f"shape of {self.name} still unknown: {self._shape}")
        initializer, ctx = self._deferred_init
        self._init_impl(initializer, ctx)

    # -- access -------------------------------------------------------------
    def data(self, ctx=None):
        if self._data is None:
            if self._provider is not None:
                # FSDP-adopted: materialize the full value from the owning
                # shard bucket (host gather — checkpoint/inspection path,
                # never the training hot path)
                mgr, pos = self._provider
                return mgr.param_ndarray(pos)
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    f"parameter {self.name} awaits shape inference; run a "
                    "forward pass or call infer_shape first")
            raise MXNetError(
                f"parameter {self.name} is not initialized; call "
                ".initialize() first")
        return self._data

    def list_data(self):
        return [self.data()]

    def grad(self, ctx=None):
        if self._data is None and self._provider is not None:
            raise MXNetError(
                f"parameter {self.name} is adopted by the FSDP compiled "
                "step (shard_params=True): gradients exist only inside the "
                "compiled program, pre-scattered into the owning shard — "
                "compile without shard_params to inspect per-param grads")
        d = self.data()
        if d._grad is None:
            raise MXNetError(f"parameter {self.name} has grad_req='null'")
        if d._grad._data.shape != d._data.shape:
            # released (release_grad): whoever asks gets the full zeros
            import jax.numpy as jnp

            d._grad._set_data(jnp.zeros(d._data.shape, d._grad._data.dtype))
        return d._grad

    def release_grad(self):
        """Let go of the gradient buffer's device memory (4 bytes an
        element in float32). A scalar zero stands in for it: an eager
        backward binds (``write``) or broadcasts into (``add``) a gradient
        of full size again, ``grad()`` gives full zeros back to whoever
        asks, ``zero_grad`` leaves it released. For a caller that keeps its
        gradients elsewhere, as the compiled train step does inside its
        program."""
        g = None if self._data is None else self._data._grad
        if g is not None and g._data.ndim:
            import jax.numpy as jnp

            g._set_data(jnp.zeros((), g._data.dtype))

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        return [self.data().ctx]

    def set_data(self, data):
        if isinstance(data, NDArray):
            data = data._data
        if self._shape is not None and _shape_known(self._shape) and \
                tuple(data.shape) != self._shape:
            raise MXNetError(
                f"shape mismatch for parameter {self.name}: expected "
                f"{self._shape}, got {tuple(data.shape)}")
        if self._data is None:
            if self._provider is not None:
                # FSDP-adopted: write through into the shard bucket
                mgr, pos = self._provider
                mgr.param_write(pos, data)
                return
            import jax.numpy as jnp

            self._shape = tuple(data.shape)
            self._data = NDArray(data)
            if self._grad_req != "null":
                self._attach_grad()
        else:
            self._data._set_data(data)

    def zero_grad(self):
        if self._data is not None and self._data._grad is not None:
            import jax.numpy as jnp

            g = self._data._grad
            g._set_data(jnp.zeros(g.shape, g.dtype))

    def reset_ctx(self, ctx):
        if self._data is not None:
            self._data = self._data.as_in_ctx(ctx)

    reset_device = reset_ctx

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is not None:
            ag_info = self._data._ag_info
            self._data._set_data(self._data._data.astype(
                "bfloat16" if str(dtype) == "bfloat16" else dtype))
            if self._grad_req != "null":
                self._attach_grad()

    def var(self):
        from ..symbol.symbol import var

        return var(self.name)

    def __repr__(self):
        return (f"Parameter {self.name} (shape={self._shape}, "
                f"dtype={self.dtype})")


class Constant(Parameter):
    """Non-trainable parameter holding a fixed value (reference:
    gluon/parameter.py Constant)."""

    def __init__(self, value, name="const"):
        if not isinstance(value, NDArray):
            value = NDArray(onp.asarray(value))
        self._value = value
        super().__init__(name=name, shape=value.shape,
                         dtype=str(value.dtype), grad_req="null",
                         init=init_mod.Constant(value))

    def initialize(self, *args, **kwargs):
        kwargs.setdefault("default_init", self.init)
        super().initialize(*args, **kwargs)
