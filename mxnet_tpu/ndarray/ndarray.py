"""NDArray: the framework's array type, backed by a PJRT device buffer.

TPU-native redesign of the reference NDArray (include/mxnet/ndarray.h:82,
src/ndarray/ndarray.cc; python surface python/mxnet/numpy/multiarray.py:272).
The reference pairs a Storage chunk with an engine var for async ordering; here
the payload is a ``jax.Array`` — an asynchronous future-backed HBM buffer whose
ordering XLA/PJRT guarantees per device. Consequences:

- every op returns immediately (async dispatch); ``wait_to_read`` /
  ``asnumpy`` block, and device-side errors are rethrown there (reference
  semantics of WaitToRead + exception-at-sync, threaded_engine.h:387).
- in-place mutation (``a[:] = x``, ``a += b``, optimizer updates) rebinds the
  underlying immutable buffer under the GIL — the Python-level program order
  provides the write-after-read ordering the reference enforced with engine
  vars. XLA may alias/donate buffers inside jit; the framework never exposes
  a stale view because NDArray is the only handle.
- one array class serves both ``mx.np`` (numpy semantics) and legacy ``mx.nd``
  namespaces (the reference kept two parallel classes).

All operators funnel through ops.registry.invoke so autograd recording and
deferred-compute tracing see every call.
"""
from __future__ import annotations

import numpy as onp

from ..base import MXNetError, canonical_dtype
from ..context import Context, current_context
from ..ops.registry import apply_op
from .. import engine

__all__ = ["NDArray", "array", "from_jax"]


def _ctx_of(jarr) -> Context:
    dev = jarr.devices() if callable(getattr(jarr, "devices", None)) else None
    if dev:
        d = next(iter(dev))
        plat = d.platform
        return Context("tpu" if plat == "tpu" else "cpu", d.id)
    return current_context()


# functions whose mx.np implementation is verified numpy-compatible —
# the analog of the reference's explicit HANDLED registry
# (numpy_dispatch_protocol.py _NUMPY_ARRAY_FUNCTION_LIST)
_NP_DISPATCH_HANDLED = frozenset({
    "concatenate", "stack", "vstack", "hstack", "dstack", "column_stack",
    "split", "array_split", "mean", "sum", "prod", "std", "var", "median",
    "max", "min", "amax", "amin", "argmax", "argmin", "clip", "reshape",
    "transpose", "swapaxes", "moveaxis", "squeeze", "expand_dims",
    "broadcast_to", "tile", "repeat", "flip", "roll", "rot90", "where",
    "take", "dot", "matmul", "tensordot", "inner", "outer", "kron",
    "trace", "diag", "diagonal", "tril", "triu", "sort", "argsort",
    "cumsum", "cumprod", "einsum", "atleast_1d", "atleast_2d",
    "atleast_3d", "ravel", "nansum", "nanmean", "nanmax", "nanmin",
    "quantile", "percentile", "average", "cov", "corrcoef", "bincount",
    "diff", "ediff1d", "interp", "meshgrid", "linspace", "logspace",
    "pad", "searchsorted", "digitize", "histogram", "zeros_like",
    "ones_like", "full_like",
})


class NDArray:
    __slots__ = ("_data", "_ag_info", "_grad", "_grad_req", "_dc_sym", "__weakref__")

    def __init__(self, data):
        import jax

        if not isinstance(data, jax.Array):
            import jax.numpy as jnp

            data = jnp.asarray(data)
        self._data = data
        self._ag_info = None
        self._grad = None
        self._grad_req = "write"
        self._dc_sym = None

    # ------------------------------------------------------------------ core
    def _set_data(self, data):
        """Rebind the device buffer (in-place semantics at the Python level)."""
        self._data = data

    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def itemsize(self):
        return self._data.dtype.itemsize

    @property
    def ctx(self) -> Context:
        return _ctx_of(self._data)

    context = ctx
    device = ctx

    @property
    def stype(self):
        return "default"  # sparse storage handled by sparse module wrappers

    @property
    def T(self):
        return apply_op("transpose", self)

    # ------------------------------------------------------------- sync / io
    def wait_to_read(self):
        engine.wait_for_var(self._data)
        return self

    def wait_to_write(self):
        # same barrier as wait_to_read by design: "writes" rebind the handle
        # to a fresh immutable buffer, so there is no write queue to drain
        # (docs/DESIGN.md "In-place semantics"); the reference needed the
        # distinction only because its engine mutated buffers in place
        return self.wait_to_read()

    def asnumpy(self) -> onp.ndarray:
        """Blocking copy to host (reference: NDArray::SyncCopyToCPU)."""
        try:
            out = onp.asarray(self._data)
            if not out.flags.owndata:
                # On CPU backends onp.asarray is a zero-copy VIEW of the
                # device buffer. Donated-buffer programs (the compiled
                # train step, the decode tick) alias and overwrite such
                # buffers in place, so a view taken here can change under
                # the caller once the allocator reuses the memory. The
                # contract is a snapshot — materialize an owned copy.
                out = out.copy()
            return out
        except MXNetError:
            raise
        except Exception as e:  # noqa: BLE001
            raise MXNetError(str(e)) from e

    def item(self):
        if self.size != 1:
            raise ValueError("can only convert an array of size 1 to a scalar")
        return self.asnumpy().reshape(()).item()

    def asscalar(self):
        return self.item()

    def tolist(self):
        return self.asnumpy().tolist()

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __index__(self):
        return int(self.item())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.item())
        raise ValueError(
            "The truth value of an array with more than one element is ambiguous."
        )

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __repr__(self):
        try:
            body = repr(self.asnumpy())
        except MXNetError as e:
            return f"<NDArray {self.shape} {self.dtype} [error: {e}]>"
        ctx = self.ctx
        suffix = f", ctx={ctx})" if ctx.device_type != "cpu" else ")"
        return body.replace("array(", "array(", 1)[:-1] + suffix if body.endswith(")") \
            else body

    def __dlpack__(self, **kw):
        return self._data.__dlpack__(**kw)

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # NumPy dispatch protocol (reference: python/mxnet/
    # numpy_dispatch_protocol.py): onp.exp(x) / onp.concatenate([x, y])
    # on framework arrays route to the registered TPU ops for the CURATED
    # function list (semantics verified against numpy); anything outside
    # the list falls back to host numpy over __array__ conversion — the
    # pre-protocol behavior, so no previously-working call breaks.
    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        out = kwargs.pop("out", None)
        if out is not None:
            import jax.numpy as jnp

            # honor numpy's in-place `out=` contract: run on host into
            # plain buffers, then write results back into NDArray outs
            outs = out if isinstance(out, tuple) else (out,)
            host_outs = tuple(
                onp.array(o.asnumpy()) if isinstance(o, NDArray) else o
                for o in outs)  # asnumpy() can be a read-only device view
            res = self._host_fallback(getattr(ufunc, method, ufunc),
                                      inputs, {**kwargs, "out": host_outs})
            res_items = res if isinstance(res, tuple) else (res,)
            filled = []
            for o, h, r in zip(outs, host_outs, res_items):
                if isinstance(o, NDArray):
                    o._set_data(jnp.asarray(h))
                    filled.append(o)
                else:
                    # None slots: numpy allocated the result itself
                    filled.append(r if o is None else o)
            return filled[0] if len(filled) == 1 else tuple(filled)
        if method == "at":
            # in-place scatter contract (onp.add.at(x, idx, v)): mutate a
            # writable host copy, then write it back into the NDArray —
            # _host_fallback alone would mutate a throwaway copy
            target = inputs[0]
            if isinstance(target, NDArray):
                import jax.numpy as jnp

                host = onp.array(target.asnumpy())
                self._host_fallback(getattr(ufunc, method),
                                    (host,) + inputs[1:], kwargs)
                target._set_data(jnp.asarray(host))
                return None
            return self._host_fallback(getattr(ufunc, method), inputs,
                                       kwargs)
        if method != "__call__":
            return self._host_fallback(getattr(ufunc, method, ufunc),
                                       inputs, kwargs)
        from .. import numpy as _mxnp

        fn = getattr(_mxnp, ufunc.__name__, None)
        if fn is not None:
            try:
                return fn(*inputs, **kwargs)
            except TypeError:
                pass
        return self._host_fallback(ufunc, inputs, kwargs)

    def __array_function__(self, func, types, args, kwargs):
        from .. import numpy as _mxnp

        if func.__name__ in _NP_DISPATCH_HANDLED:
            fn = getattr(_mxnp, func.__name__, None)
            if fn is not None:
                return fn(*args, **kwargs)
        return self._host_fallback(func, args, kwargs)

    @staticmethod
    def _host_fallback(func, args, kwargs):
        def conv(x):
            if isinstance(x, NDArray):
                return x.asnumpy()
            if isinstance(x, (list, tuple)):
                return type(x)(conv(v) for v in x)
            return x

        return func(*conv(list(args)),
                    **{k: conv(v) for k, v in kwargs.items()})

    # ----------------------------------------------------------- conversion
    def astype(self, dtype, copy=True):
        from ..base import dtype_name

        dtype = canonical_dtype(dtype)
        if not copy and self.dtype == dtype:
            return self
        return apply_op("astype", self, dtype=dtype_name(dtype))

    def copy(self):
        return apply_op("copy", self)

    def copyto(self, other):
        if isinstance(other, NDArray):
            if other is self:
                raise MXNetError("cannot copy an array onto itself")
            other._set_data(self.as_in_ctx(other.ctx)._data.astype(other.dtype))
            return other
        if isinstance(other, Context):
            return self.as_in_ctx(other)
        raise TypeError(f"copyto does not support type {type(other)}")

    def as_in_ctx(self, ctx: Context):
        """Device transfer (reference: cross-device copy op, kCopyToGPU path)."""
        import jax

        if ctx == self.ctx:
            return self
        out = NDArray(jax.device_put(self._data, ctx.jax_device()))
        out._ag_info = self._ag_info  # transfer is identity for autograd
        return out

    as_in_context = as_in_ctx
    to_device = as_in_ctx

    def as_np_ndarray(self):
        return self

    def as_nd_ndarray(self):
        return self

    # ------------------------------------------------------------- autograd
    def attach_grad(self, grad_req="write", stype=None):
        """Allocate a grad buffer and mark self as a gradient sink.

        ``stype`` is accepted for API parity but ignored: gradients are
        always dense here (reference row_sparse grads exist to skip zero
        rows on CPU; under XLA the dense grad is a fused kernel and the
        sparse optimizer paths take RowSparseNDArray grads explicitly).

        Reference: python/mxnet/numpy/multiarray.py attach_grad ->
        Imperative::MarkVariables.
        """
        from .. import autograd
        import jax.numpy as jnp

        grad = NDArray(jnp.zeros(self.shape, self.dtype))
        autograd.mark_variables([self], [grad], [grad_req])

    @property
    def grad(self):
        return self._grad

    def detach(self):
        out = NDArray(self._data)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd

        autograd.backward([self], [out_grad], retain_graph, train_mode)

    def zero_grad(self):
        if self._grad is not None:
            import jax.numpy as jnp

            self._grad._set_data(jnp.zeros(self.shape, self.dtype))

    # ------------------------------------------------------------- indexing
    def __getitem__(self, key):
        from ..ops import indexing

        return indexing.getitem(self, key)

    def __setitem__(self, key, value):
        from ..ops import indexing

        indexing.setitem(self, key, value)

    def take(self, indices, axis=None, mode="clip"):
        return apply_op("take", self, indices, axis=axis, mode=mode)

    # ------------------------------------------------------- shape manip
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return apply_op("reshape", self, newshape=shape)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return apply_op("transpose", self, axes=axes if axes else None)

    def swapaxes(self, a1, a2):
        return apply_op("swapaxes", self, axis1=a1, axis2=a2)

    def flatten(self):
        return self.reshape((-1,))

    def ravel(self):
        return self.reshape((-1,))

    def squeeze(self, axis=None):
        return apply_op("squeeze", self, axis=axis)

    def expand_dims(self, axis):
        return apply_op("expand_dims", self, axis=axis)

    def broadcast_to(self, shape):
        return apply_op("broadcast_to", self, shape=tuple(shape))

    def tile(self, reps):
        return apply_op("tile", self, reps=reps)

    def repeat(self, repeats, axis=None):
        return apply_op("repeat", self, repeats=repeats, axis=axis)

    def split(self, indices_or_sections, axis=0):
        return apply_op("split", self,
                        indices_or_sections=indices_or_sections, axis=axis)

    # --------------------------------------------------------- reductions
    def sum(self, axis=None, dtype=None, keepdims=False, **kw):
        return apply_op("sum", self, axis=axis, dtype=_dt(dtype), keepdims=keepdims)

    def mean(self, axis=None, dtype=None, keepdims=False, **kw):
        return apply_op("mean", self, axis=axis, dtype=_dt(dtype), keepdims=keepdims)

    def max(self, axis=None, keepdims=False, **kw):
        return apply_op("max", self, axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False, **kw):
        return apply_op("min", self, axis=axis, keepdims=keepdims)

    def prod(self, axis=None, keepdims=False, **kw):
        return apply_op("prod", self, axis=axis, keepdims=keepdims)

    def std(self, axis=None, ddof=0, keepdims=False, **kw):
        return apply_op("std", self, axis=axis, ddof=ddof, keepdims=keepdims)

    def var(self, axis=None, ddof=0, keepdims=False, **kw):
        return apply_op("var", self, axis=axis, ddof=ddof, keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False, **kw):
        return apply_op("argmax", self, axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False, **kw):
        return apply_op("argmin", self, axis=axis, keepdims=keepdims)

    def cumsum(self, axis=None, dtype=None):
        return apply_op("cumsum", self, axis=axis, dtype=_dt(dtype))

    def clip(self, a_min=None, a_max=None):
        return apply_op("clip", self, a_min=a_min, a_max=a_max)

    def round(self, decimals=0):
        return apply_op("round", self, decimals=decimals)

    def abs(self):
        return apply_op("abs", self)

    def dot(self, other):
        return apply_op("dot", self, other)

    def norm(self, ord=None, axis=None, keepdims=False):
        return apply_op("norm", self, ord=ord, axis=axis, keepdims=keepdims)

    def tostype(self, stype):
        if stype != "default":
            raise MXNetError("only 'default' storage is dense on TPU; see "
                             "mxnet_tpu sparse docs for row_sparse emulation")
        return self

    # --------------------------------------------------------- arithmetic
    def _binop(self, name, other, reverse=False):
        if isinstance(other, NDArray) or onp.isscalar(other) or isinstance(
            other, (onp.ndarray, list, tuple)
        ):
            if isinstance(other, (onp.ndarray, list, tuple)):
                other = NDArray(other)
            a, b = (other, self) if reverse else (self, other)
            return apply_op(name, a, b)
        return NotImplemented

    def __add__(self, o):
        return self._binop("add", o)

    def __radd__(self, o):
        return self._binop("add", o, True)

    def __sub__(self, o):
        return self._binop("subtract", o)

    def __rsub__(self, o):
        return self._binop("subtract", o, True)

    def __mul__(self, o):
        return self._binop("multiply", o)

    def __rmul__(self, o):
        return self._binop("multiply", o, True)

    def __truediv__(self, o):
        return self._binop("true_divide", o)

    def __rtruediv__(self, o):
        return self._binop("true_divide", o, True)

    def __floordiv__(self, o):
        return self._binop("floor_divide", o)

    def __rfloordiv__(self, o):
        return self._binop("floor_divide", o, True)

    def __mod__(self, o):
        return self._binop("mod", o)

    def __rmod__(self, o):
        return self._binop("mod", o, True)

    def __pow__(self, o):
        return self._binop("power", o)

    def __rpow__(self, o):
        return self._binop("power", o, True)

    def __matmul__(self, o):
        return self._binop("matmul", o)

    def __rmatmul__(self, o):
        return self._binop("matmul", o, True)

    def __neg__(self):
        return apply_op("negative", self)

    def __pos__(self):
        return self

    def __abs__(self):
        return apply_op("abs", self)

    def __invert__(self):
        return apply_op("invert", self)

    # in-place: rebind (python-level ordering provides WAR safety)
    def __iadd__(self, o):
        return self._inplace("add", o)

    def __isub__(self, o):
        return self._inplace("subtract", o)

    def __imul__(self, o):
        return self._inplace("multiply", o)

    def __itruediv__(self, o):
        return self._inplace("true_divide", o)

    def _inplace(self, name, o):
        from .. import autograd

        if autograd.is_recording() and self._ag_info is not None:
            raise MXNetError(
                "in-place operations on arrays participating in a recorded "
                "graph are not allowed inside autograd.record()"
            )
        res = self._binop(name, o)
        self._set_data(res._data.astype(self.dtype))
        return self

    # comparisons
    def __eq__(self, o):
        return self._binop("equal", o)

    def __ne__(self, o):
        return self._binop("not_equal", o)

    def __lt__(self, o):
        return self._binop("less", o)

    def __le__(self, o):
        return self._binop("less_equal", o)

    def __gt__(self, o):
        return self._binop("greater", o)

    def __ge__(self, o):
        return self._binop("greater_equal", o)

    def __hash__(self):
        return id(self)


def _dt(dtype):
    return None if dtype is None else str(canonical_dtype(dtype))


def array(obj, dtype=None, ctx=None, device=None):
    """Create an NDArray from array-like data (reference: mx.np.array)."""
    import jax
    import jax.numpy as jnp

    ctx = device or ctx
    if isinstance(obj, NDArray):
        obj = obj._data
    dtype = canonical_dtype(dtype)
    data = jnp.asarray(obj, dtype=dtype)
    if data.dtype == onp.float64:
        data = data.astype(onp.float32)  # x64 is disabled framework-wide
    if ctx is not None:
        data = jax.device_put(data, Context("cpu", 0).jax_device()
                              if ctx.device_type == "cpu" else ctx.jax_device())
    return NDArray(data)


def from_jax(jarr) -> NDArray:
    """Zero-copy wrap of an existing jax.Array."""
    return NDArray(jarr)
