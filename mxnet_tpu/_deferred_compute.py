"""Deferred compute: trace imperative execution into a Symbol graph.

TPU-native equivalent of the reference's deferred-compute mode
(python/mxnet/_deferred_compute.py; C side DCInfo in include/mxnet/imperative.h:94
and MXNDArraySetIsDeferredCompute, src/c_api/c_api_ndarray.cc:421-450). This is
how HybridBlock.hybridize captures a graph: the forward runs eagerly (real
values, real shapes) while every registry.invoke also appends a SymNode. The
captured Symbol then compiles to ONE XLA program via CachedOp.

Differences from the reference, by design:
- constants are captured automatically (arrays created inside forward become
  const nodes) instead of erroring;
- rng ops mark the trace as rng-dependent; the compiled program takes a fresh
  key input per call (reference used mutable per-op random resources);
- aux-state updates (BatchNorm moving stats) are registered as extra graph
  outputs written back after each call (reference mutated aux NDArrays
  in-kernel through the engine).
"""
from __future__ import annotations

import contextlib
import threading
import weakref

from .attribute import AttrScope
from .base import MXNetError
from .symbol.symbol import SymNode, Literal

__all__ = ["is_tracing", "context", "set_variable"]

# ``with AttrScope(__scope__="gdn"):`` around a part of a block's forward:
# under a trace every op recorded inside carries the name, and the compiled
# program replays it under ``jax.named_scope``, so the name reaches each
# instruction's ``op_name`` in the HLO and a device trace can be cut by it.
# Eagerly it does nothing.
SCOPE_ATTR = "__scope__"


class _TraceCtx:
    def __init__(self):
        self.uses_rng = False
        self.aux_updates = []  # [(target NDArray, source entry)]
        # weak refs to the arrays whose _dc_sym we set (for cleanup). Weak,
        # so the trace does not keep every intermediate of the eager
        # forward alive on the device until it ends (on a 16 GB chip that
        # bounded the traceable batch below what the compiled step runs)
        self.marked = []


class _State(threading.local):
    def __init__(self):
        self.ctx = None


_state = _State()


def is_tracing() -> bool:
    return _state.ctx is not None


def current() -> _TraceCtx:
    if _state.ctx is None:
        raise MXNetError("no deferred-compute trace is active")
    return _state.ctx


@contextlib.contextmanager
def context():
    """Enter tracing mode (reference: _deferred_compute.context)."""
    if _state.ctx is not None:
        raise MXNetError("deferred compute traces cannot nest")
    _state.ctx = _TraceCtx()
    try:
        yield _state.ctx
    finally:
        for ref in _state.ctx.marked:
            arr = ref()
            if arr is not None:
                arr._dc_sym = None
        _state.ctx = None


@contextlib.contextmanager
def suspend():
    """Temporarily leave tracing mode (used while evaluating op-internal
    python, e.g. control-flow bodies that re-enter the op registry)."""
    prev, _state.ctx = _state.ctx, None
    try:
        yield
    finally:
        _state.ctx = prev


def set_variable(arr, name: str) -> SymNode:
    """Mark an NDArray as a graph input (reference: dc.set_variable)."""
    ctx = current()
    # the traced input is concrete, so record its shape for
    # shape-sensitive graph passes (e.g. attention-mask fusion)
    node = SymNode(name=name,
                   attr_dict={"__shape__": str(tuple(arr.shape))})
    arr._dc_sym = (node, 0)
    ctx.marked.append(weakref.ref(arr))
    return node


def register_aux_update(target_arr, source_arr) -> None:
    """Record 'write source into target after every compiled call' (BN stats)."""
    ctx = current()
    if source_arr._dc_sym is None:
        raise MXNetError("aux update source was not produced by a traced op")
    ctx.aux_updates.append((target_arr, source_arr._dc_sym))


def _record_op(op, attrs, inputs, outputs) -> None:
    """Append a SymNode for an invoked op. Called from ops.registry.invoke."""
    from .ndarray.ndarray import NDArray

    ctx = current()
    entries = []
    for x in inputs:
        if isinstance(x, NDArray):
            if x._dc_sym is None:
                # constant capture: array not marked as input -> bake value
                x._dc_sym = (SymNode(value=x._data), 0)
                ctx.marked.append(weakref.ref(x))
            entries.append(x._dc_sym)
        else:
            entries.append(Literal(x))
    if op.needs_rng:
        ctx.uses_rng = True
    # an ``AttrScope(__scope__=...)`` around part of a forward names its
    # operations in the compiled program (cached_op.build_executor)
    scope = AttrScope.current()._attrs.get(SCOPE_ATTR)
    node = SymNode(op=op, attrs=attrs, inputs=entries, nout=len(outputs),
                   attr_dict={SCOPE_ATTR: scope} if scope else None)
    for i, o in enumerate(outputs):
        o._dc_sym = (node, i)
        ctx.marked.append(weakref.ref(o))
