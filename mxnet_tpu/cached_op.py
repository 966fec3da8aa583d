"""CachedOp: compile a captured Symbol into one jitted XLA program.

TPU-native redesign of the reference CachedOp (src/imperative/cached_op.cc —
THE executor of MXNet 2.0). The reference builds fwd+grad nnvm graphs, runs
shape/type/storage inference, plans memory, and replays node-by-node through
the engine (RunGraph, imperative_utils.cc:129) with bulking. Here the whole
graph becomes a single ``jax.jit`` program: XLA performs fusion, scheduling and
memory planning (``static_alloc/static_shape`` semantics are simply the default
compiled path, cached_op.cc:642 StaticForward). Shape specialization is jit's
native retrace-per-signature. Backward of a CachedOp is the ``jax.vjp`` of the
jitted function recorded as ONE tape node — the analog of CachedOp::Backward's
full-graph pass (cached_op.cc:1016).

RNG-dependent graphs (dropout) take a fresh PRNG key input per call; aux-state
updates (BN moving stats) are extra outputs written back post-call.
"""
from __future__ import annotations

import contextlib

import jax

from .base import MXNetError
from .ops.registry import Op, invoke
from .symbol.symbol import Literal, Symbol, topo_sort
from ._deferred_compute import SCOPE_ATTR

_NO_SCOPE = contextlib.nullcontext()

__all__ = ["CachedOp", "build_executor", "trace"]


def build_executor(out_entries, var_nodes):
    """Build a pure python callable replaying the graph; returns (fn, uses_rng).

    ``fn(*var_datas)`` or ``fn(key, *var_datas)`` -> tuple of output arrays.
    """
    topo = topo_sort(out_entries)
    var_index = {id(n): i for i, n in enumerate(var_nodes)}
    for n in topo:
        if n.is_var and id(n) not in var_index:
            raise MXNetError(
                f"graph references unbound variable '{n.name}'"
            )
    rng_nodes = [n for n in topo if n.op is not None and n.op.needs_rng]
    uses_rng = bool(rng_nodes)
    rng_index = {id(n): i for i, n in enumerate(rng_nodes)}

    def fn(*args):
        if uses_rng:
            key, args = args[0], args[1:]
        env = {}
        for node in topo:
            if node.is_var:
                env[id(node)] = (args[var_index[id(node)]],)
            elif node.is_const:
                env[id(node)] = (node.value,)
            else:
                ins = [
                    e.value if isinstance(e, Literal) else env[id(e[0])][e[1]]
                    for e in node.inputs
                ]
                if node.op.needs_rng:
                    sub = jax.random.fold_in(key, rng_index[id(node)])
                    ins = [sub] + ins
                scope = node.attr_dict.get(SCOPE_ATTR)
                with jax.named_scope(scope) if scope else _NO_SCOPE:
                    out = node.op.fn(**node.attrs)(*ins)
                env[id(node)] = tuple(out) if isinstance(out, (tuple, list)) \
                    else (out,)
        return tuple(env[id(n)][i] for n, i in out_entries)

    return fn, uses_rng


class CachedOp:
    """Compiled graph executor (reference: ndarray.CachedOp / MXCreateCachedOp).

    Parameters
    ----------
    sym : Symbol
        Output symbol (possibly multi-output).
    var_nodes : list[SymNode]
        Free variables in call order (data inputs first, then parameters).
    aux_updates : list[(NDArray, entry)]
        Arrays to overwrite with extra graph outputs after each call.
    """

    def __init__(self, sym, var_nodes, aux_updates=(), name="cached_op"):
        from . import telemetry as _telemetry
        from .ops.registry import _observe_compiles

        self.sym = sym
        self._name = name
        self._var_nodes = list(var_nodes)
        self._aux_targets = [t for t, _ in aux_updates]
        entries = list(sym._entries) + [e for _, e in aux_updates]
        self._n_main = len(sym._entries)
        fn, uses_rng = build_executor(entries, self._var_nodes)
        self._raw_fn = fn  # un-jitted executor (AOT tooling / __graft_entry__)
        # the watchdog observer runs at trace time only: each jit cache miss
        # of this program (a new input signature) reports one compile
        self._jitted = jax.jit(_observe_compiles(fn, f"cached_op:{name}",
                                                 None))
        self._donated_jits = {}  # (donate_argnums, name) -> observed jit
        self._telemetry = _telemetry
        self._uses_rng = uses_rng
        # wrap as a registered-op-shaped object so registry.invoke records it
        # on the autograd tape as ONE node
        self._op = Op(name, lambda **a: self._jitted, needs_rng=uses_rng,
                      nout=len(entries))

    @property
    def num_inputs(self):
        return len(self._var_nodes)

    def __call__(self, *inputs):
        if len(inputs) != len(self._var_nodes):
            raise MXNetError(
                f"CachedOp expects {len(self._var_nodes)} inputs, "
                f"got {len(inputs)}"
            )
        tm = self._telemetry
        if tm.ON:
            with tm.program_timer("cached_op"):
                outs = invoke(self._op, inputs, {})
        else:
            outs = invoke(self._op, inputs, {})
        if not isinstance(outs, tuple):
            outs = (outs,)
        main = outs[: self._n_main]
        for target, new in zip(self._aux_targets, outs[self._n_main:]):
            target._set_data(new._data)
        return main[0] if self._n_main == 1 else main

    def lower(self, *example_inputs, donate=(), name=None):
        """AOT-lower the program at the example signature (jax Lowered).

        The compiled program's leading argument for RNG graphs is the
        per-call PRNG key (see __init__); one is synthesized so lowering
        matches the program's true arity. Lowering traces the executor, so
        the recompile watchdog sees it like any jit cache miss.

        ``donate``: indices into ``example_inputs`` whose buffers the
        compiled program may reuse for its outputs (``jax.jit``
        donate_argnums — the serve/decode KV-cache update contract:
        cache in, cache out, no second residency). Indices are in
        example-input space; the RNG-key offset is applied internally.

        ``name``: the compiled module reads ``jit_<name>`` in a profiler
        trace instead of ``jit_observed``.
        """
        datas = [getattr(x, "_data", x) for x in example_inputs]
        if self._uses_rng:
            datas.insert(0, jax.random.PRNGKey(0))
        if not donate and not name:
            return self._jitted.lower(*datas)
        off = 1 if self._uses_rng else 0
        argnums = tuple(sorted(int(i) + off for i in donate))
        jitted = self._donated_jits.get((argnums, name))
        if jitted is None:
            from .ops.registry import _observe_compiles

            jitted = jax.jit(
                _observe_compiles(self._raw_fn,
                                  f"cached_op:{self._name}", None,
                                  name=name),
                donate_argnums=argnums)
            self._donated_jits[(argnums, name)] = jitted
        return jitted.lower(*datas)

    def lower_hlo(self, *example_inputs):
        """Return the StableHLO text for given example inputs (debugging)."""
        return self.lower(*example_inputs).as_text()

    def aot_compile(self, *example_inputs, donate=(), name=None):
        """Ahead-of-time compile at the example signature; returns the
        executable (jax Compiled).

        The serve fast path (``serve.Predictor``) compiles one program per
        shape bucket this way and calls the executables with raw device
        arrays, bypassing the imperative dispatch/tape layers entirely.
        The executable rejects any other input signature — pad to the
        bucket before calling. With the persistent compilation cache on
        (``context.enable_compilation_cache``), the XLA compile inside is
        a disk hit on every process after the first. ``donate`` marks
        example-input indices whose buffers the program may consume
        (see ``lower``); callers must rebind those arrays to the
        program's outputs after every call. ``name``: see ``lower``.
        """
        compiled = self.lower(*example_inputs, donate=donate,
                              name=name).compile()
        from . import telemetry as _tm

        _tm.record_program_cost(f"cached_op:{self._name}", compiled)
        return compiled


def trace(fn, inputs, params=(), transform=None):
    """Trace ``fn(*inputs)`` into (outputs_structure, CachedOp).

    - ``inputs``: list of NDArrays marked as data variables (in order);
    - ``params``: list of (name, NDArray) marked as parameter variables;
    - ``transform``: optional Symbol -> Symbol pass applied before compile
      (the optimize_for / subgraph-backend injection point, reference:
      build_subgraph.cc partitioner before graph bind).

    Returns (out_tree, flat_output_ndarrays, cached_op). The CachedOp's call
    order is [*inputs, *param arrays].
    """
    from . import _deferred_compute as dc

    with dc.context() as ctx:
        var_nodes = []
        for i, arr in enumerate(inputs):
            var_nodes.append(dc.set_variable(arr, f"data{i}"))
        for name, arr in params:
            var_nodes.append(dc.set_variable(arr, name))
        out = fn(*inputs)
        flat, tree = _flatten_out(out)
        for o in flat:
            if o._dc_sym is None:
                # output unconnected to the trace (constant forward) — bake it
                o._dc_sym = (_const_node(o), 0)
        sym = Symbol([o._dc_sym for o in flat])
        if transform is not None:
            sym = transform(sym)
        cop = CachedOp(sym, var_nodes, aux_updates=ctx.aux_updates)
    return tree, flat, cop


def _const_node(arr):
    from .symbol.symbol import SymNode

    return SymNode(value=arr._data)


def _flatten_out(out):
    """Flatten nested (tuple/list) outputs of a forward into a flat NDArray list."""
    from .ndarray.ndarray import NDArray

    if isinstance(out, NDArray):
        return [out], None
    if isinstance(out, (tuple, list)):
        flat, spec = [], []
        for o in out:
            f, s = _flatten_out(o)
            spec.append((len(f), s))
            flat.extend(f)
        return flat, (type(out), spec)
    raise MXNetError(f"hybridized forward must return NDArrays, got {type(out)}")


def unflatten_out(flat, tree):
    if tree is None:
        return flat[0]
    typ, spec = tree
    out, i = [], 0
    for n, s in spec:
        if s is None and n == 1:
            out.append(flat[i])
        else:
            out.append(unflatten_out(flat[i:i + n], s))
        i += n
    return typ(out)
