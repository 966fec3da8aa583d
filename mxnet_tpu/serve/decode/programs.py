"""The three AOT-compiled program families of the decode engine (v2).

Exactly three graph shapes exist (PyGraph's whole-iteration capture
applied to decoding — the host only feeds operands):

- ``prefill(bucket_batch, bucket_len)``: forward the whole right-padded
  prompt batch once (the exact flash-path compute of the plain forward),
  argmax the logits at each row's last valid position, and write the
  per-layer k/v, cut into whole pages, into the pool pages each row's
  page-table operand maps: one indexed update per pool. One traced graph
  per length bucket, compiled per batch bucket.
- ``prefill_ext(bucket_batch, bucket_len)``: the radix prefix-cache join
  — forward only the prompt SUFFIX from a page-aligned ``start`` offset;
  each layer writes the suffix's pages into the pool and then attends
  the gathered page view (shared prefix pages already resident, the
  suffix just written): with up to a bucket of queries a row this is a
  matrix-unit problem, and the only place a view is still gathered.
  Traced only when the prefix cache is enabled.
- ``decode_tick_k(num_slots, K)``: K tokens for EVERY slot — each layer
  puts its new rows into the pages they land in and attends the pool
  where it lies: one paged kernel a layer (``mxtpu_paged_decode``, via
  ``npx.paged_decode_attention``) walks the pages a slot's table row
  maps and stops at the slot's length (at K = 1 the same kernel stores
  the slot's row in the last page it reads) — fixed shape, traced and
  compiled exactly once. K = 1 is the plain tick; K > 1 verifies a K-1-token
  draft in one batched pass (speculative decoding). Static K keeps the
  program set fixed, so steady state never recompiles regardless of
  drafts, prefix hits, or which requests join or leave.

Each family is the model's ONE forward pass over a view of the paged pool
(``cache.PrefillView`` / ``JoinView`` / ``TickView``), followed by the
greedy choice; the pool's layout, how a layer's K/V is written and how it
is attended over live in :mod:`cache` alone. (Manifest version 3: an
artefact exported with an older pool layout is refused, not loaded.)

Beside the three, with K = 1, one tiny program a batch bucket
(``place``, compiled in ``warmup`` from the geometry alone, so an export
needs no file for it) puts a prefill's first tokens into the (S, 1) token
vector the next tick reads: the engine keeps that vector on the device
(a tick's token output IS the next tick's operand) and never waits for a
prefill before it dispatches the tick that follows.

All three donate what the cache names (``PagedKVCache.operands()``): the
pool pair (or the ONE latent pool of a model with latent attention, whose
``cache_spec()`` says so) and, for a model with recurrent layers, one state
array a layer and entry (in, out — a single device residency; on backends
without donation support XLA falls back to copying); the model's
counters, where it keeps any, follow undonated. A prefill of such a model
takes one operand more, each row's slot, to write that slot's state.
Every write into the pool is an indexed update of whole pages,
which the TPU compiles in place: no program holds a second value of the
pool's size. A write routed at the sentinel page id is out of range and
is dropped. ``export``/``from_export`` round-trip the traced graphs through
Symbol JSON + a params npz, so a fresh process can serve without the
model class — the SymbolBlock.imports analog for the decode engine.
"""
from __future__ import annotations

import json
import os
import time
import warnings

import numpy as onp

from ...base import MXNetError, dtype_name
from ..bucketing import bucket_ladder
from . import cache as kv

__all__ = ["DecodePrograms", "load_decode_manifest", "compiled_modules"]

MANIFEST_VERSION = 3

_COMPILED = {}   # HLO module name -> the newest jax Compiled of that name


def compiled_modules():
    """``{HLO module name: jax Compiled}``: the newest serving program of
    each name (``jit_mxtpu_serve_decode_k1``,
    ``jit_mxtpu_serve_prefill_b1_t128``) this process has compiled, as
    ``train_step.compiled_modules()`` is for the step: for a tool that
    holds no engine, or outlives it, and joins a device trace (events
    named by instruction, under a module's event) with the compiled text
    (where ``op_name`` carries the named scopes). Instruction names repeat
    from module to module: join by module first."""
    return dict(_COMPILED)


def load_decode_manifest(path):
    with open(path) as fh:
        m = json.load(fh)
    if m.get("kind") != "decode_engine" or \
            m.get("version") != MANIFEST_VERSION:
        raise MXNetError(
            f"unsupported decode manifest in {path}: version="
            f"{m.get('version')!r} kind={m.get('kind')!r} (this build "
            f"reads version {MANIFEST_VERSION}; older manifests describe "
            "another KV pool layout and must be re-exported)")
    return m


def _compile(cop, examples, donate, name):
    """AOT-compile suppressing the backend's 'donation not implemented'
    warning (CPU): the fallback is a copy, which is correct — the donation
    request is for the TPU path. The module is named ``jit_<name>``."""
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message=".*donat.*",
                                category=UserWarning)
        return cop.aot_compile(*examples, donate=donate, name=name)


class DecodePrograms:
    """Trace + compile + (de)serialize the engine's program table.

    Built either from a live model (``DecodePrograms(model, ...)``) or
    from an export directory (``DecodePrograms.from_export(prefix)``).
    """

    # operands in front of the pool pair (example-input space):
    # (tokens, valid[, slots], table), (tokens, valid, start, table),
    # (tokens, positions, table); what follows is the cache's
    # (``PagedKVCache.operands()``): the pool pair and the recurrent
    # state, donated, then the counters, not donated
    _LEAD = {"prefill": 3, "prefill_ext": 4, "decode": 3}

    def __init__(self, model=None, *, num_slots, max_len, prefill_batch=4,
                 max_prompt_len=None, min_prompt_bucket=8, page_tokens=128,
                 kv_pages=None, speculate_k=1, prefix_cache=True,
                 tp=1, partition_rules=None, _from_export=None):
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.prefill_batch = int(prefill_batch)
        max_prompt_len = int(max_prompt_len or self.max_len)
        if max_prompt_len > self.max_len:
            raise MXNetError(
                f"max_prompt_len {max_prompt_len} exceeds cache max_len "
                f"{self.max_len}")
        self.max_prompt_len = max_prompt_len
        # clamp to max_len: a page larger than a slot's whole capacity
        # would reserve more for a slot than max_len positions
        self.page_tokens = min(int(page_tokens), self.max_len)
        if self.page_tokens < 1:
            raise MXNetError(
                f"page_tokens must be >= 1, got {page_tokens}")
        # W: page-table columns per slot (+1 sentinel column in-table)
        self.pages_per_slot = -(-self.max_len // self.page_tokens)
        self.kv_pages = int(kv_pages or
                            self.num_slots * self.pages_per_slot)
        if self.kv_pages < -(-self.max_prompt_len // self.page_tokens):
            raise MXNetError(
                f"kv_pages {self.kv_pages} cannot hold even one "
                f"max_prompt_len={self.max_prompt_len} prompt at "
                f"page_tokens={self.page_tokens}")
        self.speculate_k = max(1, int(speculate_k))
        if self.speculate_k > self.page_tokens:
            raise MXNetError(
                f"speculate_k {self.speculate_k} exceeds page_tokens "
                f"{self.page_tokens} (a tick must fit in one new page)")
        self.prefix_cache = bool(prefix_cache)
        self.batch_ladder = bucket_ladder(self.prefill_batch)
        self.len_ladder = bucket_ladder(
            max_prompt_len, min_bucket=min(min_prompt_bucket,
                                           max_prompt_len))
        self._model = model
        self._cops = {}         # "decode:<K>" | "prefill[_ext]:<T>" -> CachedOp
        self._graph_params = {}  # graph key -> ordered param names
        self._params = {}       # name -> raw device array
        self._programs = {}     # ("decode", K) | ("prefill"[_ext], B, T)
        self._places = {}       # batch bucket -> the place program
        self._costs = {}        # program key -> (flops, bytes_accessed)
        self._signatures = {}   # str key -> trace signature
        self.cache_shape = None  # one pool's shape (cache.POOL_AXES)
        self.cache_dtype = "float32"
        # recurrent state beside the pages, and the model's counters: both
        # from cache_spec() alone (empty for a model of attention layers)
        self.state_shapes = []   # [(shape, dtype)] a layer and entry
        self.counter_names = ()
        self.pools = 2           # the K and V pair, or 1 latent pool
        self._layout = kv.view_layout({})
        # tensor parallelism: the model's column-parallel serve layout,
        # traced at per-rank local shapes and replayed under shard_map
        # over a {'tp': tp} mesh — merged activations are concatenations,
        # so the served tokens stay BITWISE the unsharded model's
        self.tp = max(1, int(tp))
        if model is not None:
            spec = model.cache_spec()
            self.state_shapes = kv.state_shapes(spec, self.num_slots)
            self.counter_names = kv.counter_names(spec)
            self._layout = kv.view_layout(spec)
            self.pools = kv.pool_count(spec)
            self._refuse_for_recurrent_state()
            self._refuse_for_latent_rows()
        self._mesh = None
        self._tp_places = {}     # param name -> (sharded dim, segments)
        self._in_shardings = {}  # program key -> per-arg NamedShardings
        if self.tp > 1:
            if _from_export is not None:
                raise MXNetError(
                    "tensor-parallel serving cannot load an export — "
                    "re-trace from the live model with tp set")
            if model is None:
                raise MXNetError("DecodePrograms needs a model for tp >= 2")
            if partition_rules is None:
                maker = getattr(model, "tp_partition_rules", None)
                if maker is None:
                    raise MXNetError(
                        "tp >= 2 needs partition_rules (or a model exposing "
                        "tp_partition_rules('serve'))")
                partition_rules = maker("serve")
            import jax

            from ...parallel.mesh import make_mesh

            if len(jax.devices()) < self.tp:
                raise MXNetError(
                    f"tp={self.tp} needs that many devices; "
                    f"{len(jax.devices())} visible")
            self._mesh = make_mesh({"tp": self.tp},
                                   devices=jax.devices()[:self.tp])
        self._tp_rules = partition_rules
        if _from_export is not None:
            self._load_export(_from_export)
        else:
            if model is None:
                raise MXNetError("DecodePrograms needs a model or an export")
            self._trace_all()

    @property
    def table_width(self):
        return self.pages_per_slot + 1

    @property
    def recurrent(self):
        return bool(self.state_shapes)

    def _refuse(self, model, cases):
        """Raise, by name, for the first of ``cases`` (on, what, why) that
        is on: never served wrong."""
        for on, what, why in cases:
            if on:
                raise MXNetError(
                    f"a model with {model} cannot be served with {what}: "
                    f"{why}")

    def _refuse_for_recurrent_state(self):
        """What a per-slot recurrent state cannot do yet, refused by name
        where the programs are built."""
        if self.recurrent:
            self._refuse("recurrent state (cache_spec()['state'])", (
                (self.prefix_cache, "prefix_cache=True",
                 "a join at a cached prefix needs a snapshot of the state "
                 "at the prefix's end"),
                (self.speculate_k > 1, f"speculate_k={self.speculate_k}",
                 "a rejected draft needs the state rolled back"),
                (self.tp > 1, f"tp={self.tp}",
                 "the state's heads are not sharded over tp")))

    def _refuse_for_latent_rows(self):
        """What a latent cache (one headless row a position) cannot do yet,
        refused by name where the programs are built."""
        if self._layout["latent"] is not None:
            self._refuse("a latent cache (cache_spec()['latent'])", (
                (self.prefix_cache, "prefix_cache=True",
                 "the prefix join gathers per-head K and V pages"),
                (self.speculate_k > 1, f"speculate_k={self.speculate_k}",
                 "a draft of K > 1 through the absorbed attention is not "
                 "tried"),
                (self.tp > 1, f"tp={self.tp}",
                 "one headless row has no head axis to shard over tp")))

    def _lead(self, family):
        """How many operands stand in front of the pool pair."""
        return self._LEAD[family] + int(family == "prefill"
                                        and self.recurrent)

    def _donate(self, family):
        first = self._lead(family)
        return tuple(range(first, first + self.pools
                           + len(self.state_shapes)))

    # ----------------------------------------------------------------- trace
    def _collect_params(self):
        return [(name, p.data())
                for name, p in self._model.collect_params().items()
                if p._data is not None]

    def _trace_all(self):
        from ... import autograd

        if self.tp > 1:
            self._trace_all_tp()
            return
        params = self._collect_params()
        self._params = {name: arr._data for name, arr in params}
        with autograd.pause():
            self._trace_graphs(params)

    def _trace_graphs(self, params):
        names = [name for name, _ in params]
        K = self.speculate_k
        prefills = ("prefill", "prefill_ext") if self.prefix_cache \
            else ("prefill",)
        for family, size in [("decode", K)] + [
                (family, T) for T in self.len_ladder for family in prefills]:
            self._cops[f"{family}:{size}"] = self._trace(family, size, params)
            self._graph_params[f"{family}:{size}"] = names

    def _trace_all_tp(self):
        """Trace every graph at per-rank LOCAL shapes: column-parallel
        parameters are temporarily swapped to their rank-0 local slices
        under an active serve-mode TPContext (the model emits tp_gather
        merges and sizes heads locally), then restored. Device residency
        for the compiled programs is the segment-permuted GLOBAL image of
        each sharded parameter, laid out so contiguous 1/tp blocks over
        'tp' ARE the per-rank local images."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from ... import autograd
        from ...ndarray.ndarray import NDArray
        from ...parallel import tp as _tpm
        from ...parallel.partition import match_partition_rules

        plist = [(name, p)
                 for name, p in self._model.collect_params().items()
                 if p._data is not None]
        specs = match_partition_rules(
            self._tp_rules, {n: p.data() for n, p in plist}, with_meta=True)
        places = {}
        for n, _ in plist:
            dim = _tpm.tp_dim(specs[n].spec)
            if dim is not None:
                places[n] = (dim, int(specs[n].meta.get("segments", 1)))
        self._tp_places = places
        for n, p in plist:
            full = p.data()._data
            if n in places:
                dim, seg = places[n]
                img = _tpm.global_image(onp.asarray(full), dim, self.tp,
                                        seg)
                ax = [None] * img.ndim
                ax[dim] = "tp"
                self._params[n] = jax.device_put(
                    jnp.asarray(img), NamedSharding(self._mesh, P(*ax)))
            else:
                self._params[n] = jax.device_put(
                    full, NamedSharding(self._mesh, P()))
        swapped = []
        ctx = _tpm.TPContext(self.tp, mode="serve")
        try:
            for n, p in plist:
                if n in places:
                    dim, seg = places[n]
                    loc = _tpm.local_slice(p.data().asnumpy(), dim, 0,
                                           self.tp, seg)
                    swapped.append((p, p._data))
                    p._data = NDArray(jnp.asarray(loc))
            params = [(n, p.data()) for n, p in plist]
            with _tpm.activate(ctx), autograd.pause():
                self._trace_graphs(params)
        finally:
            for p, full in swapped:
                p._data = full

    def _pool_pair(self):
        spec = self._model.cache_spec()
        if self.cache_shape is None:
            # the traced pool is per-rank local over heads; report the
            # GLOBAL pool geometry the engine allocates
            self.cache_shape = kv.pool_shape(
                dict(spec, heads=spec.get("heads", 1) * self.tp),
                self.kv_pages, self.page_tokens)
            self.cache_dtype = str(spec["dtype"])
        return list(kv.empty_pools(spec, self.kv_pages, self.page_tokens)) \
            + kv.empty_state(spec, self.num_slots)

    # family -> (the view its forward is handed, program name, whether it
    # forwards prompts: rows of ``valid_length`` tokens, the last one scored)
    _FAMILIES = {
        "decode": (kv.TickView, "serve_decode_tick_k{}", False),
        "prefill": (kv.PrefillView, "serve_prefill_{}", True),
        "prefill_ext": (kv.JoinView, "serve_prefill_ext_{}", True),
    }

    def _trace(self, family, size, params):
        """Trace one graph: the model's ONE forward over ``family``'s view
        of the operands, then the greedy choice. ``size`` is K for the
        tick (``tokens`` (S, K), ``positions`` (S,)), the length bucket T
        for the prefills (``tokens`` (B, T), ``valid_length`` (B,) and,
        joining a prefix, ``start`` (B,)); the page table and the donated
        pool pair close the operand list. The tick scores every column
        (logits[:, i] scores the token AFTER tokens[:, i] — greedy
        verification accepts the longest draft prefix that matches); a
        prefill scores each row's last valid position."""
        from ... import numpy as np
        from ...cached_op import trace

        model = self._model
        view_cls, name, prompt = self._FAMILIES[family]
        rows = self.prefill_batch if prompt else self.num_slots
        operands = [np.zeros((rows, size), dtype="int32")]
        if prompt:
            operands.append(np.ones((rows,), dtype="int32"))    # valid
        if family != "prefill":     # the tick's positions, the join's start
            operands.append(np.zeros((rows,), dtype="int32"))
        slotted = family == "prefill" and self.recurrent
        if slotted:                 # each row's slot: its state's row
            operands.append(np.full((rows,), self.num_slots, dtype="int32"))
        operands.append(np.full((rows, self.table_width), self.kv_pages,
                                dtype="int32"))
        operands += self._pool_pair()
        layout = self._layout

        def fn(tokens, *rest):
            if slotted:
                valid, slots, *rest = rest
                view = view_cls(tokens, valid, *rest, slots=slots, **layout)
            else:
                valid = rest[0]
                view = view_cls(tokens, *rest, **layout)
            logits = model(tokens, cache=view)
            if prompt:
                onehot = np.one_hot(valid.astype("int32") - 1, size,
                                    dtype=dtype_name(logits.dtype))
                logits = np.einsum("btv,bt->bv", logits, onehot)
            return (np.argmax(logits, axis=-1).astype("int32"),) \
                + view.state()

        _, _, cop = trace(fn, operands, params)
        cop._name = name.format(size)
        return cop

    # --------------------------------------------------------------- compile
    def _zeros(self, shape, dtype):
        import jax.numpy as jnp

        return jnp.zeros(shape, dtype)

    @staticmethod
    def _site(key):
        if key[0] == "decode":
            return f"serve.decode_tick_k{key[1]}"
        if key[0] == "prefill_ext":
            return f"serve.prefill_ext_b{key[1]}_t{key[2]}"
        return f"serve.prefill_b{key[1]}_t{key[2]}"

    @staticmethod
    def _program_name(key):
        """The compiled module's name in a profiler trace, less ``jit_``:
        ``mxtpu_serve_decode_k1``, ``mxtpu_serve_prefill_b2_t128``."""
        if key[0] == "decode":
            return f"mxtpu_serve_decode_k{key[1]}"
        return f"mxtpu_serve_{key[0]}_b{key[1]}_t{key[2]}"

    def ensure(self, kind, batch=None, length=None):
        """Compile (memoized) and return one executable."""
        if kind == "decode":
            key = ("decode", self.speculate_k)
        else:
            key = (kind, int(batch), int(length))
        prog = self._programs.get(key)
        if prog is not None:
            return prog
        from ...telemetry.watchdog import format_signature

        # the cache's operands: the pool pair, the recurrent state, the
        # counters
        held = [self._zeros(self.cache_shape, self.cache_dtype)
                for _ in range(self.pools)] \
            + [self._zeros(sh, dt) for sh, dt in self.state_shapes]
        if self.counter_names:
            held.append(self._zeros((len(self.counter_names),), "int32"))
        S = self.num_slots
        Wt = self.table_width
        if kind == "decode":
            cop = self._cops[f"decode:{self.speculate_k}"]
            examples = [self._zeros((S, self.speculate_k), "int32"),
                        self._zeros((S,), "int32"),
                        self._zeros((S, Wt), "int32")] + held
        else:
            cop = self._cops.get(f"{kind}:{length}")
            if cop is None:
                raise MXNetError(
                    f"no {kind} graph for length bucket {length} "
                    f"(ladder: {self.len_ladder}; prefix_cache="
                    f"{self.prefix_cache})")
            examples = [self._zeros((batch, length), "int32"),
                        self._zeros((batch,), "int32")]
            if kind == "prefill_ext" or self.recurrent:
                # the join's start; a recurrent model's slots
                examples.append(self._zeros((batch,), "int32"))
            examples += [self._zeros((batch, Wt), "int32")] + held
        donate = self._donate(kind)
        if self.tp > 1:
            prog = self._compile_tp(key, cop, examples, donate)
        else:
            args = examples + [self._params[n]
                               for n in self._graph_params[
                                   self._cop_key(key)]]
            prog = _compile(cop, args, donate, self._program_name(key))
        self._programs[key] = prog
        _COMPILED["jit_" + self._program_name(key)] = prog
        # per-program XLA cost, captured once per compile; run() credits
        # the flops counter with it at every dispatch
        from ... import telemetry as _tm

        site = self._site(key)
        cost = _tm.record_program_cost(site, prog)
        _tm.record_program_memory(site, prog)
        self._costs[key] = ((cost["flops"], cost["bytes_accessed"])
                            if cost else (0.0, 0.0))
        self._signatures["|".join(str(k) for k in key)] = format_signature(
            [getattr(x, "_data", x) for x in examples])
        return prog

    def compiled_programs(self):
        """``{program key: jax Compiled}`` for every executable compiled so
        far — the handle for ``as_text()`` / ``memory_analysis()``."""
        return dict(self._programs)

    def _cop_key(self, key):
        if key[0] == "decode":
            return f"decode:{key[1]}"
        return f"{key[0]}:{key[2]}"

    def _compile_tp(self, key, cop, examples, donate):
        """AOT-compile one graph under shard_map on the 'tp' mesh: KV
        pools shard over the head axis, column-parallel params over their
        declared dim, everything else replicated. The executable bakes
        these input shardings, so ``run`` device_puts its operands to the
        recorded layouts before every call."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from ...parallel.mesh import shard_map_compat

        names = self._graph_params[self._cop_key(key)]
        pool = P(*("tp" if a == "heads" else None for a in kv.POOL_AXES))
        data_specs = [P()] * (len(examples) - 2) + [pool, pool]
        pspecs = []
        for n in names:
            if n in self._tp_places:
                ax = [None] * self._params[n].ndim
                ax[self._tp_places[n][0]] = "tp"
                pspecs.append(P(*ax))
            else:
                pspecs.append(P())
        in_specs = tuple(data_specs + pspecs)
        n_aux = len(getattr(cop, "_aux_targets", ()) or ())
        out_specs = (P(), pool, pool) + (P(),) * n_aux
        off = 1 if cop._uses_rng else 0
        if off:
            in_specs = (P(),) + in_specs
        inner = shard_map_compat(cop._raw_fn, self._mesh,
                                 in_specs=in_specs, out_specs=out_specs)

        def fn(*args):
            return inner(*args)

        fn.__name__ = self._program_name(key)
        shardings = tuple(NamedSharding(self._mesh, s) for s in in_specs)
        self._in_shardings[key] = shardings
        argnums = tuple(sorted(int(i) + off for i in donate))
        datas = [getattr(x, "_data", x) for x in examples]
        if off:
            datas.insert(0, jax.random.PRNGKey(0))
        args = [jax.device_put(a, s) for a, s in zip(
            datas + [self._params[n] for n in names], shardings)]
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=".*donat.*",
                                    category=UserWarning)
            return jax.jit(
                fn, donate_argnums=argnums).lower(*args).compile()

    def n_operands(self, key, n_datas):
        """How long the list is that ``run`` hands the executable for
        ``n_datas`` data operands: they, the param tail, and a PRNG key
        for rng graphs."""
        ck = self._cop_key(key)
        return n_datas + len(self._graph_params[ck]) \
            + bool(self._cops[ck]._uses_rng)

    def run(self, key, datas):
        """Call a compiled program with raw device operands; appends the
        param tail (and a PRNG key for rng graphs) in trace order."""
        prog = self._programs[key]
        cop = self._cops[self._cop_key(key)]
        args = list(datas) + [self._params[n]
                              for n in self._graph_params[self._cop_key(key)]]
        if cop._uses_rng:
            from ... import random as _rnd

            args.insert(0, _rnd._next_key())
        if self.tp > 1:
            # the AOT executables bake their input shardings; re-lay small
            # host-made operands (a no-op for already-resident arrays)
            import jax

            args = [jax.device_put(getattr(a, "_data", a), s)
                    for a, s in zip(args, self._in_shardings[key])]
        from ... import telemetry as _tm

        if _tm.ON:
            _tm.record_flops(*self._costs.get(key, (0.0, 0.0)))
        outs = prog(*args)
        return outs if isinstance(outs, (tuple, list)) else (outs,)

    def _token_sharding(self):
        """Where the tick wants its token operand (None: the one device)."""
        if self.tp == 1:
            return None
        self.ensure("decode")
        return self._in_shardings[("decode", self.speculate_k)][0]

    def token_vector(self):
        """The (S, 1) device-side token vector a K = 1 engine starts from."""
        import jax

        return jax.device_put(onp.zeros((self.num_slots, 1), "int32"),
                              self._token_sharding())

    def _place_program(self, batch):
        """Compile (memoized) the place program of one batch bucket."""
        prog = self._places.get(batch)
        if prog is None:
            import jax

            def fn(tokens, first, slots):
                return tokens.at[slots, 0].set(first, mode="drop")

            fn.__name__ = f"mxtpu_serve_place_b{batch}"
            sharding = self._token_sharding()
            examples = [jax.device_put(onp.zeros(shape, "int32"), sharding)
                        for shape in ((self.num_slots, 1), (batch,),
                                      (batch,))]
            prog = self._places[batch] = jax.jit(fn).lower(
                *examples).compile()
        return prog

    def place(self, tokens, first, slots):
        """``tokens`` (S, 1) with ``tokens[slots[i], 0] = first[i]``: a
        prefill's first tokens (B,), still on the device, go where the
        next tick reads them. A row of the bucket that holds no request
        names slot ``num_slots``, past the vector: dropped. No operand
        is donated."""
        return self._place_program(int(first.shape[0]))(
            tokens, first, onp.asarray(slots, "int32"))

    def warmup(self):
        """Compile the whole table: decode_tick_k + every (batch, len)
        prefill (and prefix-join) bucket, and with K = 1 the place program
        of every batch bucket. After this, serving compiles nothing. Tuned
        kernel configs (``MXTPU_TUNE=1``) preload first so each trace
        resolves its blocks from the persisted winners — the engine never
        tunes online."""
        from ...tune import preload as _tune_preload

        _tune_preload()
        self.ensure("decode")
        for T in self.len_ladder:
            for B in self.batch_ladder:
                self.ensure("prefill", batch=B, length=T)
                if self.prefix_cache:
                    self.ensure("prefill_ext", batch=B, length=T)
        if self.speculate_k == 1:
            for B in self.batch_ladder:
                self._place_program(B)

    # ------------------------------------------------------------- manifests
    def manifest_dict(self, cache_dir=None, graphs=None):
        from ...context import env_signature

        import jax

        return {
            "version": MANIFEST_VERSION,
            "kind": "decode_engine",
            "env_signature": env_signature(),
            "jax_version": getattr(jax, "__version__", "?"),
            "num_slots": self.num_slots,
            "max_len": self.max_len,
            "prefill_batch": self.prefill_batch,
            "max_prompt_len": self.max_prompt_len,
            "page_tokens": self.page_tokens,
            "kv_pages": self.kv_pages,
            "speculate_k": self.speculate_k,
            "prefix_cache": self.prefix_cache,
            "tp": self.tp,
            "batch_ladder": list(self.batch_ladder),
            "len_ladder": list(self.len_ladder),
            "cache_shape": list(self.cache_shape or ()),
            "cache_dtype": self.cache_dtype,
            "signatures": dict(sorted(self._signatures.items())),
            "cache_dir": cache_dir,
            "graphs": graphs,
            "created_unix": time.time(),
        }

    # ---------------------------------------------------------------- export
    @staticmethod
    def _n_data(key):
        if key.startswith("prefill_ext:"):
            return 6
        return 5

    def export(self, prefix):
        """Write the traced graphs + params + manifest; returns the
        manifest path. A fresh process rebuilds the full program table
        from these files alone (``from_export``) — no model class needed,
        and with the persistent compile cache on, no XLA compiles either.
        """
        if self.recurrent or self.pools != 2:
            raise MXNetError(
                "export of a decode engine for a model with recurrent state "
                "or a latent cache is not supported: the manifest describes "
                "the K and V page pools only")
        if self.tp > 1:
            raise MXNetError(
                "export of a tensor-parallel decode engine is not "
                "supported: the traced graphs hold per-rank local shapes "
                "tied to this process's mesh — export from a tp=1 trace "
                "and pass tp at load time instead")
        graphs = {}
        for key, cop in self._cops.items():
            fname = f"{prefix}-{key.replace(':', '_')}-symbol.json"
            cop.sym.save(fname)
            graphs[key] = {"file": os.path.basename(fname),
                           "n_data": self._n_data(key),
                           "params": self._graph_params[key]}
        onp.savez(f"{prefix}-params.npz",
                  **{n: onp.asarray(a) for n, a in self._params.items()})
        m = self.manifest_dict(graphs=graphs)
        m["params_file"] = os.path.basename(f"{prefix}-params.npz")
        mpath = f"{prefix}-decode.manifest.json"
        tmp = mpath + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(m, fh, indent=1)
        os.replace(tmp, mpath)
        return mpath

    @classmethod
    def from_export(cls, prefix_or_manifest):
        """Rebuild the program table from ``export`` artifacts."""
        mpath = prefix_or_manifest
        if not mpath.endswith(".json"):
            mpath = f"{prefix_or_manifest}-decode.manifest.json"
        m = load_decode_manifest(mpath)
        self = cls(num_slots=m["num_slots"], max_len=m["max_len"],
                   prefill_batch=m["prefill_batch"],
                   max_prompt_len=m["max_prompt_len"],
                   page_tokens=m["page_tokens"], kv_pages=m["kv_pages"],
                   speculate_k=m["speculate_k"],
                   prefix_cache=m["prefix_cache"],
                   _from_export=(m, os.path.dirname(os.path.abspath(mpath))))
        return self

    def _load_export(self, export):
        import jax.numpy as jnp

        from ...cached_op import CachedOp
        from ...symbol.symbol import Symbol, topo_sort

        m, root = export
        self.cache_shape = tuple(int(d) for d in m["cache_shape"])
        self.cache_dtype = m["cache_dtype"]
        with onp.load(os.path.join(root, m["params_file"])) as z:
            self._params = {n: jnp.asarray(z[n]) for n in z.files}
        for key, g in m["graphs"].items():
            sym = Symbol.load(os.path.join(root, g["file"]))
            var_nodes = [n for n in topo_sort(sym._entries) if n.is_var]
            by_name = {n.name: n for n in var_nodes}
            # trace() names data inputs data0..dataN; params keep their
            # parameter names — rebuild the exact call order
            ordered, pnames = [], []
            for i in range(g["n_data"]):
                if f"data{i}" not in by_name:
                    raise MXNetError(
                        f"exported graph {key} is missing input data{i}")
                ordered.append(by_name[f"data{i}"])
            for pn in g["params"]:
                if pn in by_name:      # unused params drop out of the graph
                    ordered.append(by_name[pn])
                    pnames.append(pn)
            missing = set(by_name) - {n.name for n in ordered}
            if missing:
                raise MXNetError(
                    f"exported graph {key} has unbound inputs: "
                    f"{sorted(missing)}")
            self._cops[key] = CachedOp(sym, ordered,
                                       name=f"serve_{key.replace(':', '_')}")
            self._graph_params[key] = pnames
