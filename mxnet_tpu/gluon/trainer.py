"""gluon.Trainer — applies an optimizer to a set of Parameters.

Reference: python/mxnet/gluon/trainer.py (step:~360, _allreduce_grads:407
pushing grads through KVStore with priority=-i). TPU-native behavior:

- single device: grads are already in the Parameter grad buffers (tape
  backward); step = fused jitted update per parameter (src/operator/
  optimizer_op.cc analog).
- kvstore='device'/'dist_sync': grads are allreduced through the KVStore
  facade (XLA add / cross-host collective) before the update — preserving the
  reference's update_on_kvstore semantics when enabled.
- the high-throughput path (whole train step as one SPMD program) is
  mxnet_tpu.parallel.Learner; Trainer is the script-parity path.
"""
from __future__ import annotations

import os

from ..base import MXNetError
from .parameter import Parameter
from .. import optimizer as opt_mod
from .. import kvstore as kvs_mod
from .. import telemetry as _telemetry

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore=None,
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, dict):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise MXNetError("params must be a dict or list of Parameters")
        self._params = []
        self._params_name2idx = {}
        for i, p in enumerate(params):
            if not isinstance(p, Parameter):
                raise MXNetError(f"invalid parameter {p!r}")
            self._params.append(p)
            self._params_name2idx[p.name] = i
            p._trainer = self
        optimizer_params = optimizer_params or {}
        param_dict = {i: p for i, p in enumerate(self._params)}
        if isinstance(optimizer, opt_mod.Optimizer):
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt_mod.create(optimizer,
                                             param_dict=param_dict,
                                             **optimizer_params)
        self._states = [None] * len(self._params)
        self._kvstore = None
        self._update_on_kvstore = update_on_kvstore
        self._kv_initialized = False
        self._kvstore_spec = kvstore
        self._compression_params = compression_params
        self._scale = self._optimizer.rescale_grad
        # fused multi-tensor update path (one compiled program per dtype
        # bucket instead of one dispatch per parameter)
        self._fuse = os.environ.get("MXNET_FUSED_TRAINER", "1") != "0"
        self._fused_fn = {}        # parameter-signature -> jitted multi-step
        self._fused_traces = 0     # trace-time count: observes recompiles
        self._fused_dispatches = 0 # compiled-program calls made by fusion
        self._compiled_step = None # CompiledTrainStep from compile_step()
        self._shard_state = None   # ZeRO-1 sharded optimizer-state buckets

    # -- properties ---------------------------------------------------------
    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # -- whole-step compilation ---------------------------------------------
    def compile_step(self, net, loss_fn, mesh=None, loss_scaler=None,
                     shard_update=None, strict_batch=False,
                     shard_params=None, partition_rules=None,
                     multi_step=None, accumulate=None):
        """Compile forward + loss + backward (+ mesh allreduce) + update into
        ONE donated-buffer program; returns the CompiledTrainStep, also
        exposed as ``self.step_fn``. Semantics of the compiled callable match
        the eager loop ``loss_fn(net(x), y).mean(); backward(); step(1)``.
        Unsupported configurations fall back to that eager loop with a
        one-time warning (see CompiledTrainStep.fallback_reason).

        ``shard_update`` selects the ZeRO-1 cross-replica sharded weight
        update (reduce-scatter grads, update 1/N shard with 1/N-sharded
        optimizer state, all-gather weights — bit-identical to the
        replicated update). ``None`` = auto: on when ``mesh`` carries a
        'dp' axis of size >= 2 and the optimizer's recurrence is
        elementwise; ``MXTPU_SHARD_UPDATE=0/1`` overrides. ``strict_batch``
        restores the hard error for batches not divisible by the dp extent
        instead of in-program zero-weight padding.

        ``shard_params`` selects full-parameter sharding (ZeRO-3 / FSDP):
        weights AND optimizer state live as per-layer flat buckets sharded
        1/N over 'dp' between steps; the program all-gathers each layer
        just-in-time and gradients reduce-scatter straight into the owning
        shard — no full-sized buffer ever persists. ``None`` = auto: on
        when additionally the trainables total >=
        ``MXTPU_SHARD_PARAMS_AUTO_MB`` MiB (default 256);
        ``MXTPU_SHARD_PARAMS=0/1`` overrides. ``partition_rules`` — ordered
        ``(regex, PartitionSpec)`` pairs over parameter names (default
        ``parallel.partition.fsdp_rules()``) — decide which trainables
        shard; scalar leaves always replicate. FSDP supersedes
        ``shard_update``. See docs/DESIGN.md "Full-parameter sharding".

        ``multi_step=K`` switches the callable to scanned SUPER-step
        execution: one ``lax.scan`` program advances K optimizer steps per
        dispatch over inputs stacked ``[K, batch, ...]`` (pair with
        ``DataLoader.device_prefetch(multi_step=K)``); ``accumulate=G``
        sums gradients over G stacked microbatches before each update.
        ``MXTPU_MULTI_STEP`` overrides ``multi_step`` from the environment
        (``0`` disables). See docs/DESIGN.md "Multi-step execution"."""
        from ..context import enable_compilation_cache
        from ..train_step import CompiledTrainStep

        # the step program is the most expensive compile a trainer makes:
        # persist it where Predictor and DecodeEngine persist theirs
        enable_compilation_cache()
        self._compiled_step = CompiledTrainStep(
            self, net, loss_fn, mesh=mesh, loss_scaler=loss_scaler,
            shard_update=shard_update, strict_batch=strict_batch,
            shard_params=shard_params, partition_rules=partition_rules)
        env = os.environ.get("MXTPU_MULTI_STEP")
        if env is not None:
            env = env.strip()
            multi_step = int(env) if env else None
            if multi_step is not None and multi_step < 1:
                multi_step = None  # 0 disables any coded-in default
        if multi_step is not None or (accumulate or 1) > 1:
            self._compiled_step.compile_multi_step(
                multi_step, accumulate=accumulate or 1)
        return self._compiled_step

    @property
    def step_fn(self):
        """The functional train step built by ``compile_step``."""
        if self._compiled_step is None:
            raise MXNetError(
                "no compiled step: call trainer.compile_step(net, loss_fn) "
                "first")
        return self._compiled_step

    # -- kvstore ------------------------------------------------------------
    def _init_kvstore(self):
        spec = self._kvstore_spec
        if spec is None or spec in ("local", "device", "nccl") and \
                self._update_on_kvstore is not True:
            # single-worker fast path: no store needed
            self._kvstore = kvs_mod.create(spec) if spec else None
            self._kv_initialized = True
            return
        self._kvstore = spec if isinstance(spec, kvs_mod.KVStoreBase) \
            else kvs_mod.create(spec)
        if self._compression_params and \
                hasattr(self._kvstore, "set_gradient_compression"):
            self._kvstore.set_gradient_compression(self._compression_params)
        if self._update_on_kvstore:
            self._kvstore.set_optimizer(self._optimizer)
        self._kv_initialized = True

    @property
    def kvstore(self):
        if not self._kv_initialized:
            self._init_kvstore()
        return self._kvstore

    # -- the step -----------------------------------------------------------
    def allreduce_grads(self):
        """Explicit grad allreduce (multi-worker)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._kvstore is None or self._kvstore.num_workers == 1:
            return
        # ONE batched call: the distributed store fuses the whole parameter
        # list into one collective per dtype bucket instead of one per key
        keys, grads = [], []
        for i, p in enumerate(self._params):
            if p.grad_req == "null":
                continue
            keys.append(i)
            grads.append(p.grad())
        if keys:
            self._kvstore.pushpull(keys, grads, out=grads)

    def step(self, batch_size, ignore_stale_grad=False):
        """Rescale grads by 1/batch_size, allreduce, update.

        Reference: trainer.py step -> _allreduce_grads -> _update.
        """
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        if self._kvstore is not None and self._update_on_kvstore:
            # optimizer runs on the store (reference update_on_kvstore):
            # pushpull applies the store-side updater and writes the new
            # weight back — one batched call for the whole parameter list
            keys, grads, weights = [], [], []
            for i, p in enumerate(self._params):
                if p.grad_req == "null":
                    continue
                keys.append(i)
                grads.append(p.grad())
                weights.append(p.data())
            if keys:
                self._kvstore.pushpull(keys, grads, out=weights)
            if _telemetry.ON:
                _telemetry.mark_step()
            return
        if self._kvstore is not None and self._kvstore.num_workers > 1:
            self.allreduce_grads()
        self._update(ignore_stale_grad)
        if _telemetry.ON:
            # close one telemetry accounting row per optimization step —
            # the substrate of telemetry.step_report()
            _telemetry.mark_step()

    def _update(self, ignore_stale_grad=False):
        active = []
        for i, p in enumerate(self._params):
            if p.grad_req == "null":
                continue
            if p._data is None:
                if ignore_stale_grad:
                    continue
                raise MXNetError(f"parameter {p.name} not initialized")
            if self._states[i] is None:
                self._states[i] = \
                    self._optimizer.create_state_multi_precision(i, p.data())
            active.append(i)
        if self._update_on_kvstore and self._kvstore is not None:
            return  # optimizer ran on the store during pushpull
        for i in self._fused_update(active):
            p = self._params[i]
            self._optimizer.update(i, p.data(), p.grad(), self._states[i])

    def _fused_update(self, active):
        """Fused multi-tensor update (reference: the multi_sgd/multi_adam
        fused kernels, optimizer_op.cc:373-470). Dense float parameters are
        bucketed by dtype and each bucket updates in ONE jitted program with
        donated weight/state buffers — O(#buckets) dispatches per step, not
        O(#params). Returns the indices NOT handled here (row-sparse grads,
        non-float dtypes, fusion disabled), which the caller updates through
        the per-param path.
        """
        opt = self._optimizer
        spec = getattr(opt, "fused_step", None)
        if not self._fuse or spec is None or opt.multi_precision \
                or not active:
            return active
        import jax.numpy as jnp
        from ..ndarray.sparse import RowSparseNDArray

        raw, state_keys, needs_t, elementwise = spec
        buckets, rest = {}, []
        for i in active:
            p = self._params[i]
            w = p.data()
            if isinstance(p.grad(), RowSparseNDArray) \
                    or not jnp.issubdtype(w.dtype, jnp.floating):
                rest.append(i)
                continue
            st = self._states[i]
            if any(k not in st for k in state_keys):
                rest.append(i)  # e.g. states restored from an older run
                continue
            buckets.setdefault(str(w.dtype), []).append(i)
        for dt in sorted(buckets):
            self._run_fused_bucket(raw, state_keys, needs_t, elementwise,
                                   buckets[dt])
        return rest

    # tensors at or under this many elements are flattened into ONE kernel
    # when the step is elementwise (BN scales/biases are ~2/3 of a ResNet's
    # tensors but ~0.2% of its bytes; per-kernel overhead dominates them)
    _FUSE_FLAT_MAX = 4096

    def _run_fused_bucket(self, raw, state_keys, needs_t, elementwise, idxs):
        import jax
        import jax.numpy as jnp
        import numpy as onp

        opt = self._optimizer
        n_state = len(state_keys)
        # parameter-signature cache key: same index set -> same compiled
        # program (shapes/dtypes are fixed per index once initialized)
        key = (str(self._params[idxs[0]].data().dtype), tuple(idxs))
        fused = self._fused_fn.get(key)
        if fused is None:
            sizes = [int(onp.prod(self._params[i].data().shape))
                     for i in idxs]
            # elementwise steps only: concatenation changes per-tensor
            # reductions (LAMB trust ratio, GroupAdaGrad row sums), so those
            # keep one call per tensor
            small = [k for k in range(len(idxs))
                     if elementwise and sizes[k] <= self._FUSE_FLAT_MAX
                     and all(self._states[idxs[k]][sk].shape
                             == self._params[idxs[k]].data().shape
                             for sk in state_keys)]
            small = small if len(small) > 1 else []
            small_set = frozenset(small)
            if small:
                # the flatten/pad layout arithmetic lives in ONE place
                # (parallel.collectives.BucketSpec) shared with the ZeRO-1
                # and FSDP bucket schedules; n_shards=1 = no padding
                from ..parallel.collectives import BucketSpec

                small_bs = BucketSpec(
                    [tuple(self._params[idxs[k]].data().shape)
                     for k in small], 1)

            def multi_step(ws, ss, gs, lrs, wds, ts, rs):
                # body executes at TRACE time only — the counter observes
                # recompiles, and the Python loop unrolls into one program.
                # _fused_traces (PR 1's private counter) is kept for direct
                # assertions; the telemetry watchdog is the user-facing
                # surface: a re-trace of this program after warmup means a
                # parameter signature changed mid-run and warns loudly
                self._fused_traces += 1
                _telemetry.record_compile(
                    "trainer.fused_step", (ws, gs),
                    attrs=f"n_params={len(ws)} dtype={key[0]}")
                new_ws = [None] * len(ws)
                new_ss = [None] * len(ws)
                for k in range(len(ws)):
                    if k in small_set:
                        continue
                    g = gs[k] * rs
                    args = [ws[k], *ss[k], g, lrs[k], wds[k]]
                    if needs_t:
                        args.append(ts[k])
                    out = raw(*args)
                    if n_state:
                        new_ws[k] = out[0]
                        new_ss[k] = tuple(out[1:])
                    else:
                        new_ws[k] = out
                        new_ss[k] = ()
                if small:
                    # flatten the tiny tensors into one vector; hypers are
                    # repeated per element (same arithmetic per element ->
                    # bit-identical to the per-tensor calls)
                    ksel = jnp.asarray(small)

                    def flat(xs):
                        return small_bs.flatten([xs[k] for k in small])

                    args = [flat(ws),
                            *(small_bs.flatten([ss[k][j] for k in small])
                              for j in range(n_state)),
                            flat(gs) * rs, small_bs.spread(lrs[ksel]),
                            small_bs.spread(wds[ksel])]
                    if needs_t:
                        args.append(small_bs.spread(ts[ksel]))
                    out = raw(*args)
                    out = out if n_state else (out,)
                    parts = [small_bs.unflatten(o) for o in out]
                    for si, k in enumerate(small):
                        new_ws[k] = parts[0][si]
                        new_ss[k] = tuple(p[si] for p in parts[1:])
                return new_ws, new_ss

            from ..train_step import train_donate_argnums
            fused = jax.jit(multi_step,
                            donate_argnums=train_donate_argnums())
            self._fused_fn[key] = fused
        ws = [self._params[i].data()._data for i in idxs]
        ss = [tuple(self._states[i][k]._data for k in state_keys)
              for i in idxs]
        gs = [self._params[i].grad()._data for i in idxs]
        # scalar schedule inputs (t, lr, wd, rescale) are RUNTIME operands —
        # one stacked f32 transfer each, never trace-time constants, so a
        # changing LR schedule or step count causes zero recompiles
        ts = onp.asarray([opt._update_count(i) for i in idxs], onp.float32)
        lrs = onp.asarray([opt._get_lr(i) for i in idxs], onp.float32)
        wds = onp.asarray([opt._get_wd(i) for i in idxs], onp.float32)
        rs = onp.float32(opt.rescale_grad)
        self._fused_dispatches += 1
        if _telemetry.ON:
            # fused buckets bypass the invoke() chokepoint — count the
            # compiled-program call here so step rows stay truthful
            _telemetry.record_dispatch()
        new_ws, new_ss = fused(ws, ss, gs, lrs, wds, ts, rs)
        for k, i in enumerate(idxs):
            self._params[i].data()._set_data(new_ws[k])
            for sk, arr in zip(state_keys, new_ss[k]):
                self._states[i][sk]._set_data(arr)

    def update(self, batch_size, ignore_stale_grad=False):
        """Apply updates without allreduce (manual grad management)."""
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)
        if _telemetry.ON:
            _telemetry.mark_step()

    # -- checkpoint ---------------------------------------------------------
    def states_payload(self):
        """Host-side (numpy, pickleable) snapshot of the optimizer state in
        the classic per-param layout, whatever the residency mode: the
        ZeRO-1 / FSDP bridge gathers the dp-sharded flat buckets back to
        per-param arrays, so the payload (and any later load into a
        replicated run) is layout-identical across modes. This is the
        device→host copy the async CheckpointManager takes at a step
        boundary before handing serialization to its writer thread."""
        states = self._shard_state.gather_states() if self._shard_state \
            else self._states
        payload = []
        for st in states:
            if st is None:
                payload.append(None)
            else:
                payload.append({k: v.asnumpy() for k, v in st.items()})
        return {"states": payload,
                "num_update": self._optimizer.num_update,
                "index_count": dict(self._optimizer._index_update_count)}

    def load_states_payload(self, payload):
        """Restore a ``states_payload()`` snapshot (re-sharding into the
        live residency mode when the compiled step runs ZeRO-1 / FSDP)."""
        from ..ndarray.ndarray import NDArray

        self._states = [None if st is None else
                        {k: NDArray(v) for k, v in st.items()}
                        for st in payload["states"]]
        self._optimizer.num_update = payload["num_update"]
        self._optimizer._index_update_count = dict(payload["index_count"])
        if self._shard_state is not None:
            # re-shard the freshly loaded full states (consumes _states)
            self._shard_state.scatter_from_trainer()

    def save_states(self, fname):
        """Reference: trainer.py:482."""
        import pickle

        with open(fname, "wb") as f:
            pickle.dump(self.states_payload(), f)

    def load_states(self, fname):
        import pickle

        with open(fname, "rb") as f:
            payload = pickle.load(f)
        self.load_states_payload(payload)
