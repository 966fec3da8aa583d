"""CompiledTrainStep: the WHOLE training step as one donated-buffer program.

TPU-native analog of the reference CachedOp's graph-level bulking (and of
PyGraph's whole-iteration CUDA-graph capture): forward + loss + backward +
gradient rescale + (under a mesh) the data-parallel reduction + the
registered optimizer recurrence trace into ONE ``jax.jit`` program with the
weight and optimizer-state buffers donated. Steady state is exactly one host
dispatch per step; the loss scalar (and BN moving-stat write-backs) are the
only things that come home.

Reuses the existing pieces instead of duplicating them:

- the forward is captured with ``_deferred_compute`` tracing and replayed by
  ``CachedOp``'s executor (``build_executor``) — the same machinery
  ``hybridize()`` uses;
- the backward is ``autograd.program_vjp`` INSIDE the trace — the transposed
  program is part of the step, not a host-side tape walk;
- the update unrolls ``Optimizer._register_step``'s pure per-tensor
  recurrence (the PR-1 declaration) per parameter;
- the data-parallel path runs the body under ``shard_map`` and reduces
  gradients with ``parallel.collectives``.

Hyper-parameters (lr / wd / t / rescale / loss scale) ride as RUNTIME
operands — an LR schedule or a ``DynamicLossScaler`` causes zero recompiles.
With a loss scaler the program additionally returns an overflow flag
computed in-program (finiteness of the scaled gradients); on overflow the
update is a ``where``-select no-op and the host skips the schedule commit,
matching the eager skip-on-overflow loop.

Sharded weight update (ZeRO-1)
------------------------------
With a data-parallel mesh the replicated schedule runs the *identical*
optimizer update on every replica — weight-update FLOPs and optimizer state
(2x weights for Adam) duplicated N ways. ``shard_update`` (auto-on when the
mesh's 'dp' axis has >= 2 shards and the optimizer's recurrence is
elementwise) applies the schedule of Xu et al., "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training": the grad ``pmean``
becomes a ``reduce_scatter`` over flat per-dtype parameter buckets (padded
to a multiple of the dp extent), the recurrence runs only on each replica's
contiguous 1/N shard with optimizer state ALLOCATED sharded from
initialization, and an ``all_gather`` rebuilds the full weights — all
inside the same single donated-buffer program, where XLA overlaps the
collectives with the update on ICI. Per-replica update FLOPs and optimizer
state drop ~Nx.

Bit parity: with an elementwise optimizer, BOTH ``shard_update`` settings
dispatch the SAME compiled program — the ZeRO-1 schedule above, with state
entering as dp-sharded buckets. They differ only in state RESIDENCY
between steps: sharded keeps the persistent 1/N shard buckets (the memory
win), replicated keeps the classic per-param arrays in
``trainer._states`` and reshards them around each dispatch (inspectable
state and the pre-existing checkpoint layout, at the cost of one state
scatter + gather per step). Identical program + identical inputs means
bitwise-identical weights, unconditionally. Structurally different
sharded/replicated programs do NOT give that: XLA's global layout and
fusion passes then round a few gradient elements differently (1 ulp,
input-dependent), and neither ``optimization_barrier`` (expanded away
before fusion) nor ``reduce_precision`` pinning (reassociated across, and
a no-op in the CPU emitter) recovers parity. Non-elementwise fused
optimizers (trust-ratio / whole-tensor reductions) keep the per-tensor
psum update, replicated on every device.

Full-parameter sharding (ZeRO-3 / FSDP)
---------------------------------------
ZeRO-1 still keeps a FULL copy of every weight on every replica between
steps. ``shard_params`` goes the rest of the way: parameters AND optimizer
state live as per-layer flat buckets sharded 1/N over 'dp' end-to-end.
Which trainables shard is decided by regex partition rules
(``parallel.partition.match_partition_rules``; default: everything
non-scalar over 'dp'); ``parallel.partition.fsdp_groups`` folds them into
one ``BucketSpec`` per (layer, dtype) — scalars and explicitly-replicated
leaves pool into small replicated buckets updated identically everywhere.

Inside the single donated program each layer's bucket is ``all_gather``ed
just-in-time where the forward first needs it; with rematerialization on
(``MXTPU_FSDP_REMAT``, default ``dots`` = ``jax.checkpoint`` with the
``dots_saveable`` policy) the backward re-gathers instead of keeping full
weights live, so peak weight residency tracks the largest layer, not the
model. The gradient needs NO explicit reduce for sharded buckets: the vjp
transpose of a tiled ``all_gather`` IS ``psum_scatter``, so gradients
arrive pre-reduced in the owning shard's layout. The recurrence then runs
on resident shards and its outputs STAY sharded — there is no trailing
weight all-gather; the next step's forward gathers again. Per-replica
param + grad + optimizer-state residency all drop ~Nx (the residency
gauges ``train_step.param/grad/opt_state_bytes_per_replica`` report it).

Between steps ``Parameter._data`` is released: ``data()`` materializes a
full value on demand from the bucket (host gather — checkpoints and
inspection, not the hot path), ``set_data`` writes through into the
bucket, and checkpoints keep the classic per-param layout in both
directions. Because the FSDP program is STRUCTURALLY different from the
replicated/ZeRO-1 one, its trajectory may differ by XLA's input-dependent
1-ulp rounding (see above) — parity with the other modes is numerical
(tight tolerance), not bitwise; checkpoint round-trips remain bitwise.
"""
from __future__ import annotations

import os

from .base import MXNetError, warn_once
from . import telemetry as _telemetry
from .telemetry import span as _span

__all__ = ["CompiledTrainStep", "compiled_modules"]

_COMPILED = {}   # HLO module name -> the newest jax Compiled of that name


def compiled_modules():
    """``{HLO module name: jax Compiled}``: the newest program of each name
    (``jit_mxtpu_train_step``, ``jit_mxtpu_train_step_k<K>``) that a compiled
    step of this process has built. The accessor for a tool that holds no
    step instance, or outlives it, such as a reader that joins a device
    trace (events named by instruction) with the compiled text (where each
    instruction's ``op_name`` carries its named scopes). A handful of
    entries at most; the steps pay one dict store a compile."""
    return dict(_COMPILED)


def train_donate_argnums():
    """Donation spec for the whole-step programs: ``(0, 1)`` (weights,
    optimizer state) on accelerators, ``()`` on XLA:CPU.

    Buffer donation is the TPU memory win (update in place instead of
    holding two copies of params + state). On the CPU backend it buys
    nothing — host RAM is not the constraint — and XLA:CPU's donation
    aliasing is unsound under the multi-device host mesh: donated buffers
    can be freed while an aliased output chain still lives on them, and
    once the heap reuses the memory the live weights/state get scribbled
    (nondeterministic NaN/garbage a few steps later; reproduced by
    tests/test_multi_step.py parity after enough allocator churn).
    ``MXTPU_DONATE=0/1`` forces either behavior for A/B studies."""
    env = os.environ.get("MXTPU_DONATE")
    if env is not None:
        return (0, 1) if env.strip().lower() not in ("0", "false", "off") \
            else ()
    import jax

    return () if jax.default_backend() == "cpu" else (0, 1)


class _Program:
    """One compiled step program + the trace metadata needed to drive it."""

    __slots__ = ("fn", "uses_rng", "aux_targets", "n_aux", "sharded",
                 "fsdp", "coll_bytes", "coll_bytes_tp", "compiled", "flops",
                 "bytes_accessed", "k", "accum", "health_mode",
                 "health_groups")

    def __init__(self, fn, uses_rng, aux_targets, sharded=False, fsdp=False,
                 coll_bytes=(0, 0, 0), coll_bytes_tp=0, k=None, accum=1,
                 health_mode="off", health_groups=None):
        self.fn = fn
        self.uses_rng = uses_rng
        self.aux_targets = aux_targets
        self.n_aux = len(aux_targets)
        self.sharded = sharded
        self.fsdp = fsdp
        # (reduce_scatter, all_gather, psum) bytes per call, known at build
        # time — the host's only window into in-program collective traffic
        self.coll_bytes = coll_bytes
        # 'tp'-axis collective payload per call (megatron psums/gathers),
        # accounted by the op fallbacks during the eager trace
        self.coll_bytes_tp = coll_bytes_tp
        # the jax Compiled, bound at first _run via explicit lower+compile
        # (same single XLA compile the implicit jit call would pay, but
        # the executable handle stays reachable for cost_analysis)
        self.compiled = None
        self.flops = 0.0
        self.bytes_accessed = 0.0
        # multi-step super-step shape: k scanned optimizer steps, each
        # accumulating `accum` microbatches; k=None is the single-step path
        self.k = k
        self.accum = accum
        # in-program numerics monitor: MXTPU_NUMERICS mode baked into the
        # trace and the layer-group labels of its nonfinite-count vector
        # (None = monitor off, program emits no health outputs)
        self.health_mode = health_mode
        self.health_groups = health_groups


class _ShardedOptState:
    """ZeRO-1 optimizer state: flat per-dtype buckets sharded over 'dp'.

    Each state key of each bucket is ONE global ``(padded,)`` f32
    ``jax.Array`` under ``NamedSharding(mesh, P('dp'))`` — every replica
    materializes only its contiguous 1/N shard, from the very first
    allocation (``parallel.mesh.zeros_sharded``). While this is live it is
    the source of truth: the trainer's per-param ``_states`` stay ``None``
    and checkpoints gather back to the per-param layout (identical pickle
    format to the replicated path) and re-scatter on load.

    Gathering assumes all shards are addressable by this process (single
    controller / host-platform mesh); a multi-host checkpoint would use a
    distributed array serializer instead.
    """

    def __init__(self, mesh, opt, trainer, train_idx, buckets, state_keys):
        self.mesh = mesh
        self.opt = opt
        self.trainer = trainer
        self.train_idx = train_idx
        self.buckets = buckets          # [(dtype_str, ks, BucketSpec)]
        self.state_keys = state_keys
        self.state = []                 # per bucket: tuple over keys
        self._init()
        # gauges are samples, set once per build — no ON guard needed
        _telemetry.gauge("train_step.opt_state_bytes_per_replica").set(
            self.per_replica_state_bytes())
        _telemetry.gauge("train_step.opt_state_bytes_replicated").set(
            self.replicated_state_bytes())

    # -- allocation ---------------------------------------------------------
    def _init(self):
        from .parallel.mesh import zeros_sharded, P
        import jax.numpy as jnp

        tr, keys = self.trainer, self.state_keys
        for _, ks, bs in self.buckets:
            if not keys:
                self.state.append(())
                continue
            idxs = [self.train_idx[k] for k in ks]
            if all(tr._states[i] is None for i in idxs):
                # fresh run: allocate zeros DIRECTLY sharded — no replica
                # ever holds the full state (every registered elementwise
                # recurrence zero-initializes its state)
                self.state.append(tuple(
                    zeros_sharded(self.mesh, (bs.padded,), jnp.float32,
                                  P("dp"))
                    for _ in keys))
            else:
                # resumed/mixed: scatter the existing full state
                for i in idxs:
                    if tr._states[i] is None:
                        tr._states[i] = \
                            self.opt.create_state_multi_precision(
                                i, tr._params[i].data())
                self.state.append(self._scatter_bucket(ks, bs))
                for i in idxs:
                    tr._states[i] = None  # sharded buckets own it now

    def _scatter_bucket(self, ks, bs):
        import jax
        from .parallel.mesh import shard_1d

        tr = self.trainer
        sharding = shard_1d(self.mesh)
        return tuple(
            jax.device_put(bs.flatten_host(
                [tr._states[self.train_idx[k]][key].asnumpy() for k in ks]),
                sharding)
            for key in self.state_keys)

    # -- step rebind --------------------------------------------------------
    def rebind(self, new_state):
        """Adopt the program's donated-output state buckets."""
        self.state = [tuple(st) for st in new_state]

    # -- checkpoint bridge --------------------------------------------------
    def gather_states(self):
        """Per-param full state dicts (the replicated pickle layout)."""
        import numpy as onp
        from .ndarray.ndarray import NDArray

        out = [None] * len(self.trainer._params)
        for (_, ks, bs), st in zip(self.buckets, self.state):
            for key, arr in zip(self.state_keys, st):
                flat = onp.asarray(arr)  # gathers every shard to host
                for k, off, n, shape in zip(ks, bs.offsets, bs.sizes,
                                            bs.shapes):
                    i = self.train_idx[k]
                    if out[i] is None:
                        out[i] = {}
                    out[i][key] = NDArray(flat[off:off + n].reshape(shape))
        return out

    def scatter_from_trainer(self):
        """Re-shard after ``Trainer.load_states`` refilled ``_states``."""
        tr = self.trainer
        state = []
        for _, ks, bs in self.buckets:
            idxs = [self.train_idx[k] for k in ks]
            for i in idxs:
                if tr._states[i] is None:
                    tr._states[i] = self.opt.create_state_multi_precision(
                        i, tr._params[i].data())
            state.append(self._scatter_bucket(ks, bs))
            for i in idxs:
                tr._states[i] = None
        self.state = state

    # -- accounting ---------------------------------------------------------
    def per_replica_state_bytes(self):
        """Bytes of optimizer state ONE replica holds (its shards)."""
        total = 0
        for st in self.state:
            for arr in st:
                total += arr.addressable_shards[0].data.nbytes
        return total

    def replicated_state_bytes(self):
        """What the replicated path would hold per replica (full state)."""
        return sum(bs.total * 4 * len(self.state_keys)
                   for _, _, bs in self.buckets)


class _FSDPState:
    """FSDP residency: parameters AND optimizer state as per-layer flat
    buckets sharded 1/N over 'dp', end-to-end.

    Unlike ``_ShardedOptState`` (ZeRO-1: full weights between steps,
    sharded state only), nothing full-sized persists anywhere. On adoption
    the per-param ``Parameter._data`` buffers are released and replaced by
    bucket images (``BucketSpec.flatten_host`` + one ``device_put`` under
    ``P('dp')`` per sharded group; replicated pools go up whole);
    ``Parameter.data()`` then materializes a full value on demand from the
    bucket and ``set_data`` writes through into it — checkpoints and
    inspection keep working in the classic per-param layout. Re-traces of
    the step (new batch signature) need the stable NDArray objects the
    deferred-compute variables bind to, so ``materialize_into_params`` /
    ``release_params`` bracket each build.

    The checkpoint bridge (``gather_states``/``scatter_from_trainer``) and
    the residency gauges mirror ``_ShardedOptState`` so
    ``Trainer.save_states``/``load_states`` and dashboards are mode-
    agnostic. The single-controller gather caveat applies here too.

    dp x tp: a group with ``sharded == "tp"`` (a megatron rule matched it)
    holds ONE flat bucket of the GLOBAL length ``tp * BucketSpec.padded``
    under ``NamedSharding(mesh, P(('tp', 'dp')))`` — tp-major, so the
    contiguous 1/tp blocks are the per-rank LOCAL flat images, each
    dp-sharded exactly like a plain dp group. Inside the program the
    existing per-layer ``all_gather(..., 'dp')`` then rebuilds each tp
    rank's local image unchanged, and its AD transpose psum_scatters over
    'dp' only (correct: tp ranks own disjoint parameters). The host
    layouts (``parallel.tp.local_slice``/``merge_local``) are pure index
    permutations, so the per-param checkpoint layout stays bitwise.
    """

    def __init__(self, mesh, opt, trainer, train_idx, groups, state_keys,
                 tp_places=None, tp_size=1):
        self.mesh = mesh
        self.opt = opt
        self.trainer = trainer
        self.train_idx = train_idx
        self.groups = groups   # [(layer, dtype, ks, BucketSpec, sharded)]
        self.state_keys = state_keys
        self.tp_places = tp_places or {}  # train pos k -> (dim, segments)
        self.tp_size = int(tp_size)
        self.params = []       # per group: flat bucket jax.Array
        self.state = []        # per group: tuple over state keys
        self._where = {}       # train position k -> (group idx, slot idx)
        for gi, (_, _, ks, _, _) in enumerate(groups):
            for si, k in enumerate(ks):
                self._where[k] = (gi, si)
        self._adopt_params()
        self._init_state()
        p_shard = self.per_replica_param_bytes()
        _telemetry.gauge("train_step.param_bytes_per_replica").set(p_shard)
        _telemetry.gauge("train_step.param_bytes_replicated").set(
            self.replicated_param_bytes())
        # gradients exist only transiently in-program, pre-scattered into
        # the same shard layout — their residency bound IS the shard bytes
        _telemetry.gauge("train_step.grad_bytes_per_replica").set(p_shard)
        _telemetry.gauge("train_step.opt_state_bytes_per_replica").set(
            self.per_replica_state_bytes())
        _telemetry.gauge("train_step.opt_state_bytes_replicated").set(
            self.replicated_state_bytes())

    def _sharding(self, sharded):
        from .parallel.mesh import replicated, shard_1d

        if sharded == "tp":
            import jax

            from .parallel.mesh import P

            return jax.sharding.NamedSharding(self.mesh, P(("tp", "dp")))
        return shard_1d(self.mesh) if sharded else replicated(self.mesh)

    def _group_image(self, values, ks, bs, sh, dtype=None):
        """Host flat image for one group from full per-param arrays. tp
        groups concatenate the per-rank local flat images tp-major (each
        independently padded to the dp extent) — the exact layout
        ``P(('tp', 'dp'))`` shards contiguously."""
        kw = {"dtype": dtype} if dtype is not None else {}
        if sh != "tp":
            return bs.flatten_host(values, **kw)
        import numpy as onp

        from .parallel import tp as _tp

        outs = []
        for r in range(self.tp_size):
            locs = [_tp.local_slice(v, self.tp_places[k][0], r,
                                    self.tp_size, self.tp_places[k][1])
                    for k, v in zip(ks, values)]
            outs.append(bs.flatten_host(locs, **kw))
        return onp.concatenate(outs)

    # -- adoption -----------------------------------------------------------
    def _adopt_params(self):
        import jax

        tr = self.trainer
        for _, dt, ks, bs, sh in self.groups:
            img = self._group_image(
                [tr._params[self.train_idx[k]].data().asnumpy()
                 for k in ks], ks, bs, sh, dtype=dt)
            self.params.append(jax.device_put(img, self._sharding(sh)))
        # release the full per-param buffers; data()/set_data route here
        for k, i in enumerate(self.train_idx):
            p = tr._params[i]
            p._provider = (self, k)
            p._data = None

    def _init_state(self):
        from .parallel.mesh import P, zeros_sharded
        import jax.numpy as jnp

        tr, keys = self.trainer, self.state_keys
        for _, _, ks, bs, sh in self.groups:
            if not keys:
                self.state.append(())
                continue
            idxs = [self.train_idx[k] for k in ks]
            if all(tr._states[i] is None for i in idxs):
                if sh == "tp":
                    spec, length = P(("tp", "dp")), bs.padded * self.tp_size
                else:
                    spec, length = (P("dp"), bs.padded) if sh \
                        else (P(), bs.padded)
                self.state.append(tuple(
                    zeros_sharded(self.mesh, (length,), jnp.float32,
                                  spec)
                    for _ in keys))
            else:
                for i in idxs:
                    if tr._states[i] is None:
                        tr._states[i] = \
                            self.opt.create_state_multi_precision(
                                i, tr._params[i].data())
                self.state.append(self._scatter_group(ks, bs, sh))
                for i in idxs:
                    tr._states[i] = None  # the buckets own it now

    def _scatter_group(self, ks, bs, sh):
        import jax

        tr = self.trainer
        sharding = self._sharding(sh)
        return tuple(
            jax.device_put(self._group_image(
                [tr._states[self.train_idx[k]][key].asnumpy() for k in ks],
                ks, bs, sh),
                sharding)
            for key in self.state_keys)

    # -- Parameter provider hooks -------------------------------------------
    def _stitch(self, flat, k, si, bs):
        """One parameter's FULL value out of a tp group's global flat
        bucket: merge the per-rank local images (bitwise permutation)."""
        from .parallel import tp as _tp

        off, n = bs.offsets[si], bs.sizes[si]
        dim, seg = self.tp_places[k]
        parts = [flat[r * bs.padded + off: r * bs.padded + off + n]
                 .reshape(bs.shapes[si]) for r in range(self.tp_size)]
        return _tp.merge_local(parts, dim, segments=seg)

    def param_ndarray(self, k):
        """Materialize one adopted parameter's FULL value (host gather of
        its group bucket) — the checkpoint/inspection path."""
        import numpy as onp
        from .ndarray.ndarray import NDArray

        gi, si = self._where[k]
        _, _, _, bs, sh = self.groups[gi]
        flat = onp.asarray(self.params[gi])  # gathers every shard to host
        if sh == "tp":
            return NDArray(self._stitch(flat, k, si, bs))
        off, n = bs.offsets[si], bs.sizes[si]
        return NDArray(flat[off:off + n].reshape(bs.shapes[si]))

    def param_write(self, k, value):
        """Write-through ``set_data`` for an adopted parameter: rebuild the
        group's bucket image with the new slice (the load/re-init path)."""
        import jax
        import numpy as onp

        gi, si = self._where[k]
        _, dt, _, bs, sh = self.groups[gi]
        flat = onp.asarray(self.params[gi]).copy()
        off, n = bs.offsets[si], bs.sizes[si]
        v = onp.asarray(value).astype(onp.dtype(dt), copy=False)
        if sh == "tp":
            from .parallel import tp as _tp

            dim, seg = self.tp_places[k]
            for r in range(self.tp_size):
                flat[r * bs.padded + off: r * bs.padded + off + n] = \
                    _tp.local_slice(v, dim, r, self.tp_size, seg).reshape(-1)
        else:
            flat[off:off + n] = v.reshape(-1)
        self.params[gi] = jax.device_put(flat, self._sharding(sh))

    # -- re-trace bracket ---------------------------------------------------
    def materialize_into_params(self):
        """Temporarily restore full per-param ``_data`` (from the buckets)
        so a re-trace binds its variables to the stable NDArray objects the
        forward will read; ``release_params`` drops them again."""
        tr = self.trainer
        for k, i in enumerate(self.train_idx):
            if tr._params[i]._data is None:
                tr._params[i]._data = self.param_ndarray(k)

    def release_params(self):
        tr = self.trainer
        for i in self.train_idx:
            tr._params[i]._data = None

    # -- step rebind --------------------------------------------------------
    def rebind(self, new_params, new_state):
        """Adopt the program's donated-output param + state buckets."""
        self.params = list(new_params)
        self.state = [tuple(st) for st in new_state]

    # -- checkpoint bridge --------------------------------------------------
    def gather_states(self):
        """Per-param full state dicts (the replicated pickle layout)."""
        import numpy as onp
        from .ndarray.ndarray import NDArray

        out = [None] * len(self.trainer._params)
        for (_, _, ks, bs, sh), st in zip(self.groups, self.state):
            for key, arr in zip(self.state_keys, st):
                flat = onp.asarray(arr)
                for si, (k, off, n, shape) in enumerate(
                        zip(ks, bs.offsets, bs.sizes, bs.shapes)):
                    i = self.train_idx[k]
                    if out[i] is None:
                        out[i] = {}
                    if sh == "tp":
                        out[i][key] = NDArray(self._stitch(flat, k, si, bs))
                    else:
                        out[i][key] = NDArray(
                            flat[off:off + n].reshape(shape))
        return out

    def scatter_from_trainer(self):
        """Re-shard after ``Trainer.load_states`` refilled ``_states``."""
        tr = self.trainer
        state = []
        for _, _, ks, bs, sh in self.groups:
            idxs = [self.train_idx[k] for k in ks]
            for i in idxs:
                if tr._states[i] is None:
                    tr._states[i] = self.opt.create_state_multi_precision(
                        i, tr._params[i].data())
            state.append(self._scatter_group(ks, bs, sh))
            for i in idxs:
                tr._states[i] = None
        self.state = state

    # -- accounting ---------------------------------------------------------
    def per_replica_param_bytes(self):
        from .parallel.mesh import bytes_per_replica

        return sum(bytes_per_replica(b) for b in self.params)

    def replicated_param_bytes(self):
        """What unsharded residency would hold per replica (full weights).
        tp groups store per-rank LOCAL shapes — scale back up."""
        import numpy as onp

        return sum(bs.total * onp.dtype(dt).itemsize *
                   (self.tp_size if sh == "tp" else 1)
                   for _, dt, _, bs, sh in self.groups)

    def per_replica_state_bytes(self):
        from .parallel.mesh import bytes_per_replica

        return sum(bytes_per_replica(a) for st in self.state for a in st)

    def replicated_state_bytes(self):
        return sum(bs.total * 4 * len(self.state_keys) *
                   (self.tp_size if sh == "tp" else 1)
                   for _, _, _, bs, sh in self.groups)


class CompiledTrainStep:
    """Callable ``(x, y) -> loss`` running the whole step as one program.

    Built via ``Trainer.compile_step(net, loss_fn)``. Semantics are those of
    the eager loop ``loss_fn(net(x), y).mean(); backward(); trainer.step(1)``
    — the loss is batch-normalized by the ``.mean()``, so the optimizer's
    ``rescale_grad`` is applied as-is (no per-call batch division).

    ``shard_update`` (default: auto-on when the mesh carries a 'dp' axis of
    size >= 2 and the optimizer's recurrence is elementwise; forced by
    ``MXTPU_SHARD_UPDATE=0/1``) runs the ZeRO-1 reduce-scatter →
    shard-update → all-gather schedule with 1/N-sharded optimizer state —
    see the module docstring. Unsupported configurations keep the replicated
    in-program update with a one-time warning
    (reason in ``.shard_fallback_reason``).

    ``shard_params`` (default: auto-on when additionally the trainables
    total at least ``MXTPU_SHARD_PARAMS_AUTO_MB`` MiB, 256 by default —
    decided at first build, when shapes are known; forced by
    ``MXTPU_SHARD_PARAMS=0/1``) goes full FSDP: parameters AND optimizer
    state live dp-sharded between steps, gathered just-in-time per layer
    inside the program — see the module docstring. ``partition_rules``
    (ordered ``(regex, PartitionSpec)`` pairs, default
    ``parallel.partition.fsdp_rules()``) decide which trainables shard.
    FSDP supersedes ``shard_update`` (the weights are already sharded; the
    ZeRO-1 trailing all-gather would undo the point). Unsupported explicit
    requests keep the unsharded residency with a one-time warning (reason
    in ``.shard_params_fallback_reason``).

    A batch not divisible by the dp extent is padded IN-PROGRAM with
    zero-example-weight rows (the loss becomes the weighted mean over the
    real rows, so gradients and the loss value match the unpadded batch);
    ``strict_batch=True`` restores the hard error. Note each distinct
    trailing-batch shape compiles its own program, and BatchNorm batch
    statistics do see the padded rows.

    Falls back to the eager record/backward/``Trainer.step`` path (with a
    one-time warning per (reason, net), reason in ``.fallback_reason``) when
    the step cannot soundly compile: optimizer without a registered fusable
    recurrence (e.g. SGLD's host RNG), ``multi_precision`` master weights,
    ``update_on_kvstore``, a multi-worker kvstore (gradients reduce outside
    the program), or non-float trainables.
    """

    def __init__(self, trainer, net, loss_fn, mesh=None, loss_scaler=None,
                 name="train_step", shard_update=None, strict_batch=False,
                 shard_params=None, partition_rules=None):
        self.trainer = trainer
        self.net = net
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.loss_scaler = loss_scaler if loss_scaler is not None \
            else getattr(trainer, "_amp_loss_scaler", None)
        self.name = name
        self.strict_batch = strict_batch
        self.fallback_reason = None
        self.shard_update = False
        self.shard_fallback_reason = None
        self.shard_params = False
        self.shard_params_fallback_reason = None
        self.partition_rules = partition_rules
        self._shard_params_auto = False  # size threshold pending 1st build
        self._shard_state = None
        self._fsdp_state = None
        self._fsdp_groups = None
        self._tp_places = {}             # train pos k -> (dim, segments)
        self._fsdp_layer_bytes = ()      # [(layer, gather_b, scatter_b)]
        self._cache = {}       # input signature -> _Program
        self._train_idx = None
        self._frozen = None
        self._state_keys = ()
        self._buckets = None
        self._state_bucket_bytes = 0
        self._traces = 0       # trace-time count (observes recompiles)
        self._dispatches = 0   # compiled-program calls
        self.multi_step = None  # K scanned steps per dispatch (None = off)
        self.accumulate = 1     # microbatches psum'd per optimizer step
        self._check_supported()
        self._resolve_shard_params(shard_params)
        self._resolve_shard_update(shard_update)

    def compiled_programs(self):
        """``{input signature: jax Compiled}`` for every program dispatched
        so far — the handle for ``as_text()`` / ``memory_analysis()``."""
        return {sig: p.compiled for sig, p in self._cache.items()
                if p.compiled is not None}

    # -- support matrix -----------------------------------------------------
    def _check_supported(self):
        tr = self.trainer
        opt = tr._optimizer
        if opt.fused_step is None:
            self.fallback_reason = (
                f"{type(opt).__name__} declares no fusable per-tensor step")
            return
        if opt.multi_precision:
            self.fallback_reason = ("multi_precision uses the per-param "
                                    "master-weight path")
            return
        if not tr._kv_initialized:
            tr._init_kvstore()
        if tr._kvstore is not None and tr._update_on_kvstore:
            self.fallback_reason = "update_on_kvstore runs the optimizer " \
                                   "on the store"
            return
        if tr._kvstore is not None and \
                not tr._kvstore.supports_compiled_step:
            self.fallback_reason = (
                f"kvstore '{tr._kvstore.type}' reduces gradients outside "
                "the program (num_workers > 1)")
            return
        if self.mesh is not None:
            from .parallel.mesh import AxisNames

            if AxisNames.DP not in self.mesh.axis_names:
                raise MXNetError(
                    f"compile_step mesh must carry a '{AxisNames.DP}' axis; "
                    f"got {self.mesh.axis_names}")

    def _dp_size(self):
        if self.mesh is None:
            return 0
        from .parallel.mesh import AxisNames

        return int(self.mesh.shape[AxisNames.DP])

    def _tp_size(self):
        if self.mesh is None:
            return 1
        from .parallel.mesh import AxisNames

        return max(int(self.mesh.shape.get(AxisNames.TP, 1)), 1)

    def _shardable(self):
        """``(ok, reason)`` for BOTH flat-bucket sharded schedules (ZeRO-1
        and FSDP): a dp mesh of >= 2 shards and an elementwise fusable
        recurrence."""
        if self._dp_size() < 2:
            return False, "no mesh with a 'dp' axis of size >= 2"
        return self.trainer._optimizer.sharding_eligibility()

    def _resolve_shard_params(self, requested):
        """Decide parameter residency. ``MXTPU_SHARD_PARAMS=0/1`` overrides
        the argument; ``None`` = auto: on when shardable AND the trainables
        total at least ``MXTPU_SHARD_PARAMS_AUTO_MB`` MiB (256 by default)
        — that size check runs at first build, once shapes are known. An
        explicit request the configuration cannot honor keeps the unsharded
        parameter residency (ZeRO-1/replicated per ``shard_update``) and
        warns once per (reason, net)."""
        env = os.environ.get("MXTPU_SHARD_PARAMS")
        if env is not None:
            requested = env.strip().lower() not in ("0", "false", "off", "")
        if requested is False:
            return
        if self.fallback_reason is not None:
            return  # the whole step already falls back to eager
        ok, reason = self._shardable()
        if ok:
            if requested is None:
                self._shard_params_auto = True
            else:
                self.shard_params = True
            return
        if requested is None:
            return  # auto quietly keeps the existing schedule
        self.shard_params_fallback_reason = reason
        warn_once(("shard_params", reason, id(self.net)),
                  f"compile_step: full-parameter sharding unavailable — "
                  f"{reason}; keeping the unsharded parameter residency",
                  RuntimeWarning)

    def _resolve_shard_update(self, requested):
        """Decide the update schedule. ``MXTPU_SHARD_UPDATE=0/1`` overrides
        the argument; ``None`` = auto (on when shardable). A shard request
        the configuration cannot honor keeps the REPLICATED compiled path
        (not the eager fallback) and warns once per (reason, net)."""
        if self.shard_params:
            return  # FSDP owns the whole schedule; weights stay sharded
        env = os.environ.get("MXTPU_SHARD_UPDATE")
        if env is not None:
            requested = env.strip().lower() not in ("0", "false", "off", "")
        auto = requested is None
        if requested is False:
            return
        if self.fallback_reason is not None:
            return  # the whole step already falls back to eager
        ok, reason = self._shardable()
        if ok:
            self.shard_update = True
            return
        if auto and self.mesh is None:
            return  # plain single-device compile: nothing to announce
        self.shard_fallback_reason = reason
        warn_once(("shard_update", reason, id(self.net)),
                  f"compile_step: sharded weight update unavailable — "
                  f"{reason}; keeping the replicated update", RuntimeWarning)

    # -- multi-step configuration -------------------------------------------
    def compile_multi_step(self, multi_step, accumulate=1):
        """Switch this step to scanned super-step execution: ONE donated-
        buffer program ``lax.scan``s the whole step body over K stacked
        microbatches (``multi_step=K``), and/or accumulates gradients over
        G microbatches before each optimizer update (``accumulate=G``).

        The callable then takes STACKED inputs: ``[K, B, ...]`` with
        ``multi_step=K`` alone, ``[G, B, ...]`` with ``accumulate=G``
        alone, ``[K, G, B, ...]`` with both. ``multi_step`` is the nominal
        K — any leading extent compiles its own program (a shorter
        trailing group at epoch end reuses its program every epoch, so
        steady state stays at zero recompiles). Per-inner-step hypers
        (t/lr/wd) ride as a ``[K, n]`` runtime table indexed in-scan by
        the committed-step counter, so LR schedules advance per inner
        step with zero recompiles and an overflow-skipped inner step
        leaves the schedule untouched — exactly the eager skip. The loss
        scale itself is one runtime operand per super-step: the host
        replays the K per-inner-step overflow flags through
        ``LossScaler.replay`` at the super-step boundary (scale changes
        take effect at the next super-step; the applied update is
        identical because power-of-two scales cancel exactly against
        ``rescale``). Returns ``self``.

        Semantics match K sequential single-step dispatches bitwise for
        the replicated and ZeRO-1 residencies (same body, same bits) and
        to tight tolerance for FSDP (structurally different program).
        Batches must divide the dp extent exactly — the in-program pad
        path is per-signature and has no stacked analogue."""
        if multi_step is not None:
            multi_step = int(multi_step)
            if multi_step < 1:
                raise MXNetError(
                    f"multi_step must be >= 1, got {multi_step}")
        accumulate = int(accumulate)
        if accumulate < 1:
            raise MXNetError(f"accumulate must be >= 1, got {accumulate}")
        if self.fallback_reason is not None:
            raise MXNetError(
                "compile_multi_step: the step cannot compile "
                f"({self.fallback_reason}) and a stacked super-batch has "
                "no eager fallback")
        self.multi_step = multi_step
        self.accumulate = accumulate
        return self

    # -- stepping -----------------------------------------------------------
    def __call__(self, x, y):
        if self.multi_step is not None or self.accumulate > 1:
            return self._call_multi(x, y)
        if self.fallback_reason is not None:
            return self._eager_step(x, y)
        pad = self._validate_batch(x)
        sig = (x.shape, str(x.dtype), y.shape, str(y.dtype))
        prog = self._cache.get(sig)
        if prog is None:
            prog = self._build(x, y, pad=pad)
            if prog is None:  # trace discovered an unsupported layout
                return self._eager_step(x, y)
            self._cache[sig] = prog
        return self._run(prog, x, y)

    def _call_multi(self, x, y):
        if self.fallback_reason is not None:
            raise MXNetError(
                "multi-step dispatch cannot fall back to the eager loop "
                f"(stacked inputs): {self.fallback_reason}")
        k, x, y = self._split_super(x, y)
        g = self.accumulate
        sig = ("multi", g, x.shape, str(x.dtype), y.shape, str(y.dtype))
        prog = self._cache.get(sig)
        if prog is None:
            prog = self._build(x, y, pad=0, k=k, g=g)
            if prog is None:
                raise MXNetError(
                    "multi-step dispatch cannot fall back to the eager "
                    f"loop (stacked inputs): {self.fallback_reason}")
            self._cache[sig] = prog
        return self._run_multi(prog, x, y)

    def _split_super(self, x, y):
        """Validate the stacked super-batch layout; returns ``(k, x, y)``
        with inputs normalized to a leading step axis (accumulate-only
        calls gain a length-1 one)."""
        from .ndarray.ndarray import NDArray

        g = self.accumulate
        lead = 2 if g > 1 else 1
        if self.multi_step is None:
            # accumulate-only: [G, B, ...] -> [1, G, B, ...]
            if x.ndim < 2 or x.shape[0] != g:
                raise MXNetError(
                    f"accumulate={g} expects inputs stacked [G, batch, "
                    f"...]; got x of shape {tuple(x.shape)}")
            x = NDArray(x._data[None])
            y = NDArray(y._data[None])
        elif g > 1:
            if x.ndim < 3 or x.shape[1] != g:
                raise MXNetError(
                    f"multi_step with accumulate={g} expects inputs "
                    f"stacked [K, G, batch, ...]; got x of shape "
                    f"{tuple(x.shape)}")
        elif x.ndim < 2:
            raise MXNetError(
                "multi_step expects inputs stacked [K, batch, ...]; got "
                f"x of shape {tuple(x.shape)}")
        k = int(x.shape[0])
        if tuple(y.shape[:lead]) != tuple(x.shape[:lead]):
            raise MXNetError(
                f"stacked x/y leading axes disagree: {tuple(x.shape)} vs "
                f"{tuple(y.shape)}")
        if self.mesh is not None:
            n = self._dp_size()
            micro_b = int(x.shape[lead])
            if micro_b % n != 0:
                raise MXNetError(
                    f"multi-step microbatch {micro_b} not divisible by "
                    f"the mesh's 'dp' axis ({n} shards); the in-program "
                    "pad path has no stacked analogue — size batches to "
                    "the mesh (DataLoader last_batch='discard'/'rollover')")
        return k, x, y

    def _validate_batch(self, x):
        """Rows of in-program zero-weight padding needed to even the batch
        over the dp axis (0 when divisible, or no mesh). With
        ``strict_batch=True`` a ragged batch raises instead — the pre-pad
        contract."""
        if self.mesh is None:
            return 0
        n = self._dp_size()
        r = x.shape[0] % n
        if r == 0:
            return 0
        if self.strict_batch:
            raise MXNetError(
                f"batch {x.shape[0]} not divisible by the mesh's "
                f"'dp' axis ({n} shards) and strict_batch=True")
        return n - r

    # -- tracing ------------------------------------------------------------
    def _collect(self):
        """Partition parameters into trainables (trainer order) and frozen
        trace variables. EVERY initialized parameter of the net — including
        BN running stats and other ``grad_req='null'`` state — becomes an
        explicit graph input: an unmarked array would be captured as a baked
        CONSTANT by the tracer, so step N+1 would silently read step 0's
        stats (and a donated update could never reach them)."""
        tr = self.trainer
        train_idx = []
        for i, p in enumerate(tr._params):
            if p.grad_req == "null":
                continue
            if p._data is None:
                raise MXNetError(
                    f"parameter {p.name} not initialized — initialize the "
                    "net (and run a settle forward for deferred shapes) "
                    "before compile_step")
            train_idx.append(i)
        if not train_idx:
            return None, None, "no trainable parameters"
        seen = {id(tr._params[i]) for i in train_idx}
        frozen = []
        for pname, p in self.net.collect_params().items():
            if id(p) not in seen and p._data is not None:
                frozen.append((pname, p))
        import jax.numpy as jnp

        for i in train_idx:
            if not jnp.issubdtype(tr._params[i].data().dtype, jnp.floating):
                return None, None, \
                    f"non-float trainable parameter {tr._params[i].name}"
        return train_idx, frozen, None

    def _make_buckets(self, train_idx):
        """Per-dtype flat buckets over the trainables (positions into the
        train list), padded to the dp extent — the ZeRO-1 layout."""
        from .parallel.collectives import BucketSpec

        tr = self.trainer
        n = self._dp_size()
        by_dt = {}
        for k, i in enumerate(train_idx):
            by_dt.setdefault(str(tr._params[i].data().dtype), []).append(k)
        return [(dt, by_dt[dt],
                 BucketSpec([tuple(tr._params[train_idx[k]].data().shape)
                             for k in by_dt[dt]], n))
                for dt in sorted(by_dt)]

    def _build(self, x, y, pad=0, k=None, g=1):
        """Trace + compile one program for this input signature. Under FSDP
        the per-param buffers were released at adoption; re-traces need them
        back (the deferred-compute variables must bind to the SAME NDArray
        objects the forward reads), so builds are bracketed by
        materialize/release."""
        st = self._fsdp_state
        if st is None:
            prog = self._build_program(x, y, pad=pad, k=k, g=g)
            if prog is not None:
                self._release_eager_grads()
            return prog
        st.materialize_into_params()
        try:
            return self._build_program(x, y, pad=pad, k=k, g=g)
        finally:
            st.release_params()

    def _release_eager_grads(self):
        """Let go of the trainables' eager gradient buffers
        (``Parameter.release_grad``). ``initialize`` attaches a zero array
        of the parameter's size to every trainable for the eager tape; the
        compiled step keeps its gradients inside the program and never
        writes them, so they are dead device memory: 4 bytes a parameter
        (2.3 GiB for 626 M parameters, which was what kept a step that fits
        by ``memory_analysis`` from loading). Under FSDP there is nothing
        to release: ``release_params`` drops each parameter's array, and its
        gradient buffer with it."""
        for i in self._train_idx:
            self.trainer._params[i].release_grad()

    def _make_fsdp_groups(self, train_idx):
        """Expand the partition rules over the named trainables and fold
        them into the per-layer bucket schedule. Names come from the net's
        ``collect_params`` keys (the structured 'encoder.layers.0...' paths
        the rules are written against), falling back to ``Parameter.name``
        for trainer params outside the net."""
        from .parallel.partition import (fsdp_groups, fsdp_rules,
                                         match_partition_rules)

        tr = self.trainer
        name_of = {id(p): pname
                   for pname, p in self.net.collect_params().items()}
        names = [name_of.get(id(tr._params[i]), tr._params[i].name)
                 for i in train_idx]
        rules = self.partition_rules if self.partition_rules is not None \
            else fsdp_rules()
        specs = match_partition_rules(
            rules, {nm: tr._params[i].data()
                    for nm, i in zip(names, train_idx)}, with_meta=True)
        entries = [(k, nm, tuple(tr._params[i].data().shape),
                    str(tr._params[i].data().dtype))
                   for k, (nm, i) in enumerate(zip(names, train_idx))]
        tp_n = self._tp_size()
        groups = fsdp_groups(entries, specs, self._dp_size(), tp_size=tp_n)
        places = {}
        if tp_n > 1:
            from .parallel import tp as _tp

            for k, nm in enumerate(names):
                m = specs[nm]
                dim = _tp.tp_dim(m.spec)
                if dim is not None:
                    places[k] = (dim, int(m.meta.get("segments", 1)))
        self._tp_places = places
        return groups

    def _build_program(self, x, y, pad=0, k=None, g=1):
        import jax
        import jax.numpy as jnp
        import numpy as onp

        from . import _deferred_compute as dc
        from . import autograd as ag
        from .cached_op import build_executor
        from .ndarray.ndarray import NDArray

        tr = self.trainer
        opt = tr._optimizer
        multi = k is not None or g > 1
        if multi:
            # the forward traces on ONE microbatch; the scan supplies the
            # leading step (and accumulation) axes at run time
            if pad:
                raise MXNetError("multi-step programs take exact batches")
            idx = (0, 0) if g > 1 else (0,)
            x, y = NDArray(x._data[idx]), NDArray(y._data[idx])
        weighted = pad > 0
        with ag.train_mode():
            if any(p._data is None
                   for p in self.net.collect_params().values()):
                with ag.pause():  # settle deferred-shape init, no BN writes
                    self.net(x)
        train_idx, frozen, reason = self._collect()
        if reason is not None:
            self.fallback_reason = reason
            return None
        raw, state_keys, needs_t, _ = opt.fused_step
        fsdp = self.shard_params
        if self._shard_params_auto:
            # deferred auto decision, now that shapes are known; sticky —
            # every input signature's program shares one residency
            self._shard_params_auto = False
            if not fsdp:
                total = sum(tr._params[i].data()._data.nbytes
                            for i in train_idx)
                thresh_mb = float(os.environ.get(
                    "MXTPU_SHARD_PARAMS_AUTO_MB", "256"))
                fsdp = total >= thresh_mb * (1 << 20)
                self.shard_params = fsdp
        if fsdp:
            self.shard_update = False  # FSDP supersedes ZeRO-1
        sharded = self.shard_update
        # the flat-bucket ZeRO-1 schedule needs an elementwise recurrence
        # (it updates arbitrary chunk slices); other fused optimizers keep
        # the per-tensor psum update on a mesh
        bucketed = self.mesh is not None and opt.supports_sharded_update \
            and not fsdp
        for i in train_idx:
            if not sharded and not fsdp and tr._states[i] is None:
                tr._states[i] = opt.create_state_multi_precision(
                    i, tr._params[i].data())
            if tr._states[i] is not None and \
                    any(k not in tr._states[i] for k in state_keys):
                self.fallback_reason = (
                    f"optimizer state for {tr._params[i].name} lacks "
                    f"{state_keys} (restored from an older run?)")
                return None
        self._train_idx = train_idx
        self._frozen = frozen
        self._state_keys = state_keys

        # ONE program serves both shard_update settings: the ZeRO-1
        # schedule with state entering as dp-sharded buckets. The settings
        # differ only in state RESIDENCY between steps (persistent shards
        # vs per-param replicated arrays scattered/gathered around the
        # dispatch), so sharded and replicated trajectories are bitwise
        # identical by construction — the parity contract
        buckets = self._make_buckets(train_idx) if bucketed else None
        self._buckets = buckets
        self._state_bucket_bytes = sum(
            bs.padded * 4 for _, _, bs in buckets) * len(state_keys) \
            if bucketed else 0
        if sharded and self._shard_state is None:
            # the sharded state is per-net, not per-program: every input
            # shape's program reads the same buckets
            self._shard_state = _ShardedOptState(
                self.mesh, opt, tr, train_idx, buckets, state_keys)
            tr._shard_state = self._shard_state
        groups = None
        remat = None
        if fsdp:
            groups = self._fsdp_groups
            if groups is None:
                groups = self._make_fsdp_groups(train_idx)
                self._fsdp_groups = groups
            remat = os.environ.get("MXTPU_FSDP_REMAT",
                                   "dots").strip().lower()
            if remat not in ("dots", "full", "none"):
                raise MXNetError(
                    f"MXTPU_FSDP_REMAT={remat!r}: expected 'dots' (save "
                    "dot outputs), 'full' (save nothing) or 'none' (no "
                    "rematerialization)")
        tp_n = self._tp_size()
        if tp_n > 1 and not fsdp:
            raise MXNetError(
                "a mesh carrying a 'tp' axis of size >= 2 requires "
                "shard_params=True — the megatron layouts ride the FSDP "
                "bucket schedule")
        tp_places = self._tp_places if (fsdp and tp_n > 1) else {}

        # --- in-program numerics monitor setup (MXTPU_NUMERICS) ------------
        # 'off' leaves the program structurally untouched; cheap/full add a
        # health tuple (grad-norm, max-abs update, per-layer-group nonfinite
        # counts) as extra outputs riding the same dispatch. cheap folds its
        # grad stats into the overflow finiteness pass the off program pays
        # anyway; only full adds genuinely extra traversals (max|update|,
        # per-group norms).
        nmode = _telemetry.numerics_mode()
        if tp_n > 1:
            # per-group health attribution is not tp-aware (replicated
            # groups' tp-invariant stats would double-count under a
            # ('dp', 'tp') reduction): the in-program monitor stays off
            nmode = "off"
        monitor = nmode != "off"
        track_upd = nmode == "full"
        health_groups = None
        hg_of = None         # per-tensor path: train position -> group idx
        bucket_gids = None   # ZeRO-1 path: per-bucket flat group-id vectors
        if monitor:
            from .parallel.partition import layer_key
            if fsdp:
                # FSDP grads arrive as per-group bucket shards: the groups
                # ARE the (layer-keyed) health groups
                health_groups = tuple(layer for layer, _, _, _, _ in groups)
            else:
                name_of = {id(p): pname
                           for pname, p in self.net.collect_params().items()}
                labels, hg_of, idx_of = [], [], {}
                for i in train_idx:
                    nm = name_of.get(id(tr._params[i]), tr._params[i].name)
                    lk = layer_key(nm)
                    gi_ = idx_of.get(lk)
                    if gi_ is None:
                        gi_ = idx_of[lk] = len(labels)
                        labels.append(lk)
                    hg_of.append(gi_)
                health_groups = tuple(labels)
            n_hg = len(health_groups)
            if bucketed:
                # flat-bucket shards don't align with tensor boundaries: a
                # static group-id vector (pad rows -> sentinel n_hg) lets a
                # segment_sum recover exact per-group nonfinite counts
                import numpy as _onp

                bucket_gids = []
                for _dt, ks_, bs_ in (buckets or ()):
                    gv = _onp.full((bs_.padded,), n_hg, _onp.int32)
                    for k2, off, nsz in zip(ks_, bs_.offsets, bs_.sizes):
                        gv[off:off + nsz] = hg_of[k2]
                    bucket_gids.append(gv)

        # --- capture the forward+loss graph (the hybridize machinery) ------
        if weighted:
            # trace on PADDED shapes; the per-sample loss vector stays
            # un-meaned so the body can weight out the pad rows
            x_t = self._pad_rows(x, pad)
            y_t = self._pad_rows(y, pad)
        else:
            x_t, y_t = x, y
        import contextlib

        tp_ctx = None
        tp_swap = []
        tp_scope = contextlib.nullcontext()
        if tp_places:
            from .parallel import tp as _tp

            tp_ctx = _tp.TPContext(tp_n, mode="train")
            tp_scope = _tp.activate(tp_ctx)
            # trace with each megatron parameter's rank-0 LOCAL slice
            # bound to its variable — the traced shapes are the per-rank
            # shapes the shard_map replay feeds (trace values throwaway);
            # the active context makes the model blocks emit the matching
            # in-graph tp collectives
            for kk, (dim, seg) in tp_places.items():
                p = tr._params[train_idx[kk]]
                tp_swap.append((p, p._data))
                p._data = NDArray(jnp.asarray(_tp.local_slice(
                    p._data.asnumpy(), dim, 0, tp_n, seg)))
        try:
            with tp_scope, ag.train_mode(), dc.context() as tctx:
                dvars = [dc.set_variable(x_t, "data0"),
                         dc.set_variable(y_t, "label0")]
                wvars = [dc.set_variable(tr._params[i].data(), f"w{i}")
                         for i in train_idx]
                fvars = [dc.set_variable(p.data(), pname)
                         for pname, p in frozen]
                loss = self.loss_fn(self.net(x_t), y_t)
                if weighted:
                    if loss.ndim == 0 or loss.shape[0] != x_t.shape[0]:
                        raise MXNetError(
                            "partial-batch padding needs a per-sample loss "
                            f"(got shape {tuple(loss.shape)}); pass batches "
                            "divisible by the dp axis or strict_batch=True")
                else:
                    loss = loss.mean()
                if loss._dc_sym is None:
                    self.fallback_reason = \
                        "loss is not connected to the traced forward"
                    return None
                entries = [loss._dc_sym] + [e for _, e in tctx.aux_updates]
                aux_targets = [t for t, _ in tctx.aux_updates]
                fwd, uses_rng = build_executor(entries,
                                               dvars + wvars + fvars)
        finally:
            # restore the FULL per-param values: adoption (first build)
            # slices per-rank images out of them right after
            for p, full in tp_swap:
                p._data = full

        n_train = len(train_idx)
        n_aux = len(aux_targets)
        n_state = len(state_keys)
        scaler_on = self.loss_scaler is not None
        mesh = self.mesh
        n_dp = self._dp_size()
        site = f"train_step:{self.name}"
        attrs = (f"n_params={n_train} n_aux={n_aux} "
                 f"opt={type(opt).__name__} scaler={scaler_on} "
                 f"mesh={mesh is not None} sharded={sharded} pad={pad}")

        # the scopes reach each operation's op_name in the HLO: the step's
        # phases in a device trace
        @jax.named_scope("grad")
        def grad_part(ws, fs, xb, yb, wv, key, loss_scale):
            # forward + loss + backward for ONE microbatch: returns the
            # (reduced) loss, the all_reduce'd aux updates and the LOCAL
            # gradients — the update half applies the dp reduction.
            # Executes at TRACE time only: the python loop unrolls into
            # one program.
            if mesh is not None and uses_rng:
                from .parallel import collectives as coll

                # per-shard dropout masks: fold the shard index into the key
                key = jax.random.fold_in(key, coll.axis_index("dp"))

            if fsdp:
                from .parallel import collectives as coll

                def expand(w_tuple):
                    # JIT weight materialization: all_gather each layer's
                    # flat shard right where the forward needs it; the
                    # transpose of these gathers IS the gradient
                    # psum_scatter, so grads come back pre-reduced in the
                    # owning shard's layout
                    full = [None] * n_train
                    for (_, _, ks, bs, sh), buf in zip(groups, w_tuple):
                        flat = coll.all_gather(buf, "dp", axis=0,
                                               tiled=True) if sh else buf
                        for k, arr in zip(ks, bs.unflatten(flat)):
                            full[k] = arr
                    return full

                def wrap(lfn):
                    # rematerialize the forward in the backward so full
                    # weights are re-gathered, not kept live; 'dots' saves
                    # matmul outputs (activations), the classic FSDP policy
                    if remat == "none":
                        return lfn
                    if remat == "full":
                        return jax.checkpoint(lfn)
                    return jax.checkpoint(
                        lfn, policy=jax.checkpoint_policies.dots_saveable)
            else:
                def expand(w_tuple):
                    return list(w_tuple)

                def wrap(lfn):
                    return lfn

            if weighted:
                from .parallel import collectives as coll

                # weighted mean over the REAL rows: pad rows carry weight 0,
                # so loss and gradients match the unpadded batch exactly
                wsum = jnp.sum(wv)
                if mesh is not None:
                    wsum = coll.all_reduce(wsum, "dp", op="sum")

                def lfn(w_tuple):
                    args = ([key] if uses_rng else []) + [xb, yb] + \
                        expand(w_tuple) + list(fs)
                    outs = fwd(*args)
                    return (jnp.sum(outs[0] * wv),) + tuple(outs[1:])

                # cotangent pre-divided by the true example count: local
                # grads then SUM-reduce to the full gradient
                outs, (grads,) = ag.program_vjp(wrap(lfn), (tuple(ws),),
                                                loss_scale / wsum)
                loss_v = outs[0] / wsum
                aux = list(outs[1:])
                if mesh is not None:
                    loss_v = coll.all_reduce(loss_v, "dp", op="sum")
            else:
                def lfn(w_tuple):
                    args = ([key] if uses_rng else []) + [xb, yb] + \
                        expand(w_tuple) + list(fs)
                    return fwd(*args)

                # backward INSIDE the trace, seeded with the loss scale so a
                # DynamicLossScaler update never retraces (program_vjp)
                outs, (grads,) = ag.program_vjp(wrap(lfn), (tuple(ws),),
                                                loss_scale)
                loss_v, aux = outs[0], list(outs[1:])
                if mesh is not None:
                    from .parallel import collectives as coll

                    loss_v = coll.all_reduce(loss_v, "dp", op="mean")
            if mesh is not None:
                from .parallel import collectives as coll

                aux = [coll.all_reduce(a, "dp", op="mean") for a in aux]
            return loss_v, tuple(aux), grads

        def _grad_pass(g):
            # (sum g^2, nonfinite count) in ONE variadic-reduce traversal.
            # This REPLACES the overflow path's all(isfinite) walk when the
            # monitor is on (finite == count 0), so the grad-side stats
            # cost no extra pass over off mode; separate jnp reductions
            # each re-walk the tensor — XLA:CPU scan bodies don't fuse
            # sibling reduces (measured ~5x the fused pass at K=16)
            return jax.lax.reduce(
                (jnp.square(g.astype(jnp.float32)),
                 (~jnp.isfinite(g)).astype(jnp.int32)),
                (jnp.float32(0), jnp.int32(0)),
                lambda a, b: (a[0] + b[0], a[1] + b[1]),
                tuple(range(g.ndim)))

        def _upd_pass(nw, w):
            # max |update|: a genuinely extra traversal of the new/old
            # weights, so it runs in full mode only (cheap reports 0)
            return jnp.max(jnp.abs((nw - w).astype(jnp.float32)))

        def _per_tensor_update(ws, ss, grads, lrs, wds, ts, rescale):
            # single-device + non-elementwise-mesh path: the original
            # per-tensor unroll
            # overflow = non-finite SCALED grads, the quantity the eager
            # LossScaler.has_overflow inspects (before unscale). With the
            # monitor on, the finite verdict comes from the fused stats
            # pass (finite == zero nonfinite count) instead of a second
            # all(isfinite) walk.
            finite = jnp.bool_(True)
            tstats = []
            for g in grads:
                if monitor:
                    sq, cnt = _grad_pass(g)
                    tstats.append((sq, cnt))
                    finite = jnp.logical_and(finite, cnt == 0)
                else:
                    finite = jnp.logical_and(finite,
                                             jnp.all(jnp.isfinite(g)))
            overflow = jnp.logical_not(finite)
            new_ws, new_ss = [], []
            for k in range(n_train):
                g = grads[k] * rescale
                args = [ws[k], *ss[k], g, lrs[k], wds[k]]
                if needs_t:
                    args.append(ts[k])
                out = raw(*args)
                if n_state:
                    nw, ns = out[0], tuple(out[1:])
                else:
                    nw, ns = out, ()
                if scaler_on:
                    # skip-on-overflow as a select: the step ran, the
                    # weights didn't move (eager: trainer.step is skipped)
                    nw = jnp.where(overflow, ws[k], nw)
                    ns = tuple(jnp.where(overflow, s0, s1)
                               for s0, s1 in zip(ss[k], ns))
                new_ws.append(nw)
                new_ss.append(ns)
            health = None
            if monitor:
                # grads here are already dp-reduced (replicated): plain
                # per-tensor reductions, no collectives
                gsq = jnp.float32(0)
                mx = jnp.float32(0)
                nf = [jnp.zeros((), jnp.int32) for _ in range(n_hg)]
                gnsq = [jnp.float32(0) for _ in range(n_hg)] \
                    if nmode == "full" else None
                for k in range(n_train):
                    # sum((g*r)^2) == r^2 * sum(g^2): the rescale factor
                    # folds in as a scalar after the reduction
                    sq, cnt = tstats[k]
                    gsq = gsq + sq
                    if track_upd:
                        mx = jnp.maximum(mx, _upd_pass(new_ws[k], ws[k]))
                    nf[hg_of[k]] = nf[hg_of[k]] + cnt
                    if gnsq is not None:
                        gnsq[hg_of[k]] = gnsq[hg_of[k]] + sq
                r2 = (rescale * rescale).astype(jnp.float32)
                health = (gsq * r2, mx, jnp.stack(nf)) + \
                    ((jnp.stack(gnsq) * r2,) if gnsq is not None else ())
            return new_ws, new_ss, overflow, health

        def _bucket_update(ws, ss, grads, lrs, wds, ts, rescale, grad_op):
            """The ZeRO-1 update on flat per-dtype buckets: reduce_scatter
            the flat gradient, run the recurrence only on this replica's
            contiguous 1/N shard (state enters and leaves as dp-sharded
            buckets), all_gather the updated weights — the classic
            two-phase expansion of an all-reduce, so it pays the bandwidth
            a psum would. This is the ONLY elementwise mesh update: both
            ``shard_update`` settings dispatch the same program (and hence
            the same bits); they differ in state residency handled by the
            host in ``_run``. Earlier variants compiled a structurally
            different replicated program — XLA's global layout/fusion
            passes then make input-dependent 1-ulp rounding differences
            appear in the gradients, and no amount of per-op pinning
            (optimization_barrier, reduce_precision) stops it."""
            from .parallel import collectives as coll

            # reduce each bucket; every replica owns one contiguous slice
            # of the fully-reduced gradient
            gred, finite = [], jnp.bool_(True)
            bstats = []
            for _, ks, bs in buckets:
                flat_g = bs.flatten([grads[k] for k in ks])
                g = coll.reduce_scatter(flat_g, "dp")
                if grad_op == "mean":
                    g = g / n_dp  # pmean == psum / N, elementwise
                gred.append(g)
                if monitor:
                    # finite verdict folded into the fused stats pass
                    # (finite == zero nonfinite count): the monitor's
                    # grad-side reductions replace the all(isfinite) walk
                    # the off program pays anyway, instead of adding one
                    sq, cnt = _grad_pass(g)
                    bstats.append((sq, cnt))
                    finite = jnp.logical_and(finite, cnt == 0)
                else:
                    finite = jnp.logical_and(finite,
                                             jnp.all(jnp.isfinite(g)))
            # each replica saw only its shards: AND the verdicts so the
            # where-select (run on shards) agrees everywhere
            finite = coll.all_reduce(finite.astype(jnp.int32), "dp",
                                     op="min") > 0
            overflow = jnp.logical_not(finite)
            # health accumulators run on the same disjoint shards the
            # update touches (pad rows are zero): shard-local reductions +
            # one tiny all_reduce at the end are exact. Per-group counts
            # come from a segment_sum over the static group-id vector
            # (sentinel n_hg absorbs the pad tail).
            gsq = jnp.float32(0)
            mx = jnp.float32(0)
            nf = jnp.zeros((n_hg + 1,), jnp.int32) if monitor else None
            gnsq = jnp.zeros((n_hg + 1,), jnp.float32) \
                if monitor and nmode == "full" else None
            new_ws = [None] * n_train
            new_ss = []
            for bi, ((_, ks, bs), g) in enumerate(zip(buckets, gred)):
                ksel = jnp.asarray(ks)
                w_in = bs.flatten([ws[k] for k in ks])
                lr_v = bs.spread(lrs[ksel])
                wd_v = bs.spread(wds[ksel])
                # pad tail gets t=1 so bias-correction terms stay finite
                # (the pad region is all-zero and discarded)
                t_v = bs.spread(ts[ksel], pad_value=1.0) if needs_t else None
                sl = lambda v: bs.shard_slice(v, "dp")  # noqa: E731
                w_sh = sl(w_in)
                nw, ns = _apply_chunk(w_sh, ss[bi], g, sl(lr_v),
                                      sl(wd_v),
                                      sl(t_v) if needs_t else None,
                                      rescale, overflow)
                if monitor:
                    bsq, bad = bstats[bi]
                    gsq = gsq + bsq
                    if track_upd:
                        mx = jnp.maximum(mx, _upd_pass(nw, w_sh))
                    gid_vec = jnp.asarray(bucket_gids[bi])
                    # per-group attribution is a scatter-add — ruinously
                    # slow inside an XLA:CPU scan — so it runs only when
                    # this bucket actually saw a nonfinite value (the
                    # group-id shard slice materializes inside the branch
                    # too); healthy steps pay the predicate + a zeros fill
                    nf = nf + jax.lax.cond(
                        bad > 0,
                        lambda g=g, gv=gid_vec, sl=sl: jax.ops.segment_sum(
                            (~jnp.isfinite(g)).astype(jnp.int32), sl(gv),
                            num_segments=n_hg + 1),
                        lambda: jnp.zeros((n_hg + 1,), jnp.int32))
                    if gnsq is not None:
                        gnsq = gnsq + jax.ops.segment_sum(
                            jnp.square(g.astype(jnp.float32)), sl(gid_vec),
                            num_segments=n_hg + 1)
                flat_nw = coll.all_gather(nw, "dp", axis=0, tiled=True)
                new_ss.append(ns)
                for k, arr in zip(ks, bs.unflatten(flat_nw)):
                    new_ws[k] = arr
            health = None
            if monitor:
                # SHARD-LOCAL accumulators only: the cross-replica
                # reduction is deferred to finalize_health so a K-step
                # scan pays it once per dispatch, not once per inner step
                # (grad sums pick up the rescale factor as a scalar:
                # sum((g*r)^2) == r^2 * sum(g^2))
                r2 = (rescale * rescale).astype(jnp.float32)
                health = (gsq * r2, mx, nf[:n_hg])
                if gnsq is not None:
                    health += (gnsq[:n_hg] * r2,)
            return new_ws, tuple(new_ss), overflow, health

        def _apply_chunk(w_c, st_c, g_c, lr_c, wd_c, t_c, rescale, overflow):
            """Run the recurrence on one flat chunk (a ZeRO-1 bucket shard
            or an FSDP group shard) with per-element hypers, applying the
            skip-on-overflow select — the one code path every flat-bucket
            schedule updates through."""
            args = [w_c, *st_c, g_c * rescale, lr_c, wd_c]
            if needs_t:
                args.append(t_c)
            out = raw(*args)
            if n_state:
                nw, ns = out[0], tuple(out[1:])
            else:
                nw, ns = out, ()
            if scaler_on:
                nw = jnp.where(overflow, w_c, nw)
                ns = tuple(jnp.where(overflow, s0, s1)
                           for s0, s1 in zip(st_c, ns))
            return nw, ns

        def _fsdp_update(ws, ss, grads, lrs, wds, ts, rescale, grad_op):
            """The FSDP update: ``ws``/``ss`` are the resident per-group
            bucket shards and ``grads`` arrived PRE-SCATTERED for sharded
            groups (the vjp transpose of the forward's tiled all_gather is
            psum_scatter) — sum-reduced, so mean semantics divide by the dp
            extent. Replicated pools all_reduce their local grads instead.
            The recurrence runs on each group's shard and the outputs STAY
            sharded: no trailing weight all-gather — the next step's
            forward gathers just-in-time again."""
            from .parallel import collectives as coll

            gred, finite = [], jnp.bool_(True)
            gstats = []
            for (_, _, ks, bs, sh), g in zip(groups, grads):
                if sh:
                    if grad_op == "mean":
                        g = g / n_dp  # pmean == psum / N, elementwise
                else:
                    g = coll.all_reduce(g, "dp", op=grad_op)
                gred.append(g)
                if monitor:
                    # fused stats pass doubles as the finite verdict
                    # (finite == zero nonfinite count), replacing the
                    # all(isfinite) walk the off program pays anyway
                    sq, cnt = _grad_pass(g)
                    gstats.append((sq, cnt))
                    finite = jnp.logical_and(finite, cnt == 0)
                else:
                    finite = jnp.logical_and(finite,
                                             jnp.all(jnp.isfinite(g)))
            # each replica inspected only its shards: AND the verdicts so
            # the where-select agrees everywhere — over BOTH axes under
            # dp x tp (tp ranks inspect disjoint megatron shards)
            verdict_axes = ("dp", "tp") if tp_n > 1 else "dp"
            finite = coll.all_reduce(finite.astype(jnp.int32), verdict_axes,
                                     op="min") > 0
            overflow = jnp.logical_not(finite)
            # health: sharded groups reduce over disjoint shards (psum'd at
            # the end); replicated pools see identical full grads on every
            # replica (no reduction — psumming them would count N times)
            gsq_sh = jnp.float32(0)
            gsq_rep = jnp.float32(0)
            mx = jnp.float32(0)
            nf_sh = [jnp.zeros((), jnp.int32) for _ in range(n_hg)] \
                if monitor else None
            nf_rep = [jnp.zeros((), jnp.int32) for _ in range(n_hg)] \
                if monitor else None
            gnsq_sh = [jnp.float32(0) for _ in range(n_hg)] \
                if monitor and nmode == "full" else None
            gnsq_rep = [jnp.float32(0) for _ in range(n_hg)] \
                if monitor and nmode == "full" else None
            new_ws, new_ss = [], []
            for gi, ((_, _, ks, bs, sh), g) in enumerate(zip(groups, gred)):
                ksel = jnp.asarray(ks)
                lr_v = bs.spread(lrs[ksel])
                wd_v = bs.spread(wds[ksel])
                t_v = bs.spread(ts[ksel], pad_value=1.0) if needs_t else None
                if sh:
                    sl = lambda v: bs.shard_slice(v, "dp")  # noqa: E731
                    lr_v, wd_v = sl(lr_v), sl(wd_v)
                    t_v = sl(t_v) if needs_t else None
                nw, ns = _apply_chunk(ws[gi], ss[gi], g, lr_v, wd_v, t_v,
                                      rescale, overflow)
                if monitor:
                    sq, cnt = gstats[gi]
                    if track_upd:
                        mx = jnp.maximum(mx, _upd_pass(nw, ws[gi]))
                    if sh:
                        gsq_sh = gsq_sh + sq
                        nf_sh[gi] = nf_sh[gi] + cnt
                        if gnsq_sh is not None:
                            gnsq_sh[gi] = gnsq_sh[gi] + sq
                    else:
                        gsq_rep = gsq_rep + sq
                        nf_rep[gi] = nf_rep[gi] + cnt
                        if gnsq_rep is not None:
                            gnsq_rep[gi] = gnsq_rep[gi] + sq
                new_ws.append(nw)
                new_ss.append(ns)
            health = None
            if monitor:
                # shard-local (sharded + replicated halves kept apart):
                # finalize_health psums the sharded half once per dispatch
                # — collectives inside the scan body serialize XLA:CPU's
                # rendezvous thunks every inner step
                r2 = (rescale * rescale).astype(jnp.float32)
                health = (gsq_sh * r2, gsq_rep * r2, mx,
                          jnp.stack(nf_sh), jnp.stack(nf_rep))
                if gnsq_sh is not None:
                    health += (jnp.stack(gnsq_sh) * r2,
                               jnp.stack(gnsq_rep) * r2)
            return new_ws, tuple(new_ss), overflow, health

        # the dp reduction op is build-static: weighted (padded) batches
        # must SUM their pre-divided local grads, whole batches pmean
        grad_op = "sum" if weighted else "mean"

        @jax.named_scope("optimizer")
        def update_part(ws, ss, grads, lrs, wds, ts, rescale):
            # dp-reduce the gradients and run the optimizer recurrence —
            # the second half of the step body, shared by the single-step
            # and scanned paths
            if fsdp:
                return _fsdp_update(ws, ss, grads, lrs, wds, ts, rescale,
                                    grad_op)
            if bucketed:
                return _bucket_update(ws, ss, grads, lrs, wds, ts, rescale,
                                      grad_op)
            if mesh is not None:
                from .parallel import collectives as coll

                # non-elementwise recurrence: reduce per tensor, then run
                # the full-tensor update replicated on every device
                grads = tuple(coll.all_reduce(g, "dp", op=grad_op)
                              for g in grads)
            return _per_tensor_update(ws, ss, grads, lrs, wds, ts, rescale)

        def finalize_health(h):
            # cross-replica reduction of the shard-local health
            # accumulators, normalized to (gsq, mx, nf[, gnsq]). Applied
            # ONCE per dispatch — on the [K]-stacked values after the scan
            # for the multi-step program — because collectives inside the
            # scan body run XLA:CPU's rendezvous thunks every inner step
            # (measured 3x step cost at K=16). Elementwise collectives, so
            # a leading K axis passes straight through.
            if h is None or mesh is None:
                return h
            from .parallel import collectives as coll

            if fsdp:
                gsq_sh, gsq_rep, mx, nf_sh, nf_rep = h[:5]
                out = (coll.all_reduce(gsq_sh, "dp", op="sum") + gsq_rep,
                       coll.all_reduce(mx, "dp", op="max"),
                       coll.all_reduce(nf_sh, "dp", op="sum") + nf_rep)
                if len(h) > 5:
                    out += (coll.all_reduce(h[5], "dp", op="sum") + h[6],)
                return out
            if bucketed:
                out = (coll.all_reduce(h[0], "dp", op="sum"),
                       coll.all_reduce(h[1], "dp", op="max"),
                       coll.all_reduce(h[2], "dp", op="sum"))
                if len(h) > 3:
                    out += (coll.all_reduce(h[3], "dp", op="sum"),)
                return out
            return h  # per-tensor health is computed on psum'd grads

        def body(ws, ss, fs, xb, yb, wv, key, lrs, wds, ts, rescale,
                 loss_scale):
            loss_v, aux, grads = grad_part(ws, fs, xb, yb, wv, key,
                                           loss_scale)
            new_ws, new_ss, overflow, health = update_part(
                ws, ss, grads, lrs, wds, ts, rescale)
            if health is None:
                return loss_v, aux, new_ws, new_ss, overflow
            return loss_v, aux, new_ws, new_ss, overflow, \
                finalize_health(health)

        # shard_map specs shared by the single-step and scanned wrappers
        if mesh is not None:
            from .parallel.mesh import P, shard_map_compat

            dp = P("dp")
            if fsdp:
                # per-leaf spec pytrees: sharded groups enter/leave as
                # their 1/N shards (tp groups as 1/(tp*N) of the global
                # tp-major bucket), replicated pools as full copies
                tp_dp = P(("tp", "dp"))

                def g_spec(sh):
                    if sh == "tp":
                        return tp_dp
                    return dp if sh else P()

                ws_spec = [g_spec(sh) for _, _, _, _, sh in groups]
                ss_spec = tuple(g_spec(sh) for _, _, _, _, sh in groups)
                out_ws = list(ws_spec)
                out_state = ss_spec
            else:
                ws_spec = P()
                ss_spec = dp if bucketed else P()
                out_ws = P()
                out_state = dp if bucketed else P()

        if multi:
            # --- scanned super-step: K optimizer steps (each accumulating
            # G microbatches) as ONE lax.scan over the step body ----------
            from .parallel.collectives import match_carry_vma

            # aux (BN moving stats) must flow BETWEEN inner steps: map each
            # aux target to its frozen-input position so the scan carries
            # those fs entries (the single-step trace reads fs once)
            fs_pos = {id(p.data()): j for j, (_, p) in enumerate(frozen)}
            aux_pos = []
            for t in aux_targets:
                j = fs_pos.get(id(t))
                if j is None:
                    self.fallback_reason = (
                        "multi-step scan: an aux-update target is not a "
                        "frozen parameter input")
                    return None
                aux_pos.append(j)

            def sub_fs(fs, aux_vals):
                fs = list(fs)
                for j, a in zip(aux_pos, aux_vals):
                    fs[j] = a
                return fs

            def one_step(ws, ss, fs, xb, yb, kb, lrs, wds, ts, rescale,
                         loss_scale):
                # one optimizer step = G accumulated microbatches. Grad
                # shapes differ from ws under FSDP (pre-scattered), so the
                # accumulator is seeded by microbatch 0 and an inner scan
                # sums the remaining G-1, threading BN aux sequentially
                if g == 1:
                    loss_v, aux, grads = grad_part(ws, fs, xb, yb, None,
                                                   kb, loss_scale)
                else:
                    loss_v, aux, grads = grad_part(ws, fs, xb[0], yb[0],
                                                   None, kb[0], loss_scale)

                    def acc(c, sl):
                        l_a, g_a, aux_c = c
                        xj, yj, kj = sl
                        l_j, aux_j, g_j = grad_part(
                            ws, sub_fs(fs, aux_c), xj, yj, None, kj,
                            loss_scale)
                        return (l_a + l_j,
                                tuple(a + b for a, b in zip(g_a, g_j)),
                                aux_j), None

                    carry = (loss_v, tuple(grads), aux)
                    if mesh is not None:
                        carry = match_carry_vma(
                            acc, carry, (xb[1], yb[1], kb[1]),
                            fallback_axis="dp")
                    (loss_v, grads, aux), _ = jax.lax.scan(
                        acc, carry, (xb[1:], yb[1:], kb[1:]))
                    # mean over the G microbatches: sum-then-divide equals
                    # the mean over the G*B super-batch
                    loss_v = loss_v / g
                    grads = tuple(gr / g for gr in grads)
                new_ws, new_ss, overflow, health = update_part(
                    ws, ss, grads, lrs, wds, ts, rescale)
                return loss_v, aux, new_ws, new_ss, overflow, health

            def super_fn(ws, ss, fs, xs, ys, keys, lrs_t, wds_t, ts_t,
                         rescale, loss_scale):
                # carry structures must match the body's OUTPUT structures
                # (lists for ws, residency-dependent for ss)
                ws = list(ws)
                ss = tuple(ss) if (fsdp or bucketed) else \
                    [tuple(s) for s in ss]
                aux0 = tuple(fs[j] for j in aux_pos)

                def step(carry, sl):
                    ws_c, ss_c, aux_c, c = carry
                    xj, yj, kj = sl
                    # per-inner-step hypers indexed by the COMMITTED count
                    # c, not the loop index: an overflow-skipped step must
                    # leave the schedule untouched, exactly the eager skip
                    loss_v, aux, new_ws, new_ss, ovf, health = one_step(
                        ws_c, ss_c, sub_fs(fs, aux_c), xj, yj, kj,
                        lrs_t[c], wds_t[c], ts_t[c], rescale, loss_scale)
                    if scaler_on:
                        c = c + 1 - ovf.astype(jnp.int32)
                    else:
                        c = c + 1
                    # health (when on) stacks to [K, ...] in the scan ys:
                    # per-inner-step provenance rides the same readback
                    ys_j = (loss_v, ovf) if health is None \
                        else (loss_v, ovf, health)
                    return (new_ws, new_ss, aux, c), ys_j

                carry = (ws, ss, aux0, jnp.zeros((), jnp.int32))
                proto = (xs[0], ys[0], keys[0])
                if mesh is not None:
                    carry = match_carry_vma(step, carry, proto,
                                            fallback_axis="dp")
                (ws, ss, aux, _), ys_out = jax.lax.scan(
                    step, carry, (xs, ys, keys))
                if monitor:
                    losses, ovfs, healths = ys_out
                    # ONE set of health collectives over the [K]-stacked
                    # shard-local rows for the whole super-step
                    return losses, aux, ws, ss, ovfs, \
                        finalize_health(healths)
                losses, ovfs = ys_out
                return losses, aux, ws, ss, ovfs

            if mesh is not None:
                x_sp = P(None, None, "dp") if g > 1 else P(None, "dp")
                inner_multi = shard_map_compat(
                    super_fn, mesh,
                    in_specs=(ws_spec, ss_spec, P(), x_sp, x_sp,
                              P(), P(), P(), P(), P(), P()),
                    # the health subtree (when on) is replicated: P() prefix
                    out_specs=(P(), P(), out_ws, out_state, P()) +
                              ((P(),) if monitor else ()))
            else:
                inner_multi = super_fn
            m_attrs = attrs + f" k={k} g={g}"

            def multi_fn(ws, ss, fs, xs, ys, keys, lrs_t, wds_t, ts_t,
                         rescale, loss_scale):
                # executes at TRACE time only — the observers count
                # recompiles, not calls (the scan body may be re-traced
                # abstractly by match_carry_vma; only this top-level
                # wrapper marks the compile site)
                self._traces += 1
                _telemetry.record_compile(site, (ws, xs), attrs=m_attrs)
                return inner_multi(ws, ss, fs, xs, ys, keys, lrs_t, wds_t,
                                   ts_t, rescale, loss_scale)

            fn = multi_fn
        elif mesh is not None:
            inner = shard_map_compat(
                body, mesh,
                in_specs=(ws_spec, ss_spec, P(), dp, dp,
                          dp if weighted else P(),
                          P(), P(), P(), P(), P(), P()),
                # the health subtree (when on) is replicated: P() prefix
                out_specs=(P(), P(), out_ws, out_state, P()) +
                          ((P(),) if monitor else ()))
            if weighted:
                b = int(x.shape[0])

                def padded(ws, ss, fs, xb, yb, key, lrs, wds, ts, rescale,
                           loss_scale):
                    # executes at TRACE time only — the observers count
                    # recompiles, not calls
                    self._traces += 1
                    _telemetry.record_compile(site, (ws, xb), attrs=attrs)
                    # pad IN-PROGRAM: the host hands the ragged batch as-is
                    xb = jnp.pad(xb, ((0, pad),) + ((0, 0),) * (xb.ndim - 1))
                    yb = jnp.pad(yb, ((0, pad),) + ((0, 0),) * (yb.ndim - 1))
                    wv = (jnp.arange(b + pad) < b).astype(jnp.float32)
                    return inner(ws, ss, fs, xb, yb, wv, key, lrs, wds, ts,
                                 rescale, loss_scale)

                fn = padded
            else:
                def unweighted(ws, ss, fs, xb, yb, key, lrs, wds, ts,
                               rescale, loss_scale):
                    self._traces += 1
                    _telemetry.record_compile(site, (ws, xb), attrs=attrs)
                    wv = jnp.zeros((n_dp,), jnp.float32)  # unused
                    return inner(ws, ss, fs, xb, yb, wv, key, lrs, wds, ts,
                                 rescale, loss_scale)

                fn = unweighted
        else:
            def no_mesh(ws, ss, fs, xb, yb, key, lrs, wds, ts, rescale,
                        loss_scale):
                self._traces += 1
                _telemetry.record_compile(site, (ws, xb), attrs=attrs)
                return body(ws, ss, fs, xb, yb, None, key, lrs, wds, ts,
                            rescale, loss_scale)

            fn = no_mesh
        coll_bytes = self._collective_bytes(train_idx, aux_targets, buckets,
                                            bucketed, weighted, scaler_on,
                                            groups=groups, remat=remat)
        tp_bytes = 0
        if tp_ctx is not None:
            # accounted by the op fallbacks while the trace replayed the
            # model eagerly on rank-0 local values
            tp_bytes = int(tp_ctx.psum_bytes + tp_ctx.gather_bytes)
        if multi:
            # per-dispatch payload scales with the k*g microbatches scanned
            coll_bytes = tuple(b * (k * g) for b in coll_bytes)
            tp_bytes *= k * g
        if fsdp and self._fsdp_state is None:
            # adoption AFTER the trace (it releases the per-param buffers
            # the trace just bound); like the ZeRO-1 state, the residency
            # is per-net — every input signature's program shares it
            self._fsdp_state = _FSDPState(self.mesh, opt, tr, train_idx,
                                          groups, state_keys,
                                          tp_places=tp_places,
                                          tp_size=tp_n)
            tr._shard_state = self._fsdp_state
            gathers = 1 if remat == "none" else 2  # backward re-gather
            self._fsdp_layer_bytes = tuple(
                (layer,
                 bs.padded * onp.dtype(dt).itemsize * gathers if sh else 0,
                 bs.padded * onp.dtype(dt).itemsize if sh else 0)
                for layer, dt, _, bs, sh in groups)
        # a stable module name for the profiler's trace: jit_mxtpu_train_step
        fn.__name__ = f"mxtpu_train_step_k{k}" if multi else "mxtpu_train_step"
        return _Program(jax.jit(fn, donate_argnums=train_donate_argnums()),
                        uses_rng,
                        aux_targets, sharded=bucketed, fsdp=fsdp,
                        coll_bytes=coll_bytes, coll_bytes_tp=tp_bytes,
                        k=k if multi else None, accum=g,
                        health_mode=nmode,
                        health_groups=health_groups)

    @staticmethod
    def _pad_rows(arr, pad):
        """Host-side zero row padding (trace shapes only — runtime padding
        happens in-program)."""
        import jax.numpy as jnp

        from .ndarray.ndarray import NDArray

        return NDArray(jnp.pad(
            arr._data, ((0, pad),) + ((0, 0),) * (arr._data.ndim - 1)))

    def _collective_bytes(self, train_idx, aux_targets, buckets, bucketed,
                          weighted, scaler_on, groups=None, remat=None):
        """Statically-known per-step IN-PROGRAM collective payload (per
        replica): the dispatch site reports these since the host cannot
        observe in-program collectives. Replicated state residency adds
        its host-side scatter/gather resharding on top (in ``_run``).
        FSDP numbers are schedule-level (what the trace emits; XLA may CSE
        backward re-gathers)."""
        if self.mesh is None:
            return (0, 0, 0)
        import numpy as onp

        def nbytes(shape, dtype):
            n = 1
            for d in shape:
                n *= int(d)
            return n * onp.dtype(str(dtype)).itemsize

        aux_b = sum(nbytes(t.shape, t.dtype) for t in aux_targets)
        psum = 4 + aux_b  # loss scalar + BN stat means
        if weighted:
            psum += 4  # example-weight sum
        if groups is not None:  # FSDP
            rs = ag = 0
            gathers = 1 if remat == "none" else 2  # backward re-gather
            for _, dt, _, bs, sh in groups:
                b = bs.padded * onp.dtype(dt).itemsize
                if sh:
                    ag += b * gathers  # JIT weight gather(s)
                    rs += b            # grad psum_scatter (vjp transpose)
                else:
                    psum += b          # replicated-pool grad all_reduce
            psum += 4  # the AND-reduced finiteness verdict
            return (rs, ag, psum)
        if not bucketed:
            # non-elementwise fused optimizer: per-tensor grad psum
            grad_b = sum(nbytes(self.trainer._params[i].data().shape,
                                self.trainer._params[i].data().dtype)
                         for i in train_idx)
            return (0, 0, psum + grad_b)
        rs = ag = 0
        for dt, _, bs in buckets:
            b = bs.padded * onp.dtype(dt).itemsize
            rs += b
            ag += b
        psum += 4  # the AND-reduced finiteness verdict
        return (rs, ag, psum)

    def _scatter_replicated_state(self):
        """Flatten per-param optimizer state into dp-sharded bucket arrays
        (replicated residency, ``shard_update=False``). The program only
        ever sees sharded state; between steps the per-param arrays in
        ``trainer._states`` remain the source of truth, so inspection and
        checkpoints keep the classic layout at the cost of one state
        reshard each way per step.

        The buckets built here are DONATED (argnum 1), so they must never
        alias the live state arrays: ``flatten`` of a single-tensor bucket
        is a reshape (an alias), and once the states have been rebound to
        slices of last dispatch's sharded output, ``device_put`` at the
        already-matching sharding is a no-op on that alias — donating the
        result would free the buffer the live states still point at (the
        corruption only surfaces once the allocator reuses the memory).
        ``jnp.array(..., copy=True)`` pins a fresh buffer in the chain."""
        import jax
        import jax.numpy as jnp

        from .parallel.mesh import shard_1d

        tr = self.trainer
        idxs = self._train_idx
        sharding = shard_1d(self.mesh)
        return tuple(
            tuple(jax.device_put(
                jnp.array(bs.flatten(
                    [tr._states[idxs[k]][key]._data for k in ks]),
                    copy=True),
                sharding) for key in self._state_keys)
            for _, ks, bs in self._buckets)

    # -- the compiled step --------------------------------------------------
    def _assemble_inputs(self, prog):
        """Gather the donated weight/state operands for one dispatch,
        per the program's residency mode."""
        tr = self.trainer
        idxs = self._train_idx
        keys = self._state_keys
        if prog.fsdp:
            # FSDP: weights AND state are the resident bucket shards; no
            # full-sized value is ever assembled on the host
            ws = list(self._fsdp_state.params)
            ss = tuple(self._fsdp_state.state)
        elif prog.sharded and self.shard_update:
            ws = [tr._params[i].data()._data for i in idxs]
            ss = tuple(self._shard_state.state)
        elif prog.sharded:
            ws = [tr._params[i].data()._data for i in idxs]
            # replicated residency: scatter per-param state into the same
            # dp-sharded bucket arrays the sharded mode feeds — the ONE
            # program both modes dispatch (the parity contract)
            ss = self._scatter_replicated_state()
        else:
            ws = [tr._params[i].data()._data for i in idxs]
            ss = [tuple(tr._states[i][k]._data for k in keys) for i in idxs]
        fs = [p.data()._data for _, p in self._frozen]
        return ws, ss, fs

    def _dispatch(self, prog, args):
        """Compile on first use, account the dispatch, run the program:
        the ``train.dispatch`` span, whose time feeds ``train_step.call``
        (``.compile`` when the call traced). Returns (outputs, the span's
        seconds)."""
        with _telemetry.program_timer("train_step", "train.dispatch") as sp:
            out = self._compile_and_call(prog, args)
        return out, sp.seconds

    def _compile_and_call(self, prog, args):
        self._dispatches += 1
        if prog.compiled is None:
            # first dispatch of this signature: lower + compile explicitly
            # — the one XLA compile the implicit jit call would pay anyway
            # (the traced body still reports record_compile, so the
            # watchdog sees it like any jit cache miss), but the Compiled
            # handle stays reachable for cost_analysis
            import warnings as _warnings

            with _warnings.catch_warnings():
                # CPU backends warn that donation is unimplemented; the
                # copy fallback is correct (the donation is for TPU)
                _warnings.filterwarnings("ignore", message=".*donat.*",
                                         category=UserWarning)
                prog.compiled = prog.fn.lower(*args).compile()
            _COMPILED["jit_" + prog.fn.__name__] = prog.compiled
            cost = _telemetry.record_program_cost("train_step",
                                                  prog.compiled)
            if cost:
                prog.flops = cost["flops"]
                prog.bytes_accessed = cost["bytes_accessed"]
            _telemetry.record_program_memory("train_step", prog.compiled)
        # admission check + OOM forensics bracket BOTH dispatch paths: a
        # set lookup when admitted, a ledger dump when the device OOMs
        _telemetry.check_memory_admission("train_step")
        if _telemetry.ON:
            # ONE compiled-program call per (super-)step; this bypasses the
            # invoke() chokepoint, so count the dispatch here
            _telemetry.record_dispatch()
            _telemetry.record_flops(prog.flops, prog.bytes_accessed)
            rs_b, ag_b, ps_b = prog.coll_bytes
            if prog.sharded and not self.shard_update:
                # replicated residency: the host-side state reshard is
                # scatter + gather traffic on top of the program's own
                rs_b += self._state_bucket_bytes
                ag_b += self._state_bucket_bytes
            _telemetry.record_collective(rs_b, ag_b, ps_b,
                                         tp_bytes=prog.coll_bytes_tp)
            if prog.fsdp:
                _telemetry.record_fsdp(self._fsdp_layer_bytes)
        try:
            return prog.compiled(*args)
        except Exception as e:
            _telemetry.memory_oom_forensics("train_step", e)
            raise

    def _writeback(self, prog, new_ws, new_ss, aux):
        """Rebind the program's donated outputs into the host-visible
        parameter/state objects, per residency mode."""
        tr = self.trainer
        idxs = self._train_idx
        keys = self._state_keys
        if prog.fsdp:
            # outputs ARE the updated bucket shards: no per-param weight
            # writeback exists (or is wanted) — rebind the residency
            self._fsdp_state.rebind(new_ws, new_ss)
        elif prog.sharded and self.shard_update:
            for k, i in enumerate(idxs):
                tr._params[i].data()._set_data(new_ws[k])
            self._shard_state.rebind(new_ss)
        elif prog.sharded:
            for k, i in enumerate(idxs):
                tr._params[i].data()._set_data(new_ws[k])
            # gather updated shard buckets back into the per-param arrays
            for (_, ks, bs), st in zip(self._buckets, new_ss):
                for key, flat in zip(keys, st):
                    for k, off, n, shape in zip(ks, bs.offsets, bs.sizes,
                                                bs.shapes):
                        tr._states[idxs[k]][key]._set_data(
                            flat[off:off + n].reshape(shape))
        else:
            for k, i in enumerate(idxs):
                tr._params[i].data()._set_data(new_ws[k])
                for sk, arr in zip(keys, new_ss[k]):
                    tr._states[i][sk]._set_data(arr)
        # aux write-backs happen regardless of overflow: BN stats update
        # during the forward, before the eager loop could inspect grads
        for target, arr in zip(prog.aux_targets, aux):
            target._set_data(arr)

    def _record_health(self, prog, health, k_steps):
        """Fold the program's in-scan health outputs into the host-side
        numerics monitor. health = (grad_sq_norm, max_abs_update,
        nonfinite_counts[, group_sq_norms]) — scalars/[G] from the
        single-step program, [K]/[K, G] stacked from the scan. Returns the
        host seconds of the arithmetic (the read-back wait is not host
        work)."""
        import numpy as onp

        with _span("train.wait_health"):
            # device read-back: blocks until the step's program is done
            health = [onp.asarray(h) for h in health]
        with _span("train.health") as sp:
            gsq = onp.atleast_1d(onp.asarray(health[0], onp.float64))
            mx = onp.atleast_1d(onp.asarray(health[1], onp.float64))
            nonfin = health[2].reshape(k_steps, -1)
            gn = None
            if len(health) > 3:
                gn = onp.sqrt(onp.asarray(
                    health[3], onp.float64).reshape(k_steps, -1))
            _telemetry.record_step_health(
                prog.health_groups, onp.sqrt(gsq), mx, nonfin,
                group_norms=gn, nmode=prog.health_mode)
        return sp.seconds

    # _run and _run_multi: the spans are flat leaves that tile the caller's
    # time inside the step call (telemetry/spans.py)
    def _run(self, prog, x, y):
        import jax.numpy as jnp
        import numpy as onp

        tr = self.trainer
        opt = tr._optimizer
        idxs = self._train_idx
        scaler = self.loss_scaler
        with _span("train.assemble"):
            ws, ss, fs = self._assemble_inputs(prog)
        with _span("train.key"):
            if prog.uses_rng:
                from . import random as _rnd

                key = _rnd._next_key()
            else:
                key = jnp.zeros((2,), jnp.uint32)
        with _span("train.schedule"):
            # scalar schedule inputs are RUNTIME operands (the trainer
            # rule): counts are STAGED, not committed — an overflow-skipped
            # step must leave the schedule exactly where the eager skip
            # would
            counts, num_update = opt._staged_counts(idxs)
            ts = onp.asarray(counts, onp.float32)
            lrs = onp.asarray([opt._get_lr(i, num_update=num_update)
                               for i in idxs], onp.float32)
            wds = onp.asarray([opt._get_wd(i) for i in idxs], onp.float32)
            scale = float(scaler.loss_scale) if scaler is not None else 1.0
            rescale = onp.float32(tr._scale / scale)
            loss_scale = onp.float32(scale)
        out, _ = self._dispatch(prog, (ws, ss, fs, x._data, y._data, key,
                                       lrs, wds, ts, rescale, loss_scale))
        with _span("train.writeback"):
            if prog.health_groups is not None:
                loss_v, aux, new_ws, new_ss, overflow, health = out
            else:
                loss_v, aux, new_ws, new_ss, overflow = out
                health = None
            self._writeback(prog, new_ws, new_ss, aux)
        if scaler is not None:
            with _span("train.wait_overflow"):
                ovf = bool(overflow)  # the step's only host sync (1 byte)
        else:
            ovf = False
        with _span("train.commit"):
            if scaler is not None:
                scaler.update_scale(ovf)
            if not ovf:
                opt._commit_counts(idxs)
        if health is not None:
            # a few scalars riding the dispatch the step already paid for
            self._record_health(prog, health, k_steps=1)
        with _span("train.mark"):
            # the last references to the arrays the program consumed: some
            # hundreds of objects are freed here, inside a span, and not at
            # the frame's end outside every span
            del ws, ss
            if _telemetry.ON:
                _telemetry.mark_step()
            from .ndarray.ndarray import NDArray

            return NDArray(loss_v)

    def _run_multi(self, prog, x, y):
        import jax.numpy as jnp
        import numpy as onp

        tr = self.trainer
        opt = tr._optimizer
        idxs = self._train_idx
        scaler = self.loss_scaler
        k, g = prog.k, prog.accum
        with _span("train.assemble") as sp_a:
            ws, ss, fs = self._assemble_inputs(prog)
        with _span("train.key") as sp_k:
            if prog.uses_rng:
                from . import random as _rnd

                # one key PER MICROBATCH, drawn in the exact order the
                # sequential loop would draw them (RNG-trajectory parity)
                flat = [_rnd._next_key() for _ in range(k * g)]
                keys = jnp.stack(flat).reshape(
                    (k, g, 2) if g > 1 else (k, 2))
            else:
                keys = jnp.zeros((k, g, 2) if g > 1 else (k, 2), jnp.uint32)
        with _span("train.schedule") as sp_s:
            # per-inner-step hyper table: row j = what the j-th COMMITTED
            # step would stage; the program indexes rows by its in-scan
            # committed counter, so overflow skips freeze the schedule
            # exactly like the eager loop (and K sequential compiled steps)
            rows, nus = opt._staged_counts_k(idxs, k)
            ts = onp.asarray(rows, onp.float32)
            lrs = onp.asarray(
                [[opt._get_lr(i, num_update=nu) for i in idxs]
                 for nu in nus], onp.float32)
            wd_row = [opt._get_wd(i) for i in idxs]
            wds = onp.asarray([wd_row] * k, onp.float32)
            scale = float(scaler.loss_scale) if scaler is not None else 1.0
            rescale = onp.float32(tr._scale / scale)
            loss_scale = onp.float32(scale)
        out, dispatch_s = self._dispatch(
            prog, (ws, ss, fs, x._data, y._data, keys, lrs, wds, ts,
                   rescale, loss_scale))
        with _span("train.writeback") as sp_w:
            if prog.health_groups is not None:
                losses, aux, new_ws, new_ss, ovfs, healths = out
            else:
                losses, aux, new_ws, new_ss, ovfs = out
                healths = None
            self._writeback(prog, new_ws, new_ss, aux)
        with _span("train.wait_overflow"):
            # the super-step's only host sync: the K overflow flags (K
            # bytes)
            flags = onp.asarray(ovfs)
        with _span("train.commit") as sp_c:
            if scaler is not None:
                clean = scaler.replay(flags)
            else:
                clean = k
            for _ in range(clean):
                opt._commit_counts(idxs)
        health_s = 0.0
        if healths is not None:
            # [K]-stacked health rows ride the same dispatch; the overflow
            # sync above already waited out the device
            health_s = self._record_health(prog, healths, k_steps=k)
        with _span("train.mark"):
            del ws, ss  # freed inside a span, as in _run
            if _telemetry.ON:
                # host cost per trained step: the spans that are host work
                # (the waits are the device computing, not the host
                # dispatching)
                host_s = dispatch_s + health_s + sum(sp.seconds for sp in (
                    sp_a, sp_k, sp_s, sp_w, sp_c))
                _telemetry.gauge("train.host_ms_per_step").set(
                    host_s * 1e3 / k)
                _telemetry.gauge("train.dispatches_per_step").set(1.0 / k)
                _telemetry.mark_step(inner_steps=k)
            from .ndarray.ndarray import NDArray

            return NDArray(losses)

    # -- the uncompiled fallback -------------------------------------------
    def _eager_step(self, x, y):
        from . import autograd as ag

        # one warning per (reason, net) — NOT per CompiledTrainStep: loops
        # that rebuild the step (e.g. per epoch) must not re-warn
        warn_once(("train_step_fallback", self.fallback_reason,
                   id(self.net)),
                  f"compile_step: falling back to the eager path — "
                  f"{self.fallback_reason}", RuntimeWarning, stacklevel=3)
        tr = self.trainer
        scaler = self.loss_scaler
        with ag.record():
            loss = self.loss_fn(self.net(x), y).mean()
            head = loss if scaler is None else loss * float(scaler.loss_scale)
        head.backward()
        if scaler is not None:
            if scaler.has_overflow(tr._params):
                scaler.update_scale(True)
                if _telemetry.ON:
                    _telemetry.mark_step()
                return loss
            for p in tr._params:
                if p.grad_req != "null" and p._data is not None:
                    g = p.grad()
                    g._set_data(g._data / scaler.loss_scale)
            scaler.update_scale(False)
        tr.step(1)  # the loss carries the batch mean already
        return loss
