"""Collective wrappers for use inside shard_map'ed kernels.

Reference mapping (SURVEY §5.8): ncclReduce/ncclBcast (kvstore_nccl.h:285,402)
and ps-lite push/pull become XLA collectives over ICI/DCN. These helpers are
thin names over jax.lax so framework code and user kernels share a vocabulary.
"""
from __future__ import annotations

import jax
from jax import lax

__all__ = ["all_reduce", "all_gather", "reduce_scatter", "ppermute",
           "all_to_all", "axis_index", "axis_size", "BucketSpec"]


def all_reduce(x, axis_name, op="sum"):
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown reduce op {op}")


def all_gather(x, axis_name, axis=0, tiled=False):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, scatter_dimension=0):
    return lax.psum_scatter(x, axis_name,
                            scatter_dimension=scatter_dimension, tiled=True)


def ppermute(x, axis_name, perm):
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    return lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=tiled)


class BucketSpec:
    """Flatten/pad layout for sharding a list of tensors over a mesh axis.

    The ZeRO-1 weight-update schedule (Xu et al., "Automatic Cross-Replica
    Sharding of Weight Update") works on FLAT per-dtype buckets: tensors are
    concatenated, padded up to a multiple of the axis size, reduce-scattered
    so each replica owns a contiguous 1/N shard, updated shard-locally, and
    all-gathered back. This object is the static layout arithmetic shared by
    the trace-time body and the host-side state manager: sizes/offsets per
    tensor, the padded total, and the per-replica shard length.
    """

    __slots__ = ("shapes", "sizes", "offsets", "total", "padded", "n_shards",
                 "shard")

    def __init__(self, shapes, n_shards):
        self.shapes = [tuple(int(d) for d in s) for s in shapes]
        self.sizes = []
        for s in self.shapes:
            n = 1
            for d in s:
                n *= d
            self.sizes.append(n)
        self.offsets = []
        off = 0
        for n in self.sizes:
            self.offsets.append(off)
            off += n
        self.total = off
        self.n_shards = int(n_shards)
        self.padded = -(-self.total // self.n_shards) * self.n_shards
        self.shard = self.padded // self.n_shards

    @property
    def pad(self):
        return self.padded - self.total

    def flatten(self, xs, pad_value=0):
        """Concatenate ``xs`` (matching ``shapes``) into one padded flat
        vector. Traceable (jnp) — used inside the compiled step body."""
        import jax.numpy as jnp

        flat = jnp.concatenate([x.reshape(-1) for x in xs]) if len(xs) > 1 \
            else xs[0].reshape(-1)
        if self.pad:
            flat = jnp.pad(flat, (0, self.pad), constant_values=pad_value)
        return flat

    def unflatten(self, flat):
        """Split a padded flat vector back into tensors of ``shapes``
        (discards the pad tail)."""
        return [flat[o:o + n].reshape(s)
                for o, n, s in zip(self.offsets, self.sizes, self.shapes)]

    def flatten_host(self, xs, dtype="float32", pad_value=0):
        """Host-side (numpy) counterpart of ``flatten``: concatenate
        ``xs`` into one padded flat vector WITHOUT touching the device.
        The one code path every residency manager uses to build bucket
        images (ZeRO-1 state scatter, FSDP param/state adoption) — the
        layout arithmetic lives here, not at each call site."""
        import numpy as onp

        flat = onp.full((self.padded,), pad_value, dtype=onp.dtype(dtype))
        for x, off, n in zip(xs, self.offsets, self.sizes):
            flat[off:off + n] = onp.asarray(x).reshape(-1)
        return flat

    def spread(self, per_tensor, pad_value=0.0):
        """Per-tensor scalars -> per-element flat vector (padded). Static
        repeat lengths, so this never retraces on value changes."""
        import jax.numpy as jnp

        v = jnp.repeat(per_tensor, jnp.asarray(self.sizes),
                       total_repeat_length=self.total)
        if self.pad:
            v = jnp.pad(v, (0, self.pad), constant_values=pad_value)
        return v

    def shard_slice(self, flat, axis_name):
        """This replica's contiguous 1/N slice of a padded flat vector."""
        idx = lax.axis_index(axis_name)
        return lax.dynamic_slice_in_dim(flat, idx * self.shard, self.shard)


def axis_index(axis_name):
    return lax.axis_index(axis_name)


def axis_size(axis_name):
    return lax.psum(1, axis_name)


def _vma(x):
    """The varying-manual-axes set of a value/aval. ``jax.eval_shape``
    returns ``ShapeDtypeStruct``s whose ``vma`` is None outside shard_map:
    that is the empty set."""
    aval = x if hasattr(x, "vma") else jax.typeof(x)
    return aval.vma or frozenset()


def match_carry_vma(step_fn, carry, *xs_protos, fallback_axis=None):
    """Promote literal-zero scan carries to the loop body's varying axes.

    Under shard_map, jax tracks which mesh axes a value *varies* over (vma).
    A scan carry initialized from literals is axis-invariant, but the loop
    body usually returns values varying over the axes its collectives /
    ``axis_index`` touch — and scan requires carry types to be identical
    across iterations. This runs ``jax.eval_shape`` on one abstract step
    (zero FLOPs) and ``lax.pcast``s each init leaf up to the vma the body
    produces.

    If the abstract eval itself fails, falls back to promoting every leaf
    over ``fallback_axis`` (the caller's primary ring axis) — the carry is
    guaranteed to vary over at least that axis, and an unpromoted carry
    would only re-surface later as an opaque scan carry-type mismatch.
    """
    def up(leaf, aval):
        need = tuple(sorted(_vma(aval) - _vma(leaf)))
        return lax.pcast(leaf, need, to="varying") if need else leaf

    def promote_fallback(tree):
        if fallback_axis is None:
            return tree
        ax = (fallback_axis,) if isinstance(fallback_axis, str) \
            else tuple(fallback_axis)

        def one(leaf):
            need = tuple(a for a in ax if a not in _vma(leaf))
            return lax.pcast(leaf, need, to="varying") if need else leaf

        return jax.tree_util.tree_map(one, tree)

    # iterate to a vma fixpoint: the carry feeds back into the body, so one
    # abstract pass can under-approximate (bounded by the mesh's axis count)
    for _ in range(8):
        try:
            out = jax.eval_shape(lambda c: step_fn(c, *xs_protos)[0], carry)
        except Exception:  # noqa: BLE001 — abstract eval failed
            return promote_fallback(carry)
        grew = any(
            _vma(a) - _vma(c)
            for c, a in zip(jax.tree_util.tree_leaves(carry),
                            jax.tree_util.tree_leaves(out)))
        if not grew:
            return carry
        carry = jax.tree_util.tree_map(up, carry, out)
    return carry
