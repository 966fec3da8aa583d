"""Device-mesh construction and sharding helpers.

TPU-native replacement for the reference's device-topology machinery
(src/kvstore/gpu_topology.h tree solver; comm device lists): on TPU the
topology is a torus XLA already understands, so the framework's job is only to
pick logical axis names and sizes. Shardings are expressed as
jax.sharding.NamedSharding over the mesh.
"""
from __future__ import annotations

import jax
import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError

__all__ = ["AxisNames", "make_mesh", "default_mesh", "replicated",
           "shard_batch", "shard_params", "shard_map_compat", "P",
           "shard_1d", "zeros_sharded", "axis_extent",
           "bytes_per_replica"]


class AxisNames:
    DP = "dp"   # data parallel
    TP = "tp"   # tensor/model parallel
    PP = "pp"   # pipeline parallel
    SP = "sp"   # sequence/context parallel
    EP = "ep"   # expert parallel


def make_mesh(axes: dict[str, int] | None = None, devices=None) -> Mesh:
    """Build a Mesh from {axis_name: size}. Sizes must multiply to #devices.

    ``make_mesh({'dp': 4, 'tp': 2})`` on 8 devices. Pass -1 for one axis to
    absorb the remainder (like reshape).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not axes:
        axes = {AxisNames.DP: n}
    names = list(axes)
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise MXNetError("only one mesh axis may be -1")
    if -1 in sizes:
        known = 1
        for s in sizes:
            if s != -1:
                known *= s
        sizes[sizes.index(-1)] = n // known
    total = 1
    for s in sizes:
        total *= s
    if total != n:
        raise MXNetError(f"mesh axes {dict(zip(names, sizes))} do not cover "
                         f"{n} devices")
    arr = onp.array(devices).reshape(sizes)
    return Mesh(arr, names)


def default_mesh() -> Mesh:
    """All local devices on a single 'dp' axis."""
    return make_mesh()


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, axis: str = AxisNames.DP) -> NamedSharding:
    """Shard dim 0 (batch) over ``axis``; everything else replicated."""
    return NamedSharding(mesh, P(axis))


def shard_map_compat(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled: the compiled
    train step mixes per-shard values (``axis_index``-folded RNG keys) with
    psum'ed results, which the static vma checker over-rejects."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def shard_1d(mesh: Mesh, axis: str = AxisNames.DP) -> NamedSharding:
    """Shard a flat (1-D) buffer over ``axis`` — the layout of the ZeRO-1
    optimizer-state buckets (each replica owns one contiguous 1/N slice)."""
    return NamedSharding(mesh, P(axis))


def axis_extent(mesh: Mesh, axis: str) -> int:
    """Size of ``axis`` in ``mesh`` (0 when the axis is absent)."""
    return int(mesh.shape.get(axis, 0))


def zeros_sharded(mesh: Mesh, shape, dtype, spec) -> jax.Array:
    """Allocate zeros directly under ``NamedSharding(mesh, spec)``.

    The allocation happens INSIDE a jitted program with an output sharding
    constraint, so no replica ever materializes the full buffer — each
    device writes only its shard. This is how the sharded weight update
    gets optimizer state that is 1/N-sized from the very first step, not
    full-sized-then-resharded.
    """
    sharding = NamedSharding(mesh, spec)
    import jax.numpy as jnp

    fn = jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)
    return fn()


def bytes_per_replica(arr) -> int:
    """Bytes of ``arr`` ONE replica actually holds: the first addressable
    shard's buffer size (uniform shards — every 1/N residency claim in the
    sharded train step is this number), or the whole buffer for an
    unsharded array."""
    shards = getattr(arr, "addressable_shards", None)
    if shards:
        return shards[0].data.nbytes
    return arr.nbytes


def shard_params(mesh: Mesh, spec_fn=None):
    """Return a function NDArray/jax.Array -> NamedSharding for parameters.

    By default parameters are replicated (pure DP). ``spec_fn(name, shape)``
    may return a PartitionSpec for tensor-parallel layouts (e.g. shard the
    hidden dim of big matmuls over 'tp').
    """
    def f(name, arr):
        if spec_fn is not None:
            spec = spec_fn(name, tuple(arr.shape))
            if spec is not None:
                return NamedSharding(mesh, spec)
        return NamedSharding(mesh, P())

    return f
