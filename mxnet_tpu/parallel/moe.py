"""Expert parallelism: mixture-of-experts with the experts divided over chips.

Not present in the reference (SURVEY §2.2: EP absent). Two things live here.

``RoutedExperts`` is the routed layer gluon models build: a ``HybridBlock``
that is TOLD WHICH EXPERTS IT HOLDS (``experts_held = (lo, hi)``, a range of
the router's ``num_experts``). It routes over the router's full width,
top-k, and computes its own experts' part of the result, dropless
(``ops/moe.py``). That is what one chip of an expert-parallel job computes
between the two exchanges; on one chip it runs with no exchange, and no code
stands in for the absent chips. The exchange itself (an ``ep`` mesh axis with
its all-to-all inside ``compile_step``) is not built yet (ROADMAP D2).

``moe_apply`` / ``moe_sharded`` are the older top-1, capacity-dropping
``shard_map`` helper on raw arrays (tokens exchanged with
``lax.all_to_all``); ``five_axis.py`` and the dry run in
``__graft_entry__.py`` call it, no gluon model can.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import _deferred_compute as dc
from .. import initializer as init_mod
from .. import numpy_extension as npx
from .. import telemetry
from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter

__all__ = ["moe_apply", "moe_sharded", "RoutedExperts", "TopKRouter"]


def moe_apply(x, gate_w, expert_w1, expert_w2, axis_name="ep", capacity=None):
    """Per-shard MoE body (call inside shard_map).

    x: (T_local, D) local token shard; gate_w: (D, E_total) replicated;
    expert_w1: (E_local, D, H), expert_w2: (E_local, H, D) — local experts.
    Top-1 routing with per-expert capacity; overflow tokens pass through.
    """
    n_dev = lax.psum(1, axis_name)
    t_local, d = x.shape
    e_local = expert_w1.shape[0]
    e_total = e_local * n_dev
    cap = capacity or max(1, (t_local // e_total) * 2)

    logits = x @ gate_w  # (T, E_total)
    expert_id = jnp.argmax(logits, axis=-1)  # (T,)
    gate = jax.nn.softmax(logits, axis=-1)
    gate_val = jnp.take_along_axis(gate, expert_id[:, None], axis=1)[:, 0]

    # slot each token into its expert's capacity buffer (static shapes)
    onehot = jax.nn.one_hot(expert_id, e_total, dtype=jnp.int32)  # (T, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based slot per token
    slot = jnp.sum(pos, axis=-1) - 1  # (T,)
    keep = slot < cap
    # dispatch buffer: (E_total, cap, D)
    dispatch = jnp.zeros((e_total, cap, d), x.dtype)
    tok_idx = jnp.where(keep, expert_id, 0)
    slot_idx = jnp.where(keep, slot, 0)
    contrib = jnp.where(keep[:, None], x, 0.0)
    dispatch = dispatch.at[tok_idx, slot_idx].add(contrib)

    # all_to_all: every device sends each expert-group to its owner
    # (E_total, cap, D) -> split E_total over devices -> concat on a new axis
    shaped = dispatch.reshape(n_dev, e_local, cap, d)
    recv = lax.all_to_all(shaped, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)  # (n_dev, e_local, cap, d)
    recv = recv.transpose(1, 0, 2, 3).reshape(e_local, n_dev * cap, d)

    # local expert MLPs (batched over local experts)
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", recv, expert_w1))
    y = jnp.einsum("ech,ehd->ecd", h, expert_w2)

    # route results back to the source devices
    y = y.reshape(e_local, n_dev, cap, d).transpose(1, 0, 2, 3)
    back = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)
    back = back.reshape(e_total, cap, d)

    out = back[tok_idx, slot_idx]  # (T, D)
    out = jnp.where(keep[:, None], out * gate_val[:, None], x)  # overflow: pass-through
    return out


def moe_sharded(x, gate_w, expert_w1, expert_w2, mesh, axis="ep",
                capacity=None):
    """User-facing MoE layer over a mesh: tokens sharded over ``ep``,
    experts sharded over ``ep``, gate replicated."""
    from .mesh import shard_map_compat

    from ..ndarray.ndarray import NDArray

    if axis not in mesh.shape:
        raise MXNetError(f"mesh has no axis {axis!r}")
    unwrap = lambda a: a._data if isinstance(a, NDArray) else a  # noqa: E731
    xd, gw, w1, w2 = map(unwrap, (x, gate_w, expert_w1, expert_w2))
    fn = shard_map_compat(
        functools.partial(moe_apply, axis_name=axis, capacity=capacity),
        mesh,
        in_specs=(P(axis), P(), P(axis), P(axis)),
        out_specs=P(axis),
    )
    out = jax.jit(fn)(xd, gw, w1, w2)
    return NDArray(out) if isinstance(x, NDArray) else out



class TopKRouter(HybridBlock):
    """``forward(x)`` on (N, units) tokens: ``(weights (N, k), experts (N, k)
    int32)``, the ``top_k`` largest of ``softmax(x weight^T)`` over ALL
    ``num_experts`` in float32, renormalised to sum 1 when ``norm_topk``
    (``npx.moe_router``; ``score="sigmoid"`` and ``scaling``: a sigmoid an
    expert in the softmax's place, the renormalised weights times
    ``scaling``). A block of its own so that a forward hook sees the
    choices.

    ``expert_tokens`` (num_experts,), not trained: how many tokens chose each
    expert in the last forward. Under a trace it is an auxiliary output of
    the compiled program (written back after the call, like BatchNorm's
    moving statistics), so keeping it costs the step no read;
    ``telemetry.moe_report()`` reads it when asked."""

    def __init__(self, units, num_experts, top_k, norm_topk=True,
                 dtype="float32", weight_initializer=None, score="softmax",
                 scaling=1.0, **kwargs):
        super().__init__(**kwargs)
        if not 1 <= top_k <= num_experts:
            raise MXNetError(f"top_k {top_k} is not in 1..{num_experts}")
        self.num_experts, self.top_k = num_experts, top_k
        self._norm_topk = norm_topk
        self._score, self._scaling = score, scaling
        self.weight = Parameter(
            shape=(num_experts, units), dtype=dtype,
            init=weight_initializer or init_mod.Normal(0.02))
        self.expert_tokens = Parameter(shape=(num_experts,),
                                       dtype="float32", init="zeros",
                                       grad_req="null")

    def forward(self, x):
        weights, experts, counts = npx.moe_router(
            x, self.weight.data(), top_k=self.top_k,
            norm_topk=self._norm_topk, score=self._score,
            scaling=self._scaling)
        if dc.is_tracing():
            dc.register_aux_update(self.expert_tokens.data(), counts)
        else:
            self.expert_tokens.data()._set_data(counts._data)
        return weights, experts


class RoutedExperts(HybridBlock):
    """Router plus the routed SwiGLU experts this chip holds.

    ``num_experts``: the router's (published) width; ``experts_held``:
    ``(lo, hi)``, the experts whose weights live here (default: all);
    ``top_k`` experts a token, their weights renormalised to sum 1 when
    ``norm_topk`` (``score``, ``scaling``: the router's, see
    ``TopKRouter``). ``forward(x)`` takes (N, units) tokens and returns
    the weighted sum over each token's chosen experts THAT ARE HELD; the
    weights are those of the full top-k, so the ``num_experts / held``
    shares of a layer add up to the whole layer. Dropless: however
    skewed the router, every pair whose expert is held is computed.

    Parameters: ``router.weight`` (num_experts, units) and
    ``router.expert_tokens`` (``TopKRouter``), ``gate_up`` (held, units,
    2 x expert_units), ``down`` (held, expert_units, units).
    """

    def __init__(self, units, expert_units, num_experts, top_k,
                 experts_held=None, norm_topk=True, dtype="float32",
                 weight_initializer=None, score="softmax", scaling=1.0,
                 **kwargs):
        super().__init__(**kwargs)
        lo, hi = (0, num_experts) if experts_held is None \
            else map(int, experts_held)
        if not 0 <= lo < hi <= num_experts:
            raise MXNetError(f"experts_held {(lo, hi)} is not a range "
                             f"of the {num_experts} experts")
        self.experts_held = (lo, hi)
        self.num_experts, self.top_k = num_experts, top_k
        init = weight_initializer or init_mod.Normal(0.02)
        self.router = TopKRouter(units, num_experts, top_k, norm_topk,
                                 dtype, init, score, scaling)
        self.gate_up = Parameter(
            shape=(hi - lo, units, 2 * expert_units), dtype=dtype,
            init=init)
        self.down = Parameter(shape=(hi - lo, expert_units, units),
                              dtype=dtype, init=init)
        telemetry._moe.register(self)

    def experts(self, x, weights, experts):
        return npx.routed_experts(x, weights, experts,
                                  self.gate_up.data(), self.down.data(),
                                  experts_held=self.experts_held)

    def forward(self, x):
        return self.experts(x, *self.router(x))
