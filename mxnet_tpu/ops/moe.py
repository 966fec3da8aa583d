"""Mixture-of-experts operators: the router and the dropless routed experts
of a layer that is TOLD WHICH EXPERTS IT HOLDS.

Beyond the reference (SURVEY §2.2: no expert layer). Two registered ops, so
that ``autograd``, ``hybridize`` and ``compile_step`` see them like any other:

``moe_router(x, w, top_k, norm_topk)``
    ``p = softmax(x w^T)`` over the router's FULL width in float32 at
    ``highest`` matmul precision (a top-k choice is discontinuous: a bf16
    pass flips near-ties), the ``top_k`` largest, renormalised to sum 1.
    Returns ``(weights (N, k), experts (N, k) int32, counts (E,))`` where
    ``counts[e]`` is how many tokens chose expert ``e`` (no gradient).

``routed_experts(x, weights, experts, gate_up, down, experts_held)``
    ``y[n] = sum_j weights[n, j] * E_(experts[n, j])(x[n])`` over the chosen
    experts that lie in ``experts_held = (lo, hi)``, with
    ``E(x) = (silu(x W_g) * (x W_u)) W_d``; ``gate_up``: (hi - lo, D, 2F),
    ``down``: (hi - lo, F, D). What the absent experts would add is left
    out: on one chip of an expert-parallel job that is this chip's part of
    the layer's result. No exchange, and nothing stands in for one.

Dropless with static shapes. The token-expert pairs whose expert is held are
sorted by expert (a stable argsort: inside a group the tokens stay in order)
and laid out in tiles of ``tile`` rows, each group padded to whole tiles. The
worst case (every pair here) fixes the SHAPES: ``N * min(k, held)`` rows plus
one tile of padding an expert. The WORK follows the routing: a loop over the
experts held, and inside it a ``lax.fori_loop`` over that expert's tiles in
use (none where it received nothing), which gathers a tile's rows from
``x``, multiplies them with the expert (two grouped matrix products, ragged
groups, no capacity factor) and adds the weighted rows back into ``y``. The
expert's weights are read once an expert, however many tiles it has. No
token is dropped however skewed the router is.

The backward pass is written out (``jax.custom_vjp``): reverse-mode autodiff
cannot run a loop of unknown length backwards, and through a scan it would
keep a copy of the expert's weights for every tile. It walks the same tiles,
recomputes a tile's activations, and accumulates the expert's weight
gradients in the inner loop's carry, written back once an expert.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax

from ..base import MXNetError
from .registry import register


@register("moe_router", nout=3)
def _moe_router(top_k=1, norm_topk=True):
    def f(x, w):
        f32 = jnp.float32
        logits = jnp.matmul(x.astype(f32), w.astype(f32).T,
                            precision=lax.Precision.HIGHEST)
        p = jax.nn.softmax(logits, axis=-1)
        vals, idx = lax.top_k(p, int(top_k))
        if norm_topk:
            vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
        idx = idx.astype(jnp.int32)
        counts = jnp.sum(idx[..., None] == jnp.arange(
            w.shape[0], dtype=jnp.int32), axis=(0, 1), dtype=f32)
        return vals, idx, lax.stop_gradient(counts)

    return f


def _tile_rows(n_tokens, tile):
    """Rows of a tile: ``tile``, or the token count rounded up to 8 where
    that is smaller (tiny test sizes)."""
    return int(min(tile, -(-n_tokens // 8) * 8))


def _plan(weights, experts, lo, hi, tm):
    """The tiled layout of the pairs held here.

    Returns ``tok`` (slots,) token id of each slot (out of range, ascending
    and distinct, where the slot is padding), ``pair`` (slots,) the flat
    pair id (N * k where padding), ``w_slot`` (slots,) the pair's routing
    weight (0 where padding), and ``tile_lo``, ``tile_hi`` (held,): expert
    g's tiles are ``tile_lo[g] .. tile_hi[g] - 1`` (none where it received
    nothing). ``slots = tiles * tm`` is the static worst case."""
    N, k = experts.shape
    G, P = hi - lo, N * k
    max_tiles = -(-N * min(k, G) // tm) + G
    here = (experts >= lo) & (experts < hi)
    eid = jnp.where(here, experts - lo, G).reshape(P)
    order = jnp.argsort(eid, stable=True).astype(jnp.int32)
    sizes = jnp.sum(eid[:, None] == jnp.arange(G, dtype=jnp.int32), axis=0,
                    dtype=jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    n_tiles = tile_end[-1]
    pstart = (tile_end - tiles) * tm
    tile_expert = jnp.minimum(jnp.searchsorted(
        tile_end, jnp.arange(max_tiles, dtype=jnp.int32), side="right"),
        G - 1).astype(jnp.int32)
    slot = jnp.arange(max_tiles * tm, dtype=jnp.int32)
    g = tile_expert[slot // tm]
    j = slot - pstart[g]
    valid = (j < sizes[g]) & (slot // tm < n_tiles)
    pair = order[jnp.clip(starts[g] + j, 0, P - 1)]
    tok = jnp.where(valid, pair // k, N + slot % tm)
    w_slot = jnp.where(valid, weights.reshape(P)[pair], 0)
    return tok, jnp.where(valid, pair, P), w_slot, tile_end - tiles, tile_end


def _swiglu(h, F):
    g, u = h[:, :F], h[:, F:]
    s = jax.nn.sigmoid(g)
    return g, u, s, g * s * u


def _rows(x, tok):
    return x.at[tok].get(mode="fill", fill_value=0,
                         indices_are_sorted=True, unique_indices=True)


def _add_rows(y, tok, rows):
    return y.at[tok].add(rows, mode="drop", indices_are_sorted=True,
                         unique_indices=True)


def _tile(v, t, tm):
    return lax.dynamic_slice(v, (t * tm,), (tm,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _routed_experts(x, weights, experts, gate_up, down, lo, hi, tm):
    return _routed_fwd(x, weights, experts, gate_up, down, lo, hi, tm)[0]


def _routed_fwd(x, weights, experts, gate_up, down, lo, hi, tm):
    F = down.shape[1]
    tok, _, w_slot, tile_lo, tile_hi = _plan(weights, experts, lo, hi, tm)

    def expert(g, y):
        w_gu, w_dn = gate_up[g], down[g]     # read once an expert

        def tile(t, y):
            tk = _tile(tok, t, tm)
            a = _swiglu(_rows(x, tk) @ w_gu, F)[3]
            return _add_rows(y, tk,
                             _tile(w_slot, t, tm)[:, None] * (a @ w_dn))

        return lax.fori_loop(tile_lo[g], tile_hi[g], tile, y)

    y = lax.fori_loop(0, hi - lo, expert, jnp.zeros_like(x))
    return y, (x, weights, experts, gate_up, down)


def _routed_bwd(lo, hi, tm, res, dy):
    x, weights, experts, gate_up, down = res
    N, k = experts.shape
    F = down.shape[1]
    tok, pair, w_slot, tile_lo, tile_hi = _plan(weights, experts, lo, hi,
                                                tm)

    def expert(g, carry):
        dx, dgu, ddn, dw_slot = carry
        w_gu, w_dn = gate_up[g], down[g]

        def tile(t, carry):
            # the expert's weight gradients ride the inner loop: written
            # back once an expert, not read and written once a tile
            dx, dgu_g, ddn_g, dw_slot = carry
            tk = _tile(tok, t, tm)
            xt, dyt = _rows(x, tk), _rows(dy, tk)
            gate, u, s, a = _swiglu(xt @ w_gu, F)
            dw_slot = lax.dynamic_update_slice(
                dw_slot, jnp.sum(dyt * (a @ w_dn), axis=-1), (t * tm,))
            do = dyt * _tile(w_slot, t, tm)[:, None]
            da = do @ w_dn.T
            dh = jnp.concatenate(
                [da * u * (s * (1.0 + gate * (1.0 - s))), da * gate * s],
                axis=-1)
            return (_add_rows(dx, tk, dh @ w_gu.T), dgu_g + xt.T @ dh,
                    ddn_g + a.T @ do, dw_slot)

        dx, dgu_g, ddn_g, dw_slot = lax.fori_loop(
            tile_lo[g], tile_hi[g], tile,
            (dx, jnp.zeros_like(w_gu), jnp.zeros_like(w_dn), dw_slot))
        return (dx, lax.dynamic_update_index_in_dim(dgu, dgu_g, g, 0),
                lax.dynamic_update_index_in_dim(ddn, ddn_g, g, 0), dw_slot)

    dx, dgu, ddn, dw_slot = lax.fori_loop(
        0, hi - lo, expert,
        (jnp.zeros_like(x), jnp.zeros_like(gate_up), jnp.zeros_like(down),
         jnp.zeros(tok.shape, weights.dtype)))
    dw = jnp.zeros((N * k + 1,), weights.dtype).at[pair].add(dw_slot)[:-1]
    return (dx, dw.reshape(N, k),
            onp.zeros(experts.shape, jax.dtypes.float0), dgu, ddn)


_routed_experts.defvjp(_routed_fwd, _routed_bwd)


@register("routed_experts")
def _routed_experts_op(experts_held=None, tile=128):
    def f(x, weights, experts, gate_up, down):
        G = gate_up.shape[0]
        lo, hi = (0, G) if experts_held is None else map(int, experts_held)
        if hi - lo != G or down.shape[0] != G:
            raise MXNetError(
                f"routed_experts: experts_held {(lo, hi)} names {hi - lo} "
                f"experts, the arrays hold {G} and {down.shape[0]}")
        if gate_up.shape[2] != 2 * down.shape[1]:
            raise MXNetError(
                f"routed_experts: gate_up {gate_up.shape} is not (experts, "
                f"hidden, 2 x width) for down {down.shape}")
        return _routed_experts(x, weights.astype(x.dtype), experts, gate_up,
                               down, lo, hi, _tile_rows(x.shape[0],
                                                        int(tile)))

    return f
