"""Mixture-of-experts operators: the router and the dropless routed experts
of a layer that is TOLD WHICH EXPERTS IT HOLDS.

Beyond the reference (SURVEY §2.2: no expert layer). Two registered ops, so
that ``autograd``, ``hybridize`` and ``compile_step`` see them like any other:

``moe_router(x, w, top_k, norm_topk, score, scaling)``
    ``p = softmax(x w^T)`` over the router's FULL width in float32 at
    ``highest`` matmul precision (a top-k choice is discontinuous: a bf16
    pass flips near-ties), the ``top_k`` largest, renormalised to sum 1.
    With ``score="sigmoid"`` (DeepSeek-V3's router) ``p = sigmoid(x w^T)``,
    an expert at a time, the ``top_k`` largest divided by their sum + 1e-20
    and multiplied by ``scaling``.
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
and laid out in tiles of ``tile`` rows, each group padded to whole tiles
(``_plan``; worked out a tile at a time, so planning is a few dozen small
operations whatever the row count). The worst case (every pair here) fixes
the SHAPES: ``N * min(k, held)`` rows plus one tile of padding an expert. The
WORK follows the routing, and no token is dropped however skewed the router
is. Two forms of the forward pass compute the same sum:

*The kernel* (``pallas_kernels.grouped_experts``, ``mxtpu_experts_swiglu``;
on the TPU wherever ``experts_kernel_serves`` says its shapes fit: the Granite
serving tick and prefills). The layout's rows are gathered ONCE (a padding
slot takes any row), and one kernel walks the tiles: ``gate_up`` and ``down``
go in whole, the tile -> expert table, each tile's rows in use and each
slot's token and routing weight are scalar-prefetched, and the weight
blocks' index maps pick the tile's expert IN PLACE (a block is one expert's
matrix, whole). An expert with several tiles keeps its block index and is
fetched once; an expert no token chose has no tile and costs no bytes; the
grid steps past the last tile in use repeat its indices and do nothing.
Both products accumulate in float32 on the matrix unit (float32 rows and
weights are rounded to bfloat16 there, as the chip's default precision
rounds them), SwiGLU runs on the float32 product and is rounded once, and
each row in use is weighted and added to its token's float32 sum, which
stays in fast memory from the first tile to the last: no buffer of the
layout's results exists.

*The blocked kernel* (``pallas_kernels.grouped_experts_blocked``,
``mxtpu_experts_swiglu_blocked``; wherever an expert is too large to be
resident whole twice, such as A.X-K1's 88 MB, or the tokens' float32 sums do
not fit beside it): the same layout and tables, grid (tile, block of the
expert's inner width); a step fetches one block of the gate, up and down
matrices in place, the tile's float32 result stays resident over the blocks,
and what comes back is every slot's result, weighted and added to its token's
sum by one scatter-add outside the kernel. WHICH of the two kernels, or
neither, is one rule from what the op sees:
``pallas_kernels.experts_kernel_blocks``.

*The loop* (everywhere else: the CPU, tiny tiles, widths that are no
multiple of 128): a ``lax.fori_loop`` over the experts held, and inside it one over that
expert's tiles in use (none where it received nothing), which gathers a
tile's rows from ``x``, multiplies them with the expert and adds the weighted
rows back into ``y``. It is the reference the kernel is held to
(``tests/test_moe_kernel.py``).

The backward pass is written out (``jax.custom_vjp``): reverse-mode autodiff
cannot run a loop of unknown length backwards, and through a scan it would
keep a copy of the expert's weights for every tile. It is the loop's form
whichever way the forward ran: it walks the same tiles, recomputes a tile's
activations, and accumulates the expert's weight gradients in the inner
loop's carry, written back once an expert.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax

from ..base import MXNetError
from . import pallas_kernels as pk
from .registry import register


@register("moe_router", nout=3)
def _moe_router(top_k=1, norm_topk=True, score="softmax", scaling=1.0):
    if score not in ("softmax", "sigmoid"):
        raise MXNetError(f"moe_router: score {score!r} is neither 'softmax' "
                         "nor 'sigmoid'")

    def f(x, w):
        f32 = jnp.float32
        logits = jnp.matmul(x.astype(f32), w.astype(f32).T,
                            precision=lax.Precision.HIGHEST)
        if score == "sigmoid":
            vals, idx = lax.top_k(jax.nn.sigmoid(logits), int(top_k))
            if norm_topk:
                vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
            vals = vals * f32(scaling)
        else:
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


_Plan = collections.namedtuple(
    "_Plan", "tok pair w_slot tile_lo tile_hi tile_expert tile_rows")


def _plan(weights, experts, lo, hi, tm):
    """The tiled layout of the pairs held here.

    Returns ``tok`` (slots,) token id of each slot (out of range, ascending
    and distinct, where the slot is padding), ``pair`` (slots,) the flat
    pair id (N * k where padding), ``w_slot`` (slots,) the pair's routing
    weight (0 where padding), ``tile_lo``, ``tile_hi`` (held,): expert g's
    tiles are ``tile_lo[g] .. tile_hi[g] - 1`` (none where it received
    nothing), and by tile ``tile_expert`` (tiles,), its expert, and
    ``tile_rows`` (tiles,), its rows in use (0 past the last tile in use,
    ``tile_hi[-1]``). ``slots = tiles * tm`` is the static worst case."""
    N, k = experts.shape
    G, P = hi - lo, N * k
    max_tiles = -(-N * min(k, G) // tm) + G
    i32 = jnp.int32
    here = (experts >= lo) & (experts < hi)
    eid = jnp.where(here, experts - lo, G).reshape(P)
    order = jnp.argsort(eid, stable=True).astype(i32)
    held = jnp.arange(G, dtype=i32)
    sizes = jnp.sum(eid[:, None] == held, axis=0, dtype=i32)
    starts = jnp.cumsum(sizes) - sizes
    tiles = (sizes + tm - 1) // tm
    tile_hi = jnp.cumsum(tiles)
    tile_lo = tile_hi - tiles
    # everything below is worked out a TILE (a few dozen), not a slot: a
    # tile finds its group's numbers through a one-hot row over the experts
    t = jnp.arange(max_tiles, dtype=i32)
    tile_expert = jnp.minimum(jnp.sum(tile_hi[None, :] <= t[:, None], axis=1,
                                      dtype=i32), G - 1)
    mine = tile_expert[:, None] == held

    def of_tile(v):
        return jnp.sum(jnp.where(mine, v, 0), axis=1)

    before = (t - of_tile(tile_lo)) * tm     # the group's rows in earlier tiles
    tile_rows = jnp.clip(of_tile(sizes) - before, 0, tm)
    j = jnp.arange(tm, dtype=i32)
    valid = (j < tile_rows[:, None]).reshape(-1)
    pair = order[jnp.clip((of_tile(starts) + before)[:, None] + j, 0, P - 1)
                 .reshape(-1)]
    tok = jnp.where(valid, pair // k, jnp.tile(N + j, max_tiles))
    w_slot = jnp.where(valid, weights.reshape(P)[pair], 0)
    return _Plan(tok, jnp.where(valid, pair, P), w_slot, tile_lo, tile_hi,
                 tile_expert, tile_rows)


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
    plan = _plan(weights, experts, lo, hi, tm)
    res = (x, weights, experts, gate_up, down)

    blocks = pk.experts_kernel_blocks(x.shape[0], tm, x.shape[1], F, x.dtype,
                                      gate_up.dtype)
    if blocks is not None:
        # one gather of the layout's rows, then one kernel over its tiles;
        # a padding slot takes the last token's row, which the kernel may
        # multiply and never adds to a sum
        tok = jnp.minimum(plan.tok, x.shape[0] - 1)
        rows = x.at[tok].get(mode="promise_in_bounds")
        if blocks == 0:     # an expert resident whole, the sums with it
            y = pk.grouped_experts(
                rows, gate_up, down, plan.tile_expert, plan.tile_rows,
                plan.tile_hi[-1], tok, plan.w_slot, x.shape[0])
        else:
            # an expert in blocks of its inner width: the kernel hands back
            # every slot's result, weighted and summed a token here (a
            # padding slot's token id is out of range: dropped)
            out = pk.grouped_experts_blocked(
                rows, gate_up, down, plan.tile_expert, plan.tile_rows,
                plan.tile_hi[-1], blocks)
            y = jnp.zeros(x.shape, jnp.float32).at[plan.tok].add(
                plan.w_slot[:, None] * out, mode="drop")
        return y.astype(x.dtype), res

    # a token's sum over its experts is kept in float32 whatever x is (as
    # are the routing weights): in bfloat16 every one of up to top_k adds
    # would round; float32 x gives the program it always gave
    acc = weights.dtype

    def expert(g, y):
        w_gu, w_dn = gate_up[g], down[g]     # read once an expert

        def tile(t, y):
            tk = _tile(plan.tok, t, tm)
            a = _swiglu(_rows(x, tk) @ w_gu, F)[3]
            return _add_rows(
                y, tk, _tile(plan.w_slot, t, tm)[:, None]
                * jnp.matmul(a, w_dn, preferred_element_type=acc))

        return lax.fori_loop(plan.tile_lo[g], plan.tile_hi[g], tile, y)

    y = lax.fori_loop(0, hi - lo, expert, jnp.zeros(x.shape, acc))
    return y.astype(x.dtype), res


def _routed_bwd(lo, hi, tm, res, dy):
    x, weights, experts, gate_up, down = res
    N, k = experts.shape
    F = down.shape[1]
    plan = _plan(weights, experts, lo, hi, tm)
    tok, pair, tile_lo, tile_hi = (plan.tok, plan.pair, plan.tile_lo,
                                   plan.tile_hi)
    w_slot = plan.w_slot.astype(x.dtype)   # the backward runs in x's dtype

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
                dw_slot, jnp.sum(dyt * (a @ w_dn), axis=-1)
                .astype(dw_slot.dtype), (t * tm,))
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
        return _routed_experts(
            x, weights.astype(jnp.promote_types(x.dtype, jnp.float32)),
            experts, gate_up, down, lo, hi, _tile_rows(x.shape[0],
                                                       int(tile)))

    return f
