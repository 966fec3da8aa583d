"""Linear attention by the gated delta rule, computed in chunks, and the
short causal depthwise convolution that feeds it.

Beyond the reference (which has no linear-attention operator). The rule
(Yang et al., "Gated Delta Networks", arXiv:2412.06464) keeps, per value
head, a state ``S`` in R^(d_k x d_v) and at every position ``t`` does::

    S = exp(g_t) * S;  u = beta_t * (v_t - S^T k_t);  S = S + k_t u^T
    o_t = S^T q_t

Step by step that is T dependent rank-one updates: nothing for the MXU. The
op computes the same in chunks of C positions (the published code's form,
C = 64): inside a chunk the C updates are one unit-lower-triangular system
(the WY representation), solved once, and the state moves chunk to chunk in
a ``lax.scan``: every product is a matrix product over (C, d) tiles.

TPU design notes:

- all exponents are differences of a cumulative log-decay inside ONE chunk
  taken later-minus-earlier, so they are <= 0: nothing overflows, whatever
  the decay;
- the triangular system is inverted exactly, without a 64-step loop over
  rows: forward substitution inside 16 x 16 diagonal blocks (15 small
  vector steps), then two levels of 2 x 2 block merges
  (``[[A, 0], [C, D]]^-1 = [[A^-1, 0], [D^-1 C A^-1, D^-1]]``) as matrix
  products at ``highest`` precision. A Neumann product ``(I + M)(I + M^2)..``
  would be all MXU but cancels catastrophically for correlated keys;
- the whole rule sits under ``jax.checkpoint``: the backward pass recomputes
  the chunk quantities from q, k, v, g, beta instead of keeping a dozen
  (B, H, T, d) arrays a layer alive through the step. The recomputation is
  the cheap part (about 11 MFLOP a chunk and head);
- the chunk products run at ``high`` matmul precision whatever the model's
  default: a recurrent state forgives rounding less than a feed-forward
  product does;
- differentiated by jax through the scan: ``autograd`` and ``compile_step``
  see one registered op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import register

_BLOCK = 16   # diagonal blocks solved by substitution; chunk = 16 * 2^m


@register("causal_conv1d")
def _causal_conv1d(activation=None):
    """Depthwise causal convolution over time: ``x`` (B, T, C), ``w``
    (C, K); ``y[t] = sum_j w[:, j] * x[t - (K - 1) + j]`` with zeros left of
    the sequence, no bias; optionally followed by ``silu``."""
    if activation not in (None, "silu"):
        raise MXNetError(f"causal_conv1d: activation {activation!r} is not "
                         "None or 'silu'")

    def f(x, w):
        K, T = w.shape[1], x.shape[1]
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
        y = xp[:, 0:T] * w[:, 0]
        for j in range(1, K):
            y = y + xp[:, j:j + T] * w[:, j]
        return jax.nn.silu(y) if activation == "silu" else y

    return f


def _unit_lower_inverse(m):
    """``(I - m)^-1`` for strictly lower triangular ``m`` (..., C, C)."""
    C = m.shape[-1]
    b = min(_BLOCK, C)
    nb = C // b
    lead = m.shape[:-2]
    blocks = m.reshape(lead + (nb, b, nb, b))
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], axis=-3)
    # forward substitution, all diagonal blocks at once: row i of the
    # inverse (less the identity) from the rows above it
    rows = [diag[..., 0, :]]
    for i in range(1, b):
        above = jnp.stack(rows, axis=-2)                     # (..., i, b)
        rows.append(diag[..., i, :] + jnp.einsum(
            "...j,...jk->...k", diag[..., i, :i], above,
            precision=lax.Precision.HIGHEST))
    inv = jnp.stack(rows, axis=-2) + jnp.eye(b, dtype=m.dtype)
    parts = [inv[..., i, :, :] for i in range(nb)]
    size = b
    while len(parts) > 1:
        merged = []
        for p in range(0, len(parts), 2):
            a_inv, d_inv = parts[p], parts[p + 1]
            c = m[..., (p + 1) * size:(p + 2) * size,
                  p * size:(p + 1) * size]
            low = jnp.matmul(jnp.matmul(d_inv, c,
                                        precision=lax.Precision.HIGHEST),
                             a_inv, precision=lax.Precision.HIGHEST)
            top = jnp.concatenate([a_inv, jnp.zeros_like(a_inv)], axis=-1)
            merged.append(jnp.concatenate(
                [top, jnp.concatenate([low, d_inv], axis=-1)], axis=-2))
        parts, size = merged, size * 2
    return parts[0]


@jax.default_matmul_precision("high")
def _chunked_rule(q, k, v, g, beta, chunk):
    """q, k: (B, H, T, dk), normalised and scaled; v: (B, H, T, dv); g, beta:
    (B, H, T), float32. T is a multiple of ``chunk``. Returns (B, H, T, dv).
    Every product here runs at ``high`` matmul precision (on a TPU three
    bf16 passes, not one): the state is carried through the whole sequence
    and the output is normalised a head, and with single passes the rule
    alone put 0.035 of the logits' spread of error into the model (measured
    on the chip, PERF.md PR 28)."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    N, C = T // chunk, chunk

    def cut(x):
        return x.reshape((B, H, N, C) + x.shape[3:])

    q, k, v, g, beta = map(cut, (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                              # (B, H, N, C)
    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb = k * beta[..., None]
    # (I + L) U = beta * V, L the decayed key overlaps below the diagonal
    m = jnp.where(strict, -(kb @ jnp.swapaxes(k, -1, -2)) * decay, 0.0)
    inv = _unit_lower_inverse(m)
    u = inv @ (v * beta[..., None])                          # (.., C, dv)
    w = inv @ (kb * jnp.exp(gc)[..., None])                  # (.., C, dk)
    local = jnp.where(lower, (q @ jnp.swapaxes(k, -1, -2)) * decay, 0.0)
    qg = q * jnp.exp(gc)[..., None]
    g_last = gc[..., -1]                                     # (B, H, N)
    k_out = k * jnp.exp(g_last[..., None] - gc)[..., None]

    def step(S, xs):
        w_i, u_i, qg_i, local_i, k_i, gl_i = xs
        v_new = u_i - w_i @ S
        o_i = qg_i @ S + local_i @ v_new
        S = S * jnp.exp(gl_i)[..., None, None] \
            + jnp.swapaxes(k_i, -1, -2) @ v_new
        return S, o_i

    def chunks_first(x):
        return jnp.moveaxis(x, 2, 0)

    S0 = jnp.zeros((B, H, dk, dv), q.dtype)
    _, o = lax.scan(step, S0, tuple(map(
        chunks_first, (w, u, qg, local, k_out, g_last))))
    return jnp.moveaxis(o, 0, 2).reshape(B, H, T, dv)


@functools.partial(jax.checkpoint, static_argnums=(5, 6, 7))
def _gated_delta_rule(q, k, v, g, beta, chunk, scale, l2norm):
    B, T, Hk, dk = q.shape
    Hv = v.shape[2]
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    if l2norm:
        q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q * scale

    def heads_first(x, reps=1):
        x = jnp.swapaxes(x, 1, 2)                            # (B, H, T, ..)
        return jnp.repeat(x, reps, axis=1) if reps > 1 else x

    reps = Hv // Hk
    q, k = heads_first(q, reps), heads_first(k, reps)
    v = heads_first(v)
    g, beta = heads_first(g.astype(f32)), heads_first(beta.astype(f32))
    pad = (-T) % chunk
    if pad:
        # padded positions: beta 0 and k 0 leave the state alone, g 0 does
        # not decay it; their outputs are cut off
        def padt(x):
            return jnp.pad(x, ((0, 0), (0, 0), (0, pad))
                           + ((0, 0),) * (x.ndim - 3))

        q, k, v, g, beta = map(padt, (q, k, v, g, beta))
    o = _chunked_rule(q, k, v, g, beta, chunk)[:, :, :T]
    return jnp.swapaxes(o, 1, 2)                             # (B, T, Hv, dv)


@register("gated_delta_rule")
def _gated_delta_rule_op(chunk=64, scale=None, l2norm=True):
    """``q``, ``k``: (B, T, H_k, d_k); ``v``: (B, T, H_v, d_v) with H_v a
    multiple of H_k (each key head serves H_v / H_k value heads, in order);
    ``g``: (B, T, H_v) log-decay (<= 0); ``beta``: (B, T, H_v) in (0, 1).
    Returns (B, T, H_v, d_v) in float32. ``l2norm`` normalises q and k over
    d_k (eps 1e-6); q is scaled by ``scale`` (default d_k^-0.5)."""
    chunk = int(chunk)
    if chunk < 1 or (chunk > _BLOCK and (
            chunk % _BLOCK or (chunk // _BLOCK) & (chunk // _BLOCK - 1))):
        raise MXNetError(f"gated_delta_rule: chunk {chunk} is not <= "
                         f"{_BLOCK} or {_BLOCK} times a power of two")

    def f(q, k, v, g, beta):
        if v.shape[2] % q.shape[2]:
            raise MXNetError(
                f"gated_delta_rule: {v.shape[2]} value heads are not a "
                f"multiple of {q.shape[2]} key heads")
        s = float(scale) if scale is not None else q.shape[-1] ** -0.5
        return _gated_delta_rule(q, k, v, g, beta, chunk, s, bool(l2norm))

    return f
